"""A meshed global on its normal path: `read_config` -> `Server` -> gRPC
import from two `ForwardClient`s -> the server's own ticker -> egress lane
-> sink, on the suite's virtual CPU devices at a small size (the
deployment `benchmark/configs/global-mesh4.json` runs on four chips).

The reference is written here, in numpy float64, from the documented
rule: singleton centroids are samples, and the quantile of n unit-weight
points is numpy's "hazen" percentile.  It imports nothing of `serving` or
`sketches`.
"""

import threading
import time

import numpy as np
import pytest
import yaml

from veneur_tpu import config as config_mod
from veneur_tpu.core.server import Server
from veneur_tpu.forward.client import ForwardClient
from veneur_tpu.protocol import metric_pb2, tdigest_pb2
from veneur_tpu.sinks.simple import ChannelMetricSink

KEYS, CENTROIDS, LOCALS = 64, 16, 2
PCTS = (0.5, 0.9, 0.99)
INTERVAL = 1.0


def payload(seed: int):
    """values [locals, keys, centroids] sorted along the last axis, and the
    protobufs each local forwards: one digest of singleton centroids a key.
    Even keys are mixed-scope (a global emits their percentiles only), odd
    ones global-only (it emits their aggregates too)."""
    vals = np.sort(np.random.default_rng(seed).gamma(
        2.0, 10.0, (LOCALS, KEYS, CENTROIDS)), axis=2)
    pbs = []
    for loc in range(LOCALS):
        out = []
        for k in range(KEYS):
            v = vals[loc, k]
            td = tdigest_pb2.MergingDigestData(
                compression=100.0, min=v[0], max=v[-1],
                reciprocalSum=float((1.0 / v).sum()))
            for x in v.tolist():
                td.main_centroids.add(mean=x, weight=1.0)
            pb = metric_pb2.Metric(
                name=f"t.h.{k}", tags=[f"shard:{k % 7}"],
                type=metric_pb2.Histogram,
                scope=metric_pb2.Global if k % 2 else metric_pb2.Mixed)
            pb.histogram.t_digest.CopyFrom(td)
            out.append(pb)
        pbs.append(out)
    return vals, pbs


def expected(vals) -> dict:
    """name -> value, by the documented rule in float64."""
    merged = np.concatenate(list(vals), axis=1)     # [keys, locals * c]
    qs = np.percentile(merged, [q * 100 for q in PCTS], axis=1,
                       method="hazen")
    out = {}
    for k in range(KEYS):
        for j, q in enumerate(PCTS):
            out[f"t.h.{k}.{int(q * 100)}percentile"] = qs[j, k]
        if k % 2:
            out[f"t.h.{k}.min"] = merged[k].min()
            out[f"t.h.{k}.max"] = merged[k].max()
            out[f"t.h.{k}.count"] = float(merged.shape[1])
    return out


def boot(tmp_path, **over):
    cfg = {"hostname": "mesh-global", "interval": f"{INTERVAL:g}s",
           "percentiles": list(PCTS), "aggregates": ["min", "max", "count"],
           "grpc_address": "127.0.0.1:0", "native_ingest": False,
           "synchronize_with_interval": True, **over}
    path = tmp_path / "global.yaml"
    path.write_text(yaml.safe_dump(cfg))
    sink = ChannelMetricSink()
    srv = Server(config_mod.read_config(str(path)),
                 extra_metric_sinks=[sink])
    srv.start()
    return srv, sink


MESHED = {"mesh_devices": 4, "mesh_replicas": 2,
          "prewarm_flush_shapes": True, "prewarm_depths": [32],
          # 64 keys and the server's own flush timer: 65 rows, the
          # bucket of 128 (as 65,537 land in 131,072 on the chip)
          "arena_initial_capacity": 128}


def forward_all(clients, pbs, epoch):
    threads = [threading.Thread(target=c.send_pbs, args=(p,),
                                kwargs={"epoch": epoch})
               for c, p in zip(clients, pbs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def ours(batch) -> dict:
    return {m.name: m.value for m in batch if m.name.startswith("t.h.")}


def flush_once(srv, sink) -> dict:
    srv.flush()
    srv.egress.settle(timeout_s=30)
    return ours(sink.queue.get(timeout=30))


def test_served_by_its_own_ticker_three_intervals(tmp_path):
    """(a) + (b): three intervals flushed by `serve()`, each interval's
    forwards sent while the tick's flush is still running; every emitted
    number against the rule; nothing compiled after the prewarm."""
    vals, pbs = payload(11)
    want = expected(vals)
    srv, sink = boot(tmp_path, **MESHED)
    clients = []
    try:
        # a meshed boot compiles before it listens: start() returned,
        # and the count is written when the prewarm has ended
        agg = srv.aggregator
        assert srv.prewarm_stats["programs"] > 0
        assert agg.prewarm([32], 128) == 0       # all compiled: nothing to do
        events = agg.compile_events
        assert events == srv.prewarm_stats["programs"]
        addr = f"127.0.0.1:{srv.grpc_import.port}"
        clients = [ForwardClient(addr, timeout_s=10.0, source=f"local{i}")
                   for i in range(LOCALS)]
        threading.Thread(target=srv.serve, daemon=True).start()
        got, rows = [], []
        seen = srv.flush_count
        for interval in range(3):
            # the tick's flush has taken its snapshot (flush_count moves
            # right after the dispatch) and is fetching, emitting and
            # handing to the lane while this interval's forwards arrive
            deadline = time.time() + 10 * INTERVAL
            while srv.flush_count == seen:
                assert time.time() < deadline, "the ticker stopped"
                time.sleep(0.001)
            seen = srv.flush_count
            forward_all(clients, pbs, epoch=interval + 1)
        deadline = time.time() + 10 * INTERVAL
        while len(got) < 3 and time.time() < deadline:
            try:
                batch = ours(sink.queue.get(timeout=0.2))
            except Exception:       # queue.Empty
                continue
            if batch:
                got.append(batch)
        assert len(got) == 3
        rows = [r for r in srv.flush_timeline.snapshot()
                if r.get("imported")]
        assert [r["imported"] for r in rows] == [LOCALS * KEYS] * 3
        assert srv.grpc_import.import_errors == 0
        for batch in got:
            assert set(batch) == set(want)
            for name, w in want.items():
                # float32 on the device against float64 here
                assert batch[name] == pytest.approx(w, rel=2e-6, abs=1e-5), \
                    name
        assert got[1] == got[0] and got[2] == got[0]
        for r in rows:
            assert (r["device_rows"], r["device_depth"]) == (128, 32)
        assert agg.compile_events == events, sorted(
            map(repr, agg._compiled_shapes))
    finally:
        for c in clients:
            c.close()
        srv.shutdown()


def test_prewarm_second_boot_in_process_compiles_nothing_new(tmp_path):
    """(b), the count: on a mesh `prewarm` returns what it compiled, the
    flush program at the configured bucket among it, and 0 once all is."""
    srv, _sink = boot(tmp_path, **MESHED)
    try:
        agg = srv.aggregator
        keys = [k for k in agg._compiled_shapes
                if isinstance(k[0], tuple) and k[0][0] == (128, 32)]
        assert len(keys) == 1 and keys[0][2] is True    # a global donates
        lanes = {k[0] for k in agg._compiled_shapes
                 if isinstance(k[0], str)}
        assert lanes == {"set_lane_reset", "set_lane_scatter"}
        assert agg.prewarm([32], 128) == 0
        assert agg.prewarm([32], 512) > 0       # another bucket: compiled
    finally:
        srv.shutdown()


def shard_union_bytes(shards: int, m_u: int = 1 << 14) -> int:
    return m_u * 2 * (shards - 1) // shards


@pytest.mark.parametrize("devices,replicas", [(4, 2), (4, 1), (0, 0)])
def test_timeline_row_says_what_mesh_it_ran_on(tmp_path, devices, replicas):
    """(c): `mesh_shape`, `collective_bytes`, `device_rows` /
    `device_depth` on the flush timeline row; none of them mesh-less."""
    _vals, pbs = payload(12)
    over = ({"mesh_devices": devices, "mesh_replicas": replicas}
            if devices else {})
    srv, sink = boot(tmp_path, **over)
    client = ForwardClient(f"127.0.0.1:{srv.grpc_import.port}",
                           timeout_s=10.0, source="local0")
    try:
        client.send_pbs(pbs[0], epoch=1)
        assert len(flush_once(srv, sink)) > 0
        row = srv.flush_timeline.snapshot()[-1]
        if not devices:
            assert not {"mesh_shape", "collective_bytes", "device_rows",
                        "device_depth"} & set(row)
            return
        shards = devices // replicas
        assert row["mesh_shape"] == f"{shards}x{replicas}"
        assert row["devices"] == devices
        k, d = row["device_rows"], row["device_depth"]
        assert (k, d) == (64, 16)
        s_rows, m = srv.aggregator.sets.lanes_regs.shape[1:]
        k2 = srv.aggregator.counters.values.shape[1]
        if replicas == 1:
            # nothing crosses the replica axis; the unique-timeseries
            # registers' union over `shard` is the one collective left
            assert row["collective_bytes"] == shard_union_bytes(shards)
        else:
            a2a = 2 * (k // shards) * (d // replicas) * 4 // 2
            psum = (k2 // shards) * 2 * 4
            pmax = (s_rows // shards) * m + (1 << 14)
            assert row["collective_bytes"] == (
                a2a + psum + pmax + shard_union_bytes(shards))
            assert row["collective_bytes"] > 0
    finally:
        client.close()
        srv.shutdown()


@pytest.fixture(scope="module")
def meshless_answer(tmp_path_factory):
    _vals, pbs = payload(13)
    srv, sink = boot(tmp_path_factory.mktemp("meshless"))
    clients = [ForwardClient(f"127.0.0.1:{srv.grpc_import.port}",
                             timeout_s=10.0, source=f"local{i}")
               for i in range(LOCALS)]
    try:
        forward_all(clients, pbs, epoch=1)
        return flush_once(srv, sink)
    finally:
        for c in clients:
            c.close()
        srv.shutdown()


@pytest.mark.parametrize("devices", [4, 8])
def test_cut_from_eight_chips_to_four_changes_no_number(
        tmp_path, meshless_answer, devices):
    """(d): shard 2 x replica 2 (the four-chip host) and shard 4 x
    replica 2 (BASELINE.json's v5e-8) against the mesh-less server, one
    payload, `tests/test_parallel.py`'s tolerance."""
    _vals, pbs = payload(13)
    srv, sink = boot(tmp_path, mesh_devices=devices, mesh_replicas=2)
    clients = [ForwardClient(f"127.0.0.1:{srv.grpc_import.port}",
                             timeout_s=10.0, source=f"local{i}")
               for i in range(LOCALS)]
    try:
        forward_all(clients, pbs, epoch=1)
        got = flush_once(srv, sink)
        assert srv.flush_timeline.snapshot()[-1]["mesh_shape"] == \
            f"{devices // 2}x2"
    finally:
        for c in clients:
            c.close()
        srv.shutdown()
    assert set(got) == set(meshless_answer)
    assert len(got) == KEYS * len(PCTS) + (KEYS // 2) * 3
    for name, v in meshless_answer.items():
        np.testing.assert_allclose(got[name], v, rtol=1e-4, atol=1e-4,
                                   err_msg=name)
