"""The served (mesh-less, host-register) set estimate: the locked snapshot
only copies the touched rows' registers into a power-of-two row bucket;
`_dispatch_sets` uploads the copy and launches `hll.estimate` on the
device, outside the aggregator lock; `_fetch_flush` reads the estimates
back.  Fewer than `_SET_DEVICE_MIN_ROWS` rows launch nothing: numpy
estimates them at dispatch.  The numpy twin `hll.estimate_np_rows` is the
reference here."""

import socket

import numpy as np
import pytest

from tests.test_aggregator import mk
from tests.test_interval_ledger import _wait
from veneur_tpu import config as config_mod
from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.aggregator import (_SET_DEVICE_MIN_ROWS,
                                        ROW_ONLY_SEGMENT_KEYS,
                                        MetricAggregator)
from veneur_tpu.core.server import Server
from veneur_tpu.samplers import samplers as sm
from veneur_tpu.samplers.metric_key import MetricKey, MetricScope
from veneur_tpu.sinks import simple as simple_sinks
from veneur_tpu.sketches import hll as hll_mod


def _stage_sets(agg, members_per_row, scope=MetricScope.GLOBAL_ONLY,
                prefix="dev.s", seed=3) -> np.ndarray:
    """Touch one set row per entry of `members_per_row`, each holding
    that many distinct members (random 64-bit hashes, as the native
    engine stages them).  Returns the rows."""
    rng = np.random.default_rng(seed)
    rows = np.asarray(
        [agg.sets.row_for(MetricKey(f"{prefix}{i}", sm.TYPE_SET, ""),
                          scope, [])
         for i in range(len(members_per_row))], np.int64)
    with agg.lock:
        for row, n in zip(rows, members_per_row):
            if n:
                agg.sets.stage_hash_batch(
                    np.full(n, row, np.int64),
                    rng.integers(0, 2 ** 64, n, dtype=np.uint64))
        agg.sets.touched[rows] = True
    return rows


def _registers(agg, rows) -> np.ndarray:
    """The rows' registers as the flush will see them (before it)."""
    with agg.lock:
        agg.sets.sync()
        return agg.sets.host_regs[rows].copy()


def _emitted(res, prefix="dev.s") -> dict:
    return {m.name: m.value for m in res.metrics
            if m.name.startswith(prefix)}


@pytest.mark.parametrize("members", [0, 1, 100, 10_000, 1_000_000])
def test_served_estimate_equals_the_numpy_twin(members):
    agg = MetricAggregator(percentiles=[0.5], is_local=False)
    # beside it, rows of other sizes: the program reduces rows apart
    rows = _stage_sets(agg, [members, 3, 250, 0, 1, 17, 2, 9000, 64])
    want = hll_mod.estimate_np_rows(_registers(agg, rows))
    got = _emitted(agg.flush(is_local=False))
    assert len(got) == 9
    for i, w in enumerate(want):
        assert abs(got[f"dev.s{i}"] - float(w)) <= 1.0, (i, w)
    if members:
        # ... and the twin itself is an estimate of the truth
        assert got["dev.s0"] == pytest.approx(members, rel=0.03, abs=1)
    else:
        assert got["dev.s0"] == 0.0
    seg = agg.last_flush_segments
    assert seg["set_rows_device"] == 9
    assert seg["set_upload_bytes"] == 16 * agg.sets.m
    assert seg["set_device_s"] >= 0.0


@pytest.mark.parametrize("n_rows", [1, 7, 8, 9, 1000, 1025])
def test_row_bucket_edges_emit_every_row_and_no_padding_row(n_rows):
    agg = MetricAggregator(percentiles=[0.5], is_local=False)
    rows = _stage_sets(agg, [1 + i % 9 for i in range(n_rows)])
    want = hll_mod.estimate_np_rows(_registers(agg, rows))
    res = agg.flush(is_local=False)
    got = _emitted(res)
    assert len(got) == n_rows == len(res.metrics)
    assert max(abs(got[f"dev.s{i}"] - float(w))
               for i, w in enumerate(want)) <= 1.0
    seg = agg.last_flush_segments
    if n_rows < _SET_DEVICE_MIN_ROWS:
        # a handful of rows: numpy at dispatch, no program, no compile
        assert seg["set_rows_device"] == 0 == agg.compile_events
        assert "set_upload_bytes" not in seg and "device_sets" not in seg
    else:
        assert seg["set_rows_device"] == n_rows
        assert seg["set_upload_bytes"] \
            == arena_mod._pow2(n_rows) * agg.sets.m
    # the interval was reset under the lock: an idle flush emits nothing
    assert len(agg.flush(is_local=False).metrics) == 0


def test_mixed_scope_local_flush_forwards_the_registers_it_had():
    """A forwarding tier marshals its MIXED rows from the snapshot's host
    copy, byte for byte; only the rows it keeps are estimated."""
    agg = MetricAggregator(percentiles=[0.5], is_local=True)
    mixed = _stage_sets(agg, [5, 700, 40_000], MetricScope.MIXED,
                        prefix="fwd.s")
    local = _stage_sets(agg, [12, 3000, 1, 1, 2, 3, 5, 8],
                        MetricScope.LOCAL_ONLY, prefix="dev.s", seed=4)
    want_wire = [hll_mod.marshal(r) for r in _registers(agg, mixed)]
    want_local = hll_mod.estimate_np_rows(_registers(agg, local))
    res = agg.flush(is_local=True)
    fwd = {f.name: f.hll for f in res.forward}
    assert [fwd[f"fwd.s{i}"] for i in range(3)] == want_wire
    got = _emitted(res)
    assert set(got) == {f"dev.s{i}" for i in range(8)}
    assert not _emitted(res, "fwd.s")
    for i, w in enumerate(want_local):
        assert abs(got[f"dev.s{i}"] - float(w)) <= 1.0
    assert agg.last_flush_segments["set_rows_device"] == 11


def test_no_estimate_runs_under_the_aggregator_lock(monkeypatch):
    """The numpy reduction, the device program and its dispatch are all
    reached with the aggregator lock released, many rows or few; a flush
    with no set rows launches nothing."""
    agg = MetricAggregator(percentiles=[0.5], is_local=False,
                           count_unique_timeseries=True)
    calls = []

    def watched(name, fn):
        def wrapper(*a, **kw):
            calls.append((name, agg.lock.locked()))
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(hll_mod, "estimate_np_rows", watched(
        "estimate_np_rows", hll_mod.estimate_np_rows))
    monkeypatch.setattr(hll_mod, "estimate", watched(
        "estimate", hll_mod.estimate))
    monkeypatch.setattr(agg, "_dispatch_sets", watched(
        "dispatch", agg._dispatch_sets))
    _stage_sets(agg, [10, 2000] * 6)
    with agg.lock:
        assert agg.lock.locked()        # what `watched` would record
    assert len(_emitted(agg.flush(is_local=False))) == 12
    assert ("dispatch", False) in calls and ("estimate", False) in calls
    assert not [c for c in calls if c[1]], calls

    calls.clear()
    _stage_sets(agg, [10, 2000])        # a handful: numpy, at dispatch
    assert len(_emitted(agg.flush(is_local=False))) == 2
    assert calls.count(("estimate_np_rows", False)) == 2    # + unique-ts
    assert not [c for c in calls if c[1] or c[0] == "estimate"], calls

    calls.clear()
    agg.process_metric(mk("dev.c", "counter", 1.0))
    agg.flush(is_local=False)
    assert not [c for c in calls if c[0] in ("dispatch", "estimate")]
    seg = agg.last_flush_segments
    assert seg["set_rows_device"] == 0
    assert "device_sets" not in seg and "set_upload_bytes" not in seg


def test_one_compile_per_row_bucket():
    agg = MetricAggregator(percentiles=[0.5], is_local=False)
    _stage_sets(agg, [2] * 1000)
    agg.flush(is_local=False)
    assert agg.compile_events == 1
    _stage_sets(agg, [2] * 900)
    agg.flush(is_local=False)
    assert agg.compile_events == 1          # the 1024-row bucket again
    assert agg.last_flush_segments["set_rows_device"] == 900
    _stage_sets(agg, [2] * 500)
    agg.flush(is_local=False)
    assert agg.compile_events == 2          # the 512-row bucket
    # the one row a server's own telemetry touches now and then
    # (ssf.names_unique, 1 flush in 100) compiles nothing
    _stage_sets(agg, [2])
    assert len(_emitted(agg.flush(is_local=False))) == 1
    assert agg.compile_events == 2
    assert not agg.compile_in_progress.is_set()


def test_prewarm_covers_the_set_arena_capacity_bucket():
    agg = MetricAggregator(percentiles=[0.5], is_local=False)
    agg.prewarm([1], max_keys=128, min_keys=128)
    base = agg.compile_events
    _stage_sets(agg, [2] * (agg.sets.capacity - 10))
    agg.flush(is_local=False)
    assert agg.compile_events == base


# -- the server's outlets: the timeline row and the flush's trace ----------

@pytest.fixture
def server():
    servers = []

    def boot():
        sink = simple_sinks.ChannelMetricSink()
        srv = Server(config_mod.Config(
            statsd_listen_addresses=["udp://127.0.0.1:0"], interval=10.0,
            percentiles=[0.5], hostname="set-estimate-test"),
            extra_metric_sinks=[sink])
        # the flush's own spans are sampled (1 %) into uniqueness SETS:
        # none here, so an interval has the set rows the test sent
        srv.metric_extraction.uniqueness_rate = 0.0
        servers.append(srv)
        return srv

    yield boot
    for srv in servers:
        srv.shutdown()


def _send(srv, lines: list) -> None:
    before = srv.native.engine.totals()[0]
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    tx.sendto(b"\n".join(lines), srv.statsd_addrs[0][1])
    tx.close()
    assert _wait(lambda: (srv._drain_native() or True)
                 and srv.native.engine.totals()[0] >= before + len(lines))


def _flush_trace(srv) -> tuple:
    """One flush: its timeline row and the spans of its trace."""
    srv.flush()
    assert srv.egress.settle(timeout_s=10.0)
    row = srv.flush_timeline.snapshot()[-1]
    tid = int(row["trace_id"], 16)
    assert _wait(lambda: any(
        s["name"] == "flush" and s["trace_id"] == tid
        for s in srv.flight_recorder.snapshot()))
    return row, [s for s in srv.flight_recorder.snapshot()
                 if s["trace_id"] == tid]


def test_row_counter_and_span_say_the_program_engaged(server):
    from tests.test_self_telemetry import FakeStatsd

    srv = server()
    srv.statsd = FakeStatsd()
    srv.start()
    _send(srv, [b"dev.set%d:m%d|s" % (i % 12, i) for i in range(40)]
          + [b"dev.t:%d|ms" % i for i in range(8)])
    row, trace = _flush_trace(srv)
    assert row["keys_set"] == 12 and row["set_rows_device"] == 12
    assert row["set_upload_bytes"] == 16 * srv.aggregator.sets.m
    assert row["set_device_ms"] >= 0.0
    by = {s["name"]: s for s in trace}
    sets = by["flush.seg.device.sets"]
    assert sets["parent_id"] == by["flush.seg.device"]["span_id"]
    assert sets["tags"]["rows"] == "12"
    assert sets["duration_ms"] >= row["set_device_ms"] - 0.002
    # the row and the span are the outlet: no new series per flush
    names = {c[1] for c in srv.statsd.calls}
    assert "flush.segment.device_ms" in names           # as before
    for key in ROW_ONLY_SEGMENT_KEYS:
        stem = key[:-2] if key.endswith("_s") else key
        assert f"flush.segment.{stem}_ms" not in names, key
        assert f"flush.{key}" not in names, key

    # an interval with no set rows: no program, no span, the counter 0
    _send(srv, [b"dev.t:%d|ms" % i for i in range(8)])
    row, trace = _flush_trace(srv)
    assert row["keys_set"] == 0 and row["set_rows_device"] == 0
    assert "set_upload_bytes" not in row and "set_device_ms" not in row
    assert not [s for s in trace if s["name"] == "flush.seg.device.sets"]
    assert [s for s in trace if s["name"] == "flush.seg.device"]
