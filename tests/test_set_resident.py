"""The resident set path: an unmeshed arena whose capacity the
configuration pre-sized (`set_arena_initial_capacity`) keeps its
registers on the device as one `[1, capacity, m]` lane; forwarded
sketches stage as what they are (sparse: decoded triples, dense: a
register row) and scatter / merge into the lane in fixed chunks; a
flush that forwards no set estimates on the device and reads back 4
bytes a row.  An arena at its default size keeps host registers.  The
numpy twin (`hll.unmarshal`, `hll.estimate_np_rows`) is the reference.
"""

import numpy as np
import pytest

from tests.test_aggregator import mk
from veneur_tpu import config as config_mod
from veneur_tpu import ingest
from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.aggregator import (SET_LEDGER_KEYS,
                                        MetricAggregator)
from veneur_tpu.core.server import Server
from veneur_tpu.parallel import serving
from veneur_tpu.protocol import forward_pb2, metric_pb2
from veneur_tpu.sinks import simple as simple_sinks
from veneur_tpu.sketches import hll as hll_mod

PRESIZED = 2048         # > the arena's default 1,024: resident
# members per set: sparse on the wire, at the form's crossover, dense
SIZES = (0, 1, 17, 300, 1500, 2600, 9000, 60_000)


def _registers(rng, n: int, p: int) -> np.ndarray:
    regs = np.zeros(1 << p, np.uint8)
    idx, rank = hll_mod.split_hashes(
        rng.integers(0, 2 ** 64, n, dtype=np.uint64), p)
    np.maximum.at(regs, idx, rank)
    return regs


def _payload(seed: int, p: int, sizes=SIZES, reps: int = 2):
    """One local's MetricList of Set sketches (hll.marshal's own choice
    of form) and, per name, the registers a reader must recover."""
    rng = np.random.default_rng([seed, p])
    ml = forward_pb2.MetricList()
    want, dense = {}, 0
    for k, n in enumerate(sizes * reps):
        regs = _registers(rng, n, p)
        pb = ml.metrics.add(name=f"res.s{k}", tags=[f"k:{k % 3}"],
                            type=metric_pb2.Set, scope=metric_pb2.Mixed)
        pb.set.hyper_log_log = wire = hll_mod.marshal(regs)
        dense += wire[3] == 0
        want[pb.name] = hll_mod.unmarshal(wire)
    return ml.SerializeToString(), want, dense


def _agg(resident: bool, p: int, **kw) -> MetricAggregator:
    if resident:
        kw["set_initial_capacity"] = PRESIZED
    return MetricAggregator(set_precision=p, is_local=False, **kw)


def _live_registers(agg, names) -> dict:
    """name -> the registers the next flush will read (after a sync)."""
    with agg.lock:
        agg.sets.sync()
        rows = {m.key.name: r for r, m in enumerate(agg.sets.meta)
                if m is not None}
        if agg.sets.host_regs is not None:
            return {n: agg.sets.host_regs[rows[n]].copy() for n in names}
        lanes = np.asarray(agg.sets.lanes_regs)
        return {n: lanes[0, rows[n]].copy() for n in names}


# -- the residency rule -------------------------------------------------------

def test_residency_follows_what_the_configuration_says():
    default = MetricAggregator()
    assert not default.sets.resident and default.sets.host_regs is not None
    followed = MetricAggregator(initial_capacity=4096)
    assert not followed.sets.resident      # the digest knob is not the set's
    presized = MetricAggregator(set_initial_capacity=PRESIZED)
    assert presized.sets.resident and presized.sets.host_regs is None
    assert presized.sets.lanes_regs.shape == (1, PRESIZED,
                                              presized.sets.m)
    flagged = MetricAggregator(flush_resident_arenas=True)
    assert flagged.sets.resident
    assert flagged.sets.lanes_regs.shape[1] == arena_mod._INITIAL_CAPACITY


# -- import: the same registers on every side ---------------------------------

@pytest.mark.parametrize("p", [14, 12])
def test_import_gives_the_same_registers_and_estimates(p):
    """Two locals' seeded sparse and dense payloads through
    import_payload: resident arena, host-register arena and the numpy
    reference hold the same registers bit for bit, and the flushes emit
    the same estimates (the device's f32 sums against numpy's: within
    one count of the rounding, or 2e-6)."""
    (pay0, want0, dense0), (pay1, want1, dense1) = \
        _payload(1, p), _payload(2, p)
    assert 0 < dense0 < len(want0)          # both wire forms are in it
    union = {n: np.maximum(want0[n], want1[n]) for n in want0}
    emitted = {}
    for resident in (True, False):
        agg = _agg(resident, p)
        assert agg.sets.resident is resident
        for pay in (pay0, pay1):
            assert agg.import_payload(pay) == (len(want0), 0)
        agg.sync_staged(min_samples=1)      # a drain tick's lane sync
        live = _live_registers(agg, union)
        for name, regs in union.items():
            assert np.array_equal(live[name], regs), (resident, name)
        res = agg.flush(is_local=False)
        seg = agg.last_flush_segments
        assert seg["set_import_sparse"] == 2 * len(want0) - dense0 - dense1
        assert seg["set_import_dense"] == dense0 + dense1
        assert seg["set_merge_rows"] > 0 and seg["set_scatter_points"] > 0
        emitted[resident] = {m.name: m.value for m in res.metrics
                             if m.name.startswith("res.s")}
        assert set(emitted[resident]) == set(union)
    ref = hll_mod.estimate_np_rows(np.stack(list(union.values())))
    for side in emitted.values():
        got = np.asarray([side[n] for n in union])
        assert np.all(np.abs(got - ref) <= np.maximum(1.0, 2e-6 * ref))
    assert emitted[True] == emitted[False]


def test_sparse_sketches_never_become_dense_rows():
    """A 17-member set stages as 17 triples beside the hash batches —
    the dense-row dict holds dense sketches only."""
    pay, want, dense = _payload(5, 14)
    agg = _agg(True, 14)
    agg.import_payload(pay)
    assert len(agg.sets._merge_rows) <= dense
    assert sum(len(r) for r, _, _ in agg.sets._stage_triples) == sum(
        int(np.count_nonzero(r)) for r in want.values()
        if np.count_nonzero(r) * 4 + 20 < agg.sets.m // 2 + 9)


def test_wire_scan_reads_what_unmarshal_reads():
    """The native scan's set columns against hll.unmarshal, with a
    tmpSet entry, a truncated list and a legacy payload in the batch:
    what the scan cannot read takes the protobuf path."""
    import struct

    rng = np.random.default_rng(9)
    ml = forward_pb2.MetricList()
    good = hll_mod.marshal(_registers(rng, 40, 14))
    # the same sketch with its first key moved into the tmpSet
    count, _last, blen = struct.unpack_from(">III", good, 8)
    keys = hll_mod._decode_varint_list(good[20:20 + blen], count)
    with_tmp = (good[:4] + struct.pack(">II", 1, int(keys[0]))
                + struct.pack(">II", count - 1, int(keys[-1])))
    # the list's deltas restart from zero: re-encode the tail whole
    rest = hll_mod._encode_varint_list(keys[1:])
    with_tmp += struct.pack(">I", len(rest)) + rest
    truncated = good[:-3]
    legacy = b"VH" + struct.pack("<BBB", 2, 14, 0) \
        + struct.pack("<I", 0)
    for i, wire in enumerate((good, with_tmp, truncated, legacy)):
        pb = ml.metrics.add(name=f"scan.s{i}", type=metric_pb2.Set,
                            scope=metric_pb2.Mixed)
        pb.set.hyper_log_log = wire
    payload = ml.SerializeToString()
    scan = ingest.import_scan(payload)
    assert scan["set_form"].tolist() == [1, 1, 0, 0]
    for i in (0, 1):
        regs = np.zeros(1 << 14, np.uint8)
        o, n = int(scan["set_off"][i]), int(scan["set_n"][i])
        np.maximum.at(regs, scan["set_idx"][o:o + n],
                      scan["set_rank"][o:o + n])
        off, ln = int(scan["hll_off"][i]), int(scan["hll_len"][i])
        assert np.array_equal(regs,
                              hll_mod.unmarshal(payload[off:off + ln]))
    agg = _agg(True, 14)
    assert agg.import_payload(payload) == (3, 1)    # truncated: failed


# -- the flush ----------------------------------------------------------------

def test_resident_global_flush_reads_back_no_register():
    pay, want, _ = _payload(3, 14)
    agg = _agg(True, 14)
    agg.import_payload(pay)
    agg.flush(is_local=False)
    seg = agg.last_flush_segments
    assert seg["set_rows_device"] == len(want)
    assert seg["set_upload_bytes"] == 0
    assert seg["set_readback_bytes"] == 4 * PRESIZED     # f32 a row
    assert seg["set_resident_bytes"] == PRESIZED * agg.sets.m
    assert seg["set_sync_s"] > 0 and seg["set_scatter_launches"] >= 1
    assert set(SET_LEDGER_KEYS) <= set(seg)
    # the host-register side uploads its copy, and holds nothing there
    host = _agg(False, 14)
    host.import_payload(pay)
    host.flush(is_local=False)
    seg = host.last_flush_segments
    assert seg["set_resident_bytes"] == 0
    assert seg["set_upload_bytes"] == 16 * host.sets.m
    assert seg["set_readback_bytes"] == 4 * 16


def test_a_handful_of_rows_gathers_registers_for_numpy():
    agg = _agg(True, 14)
    for i in range(3):
        agg.process_metric(mk(f"few.s{i}", "set", f"m{i}"))
    res = agg.flush(is_local=False)
    seg = agg.last_flush_segments
    assert seg["set_rows_device"] == 0
    assert seg["set_readback_bytes"] == 4 * agg.sets.m   # bucket of 4 rows
    assert {m.name: m.value for m in res.metrics
            if m.name.startswith("few.")} == {f"few.s{i}": 1.0
                                              for i in range(3)}


def test_forwarding_local_marshals_the_same_bytes():
    """A local that forwards its mixed-scope sets reads u8 registers
    back and marshals them: the same wire bytes from resident and host
    registers."""
    wires = []
    for resident in (True, False):
        agg = MetricAggregator(
            is_local=True,
            **({"set_initial_capacity": PRESIZED} if resident else {}))
        for k in range(12):
            for i in range((k + 1) * 40):
                agg.process_metric(mk(f"fw.s{k}", "set", f"m{k}.{i}"))
        res = agg.flush(is_local=True)
        seg = agg.last_flush_segments
        if resident:        # registers come back, not estimates
            assert seg["set_rows_device"] == 0
            assert seg["set_readback_bytes"] == 16 * agg.sets.m
        wires.append(sorted((f.name, f.hll) for f in res.forward
                            if f.name.startswith("fw.s")))
        assert len(wires[-1]) == 12
    assert wires[0] == wires[1]


# -- the lanes ----------------------------------------------------------------

def test_pinned_snapshot_copies_and_overlapping_flushes_keep_registers(
        monkeypatch):
    """While a dispatched flush holds a lane snapshot, updates go
    through the copying kernels (on a backend that donates otherwise),
    and two flushes in flight emit their own intervals' estimates."""
    agg = _agg(True, 14)
    forms = []
    launch = agg.sets._lane_scatter

    def spy(lanes, pr, pi, pk, lane, donate):
        forms.append(donate)
        return launch(lanes, pr, pi, pk, lane, False)   # the CPU's form

    monkeypatch.setattr(agg.sets, "_lane_scatter", spy)
    monkeypatch.setattr(serving, "lane_donation_ok", lambda: True)
    pay_a, want_a, _ = _payload(11, 14, sizes=(17, 300, 1500, 9000))
    pay_b, want_b, _ = _payload(12, 14, sizes=(17, 300, 1500, 9000))
    agg.import_payload(pay_a)
    agg.sync_staged(min_samples=1)
    assert forms == [True]                  # nothing pinned: in place
    first = agg.flush_dispatch(is_local=False)
    assert agg.sets._snapshot_inflight == 1
    agg.import_payload(pay_b)
    agg.sync_staged(min_samples=1)
    assert forms == [True, False]           # pinned: the copying kernel
    second = agg.flush_dispatch(is_local=False)
    assert agg.sets._snapshot_inflight == 2
    got_b = {m.name: m.value for m in second.emit().metrics
             if m.name.startswith("res.s")}
    got_a = {m.name: m.value for m in first.emit().metrics
             if m.name.startswith("res.s")}
    assert agg.sets._snapshot_inflight == 0
    for got, want in ((got_a, want_a), (got_b, want_b)):
        ref = hll_mod.estimate_np_rows(np.stack(list(want.values())))
        assert np.all(np.abs(np.asarray([got[n] for n in want]) - ref)
                      <= np.maximum(1.0, 2e-6 * ref))
    assert got_a != got_b


@pytest.mark.parametrize("p", [14, 10])
def test_reset_zeroes_exactly_the_touched_rows(p):
    ar = arena_mod.SetArena(capacity=256, precision=p, resident=True)
    assert ar.resident and ar.lanes_regs.shape == (1, 256, 1 << p)
    rng = np.random.default_rng(p)
    rows = np.arange(0, 256, 3)
    n = 4000
    ar.stage_triples(rng.choice(rows, n).astype(np.int32),
                     rng.integers(0, 1 << p, n).astype(np.int32),
                     rng.integers(1, 30, n).astype(np.uint8))
    ar.sync()
    before = np.asarray(ar.lanes_regs)[0]
    assert before[rows].any(axis=1).all()
    cleared = rows[::2]
    ar.snapshot_lanes()                     # a flush pins, then resets
    ar.reset_rows(cleared)
    after = np.asarray(ar.lanes_regs)[0]
    assert not after[cleared].any()
    kept = np.setdiff1d(np.arange(256), cleared)
    assert np.array_equal(after[kept], before[kept])


_SMALL, _CHUNK = serving.LANE_SCATTER_SMALL, serving.LANE_SCATTER_CHUNK


@pytest.mark.parametrize("meshed,n,lengths", [
    (False, 1, [1]),                        # the server's own set
    (False, 2, [_SMALL]),
    (False, _SMALL, [_SMALL]),              # a node's UDP set lines
    (False, _SMALL + 1, [_CHUNK]),
    (False, _CHUNK + 5, [_CHUNK, _CHUNK]),  # a fleet's burst
    (True, 1, [1]),                         # meshed: the tick's own
    (True, 5, [8]),                         # power of two, one launch
    (True, _SMALL + 1, [2 * _SMALL]),
])
def test_a_tick_launches_at_the_lengths_its_plane_keeps(meshed, n, lengths,
                                                        monkeypatch):
    """A tick's triples pad to the closed set on the resident plane (a
    thousand triples do not upload and scatter 2^20) and, meshed, to
    the tick's own power of two as they always did."""
    from veneur_tpu.parallel import mesh as mesh_mod
    ar = arena_mod.SetArena(capacity=16, precision=10,
                            mesh=mesh_mod.make_mesh(4) if meshed else None,
                            resident=not meshed)
    assert ar.resident != meshed and ar.lanes_regs is not None
    launched = []

    def record(lanes, pr, pi, pk, lane, donate):
        launched.append((len(pr), len(pi), len(pk)))
        return lanes
    monkeypatch.setattr(ar, "_lane_scatter", record)
    ar.stage_triples(np.zeros(n, np.int32), np.zeros(n, np.int32),
                     np.ones(n, np.uint8))
    ar.sync()
    assert launched == [(k, k, k) for k in lengths]
    assert ar._lane_stats["scatter_points"] == n
    assert ar._lane_stats["scatter_launches"] == len(lengths)


def test_launches_come_in_the_chunks_the_boot_launched():
    """After prewarm_lanes, no tick's triple count, dense-row count or
    touched-row count compiles a lane program: the set is closed."""
    agg = _agg(True, 14)
    launched = agg.sets.prewarm_lanes()
    lane_keys = {k for k in agg._compiled_shapes
                 if isinstance(k[0], str) and k[0].startswith("set_")}
    assert launched == len(lane_keys) == agg.compile_events
    for pad in (1, serving.LANE_SCATTER_SMALL, serving.LANE_SCATTER_CHUNK):
        assert ("set_lane_scatter", (1, PRESIZED, agg.sets.m), pad, 0,
                False) in lane_keys
    for seed, sizes in ((21, SIZES), (22, (17,) * 9),
                        (23, (9000,) * 3 + (2,) * 40), (24, (5,))):
        pay, _want, _ = _payload(seed, 14, sizes=sizes, reps=1)
        agg.import_payload(pay)
        agg.sync_staged(min_samples=1)
        agg.flush(is_local=False)
    assert {k for k in agg._compiled_shapes
            if isinstance(k[0], str) and k[0].startswith("set_")} \
        == lane_keys


# -- the server ---------------------------------------------------------------

@pytest.fixture
def server():
    servers = []

    def boot(**kw):
        sink = simple_sinks.ChannelMetricSink()
        srv = Server(config_mod.Config(
            statsd_listen_addresses=["udp://127.0.0.1:0"], interval=10.0,
            grpc_address="127.0.0.1:0", percentiles=[0.5],
            hostname="set-resident-test", **kw),
            extra_metric_sinks=[sink])
        srv.metric_extraction.uniqueness_rate = 0.0
        servers.append(srv)
        return srv

    yield boot
    for srv in servers:
        srv.shutdown()


def test_presized_boot_launches_its_lanes_before_it_listens(server):
    from veneur_tpu import http_api

    srv = server(set_arena_initial_capacity=PRESIZED)
    srv.start()
    agg = srv.aggregator
    assert agg.sets.resident
    assert srv.prewarm_stats["programs"] == agg.compile_events > 0
    assert any(s["name"] == "server.prewarm"
               for s in srv.flight_recorder.snapshot())
    events = agg.compile_events
    pay, want, _ = _payload(31, 14)
    assert agg.import_payload(pay) == (len(want), 0)
    srv.flush()
    assert srv.egress.settle(timeout_s=10.0)
    row = srv.flush_timeline.snapshot()[-1]
    assert row["set_rows_device"] == len(want)
    assert row["set_resident_bytes"] == PRESIZED * agg.sets.m
    assert row["set_upload_bytes"] == 0
    assert row["set_readback_bytes"] == 4 * PRESIZED
    assert row["set_import_sparse"] + row["set_import_dense"] == len(want)
    assert row["set_sync_ms"] > 0
    # the set programs compiled at boot; the flush brought the digest
    # program of the server's own timers at most
    assert {k[0] for k in agg._compiled_shapes
            if isinstance(k[0], str)} >= {"set_estimate_plane"}
    assert agg.compile_events - events <= 2
    stats = http_api.debug_vars(srv)
    assert stats["set_lanes"]["set_resident_bytes"] == PRESIZED * agg.sets.m
    assert stats["set_lanes"]["set_rows_device"] == len(want)
    assert stats["prewarm_programs"] == srv.prewarm_stats["programs"]


def test_default_boot_launches_nothing_new(server):
    srv = server()
    srv.start()
    assert not srv.aggregator.sets.resident
    assert srv.prewarm_stats["programs"] == 0
    assert not any(s["name"] == "server.prewarm"
                   for s in srv.flight_recorder.snapshot())
