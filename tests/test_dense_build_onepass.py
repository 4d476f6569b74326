"""The large dense build: ONE native pass (vn_build_dense) from the staged
COO into operands the arena keeps, engaged inside `build_dense` by the
padded operand bytes (`arena._ONEPASS_MIN_BYTES`).

(a) It is bit-equal — value matrix, weight matrix or depth vector, minmax
    — to the numpy builder and to the native fill the smaller builds keep,
    over seeds x {uniform, weighted} x {unmeshed, a 2 x 2 mesh's padding
    with floors} on both sides of the constant (monkeypatched low: the
    CPU run is small).
(b) The kept buffers: a smaller interval leaves no stale cell, a deeper
    or wider one re-makes them and says so; corrupt staging falls back
    and answers as the parent does.
(c) Keeping is safe: the next build waits for the launches that read the
    buffers, a forwarding tier's export operands are never the kept
    memory, and consecutive meshed flushes answer as fresh-operand ones.
(d) `build_onepass` / `build_fresh_bytes` are on the timeline row and
    under /debug/vars.
"""

import jax
import numpy as np
import pytest

from veneur_tpu import http_api
from veneur_tpu import ingest as ingest_mod
from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.aggregator import (HOT_LEDGER_KEYS,
                                        ROW_ONLY_SEGMENT_KEYS,
                                        MetricAggregator)
from veneur_tpu.core.server import Server
from veneur_tpu.config import Config
from veneur_tpu.parallel import mesh as mesh_mod
from veneur_tpu.samplers.metric_key import MetricKey, MetricScope
from veneur_tpu.sinks.simple import ChannelMetricSink

PCTS = [0.5, 0.9, 0.99]
# the size is judged from the MEAN depth (the deepest row holds at least
# that): the ragged intervals below average ~7 points a row, so a
# uniform [512, 8] sits ON the constant and 128 rows below it
LOW = 512 * 8 * 4
NEVER = 1 << 60


def _arena(meshed: bool, capacity: int = 2048):
    ar = arena_mod.DigestArena(capacity=capacity)
    if meshed:
        # the padding rules of a 2 x 2 mesh, without its devices
        ar.n_shards = ar.n_replicas = 2
    return ar


def _interval(seed, n_rows, depth, capacity=2048, ragged=True):
    """Staged COO in shuffled arrival order over `n_rows` touched rows
    (scattered row ids), the deepest exactly `depth`; weights that
    float32 rounds (so a cast moved to the wrong side would show)."""
    rng = np.random.default_rng([seed, 38])
    touched = np.sort(rng.choice(capacity, n_rows, replace=False))
    counts = (rng.integers(1, depth + 1, n_rows) if ragged
              else np.full(n_rows, depth))
    counts[rng.integers(n_rows)] = depth
    rows = np.repeat(touched, counts)[rng.permutation(int(counts.sum()))]
    vals = rng.gamma(2.0, 10.0, len(rows))
    wts = rng.integers(1, 9, len(rows)) / 3.0
    d_min = rng.random(n_rows)
    return (rows.astype(np.int64), vals, wts), touched, d_min, d_min + 50.0


def _build(ar, interval, uniform, floors, monkeypatch, limit):
    monkeypatch.setattr(arena_mod, "_ONEPASS_MIN_BYTES", limit)
    staged, touched, d_min, d_max = interval
    out = ar.build_dense(staged, touched, d_min, d_max, uniform=uniform,
                         **floors)
    stats = ar.take_build_stats()
    ar.hold_dense([])
    return out, stats


def _same(got, want):
    assert len(got) == len(want) == 3
    for g, w in zip(got, want):
        if w is None:
            assert g is None
        else:
            assert g.dtype == w.dtype and g.shape == w.shape
            assert np.array_equal(g, w)


@pytest.mark.parametrize("meshed", [False, True], ids=["unmeshed", "mesh2x2"])
@pytest.mark.parametrize("uniform", [True, False],
                         ids=["uniform", "weighted"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_one_pass_is_bit_equal_to_the_other_builders(seed, uniform, meshed,
                                                     monkeypatch):
    """Above the constant the one-pass build, against the numpy builder
    (the native fill's floor raised out of reach) and the native fill;
    below it the build IS the parent's, and says so."""
    floors = {"u_floor": 400, "d_floor": 9} if meshed else {}
    interval = _interval(seed, 300, 13)
    got, stats = _build(_arena(meshed), interval, uniform, floors,
                        monkeypatch, LOW)
    assert stats["onepass"] == 1
    assert got[0].shape == (512, 16)
    native, nstats = _build(_arena(meshed), interval, uniform, floors,
                            monkeypatch, NEVER)
    assert nstats["onepass"] == 0
    _same(got, native)
    monkeypatch.setattr(arena_mod, "_NATIVE_FILL_MIN", NEVER)
    numpy_built, _ = _build(_arena(meshed), interval, uniform, floors,
                            monkeypatch, NEVER)
    _same(got, numpy_built)
    # the other side of the constant: a quarter of the rows (a weighted
    # build counts twice), the parent's code
    small = _interval(seed, 100, 13)
    ar = _arena(meshed)
    floors = {"u_floor": 120, "d_floor": 5} if meshed else {}
    below, bstats = _build(ar, small, uniform, floors, monkeypatch, LOW)
    assert below[0].shape == (128, 16)
    assert bstats["onepass"] == 0 and bstats["fresh_bytes"] > 0
    assert not ar._dense_keep
    _same(below, _build(_arena(meshed), small, uniform, floors,
                        monkeypatch, NEVER)[0])


@pytest.mark.parametrize("uniform", [True, False],
                         ids=["uniform", "weighted"])
def test_kept_buffers_hold_no_stale_cell_and_say_when_they_are_remade(
        uniform, monkeypatch):
    ar = _arena(False)
    full = _interval(5, 500, 16, ragged=False)
    first, stats = _build(ar, full, uniform, {}, monkeypatch, LOW)
    assert stats["onepass"] == 1
    assert stats["fresh_bytes"] >= first[0].nbytes
    kept = dict(ar._dense_keep)
    # (what a build returns is the caller's only until the next build)
    first = [None if a is None else a.copy() for a in first]
    # a SMALLER interval (fewer rows, shallower) into the same buffers
    small = _interval(6, 290, 11)
    got, stats = _build(ar, small, uniform, {}, monkeypatch, LOW)
    assert stats == {"onepass": 1, "fresh_bytes": 0}
    for name, buf in ar._dense_keep.items():
        assert buf is kept[name], name
    assert got[0] is kept["dv"]
    _same(got, _build(_arena(False), small, uniform, {}, monkeypatch,
                      NEVER)[0])
    # a deeper one re-makes the matrices, a wider one the vectors too
    deeper = _interval(7, 290, 30)
    got, stats = _build(ar, deeper, uniform, {}, monkeypatch, LOW)
    assert got[0].shape == (512, 32) and stats["onepass"] == 1
    assert stats["fresh_bytes"] == got[0].nbytes * (1 if uniform else 2)
    _same(got, _build(_arena(False), deeper, uniform, {}, monkeypatch,
                      NEVER)[0])
    wider = _interval(8, 700, 30)
    got, stats = _build(ar, wider, uniform, {}, monkeypatch, LOW)
    assert got[0].shape == (1024, 32)
    assert stats["fresh_bytes"] > got[0].nbytes * (1 if uniform else 2)
    _same(got, _build(_arena(False), wider, uniform, {}, monkeypatch,
                      NEVER)[0])
    # and back to the first shape: re-made again, nothing of `wider` left
    got, stats = _build(ar, full, uniform, {}, monkeypatch, LOW)
    assert stats["onepass"] == 1 and stats["fresh_bytes"] > 0
    _same(got, first)


def _corrupt(kind, interval):
    (rows, vals, wts), touched, d_min, d_max = interval
    rows = rows.copy()
    if kind == "negative_row":
        rows[7] = -3
    elif kind == "row_past_capacity":
        rows[7] = 1 << 40
    else:                                   # a staged row outside `touched`
        rows[7] = np.setdiff1d(np.arange(2048), touched)[0]
    return (rows, vals, wts), touched, d_min, d_max


@pytest.mark.parametrize("kind", ["negative_row", "row_past_capacity",
                                  "row_not_touched"])
def test_corrupt_staging_falls_back_and_answers_as_the_parent(
        kind, monkeypatch, caplog):
    bad = _corrupt(kind, _interval(9, 400, 16))

    def attempt(limit):
        ar = _arena(False)
        try:
            return _build(ar, bad, False, {}, monkeypatch, limit)
        except IndexError as e:             # the numpy builder's own answer
            return type(e), ar.take_build_stats()

    got, stats = attempt(LOW)
    want, _ = attempt(NEVER)
    assert stats["onepass"] == 0
    if isinstance(want, type):
        assert got is want
    else:
        _same(got, want)
        assert "out-of-bounds" in caplog.text


def test_a_row_past_the_kept_depth_is_refused_by_the_native_call():
    """The native call never writes past a row: a deepest row beyond the
    operands' depth returns the depth and fills nothing (the caller
    re-makes the operands), whatever the thread count."""
    (rows, vals, wts), touched, _lo, _hi = _interval(10, 300, 20)
    u_pad = 512
    row_map = np.empty(2048, np.int32)
    cursors = np.empty(ingest_mod.BUILD_DENSE_THREADS * u_pad, np.int32)
    dv = np.full((u_pad, 16), 7.0, np.float32)
    depths = np.zeros(u_pad, np.int16)
    status, depth = ingest_mod.build_dense(
        rows, vals, None, touched, row_map, cursors, dv, None, depths,
        u_pad, 16)
    assert (status, depth) == (-1, 20)
    assert (dv == 7.0).all()
    status, depth = ingest_mod.build_dense(
        rows, vals, None, touched, row_map, cursors, None, None, None,
        u_pad, 0)
    assert (status, depth) == (-1, 20)
    with pytest.raises(ValueError):
        ingest_mod.build_dense(rows, vals.astype(np.float32), None, touched,
                               row_map, cursors, None, None, None, u_pad, 0)


# -- through the aggregator --------------------------------------------------

KEYS = 300


def _agg(mesh=None, is_local=False, **kw):
    return MetricAggregator(percentiles=PCTS, is_local=is_local, mesh=mesh,
                            initial_capacity=2048, **kw)


def _mesh():
    return mesh_mod.make_mesh(4, 2)


def _feed(agg, seed, keys=KEYS, depth=16, scope=MetricScope.LOCAL_ONLY):
    rng = np.random.default_rng([seed, 83])
    with agg.lock:
        rows = np.asarray([agg.digests.row_for(
            MetricKey(f"t.{k}", "timer", ""), scope, [])
            for k in range(keys)], np.int64)
        rows = np.repeat(rows, depth)[rng.permutation(keys * depth)]
        vals = np.round(rng.gamma(2.0, 10.0, len(rows)), 3)
        agg.digests.sample_batch(rows, vals, np.ones(len(rows)))
        agg.digests.sync()


def _timers(res) -> dict:
    return {m.name: m.value for m in res.metrics if m.name.startswith("t.")}


def _fresh_answer(seed, **kw):
    """What the interval answers from fresh operands (the parent's)."""
    agg = _agg(**kw)
    _feed(agg, seed)
    res = agg.flush(is_local=agg.is_local)
    assert agg.last_flush_segments["build_onepass"] == 0
    return _timers(res)


@pytest.mark.parametrize("meshed", [False, True], ids=["unmeshed", "mesh2x2"])
def test_consecutive_flushes_answer_as_fresh_operand_flushes(meshed,
                                                             monkeypatch):
    """Two flushes of different content through _dispatch_flush /
    _fetch_flush, the second built into the first's buffers."""
    mesh = _mesh() if meshed else None
    want = [_fresh_answer(seed, mesh=mesh) for seed in (21, 22)]
    assert want[0] != want[1]
    monkeypatch.setattr(arena_mod, "_ONEPASS_MIN_BYTES", LOW)
    agg = _agg(mesh=mesh)
    kept = None
    for i, seed in enumerate((21, 22)):
        _feed(agg, seed)
        got = _timers(agg.flush(is_local=False))
        seg = agg.last_flush_segments
        assert seg["build_onepass"] == 1
        assert (seg["build_fresh_bytes"] > 0) == (i == 0)
        assert got == want[i]
        if kept is None:
            kept = dict(agg.digests._dense_keep)
    assert all(agg.digests._dense_keep[n] is b for n, b in kept.items())


def _aligned(shape, dtype):
    """A buffer device_put MAY alias on the CPU backend (64-byte
    aligned; numpy's own large arrays sit at 16 mod 64)."""
    n = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.zeros(n + 64, np.uint8)
    off = (-raw.ctypes.data) % 64
    return raw[off:off + n].view(dtype).reshape(shape)


def _device_put_aliases() -> bool:
    probe = _aligned((64, 16), np.float32)
    return jax.device_put(probe).unsafe_buffer_pointer() == probe.ctypes.data


def _seed_aligned_keep(agg, shape, uniform):
    keep = agg.digests._dense_keep
    keep["dv"] = _aligned(shape, np.float32)
    keep["depths"] = _aligned(shape[:1], np.int16)
    if not uniform:
        keep["dw"] = _aligned(shape, np.float32)
    agg.digests.hold_dense([])


@pytest.mark.parametrize("meshed", [False, True], ids=["unmeshed", "mesh2x2"])
def test_a_second_dispatch_waits_for_the_first_flushs_launch(meshed,
                                                             monkeypatch):
    """Two flushes dispatched before either is fetched (the aggregator's
    API allows it; a served node's are serial), into kept buffers that
    device_put may alias: the first answers what it answers alone."""
    mesh = _mesh() if meshed else None
    want = _fresh_answer(31, mesh=mesh)
    monkeypatch.setattr(arena_mod, "_ONEPASS_MIN_BYTES", LOW)
    agg = _agg(mesh=mesh)
    _seed_aligned_keep(agg, (512, 16), uniform=not meshed)
    _feed(agg, 31)
    pend_a = agg.flush_dispatch(is_local=False)
    assert agg.last_flush_segments["build_onepass"] == 1
    assert agg.last_flush_segments["build_fresh_bytes"] \
        == agg.digests._dense_keep["row_map"].nbytes \
        + agg.digests._dense_keep["cursors"].nbytes \
        + (agg.digests._dense_keep["minmax"].nbytes if meshed else 0)
    launched = agg.digests._dense_readers
    assert launched
    # (on the CPU the first program is usually done before the second
    # build starts, so the answer alone would not show a missing wait)
    waited = []
    ready = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (waited.append(x), ready(x))[1])
    _feed(agg, 32)
    pend_b = agg.flush_dispatch(is_local=False)
    assert any(w is launched for w in waited)
    assert agg.last_flush_segments["build_onepass"] == 1
    assert agg.last_flush_segments["build_fresh_bytes"] == 0
    assert _timers(pend_a.emit()) == want
    pend_b.emit()


@pytest.mark.parametrize("meshed", [False, True], ids=["unmeshed", "mesh2x2"])
def test_a_forwarding_tiers_export_is_unaffected_by_the_next_build(
        meshed, monkeypatch):
    """A forwarding tier keeps its dense operands on the device for the
    digest export, which runs at emit: build N+1 between flush N's
    dispatch and its emit must not reach them — where the device array
    aliased the kept buffer, the arena let go of it."""
    mesh = _mesh() if meshed else None

    def run(limit, seeded):
        monkeypatch.setattr(arena_mod, "_ONEPASS_MIN_BYTES", limit)
        agg = _agg(mesh=mesh, is_local=True)
        if seeded:
            _seed_aligned_keep(agg, (512, 16), uniform=not meshed)
        _feed(agg, 41, scope=MetricScope.MIXED)
        pend_a = agg.flush_dispatch(is_local=True)
        seg_a = dict(agg.last_flush_segments)
        if seeded and not meshed and _device_put_aliases():
            # the whole [512, 16] went up as one aligned piece (a mesh's
            # depth slices are not contiguous: device_put copies them)
            assert "dv" not in agg.digests._dense_keep
        _feed(agg, 42, scope=MetricScope.MIXED)
        pend_b = agg.flush_dispatch(is_local=True)
        seg_b = dict(agg.last_flush_segments)
        out = _export(pend_a.emit())
        pend_b.emit()
        return out, seg_a, seg_b

    want, seg, _ = run(NEVER, False)
    assert seg["build_onepass"] == 0 and len(want) == KEYS
    got, seg_a, seg_b = run(LOW, True)
    assert seg_a["build_onepass"] == seg_b["build_onepass"] == 1
    assert got == want


def _export(res) -> dict:
    """A forwarding flush's exported digests: name -> (means, weights)."""
    return {f.name: (f.digest_means, f.digest_weights)
            for f in res.forward if f.digest_means is not None}


def test_row_fields_reach_the_timeline_row_and_debug_vars(monkeypatch):
    assert {"build_onepass", "build_fresh_bytes"} <= set(HOT_LEDGER_KEYS)
    assert {"build_onepass", "build_fresh_bytes"} <= ROW_ONLY_SEGMENT_KEYS
    sink = ChannelMetricSink()
    srv = Server(Config(
        statsd_listen_addresses=[], interval=60.0, percentiles=PCTS,
        hostname="onepass-test", native_ingest=False,
        arena_initial_capacity=2048), extra_metric_sinks=[sink])
    try:
        srv.start()
        agg = srv.aggregator
        # below the constant: the parent's build, whatever it allocates
        _feed(agg, 51)
        srv.flush()
        assert srv.egress.settle(timeout_s=20.0)
        row = srv.flush_timeline.snapshot()[-1]
        assert row["build_onepass"] == 0 and row["build_fresh_bytes"] > 0
        below = row["build_fresh_bytes"]
        monkeypatch.setattr(arena_mod, "_ONEPASS_MIN_BYTES", LOW)
        for want_fresh in (True, False):
            _feed(agg, 52)
            srv.flush()
            assert srv.egress.settle(timeout_s=20.0)
            row = srv.flush_timeline.snapshot()[-1]
            assert row["build_onepass"] == 1
            assert (row["build_fresh_bytes"] > 0) == want_fresh
        stats = http_api.debug_vars(srv)["hot_lane"]
        assert stats["build_onepass"] == 1
        assert stats["build_fresh_bytes"] == 0
        # and back below it: unchanged from the first flush
        monkeypatch.setattr(arena_mod, "_ONEPASS_MIN_BYTES", NEVER)
        _feed(agg, 53)
        srv.flush()
        assert srv.egress.settle(timeout_s=20.0)
        row = srv.flush_timeline.snapshot()[-1]
        assert row["build_onepass"] == 0
        # (the server's own flush timers stage a point or two more)
        assert below <= row["build_fresh_bytes"] <= below + 64
        names = {m.name for batch in list(sink.queue.queue) for m in batch}
        assert not [n for n in names if "build_onepass" in n
                    or "build_fresh_bytes" in n]
    finally:
        srv.shutdown()
