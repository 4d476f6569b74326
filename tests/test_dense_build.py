"""The dense build of a digest flush: ONE routine (`DigestArena.build_dense`)
for the single operand, a skewed interval's two tiers and a mesh's floors,
ONE native call (vn_build_tiers) into operands the ARENA keeps, and one
plain numpy builder (`DigestArena.build_dense_numpy`) — the reference below,
and what builds where the native call declines.

(a) The build is bit-equal — value matrix, weight matrix or depth vector,
    minmax — to the numpy reference over each tier's own points: seeds x
    {uniform, weighted} x {unmeshed, a 2 x 2 mesh's padding with floors, 40
    and 600 deep rows} x what the kept buffers held; and at the shapes the
    benchmark's cells run, at 1, 2, 3 and 4 threads, over consecutive
    intervals whose rows shrink and grow.
(b) The kept buffers: a smaller interval leaves no stale cell, a deeper or
    wider one re-makes them and says so, a change of form follows; what the
    native call declines (no engine, a dtype it would round, corrupt
    staging) is built by the reference, and nothing kept is written.
(c) Keeping is safe: the next build waits for the launches that read the
    buffers, a forwarding tier's export operands are never the kept memory,
    and consecutive flushes answer as fresh-operand ones.
(d) `build_onepass` / `build_fresh_bytes` are on the timeline row and under
    /debug/vars.
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
from tests.test_hot_lane import _zipf_interval

PCTS = [0.5, 0.9, 0.99]
CAPACITY = 4096
DEEP_FLOORS = {"u_floor": arena_mod.DEEP_TIER_MIN_ROWS,
               "d_floor": arena_mod.DENSE_DEPTH_CAP}


def _arena(meshed: bool = False, capacity: int = CAPACITY):
    ar = arena_mod.DigestArena(capacity=capacity)
    if meshed:
        # the padding rules of a 2 x 2 mesh, without its devices
        ar.n_shards = ar.n_replicas = 2
    return ar


def _part(seed, n_tail, n_deep, tail_depth, weighted, capacity=CAPACITY,
          ragged=True):
    """A digest part as a snapshot hands it over: staged COO in shuffled
    arrival order over scattered row ids, `n_deep` of the touched rows
    past DEEP_TIER_THRESHOLD points (the deepest at DENSE_DEPTH_CAP; 0: no
    deep tier, one operand), the others at most `tail_depth` deep (one
    exactly); the deep rows' weights, and a weighted interval's, are ones
    float32 rounds (a cast on the wrong side would show)."""
    rng = np.random.default_rng([seed, 47])
    nd = n_tail + n_deep
    touched = np.sort(rng.choice(capacity, nd, replace=False))
    is_deep = np.zeros(nd, bool)
    deep = None
    if n_deep:
        deep = np.sort(rng.choice(nd, n_deep, replace=False))
        is_deep[deep] = True
    counts = np.where(is_deep, rng.integers(65, 160, nd),
                      rng.integers(1, tail_depth + 1, nd) if ragged
                      else tail_depth)
    if n_deep:
        counts[deep[rng.integers(n_deep)]] = arena_mod.DENSE_DEPTH_CAP
    counts[np.nonzero(~is_deep)[0][rng.integers(n_tail)]] = tail_depth
    n = int(counts.sum())
    order = rng.permutation(n)
    rows = np.repeat(touched, counts)[order].astype(np.int64)
    vals = rng.gamma(2.0, 10.0, n)
    wts = np.where(np.repeat(is_deep | weighted, counts)[order],
                   rng.integers(1, 9, n) / 3.0, 1.0)
    d_min = rng.random(nd)
    return {"staged": (rows, vals, wts), "rows": touched, "deep": deep,
            "uniform": not weighted and not n_deep,
            "shallow_uniform": not weighted,
            "d_min": d_min, "d_max": d_min + 50.0}


def _build(ar, part, **floors):
    """The one build, as the aggregator calls it, and what it says it did;
    then the caller's report (the launches are done: nobody reads)."""
    deep = part["deep"]
    built = ar.build_dense(
        part["staged"], part["rows"], part["d_min"], part["d_max"],
        uniform=part["uniform"] if deep is None else part["shallow_uniform"],
        sels=None if deep is None else ar.tier_rows(len(part["rows"]), deep),
        **floors)
    stats = ar.take_build_stats()
    ar.hold_dense([])
    return built, stats


def _reference(part, meshed=False, capacity=CAPACITY, **floors):
    """The plain form: the numpy builder, from a fresh arena, over each
    tier's own copy of the points."""
    ar = _arena(meshed, capacity)
    rows, vals, wts = part["staged"]
    touched, deep = part["rows"], part["deep"]
    if deep is None:
        return [ar.build_dense_numpy(
            part["staged"], touched, part["d_min"], part["d_max"],
            uniform=part["uniform"], **floors)]
    is_deep = np.zeros(capacity, bool)
    is_deep[touched[deep]] = True
    in_deep = is_deep[rows]
    tail = np.nonzero(~is_deep[touched])[0]
    return [ar.build_dense_numpy(
        (rows[mine], vals[mine], wts[mine]), touched[sel],
        part["d_min"][sel], part["d_max"][sel], uniform=uniform, **fl)
        for sel, mine, uniform, fl in (
            (tail, ~in_deep, part["shallow_uniform"], {}),
            (deep, in_deep, False, DEEP_FLOORS))]


def _same(got, want):
    assert len(got) == len(want)
    for g_tier, w_tier in zip(got, want):
        assert len(g_tier) == len(w_tier) == 3
        for g, w in zip(g_tier, w_tier):
            if w is None:
                assert g is None
            else:
                assert g.dtype == w.dtype and g.shape == w.shape
                assert np.array_equal(g, w)


def _no_engine(monkeypatch):
    """The native engine, patched away (a host without a compiler)."""
    def refuse():
        raise OSError("no native engine")
    monkeypatch.setattr(ingest_mod, "load_library", refuse)


def _decline(monkeypatch):
    """The native call, declining whatever it is asked."""
    monkeypatch.setattr(arena_mod.DigestArena, "_build_kept",
                        lambda *a, **k: None)


# -- (a) bit-equal to the numpy reference ------------------------------------

# the interval under test: (n_tail, n_deep, tail_depth), the floors a
# mesh's lockstep gives, and the shapes it must come out at
SHAPES = {
    "unmeshed": dict(dims=(300, 0, 13), floors={}, shapes=[(512, 16)]),
    "mesh2x2": dict(dims=(300, 0, 13),
                    floors={"u_floor": 400, "d_floor": 9},
                    shapes=[(512, 16)]),
    "deep40": dict(dims=(700, 40, 47), floors={},
                   shapes=[(1024, 64), (512, 512)]),
    "deep600": dict(dims=(700, 600, 47), floors={},
                    shapes=[(1024, 64), (1024, 512)]),
}
# what the kept buffers hold when the interval under test is built: made
# for it; left by an interval deeper and wider in every way inside the
# same shapes (stale cells past every new count, and in rows the new
# interval does not have); left by one of other shapes in every tier
KEPT = {"clean": None,
        "deeper_and_wider": dict(more_tail=1.4, tail_depth=1.3,
                                 more_deep=1.5),
        "another_shape": dict(more_tail=0.4, tail_depth=0.4,
                              more_deep=0.0)}


@pytest.mark.parametrize("kept", list(KEPT))
@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["uniform", "weighted"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_build_is_bit_equal_to_the_numpy_reference(seed, weighted,
                                                       shape, kept):
    """Every array of every triple, `np.array_equal`, against the numpy
    builder over each tier's own points — whatever the kept buffers
    held."""
    case = SHAPES[shape]
    meshed = shape == "mesh2x2"
    n_tail, n_deep, depth = case["dims"]
    ar = _arena(meshed)
    part = _part(seed, n_tail, n_deep, depth, weighted)
    want = _reference(part, meshed, **case["floors"])
    assert [t[0].shape for t in want] == case["shapes"]
    before = None
    if KEPT[kept] is not None:
        prev = KEPT[kept]
        last = _part(
            seed + 10, int(n_tail * prev["more_tail"]),
            n_deep and (int(n_deep * prev["more_deep"])
                        or (600 if n_deep == 40 else 40)),
            max(2, int(depth * prev["tail_depth"])), weighted)
        same_shapes = kept == "deeper_and_wider"
        built, stats = _build(ar, last,
                              **(case["floors"] if same_shapes else {}))
        assert stats["onepass"] == 1 and stats["fresh_bytes"] > 0
        for b, w in zip(built, want):
            assert (b[0].shape == w[0].shape) == same_shapes
        before = {id(b) for ops in ar._dense_keep for b in ops.values()}
    got, stats = _build(ar, part, **case["floors"])
    assert stats["onepass"] == 1
    if kept == "deeper_and_wider":
        assert stats["fresh_bytes"] == 0
        assert {id(b) for ops in ar._dense_keep
                for b in ops.values()} == before
    else:
        remade = sum(a.nbytes for tier in got for a in tier[:2]
                     if a.ndim == 2)
        assert stats["fresh_bytes"] >= remade
    _same(got, want)
    assert got[0][0] is ar._dense_keep[0]["dv"]
    if n_deep:
        assert got[1][1] is ar._dense_keep[1]["dw"]


# the operands the benchmark's cells build, three consecutive intervals
# each (n_tail, n_deep, tail_depth, ragged): rows shrink, then grow
CELLS = {
    # node1.fanout: 20,000+ keys, 4 samples each, uniform
    "fanout_32768x4": dict(
        capacity=32768, weighted=False, floors={}, shapes=[(32768, 4)],
        intervals=[(30000, 0, 4, True), (17000, 0, 3, True),
                   (32768, 0, 4, False)]),
    # fleet8.steady: forwarded digests, <= 256 weighted centroids a key
    "fleet8_2048x256": dict(
        capacity=2048, weighted=True, floors={}, shapes=[(2048, 256)],
        intervals=[(2000, 0, 256, True), (1100, 0, 200, True),
                   (2048, 0, 256, False)]),
    # mesh4.steady's padding rules and lockstep floors
    "mesh2x2_floors": dict(
        capacity=CAPACITY, weighted=True,
        floors={"u_floor": 400, "d_floor": 9}, shapes=[(512, 16)],
        intervals=[(300, 0, 13, True), (200, 0, 5, True),
                   (400, 0, 16, False)]),
    # zipf.hotset / zipf.churn: a long tail and a deep tier
    "zipf_tiers": dict(
        capacity=CAPACITY, weighted=False, floors={},
        shapes=[(1024, 64), (512, 512)],
        intervals=[(700, 40, 47, True), (600, 30, 40, True),
                   (900, 60, 60, True)]),
    # ... and an interval of theirs with no deep key
    "zipf_no_deep": dict(
        capacity=CAPACITY, weighted=False, floors={}, shapes=[(1024, 64)],
        intervals=[(700, 0, 47, True), (520, 0, 33, True),
                   (1000, 0, 64, True)]),
}


@pytest.mark.parametrize("threads", [1, 2, 3, 4])
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_cells_operands_over_consecutive_intervals(cell, threads,
                                                       monkeypatch):
    """Whatever the thread count: each interval bit-equal to the
    reference, no cell of the last one left, and nothing allocated after
    the first."""
    monkeypatch.setattr(ingest_mod, "BUILD_DENSE_THREADS", threads)
    # (every interval on the asked number of threads, the small ones too)
    monkeypatch.setattr(ingest_mod, "BUILD_POINTS_PER_THREAD", 1)
    case = CELLS[cell]
    meshed = cell.startswith("mesh")
    ar = _arena(meshed, case["capacity"])
    for i, (n_tail, n_deep, depth, ragged) in enumerate(case["intervals"]):
        part = _part(60 + i, n_tail, n_deep, depth, case["weighted"],
                     capacity=case["capacity"], ragged=ragged)
        got, stats = _build(ar, part, **case["floors"])
        assert [t[0].shape for t in got] == case["shapes"]
        assert stats["onepass"] == 1
        assert (stats["fresh_bytes"] == 0) == (i > 0)
        _same(got, _reference(part, meshed, case["capacity"],
                              **case["floors"]))


def test_a_build_takes_threads_only_where_it_has_points(monkeypatch):
    """A spawn and a join are not free: a build of a few points runs on
    the calling thread, one of 65,536 points or more on all four — the
    operands are the same either way (above)."""
    lib = ingest_mod.load_library()
    real, asked = lib.vn_build_tiers, []
    monkeypatch.setattr(
        lib, "vn_build_tiers",
        lambda *a: (asked.append(a[-2]), real(*a))[1], raising=False)
    for n_tail, depth, want in ((1, 1, 1), (300, 13, 1), (12000, 4, 2),
                                (32768, 4, ingest_mod.BUILD_DENSE_THREADS)):
        part = _part(3, n_tail, 0, depth, False, capacity=32768,
                     ragged=False)
        del asked[:]
        got, stats = _build(_arena(capacity=32768), part)
        assert stats["onepass"] == 1 and set(asked) == {want}
        _same(got, _reference(part, capacity=32768))


# -- (b) the kept buffers ------------------------------------------------------

@pytest.mark.parametrize("uniform", [True, False],
                         ids=["uniform", "weighted"])
def test_kept_buffers_hold_no_stale_cell_and_say_when_they_are_remade(
        uniform):
    ar = _arena()
    full = _part(5, 500, 0, 16, not uniform, ragged=False)
    first, stats = _build(ar, full)
    assert stats["onepass"] == 1
    assert stats["fresh_bytes"] >= first[0][0].nbytes
    kept = dict(ar._dense_keep[0])
    # (what a build returns is the caller's only until the next build)
    first = [[None if a is None else a.copy() for a in first[0]]]
    # a SMALLER interval (fewer rows, shallower) into the same buffers
    small = _part(6, 290, 0, 11, not uniform)
    got, stats = _build(ar, small)
    assert stats == {"onepass": 1, "fresh_bytes": 0}
    for name, buf in ar._dense_keep[0].items():
        assert buf is kept[name], name
    assert got[0][0] is kept["dv"]
    _same(got, _reference(small))
    # a deeper one re-makes the matrices, a wider one the vectors too
    deeper = _part(7, 290, 0, 30, not uniform)
    got, stats = _build(ar, deeper)
    assert got[0][0].shape == (512, 32) and stats["onepass"] == 1
    assert stats["fresh_bytes"] == got[0][0].nbytes * (1 if uniform else 2)
    _same(got, _reference(deeper))
    wider = _part(8, 700, 0, 30, not uniform)
    got, stats = _build(ar, wider)
    assert got[0][0].shape == (1024, 32)
    assert stats["fresh_bytes"] > got[0][0].nbytes * (1 if uniform else 2)
    _same(got, _reference(wider))
    # and back to the first shape: re-made again, nothing of `wider` left
    got, stats = _build(ar, full)
    assert stats["onepass"] == 1 and stats["fresh_bytes"] > 0
    _same(got, first)
    # a build nobody reported on (no hold_dense) is not the arena's to
    # rewrite: the next one makes its own buffers
    a = ar.build_dense(small["staged"], small["rows"], small["d_min"],
                       small["d_max"], uniform=uniform)
    b = ar.build_dense(small["staged"], small["rows"], small["d_min"],
                       small["d_max"], uniform=uniform)
    assert a[0][0] is not b[0][0] and np.array_equal(a[0][0], b[0][0])
    _same(b, _reference(small))


def test_the_build_follows_a_change_of_form_and_a_fallback(monkeypatch):
    """The same kept buffers through uniform and weighted tails, one and
    two tiers, and flushes the numpy builder made in between (fresh
    operands: the record of what the native pass left still holds): every
    interval bit-equal to the reference."""
    agg = _agg(initial_capacity=CAPACITY)
    for i, (weighted, native, n_deep) in enumerate([
            (False, True, 30), (True, True, 35), (False, True, 40),
            (True, False, 45), (True, True, 50), (False, False, 55),
            (False, True, 60), (False, True, 0), (True, True, 0),
            (True, False, 0), (False, True, 65), (False, True, 0)]):
        part = _part(20 + i, 500 + 60 * (i % 3), n_deep, 33 + 4 * (i % 4),
                     weighted)
        with monkeypatch.context() as m:
            if not native:
                _decline(m)
            tiers = agg._build_tiers(part)
        assert agg.digests.take_build_stats()["onepass"] == native
        agg.digests.hold_dense([])
        if n_deep:
            assert [(t["deep"], t["uniform"]) for t in tiers] \
                == [(False, not weighted), (True, False)]
            assert np.array_equal(tiers[1]["sel"], part["deep"])
        else:
            assert [(t["deep"], t["uniform"], t["sel"]) for t in tiers] \
                == [(False, not weighted, None)]
        _same([t["dense"] for t in tiers], _reference(part))


def _calls(monkeypatch):
    """The depth the first tier's operand had at each native call a build
    makes."""
    made = []
    real = ingest_mod.build_tiers
    monkeypatch.setattr(
        ingest_mod, "build_tiers",
        lambda *a: (made.append(a[-1][0][4]), real(*a))[1])
    return made


@pytest.mark.parametrize("n_deep", [0, 40], ids=["single", "tiered"])
def test_a_row_past_the_kept_depth_is_one_retry_not_a_fallback(
        n_deep, monkeypatch):
    ar = _arena()
    made = _calls(monkeypatch)
    shallow = _part(31, 700, n_deep, 30, False)
    built, stats = _build(ar, shallow)
    assert built[0][0].shape == (1024, 32)
    assert made == [0, 32]              # nothing kept: a count, then the fill
    del made[:]
    deeper = _part(32, 700, n_deep, 50, False)
    dv_deep = ar._dense_keep[1].get("dv")
    got, stats = _build(ar, deeper)
    assert made == [32, 64]             # refused at the kept depth, then made
    assert stats == {"onepass": 1, "fresh_bytes": 1024 * 64 * 4}
    assert got[0][0].shape == (1024, 64)
    assert ar._dense_keep[1].get("dv") is dv_deep
    _same(got, _reference(deeper))
    # and a shallower one after it: filled at the kept depth, which is
    # not the reference's shape for it, so once more at that
    del made[:]
    got, stats = _build(ar, shallow)
    assert made == [64, 32] and stats["onepass"] == 1
    _same(got, _reference(shallow))
    # the steady case is one call
    del made[:]
    _build(ar, _part(33, 650, n_deep and 44, 31, False))
    assert made == [32]


def test_a_row_past_the_operands_depth_is_refused_by_the_native_call():
    """The native call never writes past a row: a deepest row beyond the
    operands' depth returns the depth and fills nothing (the caller
    re-makes the operands), whatever the thread count."""
    part = _part(10, 300, 0, 20, False, capacity=2048)
    rows, vals, wts = part["staged"]
    touched, none = part["rows"], np.empty(0, np.int64)
    u_pad = 512
    row_map = np.empty(2048, np.int32)
    cursors = np.empty((ingest_mod.BUILD_DENSE_THREADS + 1) * u_pad,
                       np.int32)
    dv = np.full((u_pad, 16), 7.0, np.float32)
    depths = np.zeros(u_pad, np.int16)
    status, depth = ingest_mod.build_tiers(
        rows, vals, None, touched, none, row_map, cursors,
        [(dv, None, depths, u_pad, 16)])
    assert (status, depth) == (-1, (20, 0))
    assert (dv == 7.0).all() and not depths.any()
    status, depth = ingest_mod.build_tiers(
        rows, vals, None, touched, none, row_map, cursors,
        [(None, None, None, u_pad, 0)])
    assert (status, depth) == (-1, (20, 0))
    with pytest.raises(ValueError):
        ingest_mod.build_tiers(rows, vals.astype(np.float32), None, touched,
                               none, row_map, cursors,
                               [(None, None, None, u_pad, 0)])
    with pytest.raises(ValueError):         # a weighted tier, no weights
        ingest_mod.build_tiers(rows, vals, None, touched, none, row_map,
                               cursors, [(dv, dv.copy(), depths, u_pad, 16)])


def _spoil(kind, part):
    rows, vals, wts = part["staged"]
    rows = rows.copy()
    part = dict(part, staged=(rows, vals, wts))
    if kind == "negative_row":
        rows[7] = -3
    elif kind == "row_past_capacity":
        rows[7] = 1 << 40
    elif kind == "row_not_touched":
        rows[7] = np.setdiff1d(np.arange(CAPACITY), part["rows"])[0]
    elif kind == "deep_out_of_order":
        part["deep"] = part["deep"][::-1].copy()
    return part


DECLINED = ["engine_absent", "float64_eval", "negative_row",
            "row_past_capacity", "row_not_touched"]


@pytest.mark.parametrize("n_deep,kind", [(0, k) for k in DECLINED] + [
    (40, k) for k in DECLINED + ["deep_out_of_order"]])
def test_what_the_native_call_declines_is_built_by_the_reference(
        n_deep, kind, monkeypatch, caplog):
    """No engine, a dtype the native fill would round, corrupt staging:
    the native call writes nothing kept, and the build answers with the
    numpy builder's operands — corrupt points dropped loudly — or its
    exception."""
    part = _spoil(kind, _part(41, 700, n_deep, 47, False))

    def attempt(declined):
        ar = _arena()
        seeded, _ = _build(ar, _part(42, 900, n_deep and 60, 60, False))
        held = [a.copy() for tier in seeded for a in tier if a is not None]
        if kind == "float64_eval":
            ar.eval_dtype = ar.stage_dtype = np.dtype(np.float64)
        with monkeypatch.context() as m:
            if kind == "engine_absent":
                _no_engine(m)
            if declined:
                _decline(m)
            try:
                got, stats = _build(ar, part)
            except IndexError as e:         # the numpy builder's own answer
                return type(e)
        assert stats["onepass"] == 0 and stats["fresh_bytes"] > 0
        # declined before a kept cell was written
        now = [a for tier in seeded for a in tier if a is not None]
        assert all(np.array_equal(a, b) for a, b in zip(now, held))
        return got

    got, want = attempt(False), attempt(True)
    if isinstance(want, type):
        assert got is want
    else:
        _same(got, want)
        assert len(got) == (2 if n_deep else 1)
    if kind in ("negative_row", "row_past_capacity"):
        assert "out-of-bounds" in caplog.text
    elif kind in ("engine_absent", "deep_out_of_order"):
        _same(got, _reference(part))


# -- through the aggregator --------------------------------------------------

KEYS = 300
KINDS = ["unmeshed", "mesh2x2", "tiered"]
# the operands of `_feed`'s interval, per tier slot: (shape, weighted)
OPERANDS = {"unmeshed": [((512, 16), False)],
            "mesh2x2": [((512, 16), True)],
            "tiered": [((2048, 64), False), ((512, 512), True)]}


def _agg(mesh=None, is_local=False, **kw):
    kw.setdefault("initial_capacity", 2048)
    return MetricAggregator(percentiles=PCTS, is_local=is_local, mesh=mesh,
                            **kw)


def _mesh(kind):
    return mesh_mod.make_mesh(4, 2) if kind == "mesh2x2" else None


def _feed(agg, seed, kind="unmeshed", scope=MetricScope.LOCAL_ONLY):
    """One interval: KEYS keys of 16 samples each or — tiered — a Zipf
    draw over 2,000 keys, whose hottest stand past the deep threshold."""
    rng = np.random.default_rng([seed, 83])
    if kind == "tiered":
        keys, vals = _zipf_interval(seed)
    else:
        keys = np.repeat(np.arange(KEYS), 16)[rng.permutation(KEYS * 16)]
        vals = np.round(rng.gamma(2.0, 10.0, len(keys)), 3)
    with agg.lock:
        row_of = {k: agg.digests.row_for(
            MetricKey(f"t.{k}", "timer", ""), scope, [])
            for k in np.unique(keys).tolist()}
        agg.digests.sample_batch(
            np.asarray([row_of[k] for k in keys.tolist()], np.int64),
            vals, np.ones(len(vals)))
        agg.digests.sync()


def _timers(res) -> dict:
    return {m.name: m.value for m in res.metrics if m.name.startswith("t.")}


def _fresh_answer(seed, kind, monkeypatch):
    """What the interval answers from the reference's fresh operands."""
    agg = _agg(mesh=_mesh(kind))
    _feed(agg, seed, kind)
    with monkeypatch.context() as m:
        _decline(m)
        res = agg.flush(is_local=False)
    assert agg.last_flush_segments["build_onepass"] == 0
    return _timers(res)


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


def _seed_aligned_keep(agg, kind):
    """Kept operands of the interval's shapes that device_put may alias
    (all zeros, record 0: what a new buffer is)."""
    for keep, (shape, weighted) in zip(agg.digests._dense_keep,
                                       OPERANDS[kind]):
        keep["dv"] = _aligned(shape, np.float32)
        keep["depths"] = _aligned(shape[:1], np.int16)
        if weighted:
            keep["dw"] = _aligned(shape, np.float32)
    agg.digests.hold_dense([])


def _unseeded_bytes(agg) -> int:
    return sum(buf.nbytes for keep in agg.digests._dense_keep
               for name, buf in keep.items()
               if name in ("row_map", "cursors", "minmax"))


@pytest.mark.parametrize("kind", KINDS)
def test_consecutive_flushes_answer_as_fresh_operand_flushes(kind,
                                                             monkeypatch):
    """Three flushes of different content through _dispatch_flush /
    _fetch_flush, the later ones built into the first's buffers (which
    device_put may alias, and a global's program is given to donate)."""
    want = [_fresh_answer(seed, kind, monkeypatch) for seed in (21, 22, 23)]
    assert want[0] != want[1]
    agg = _agg(mesh=_mesh(kind))
    _seed_aligned_keep(agg, kind)
    kept = None
    for i, seed in enumerate((21, 22, 23)):
        _feed(agg, seed, kind)
        got = _timers(agg.flush(is_local=False))
        seg = agg.last_flush_segments
        assert seg["build_onepass"] == 1
        if kind != "mesh2x2":       # (a meshed flush builds no tiers)
            assert seg["dense_tiers"] == len(OPERANDS[kind])
        assert (seg["build_fresh_bytes"] > 0) == (i == 0)
        assert got == want[i]
        if kept is None:
            kept = [dict(keep) for keep in agg.digests._dense_keep]
    for keep, before in zip(agg.digests._dense_keep, kept):
        assert all(keep[n] is b for n, b in before.items())


@pytest.mark.parametrize("kind", KINDS)
def test_a_second_dispatch_waits_for_the_first_flushs_launch(kind,
                                                             monkeypatch):
    """Two flushes dispatched before either is fetched (the aggregator's
    API allows it; a served node's are serial), into kept buffers that
    device_put may alias: the first answers what it answers alone."""
    want = _fresh_answer(31, kind, monkeypatch)
    agg = _agg(mesh=_mesh(kind))
    _seed_aligned_keep(agg, kind)
    _feed(agg, 31, kind)
    pend_a = agg.flush_dispatch(is_local=False)
    assert agg.last_flush_segments["build_onepass"] == 1
    assert agg.last_flush_segments["build_fresh_bytes"] \
        == _unseeded_bytes(agg)
    launched = agg.digests._dense_readers
    assert launched
    # (on the CPU the first program is usually done before the second
    # build starts, so the answer alone would not show a missing wait)
    waited = []
    ready = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (waited.append(x), ready(x))[1])
    _feed(agg, 32, kind)
    pend_b = agg.flush_dispatch(is_local=False)
    assert any(w is launched for w in waited)
    assert agg.last_flush_segments["build_onepass"] == 1
    assert agg.last_flush_segments["build_fresh_bytes"] == 0
    assert _timers(pend_a.emit()) == want
    pend_b.emit()


def _export(res) -> dict:
    """A forwarding flush's exported digests: name -> (means, weights)."""
    return {f.name: (f.digest_means, f.digest_weights)
            for f in res.forward if f.digest_means is not None}


@pytest.mark.parametrize("kind", KINDS)
def test_a_forwarding_tiers_export_is_unaffected_by_the_next_build(
        kind, monkeypatch):
    """A forwarding tier keeps its dense operands on the device for the
    digest export, which runs at emit: build N+1 between flush N's
    dispatch and its emit must not reach them — where the device array
    aliased a kept buffer, the arena let go of it.  `unmeshed` is the
    small build (a [512, 16] operand) a size threshold once kept from
    the kept buffers."""
    def run(native, seeded):
        with monkeypatch.context() as m:
            if not native:
                _decline(m)
            agg = _agg(mesh=_mesh(kind), is_local=True)
            if seeded:
                _seed_aligned_keep(agg, kind)
            _feed(agg, 41, kind, scope=MetricScope.MIXED)
            pend_a = agg.flush_dispatch(is_local=True)
            seg_a = dict(agg.last_flush_segments)
            if seeded and kind != "mesh2x2" and _device_put_aliases():
                # each operand went up as one aligned piece (a mesh's
                # depth slices are not contiguous: device_put copies them)
                for keep, (_shape, weighted) in zip(
                        agg.digests._dense_keep, OPERANDS[kind]):
                    assert "dv" not in keep
                    assert not weighted or "dw" not in keep
            _feed(agg, 42, kind, scope=MetricScope.MIXED)
            pend_b = agg.flush_dispatch(is_local=True)
            seg_b = dict(agg.last_flush_segments)
            out = _export(pend_a.emit())
            pend_b.emit()
        return out, seg_a, seg_b

    want, seg, _ = run(False, False)
    assert seg["build_onepass"] == 0
    assert len(want) == KEYS if kind != "tiered" else len(want) > 1000
    got, seg_a, seg_b = run(True, True)
    assert seg_a["build_onepass"] == seg_b["build_onepass"] == 1
    assert got == want


@pytest.mark.parametrize("engine", ["native", "absent"])
def test_kept_operands_serve_the_next_flush_and_answer_the_same(
        engine, monkeypatch):
    """A tiered flush builds into the buffers the last one left — one
    native pass that zeroes only what the last build filled past a row's
    new count; without the engine the numpy builder makes each tier's
    operands anew and leaves the kept ones alone — and what an interval
    answers does not depend on what the buffers held, nor on which of
    the two built it."""
    fresh = _agg()
    _feed(fresh, 71, "tiered")
    want = _timers(fresh.flush(is_local=False))
    seg = fresh.last_flush_segments
    assert seg["build_onepass"] == 1 and seg["dense_tiers"] == 2
    assert seg["build_fresh_bytes"] >= 2048 * 64 * 4 + 2 * 512 * 512 * 4
    agg = _agg()
    _feed(agg, 72, "tiered")
    agg.flush(is_local=False)
    keeps = agg.digests._dense_keep
    kept = [dict(ops) for ops in keeps]
    assert {"dv", "depths"} <= set(keeps[0]) and "dw" not in keeps[0]
    assert {"dv", "dw", "depths", "minmax"} <= set(keeps[1])
    if engine == "absent":
        _no_engine(monkeypatch)
    _feed(agg, 71, "tiered")
    got = _timers(agg.flush(is_local=False))
    seg = agg.last_flush_segments
    assert seg["dense_tiers"] == 2
    assert seg["build_onepass"] == (engine == "native")
    assert (seg["build_fresh_bytes"] == 0) == (engine == "native")
    for ops, before in zip(keeps, kept):
        assert ops.keys() == before.keys()
        for name, buf in ops.items():
            assert buf is before[name], name     # the same memory
    assert got == want


@pytest.mark.parametrize("kind", ["unmeshed", "tiered"])
def test_row_fields_reach_the_timeline_row_and_debug_vars(kind,
                                                          monkeypatch):
    assert {"build_onepass", "build_fresh_bytes"} <= set(HOT_LEDGER_KEYS)
    assert {"build_onepass", "build_fresh_bytes"} <= ROW_ONLY_SEGMENT_KEYS
    sink = ChannelMetricSink()
    srv = Server(Config(
        statsd_listen_addresses=[], interval=60.0, percentiles=PCTS,
        hostname="onepass-test", native_ingest=False,
        arena_initial_capacity=2048), extra_metric_sinks=[sink])
    tiers = len(OPERANDS[kind])

    def flush(seed):
        _feed(srv.aggregator, seed, kind)
        srv.flush()
        assert srv.egress.settle(timeout_s=20.0)
        return srv.flush_timeline.snapshot()[-1]

    try:
        srv.start()
        # nothing allocated from the second flush on
        for i, seed in enumerate((51, 52, 53)):
            row = flush(seed)
            assert row["dense_tiers"] == tiers and row["build_onepass"] == 1
            assert (row["build_fresh_bytes"] == 0) == (i > 0)
        stats = http_api.debug_vars(srv)["hot_lane"]
        assert stats["build_onepass"] == 1 and stats["dense_tiers"] == tiers
        assert stats["build_fresh_bytes"] == 0
        # the numpy builder says so, and what it allocated
        with monkeypatch.context() as m:
            _decline(m)
            row = flush(54)
        assert row["build_onepass"] == 0 and row["build_fresh_bytes"] > 0
        # ... and left the kept operands as their record says: the next
        # native build allocates nothing
        row = flush(55)
        assert row["build_onepass"] == 1 and row["build_fresh_bytes"] == 0
        names = {m.name for batch in list(sink.queue.queue) for m in batch}
        assert not [n for n in names if "build_onepass" in n
                    or "build_fresh_bytes" in n]
    finally:
        srv.shutdown()
