"""The hot-key lane: a node whose timer keys are Zipfian.

(a) `serving.partial_digests` at the lane's one tile shape against the
    float64 compress the benchmark keeps (benchmark/reference/
    tdigest_compress.py — it imports nothing of the program), centroid
    for centroid.
(b) An aggregator fed a Zipf interval in shuffled ticks: shallow keys are
    numpy's hazen percentile, hot keys sit inside the rank limits the
    benchmark's cell holds them to, count / min / max are exact, and the
    tiered flush answers what the single-operand flush (the parent's
    `build_dense` over every row) answers on the same staged points.
(c) The set of shapes a flush and a pre-reduce may launch is closed: the
    enumeration below, and after the boot's launches a second Zipf seed
    compiles nothing.
(d) The tiers' operands come from ONE native pass over the staged points
    (`DigestArena.build_tiers` -> vn_build_tiers) into the buffers the
    aggregator keeps, zeroed only where the last build filled past a
    row's new count: bit-equal to the two `build_dense(keep=)` calls it
    replaced — which stay as its fallback — whatever the buffers held;
    the row says `build_onepass` / `build_fresh_bytes`.
"""

import importlib.util
import os

import numpy as np
import pytest

from veneur_tpu import ingest as ingest_mod
from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.aggregator import MetricAggregator
from veneur_tpu.parallel import serving
from veneur_tpu.samplers.metric_key import MetricKey, MetricScope
from veneur_tpu.sketches import tdigest as td
from tests.test_dense_build_onepass import (_aligned, _device_put_aliases,
                                            _export)

REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "reference")


def _ref(name):
    spec = importlib.util.spec_from_file_location(
        f"hot_lane_ref_{name}", os.path.join(REF, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tc = _ref("tdigest_compress")
rule = _ref("tdigest_rule")

DELTA = 100.0
CAP = td.centroid_capacity(DELTA)
TILE = (arena_mod.HOT_TILE_ROWS, arena_mod.HOT_TILE_WIDTH)
PCTS = [0.5, 0.9, 0.99]
EPS32 = 2.0 ** -24


# -- (a) the compress at the tile's shape -----------------------------------

def _tile(seed):
    """A tile as a drain tick fills it: rows of every fill from one point
    to the full width, each a key's <= ccap weighted centroids (what the
    last pass left) followed by unit-weight samples."""
    rng = np.random.default_rng(seed)
    dv = np.zeros(TILE, np.float32)
    dw = np.zeros(TILE, np.float32)
    fills = np.concatenate([[1, 2, 513, TILE[1]],
                            rng.integers(514, TILE[1], TILE[0] - 6), [0, 0]])
    for r, n in enumerate(fills):
        vals = np.round(rng.gamma(2.0, 10.0, n), 3)
        wts = np.ones(n)
        k = min(n // 2, 140) if r % 2 else 0
        wts[:k] = rng.integers(1, 120, k)
        dv[r, :n], dw[r, :n] = vals, wts
    return dv, dw


@pytest.mark.parametrize("seed", [3, 4])
def test_tile_compress_is_the_float64_compress(seed):
    """Centroid for centroid.  What float32 costs, and is allowed:

    * a point whose scale value k(q_left) lies within float32 rounding of
      an integer may fall in the neighbouring cluster (k <= 150 carries
      ~1e-5 of absolute rounding, so one point in ~1e5 does): the two
      clusters then differ by that point's weight.  Rows with such a flip
      are held to that — cumulative weights never further apart than the
      heaviest point — and must be few;
    * a centroid's mean is a difference of two float32 prefix sums of
      weight x mean over the row: each carries up to a few ulps of the
      row's whole sum S (4,096 columns of ~20 are S ~ 8e4, ulp 0.008),
      so the mean of a cluster of weight w may be off by ~8 eps S / w —
      for a TAIL centroid of one sample ~0.04 in value, 4e-4 of a span of
      100, i.e. (at the tail's density of ~1e-3 per unit) under 1e-4 in
      rank, a twentieth of the p99 cluster's width.  Tested below at that
      bound, and the total weight is conserved to the last unit."""
    dv, dw = _tile(seed)
    pm, pw = serving.partial_digests(dv, dw, DELTA, CAP)
    pm, pw = np.asarray(pm, np.float64), np.asarray(pw, np.float64)
    assert pm.shape == (TILE[0], CAP)
    flipped, worst = 0, 0.0
    for r in range(TILE[0]):
        m64, w64 = tc.compress(dv[r], dw[r], DELTA, CAP)
        occ = pw[r] > 0
        assert pw[r].sum() == dw[r].astype(np.float64).sum() == w64.sum()
        assert not occ[occ.sum():].any()          # empties packed last
        m32, w32 = pm[r][occ], pw[r][occ]
        assert (np.diff(m32) >= 0).all()
        if not len(w64):
            assert not len(w32)         # an empty row stays empty
            continue
        if len(w32) == len(w64) and np.array_equal(w32, w64):
            s_row = float((dw[r].astype(np.float64) * dv[r]).sum())
            tol = 8 * EPS32 * s_row / w64 + 1e-6
            err = np.abs(m32 - m64)
            assert (err <= tol).all(), (r, float((err / tol).max()))
            worst = max(worst, float((err / tol).max()))
            continue
        flipped += 1
        # the same points, one of them on the other side of a boundary:
        # every cluster boundary (in cumulative weight) of the one has a
        # boundary of the other no further off than the heaviest point
        cum32, cum64 = np.cumsum(w32), np.cumsum(w64)
        for a, b in ((cum32, cum64), (cum64, cum32)):
            near = np.abs(a[:, None] - b[None, :]).min(axis=1)
            assert near.max() <= float(dw[r].max()), (r, near.max())
    assert flipped <= TILE[0] // 4
    assert worst > 0        # float32 did cost something: the bound is live


def test_singletons_under_the_cap_come_back_as_singletons():
    """A row no deeper than the clusters are many is its own digest:
    every point its own cluster of weight 1, its mean off by the prefix
    sums' rounding and no more (the float64 compress returns the row
    bit for bit: benchmark/tests/test_tdigest_compress.py)."""
    dv = np.zeros(TILE, np.float32)
    dw = np.zeros(TILE, np.float32)
    vals = np.sort(np.random.default_rng(1).gamma(2.0, 10.0, 40))
    dv[0, :40], dw[0, :40] = vals, 1.0
    pm, pw = serving.partial_digests(dv, dw, DELTA, CAP)
    np.testing.assert_array_equal(np.asarray(pw)[0, :40], 1.0)
    np.testing.assert_allclose(np.asarray(pm)[0, :40], vals, rtol=0,
                               atol=8 * EPS32 * vals.sum())
    assert not np.asarray(pw)[1:].any()


# -- (b) a Zipf interval through the aggregator ------------------------------

N_KEYS, N_SAMPLES = 2000, 40_000


def _zipf_interval(seed, n_keys=N_KEYS, n_samples=N_SAMPLES):
    """(key of each sample in arrival order, value): counts a multinomial
    draw with p(rank r) ~ r ** -0.99, rank -> key a seeded permutation,
    arrival order shuffled."""
    rng = np.random.default_rng([seed, 17])
    p = np.arange(1, n_keys + 1, dtype=np.float64) ** -0.99
    counts = rng.multinomial(n_samples, p / p.sum())
    keys = np.repeat(rng.permutation(n_keys), counts)[
        rng.permutation(n_samples)]
    return keys, np.round(rng.gamma(2.0, 10.0, n_samples), 3)


def _agg(**kw):
    kw.setdefault("initial_capacity", 2048)
    return MetricAggregator(percentiles=PCTS, is_local=False, **kw)


def _feed(agg, keys, vals, ticks=5):
    """The interval in `ticks` drain ticks, each followed by a sync."""
    with agg.lock:
        row_of = {}
        for k in np.unique(keys).tolist():
            row_of[k] = agg.digests.row_for(
                MetricKey(f"t.{k}", "timer", ""), MetricScope.LOCAL_ONLY, [])
    rows = np.asarray([row_of[k] for k in keys.tolist()], np.int64)
    for r, v in zip(np.array_split(rows, ticks), np.array_split(vals, ticks)):
        with agg.lock:
            agg.digests.sample_batch(r, v, np.ones(len(v)))
            agg.digests.sync()


def _flush(agg):
    res = agg.flush(is_local=False)
    return {m.name: m.value for m in res.metrics}, agg.last_flush_segments


@pytest.fixture(scope="module")
def zipf_run():
    keys, vals = _zipf_interval(41)
    agg = _agg()
    _feed(agg, keys, vals)
    by, seg = _flush(agg)
    return keys, vals, by, dict(seg), agg


def test_zipf_interval_engages_the_lane(zipf_run):
    _keys, _vals, _by, seg, agg = zipf_run
    assert seg["dense_tiers"] == 2
    assert seg["hot_keys"] >= 5 and seg["hot_compress_launches"] >= 5
    assert seg["hot_points_in"] > seg["hot_points_out"] > 0
    assert seg["hot_compress_held_s"] > 0
    assert seg["hot_compress_tile_bytes"] == 2 * 4 * TILE[0] * (
        TILE[1] + CAP) == agg.digests.hot_tile_bytes
    # the tiers' padded elements, against the single operand's
    # [2048, 512]: the fill the cell reports as flush_dense_fill
    assert seg["dense_elems"] == 2048 * 64 + 512 * 512
    assert seg["staged_points"] / seg["dense_elems"] \
        > 2.5 * seg["staged_points"] / (2048 * 512)
    # every launch was of the closed set's shapes
    assert ("hot_compress", TILE) in agg._compiled_shapes
    assert ("deep_tier", (512, 512), True) in agg._compiled_shapes
    assert ((2048, 64), True, True) in agg._compiled_shapes


def test_zipf_interval_answers(zipf_run):
    keys, vals, by, _seg, _agg_ = zipf_run
    order = np.argsort(keys, kind="stable")
    ks, vs = keys[order], vals[order]
    starts = np.searchsorted(ks, np.arange(N_KEYS + 1))
    n_hot = 0
    worst = {q: 0.0 for q in PCTS}
    for k in np.unique(keys).tolist():
        s = np.sort(vs[starts[k]:starts[k + 1]])
        assert by[f"t.{k}.count"] == len(s)
        assert by[f"t.{k}.min"] == s[0] and by[f"t.{k}.max"] == s[-1]
        span = (s[-1] - s[0]) or 1.0
        for q in PCTS:
            have = by[f"t.{k}.{int(q * 100)}percentile"]
            if len(s) <= arena_mod.DENSE_DEPTH_CAP:
                # never compressed: every sample a singleton centroid
                want = np.percentile(s, q * 100, method="hazen")
                assert abs(have - want) / span <= 1e-5, (k, q, len(s))
            else:
                worst[q] = max(worst[q], tc.rank_error(s, have, q))
        n_hot += len(s) > arena_mod.DENSE_DEPTH_CAP
    assert n_hot >= 5
    # the cell's limits (benchmark/configs/node1-zipf.json): one cluster
    # width of the scale function at q
    for q in PCTS:
        assert worst[q] <= tc.cluster_width(q, DELTA), (q, worst[q])


def test_tiered_flush_is_the_single_operand_flush(zipf_run, monkeypatch):
    """The same staged points through the parent's plain form —
    build_dense over every touched row, one weighted [2048, 512] operand
    — answer the same numbers."""
    keys, vals, by, _seg, _agg_ = zipf_run
    monkeypatch.setattr(arena_mod.DigestArena, "deep_rows",
                        lambda self, rows: None)
    agg = _agg()
    _feed(agg, keys, vals)
    single, seg = _flush(agg)
    assert seg["dense_tiers"] == 1 and seg["dense_elems"] == 2048 * 512
    assert ((2048, 512), False, True) in agg._compiled_shapes
    assert single.keys() == by.keys()
    names = sorted(n for n in by if n.startswith("t."))
    a = np.asarray([by[n] for n in names])
    b = np.asarray([single[n] for n in names])
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_a_forwarding_local_exports_from_both_tiers():
    """A local that forwards its histograms compresses each forwarded
    row's staged points from the tier that holds it: the wire centroids'
    weights add up to the samples sent, hot key or not."""
    keys, vals = _zipf_interval(43)
    agg = MetricAggregator(percentiles=PCTS, is_local=True,
                           initial_capacity=2048)
    with agg.lock:
        row_of = {k: agg.digests.row_for(
            MetricKey(f"t.{k}", "timer", ""), MetricScope.MIXED, [])
            for k in np.unique(keys).tolist()}
        agg.digests.sample_batch(
            np.asarray([row_of[k] for k in keys.tolist()], np.int64),
            vals, np.ones(len(vals)))
    res = agg.flush(is_local=True)
    assert agg.last_flush_segments["dense_tiers"] == 2
    counts = np.bincount(keys, minlength=N_KEYS)
    assert len(res.forward) == int((counts > 0).sum())
    for fm in res.forward:
        k = int(fm.name.split(".")[1])
        assert sum(fm.digest_weights) == pytest.approx(counts[k], abs=1e-3)
        assert len(fm.digest_means) <= CAP
        # (float32 means beside the exact float64 scalars)
        assert fm.digest_min - 1e-3 <= min(fm.digest_means) \
            and max(fm.digest_means) <= fm.digest_max + 1e-3


# -- (c) the set of shapes is closed -----------------------------------------

def closed_set(capacity: int, donate: bool) -> set:
    """Every guard key an unmeshed digest flush or pre-reduce may launch
    under, for an arena of `capacity` rows: the single operand / the long
    tail at pow2 rows x pow2 depth <= DENSE_DEPTH_CAP in either form, the
    deep tier at pow2 rows >= DEEP_TIER_MIN_ROWS x DENSE_DEPTH_CAP, the
    compress at its one tile."""
    rows = [1 << i for i in range(capacity.bit_length())
            if 1 << i <= capacity]
    depths = [1 << i for i in range(1, 10)]
    assert depths[-1] == arena_mod.DENSE_DEPTH_CAP
    out = {((u, d), uniform, donate)
           for u in rows for d in depths for uniform in (True, False)}
    out |= {("deep_tier", (u, arena_mod.DENSE_DEPTH_CAP), donate)
            for u in rows if u >= arena_mod.DEEP_TIER_MIN_ROWS}
    out.add(("hot_compress", TILE))
    return out


def test_boot_launches_a_closed_set_and_zipf_seeds_stay_inside():
    agg = _agg()
    launched = agg.prewarm_launch([64], 2048)
    boot = set(agg._compiled_shapes)
    assert launched == len(boot) == agg.compile_events
    assert boot <= closed_set(2048, donate=True)
    assert boot == {
        ((2048, 64), True, True), ((2048, 64), False, True),
        ((1024, 64), True, True), ((1024, 64), False, True),
        ("deep_tier", (512, 512), True), ("hot_compress", TILE)}
    assert agg.prewarm_launch([64], 2048) == 0      # all compiled
    for seed in (51, 52):
        _feed(agg, *_zipf_interval(seed), ticks=4 + seed % 3)
        _by, seg = _flush(agg)
        assert seg["dense_tiers"] == 2 and seg["hot_compress_launches"] > 0
        # nothing outside what the boot launched: no compile event
        assert agg.compile_events == launched
        assert set(agg._compiled_shapes) == boot


def _no_engine(monkeypatch):
    """The native engine, patched away (a host without a compiler)."""
    def refuse():
        raise OSError("no native engine")
    monkeypatch.setattr(ingest_mod, "load_library", refuse)


@pytest.mark.parametrize("engine", ["native", "absent"])
def test_tiers_keep_their_operands_and_answer_the_same(engine, monkeypatch):
    """A tiered flush builds into the buffers the last one left — one
    native pass that zeroes only what the last build filled past a row's
    new count; without the engine `build_dense(keep=...)` twice: a
    memset, no fresh pages — and what an interval answers does not
    depend on what the buffers held, nor on which of the two built it."""
    keys, vals = _zipf_interval(71)
    fresh = _agg()
    _feed(fresh, keys, vals)
    want, seg = _flush(fresh)
    assert seg["build_onepass"] == 1 and seg["dense_tiers"] == 2
    assert seg["build_fresh_bytes"] >= 2048 * 64 * 4 + 2 * 512 * 512 * 4
    if engine == "absent":
        _no_engine(monkeypatch)
    agg = _agg()
    _feed(agg, *_zipf_interval(72))
    _flush(agg)
    kept = [dict(ops) for ops in agg._tier_operands]
    assert {"dv", "depths"} <= set(agg._tier_operands[0])
    assert {"dv", "dw", "minmax"} <= set(agg._tier_operands[1])
    _feed(agg, keys, vals)
    got, seg = _flush(agg)
    assert seg["dense_tiers"] == 2
    assert seg["build_onepass"] == (engine == "native")
    if engine == "native":
        assert seg["build_fresh_bytes"] == 0
        # (the tiers are the aggregator's: the arena was told of no
        # reader and keeps nothing of its own)
        assert agg.digests._dense_readers is None
        assert not agg.digests._dense_keep
    for ops, before in zip(agg._tier_operands, kept):
        for name, buf in ops.items():
            assert buf is before[name], name     # the same memory
    assert {n: v for n, v in got.items() if n.startswith("t.")} \
        == {n: v for n, v in want.items() if n.startswith("t.")}
    # the single operand stays the parent's: fresh arrays every build
    ar = agg.digests
    rows = np.arange(4, dtype=np.int64)
    staged = (np.repeat(rows, 3), np.arange(12.0), np.ones(12))
    a = ar.build_dense(staged, rows, np.zeros(4), np.ones(4), uniform=True)
    b = ar.build_dense(staged, rows, np.zeros(4), np.ones(4), uniform=True)
    assert a[0] is not b[0] and np.array_equal(a[0], b[0])


def test_a_second_dispatch_does_not_overwrite_the_first_flushs_operands():
    """Two tiered flushes dispatched before either is emitted (the
    aggregator's API allows it; a served node's flushes are serial): the
    second build waits for the launches that read the kept buffers, so
    the first interval answers what it answers alone — on a backend
    whose device_put aliases the host buffer, too."""
    first, second = _zipf_interval(73), _zipf_interval(74)
    alone = _agg()
    _feed(alone, *first)
    want, _seg = _flush(alone)
    agg = _agg()
    _feed(agg, *first)
    pend_a = agg.flush_dispatch(is_local=False)
    _feed(agg, *second)
    pend_b = agg.flush_dispatch(is_local=False)
    got = {m.name: m.value for m in pend_a.emit().metrics}
    pend_b.emit()
    assert {n: v for n, v in got.items() if n.startswith("t.")} \
        == {n: v for n, v in want.items() if n.startswith("t.")}


def test_an_interval_with_no_deep_key_is_one_operand():
    """`node1.fanout`'s shape of interval (every key 4 samples) and
    `fleet8.steady`'s (every key 256 weighted centroids: all rows deep,
    so the rows ARE the operand) build what the parent built."""
    agg = _agg()
    rng = np.random.default_rng(2)
    with agg.lock:
        rows = np.asarray([agg.digests.row_for(
            MetricKey(f"u.{k}", "timer", ""), MetricScope.LOCAL_ONLY, [])
            for k in range(300)], np.int64)
        agg.digests.sample_batch(np.repeat(rows, 4),
                                 rng.gamma(2.0, 10.0, 1200), np.ones(1200))
    _by, seg = _flush(agg)
    assert seg["dense_tiers"] == 1 and seg["dense_elems"] == 512 * 4
    assert ((512, 4), True, True) in agg._compiled_shapes
    with agg.lock:
        for k in range(300):        # touched again
            agg.digests.row_for(MetricKey(f"u.{k}", "timer", ""),
                                MetricScope.LOCAL_ONLY, [])
        agg.digests.sample_batch(np.repeat(rows, 256),
                                 rng.gamma(2.0, 10.0, 300 * 256),
                                 np.full(300 * 256, 2.0))
    _by, seg = _flush(agg)
    assert seg["dense_tiers"] == 1 and seg["dense_elems"] == 512 * 256
    assert ((512, 256), False, True) in agg._compiled_shapes
    assert seg["hot_keys"] == 0 and seg["hot_compress_launches"] == 0


# -- (d) both tiers from one native pass --------------------------------------

CAPACITY = 4096


def _tiered_interval(seed, n_tail, n_deep, tail_depth, weighted):
    """A digest part as a snapshot of a skewed interval hands it over:
    staged COO in shuffled arrival order over scattered row ids, `n_deep`
    of the touched rows past DEEP_TIER_THRESHOLD points (the deepest at
    DENSE_DEPTH_CAP), the others at most `tail_depth` deep (one exactly);
    the deep rows' weights, and a weighted tail's, are ones float32
    rounds (a cast on the wrong side would show)."""
    rng = np.random.default_rng([seed, 47])
    nd = n_tail + n_deep
    touched = np.sort(rng.choice(CAPACITY, nd, replace=False))
    deep = np.sort(rng.choice(nd, n_deep, replace=False))
    is_deep = np.zeros(nd, bool)
    is_deep[deep] = True
    counts = np.where(is_deep, rng.integers(65, 160, nd),
                      rng.integers(1, tail_depth + 1, nd))
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
            "uniform": False, "shallow_uniform": not weighted,
            "d_min": d_min, "d_max": d_min + 50.0}


def _parents_tiers(ar, part, keeps):
    """What the tiered flush built before the one pass: a `[capacity]`
    map, each tier's own copy of the points, build_dense(keep=) twice."""
    rows, vals, wts = part["staged"]
    touched, deep = part["rows"], part["deep"]
    is_deep = np.zeros(ar.capacity, bool)
    is_deep[touched[deep]] = True
    in_deep = is_deep[rows]
    tail = np.nonzero(~is_deep[touched])[0]
    return [ar.build_dense(
        (rows[mine], vals[mine], wts[mine]), touched[sel],
        part["d_min"][sel], part["d_max"][sel], uniform=uniform, keep=keep,
        **floors)
        for sel, mine, uniform, floors, keep in (
            (tail, ~in_deep, part["shallow_uniform"], {}, keeps[0]),
            (deep, in_deep, False,
             {"u_floor": arena_mod.DEEP_TIER_MIN_ROWS,
              "d_floor": arena_mod.DENSE_DEPTH_CAP}, keeps[1]))]


def _one_pass(ar, part, keeps):
    touched, deep = part["rows"], part["deep"]
    tail = np.setdiff1d(np.arange(len(touched)), deep)
    built = ar.build_tiers(part["staged"], touched, (tail, deep),
                           part["d_min"], part["d_max"],
                           part["shallow_uniform"], keeps)
    return built, ar.take_build_stats()


def _same_tiers(got, want):
    assert len(got) == len(want) == 2
    for g_tier, w_tier in zip(got, want):
        assert len(g_tier) == len(w_tier) == 3
        for g, w in zip(g_tier, w_tier):
            if w is None:
                assert g is None
            else:
                assert g.dtype == w.dtype and g.shape == w.shape
                assert np.array_equal(g, w)


# what the kept buffers hold when the interval under test is built: made
# for it; left by an interval deeper and wider in every way inside the
# same shapes (stale cells past every new count, and in rows the new
# interval does not have); left by one of other shapes in both tiers
KEPT = {"clean": None,
        "deeper_and_wider": dict(n_tail=1000, tail_depth=61, more_deep=1.5),
        "another_shape": dict(n_tail=300, tail_depth=20, more_deep=0.0)}


@pytest.mark.parametrize("kept", list(KEPT))
@pytest.mark.parametrize("n_deep", [40, 600], ids=["deep40", "deep600"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["uniform_tail", "weighted_tail"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_one_pass_tiers_are_bit_equal_to_the_two_builds(seed, weighted,
                                                        n_deep, kept):
    """Every array of both triples, `np.array_equal`, against the
    parent's two `build_dense(keep=)` calls from fresh dicts — 40 deep
    rows under the 512-row bucket (its points under `_NATIVE_FILL_MIN`:
    the parent's argsort), 600 over it (a 1,024-row bucket, the native
    fill)."""
    ar = arena_mod.DigestArena(capacity=CAPACITY)
    part = _tiered_interval(seed, 700, n_deep, 47, weighted)
    want = _parents_tiers(arena_mod.DigestArena(capacity=CAPACITY), part,
                          ({}, {}))
    assert want[0][0].shape == (1024, 64)
    assert want[1][0].shape == (512 if n_deep == 40 else 1024, 512)
    keeps = ({}, {})
    before = None
    if KEPT[kept] is not None:
        prev = KEPT[kept]
        last = _tiered_interval(
            seed + 10, prev["n_tail"],
            int(n_deep * prev["more_deep"]) or (600 if n_deep == 40 else 40),
            prev["tail_depth"], weighted)
        built, stats = _one_pass(ar, last, keeps)
        assert stats["onepass"] == 1 and stats["fresh_bytes"] > 0
        same_shapes = kept == "deeper_and_wider"
        assert (built[0][0].shape == want[0][0].shape) == same_shapes
        assert (built[1][0].shape == want[1][0].shape) == same_shapes
        before = {id(b) for ops in keeps for b in ops.values()}
    got, stats = _one_pass(ar, part, keeps)
    assert stats["onepass"] == 1
    if kept == "deeper_and_wider":
        assert stats["fresh_bytes"] == 0
        assert {id(b) for ops in keeps for b in ops.values()} == before
    else:
        remade = sum(a.nbytes for tier in got for a in tier[:2]
                     if a.ndim == 2)
        assert stats["fresh_bytes"] >= remade
    _same_tiers(got, want)
    assert got[0][0] is keeps[0]["dv"] and got[1][1] is keeps[1]["dw"]


def test_one_pass_tiers_follow_a_change_of_form_and_a_fallback(monkeypatch):
    """The same dicts through uniform and weighted tails and a flush the
    numpy builders made in between (which zero and fill whole operands,
    so the record of what the one pass left is void): every interval
    bit-equal to the parent's from fresh dicts."""
    agg = _agg(initial_capacity=CAPACITY)
    for i, (weighted, engine) in enumerate([
            (False, True), (True, True), (False, True), (True, False),
            (True, True), (False, False), (False, True)]):
        part = _tiered_interval(20 + i, 500 + 60 * (i % 3), 30 + 5 * i,
                                33 + 4 * (i % 4), weighted)
        want = _parents_tiers(arena_mod.DigestArena(capacity=CAPACITY),
                              part, ({}, {}))
        with monkeypatch.context() as m:
            if not engine:
                m.setattr(arena_mod.DigestArena, "build_tiers",
                          lambda *a, **k: None)
            tiers = agg._build_tiers(part)
        assert agg.digests.take_build_stats()["onepass"] == engine
        assert [(t["deep"], t["uniform"]) for t in tiers] \
            == [(False, not weighted), (True, False)]
        assert np.array_equal(tiers[1]["sel"], part["deep"])
        _same_tiers([t["dense"] for t in tiers], want)


def _calls(monkeypatch):
    """Counts the native calls a build makes."""
    made = []
    real = ingest_mod.build_tiers
    monkeypatch.setattr(
        ingest_mod, "build_tiers",
        lambda *a: (made.append(a[-1][0][4]), real(*a))[1])
    return made


def test_a_tail_row_past_the_kept_depth_is_one_retry_not_a_fallback(
        monkeypatch):
    ar = arena_mod.DigestArena(capacity=CAPACITY)
    keeps = ({}, {})
    made = _calls(monkeypatch)
    shallow = _tiered_interval(31, 700, 40, 30, False)
    built, stats = _one_pass(ar, shallow, keeps)
    assert built[0][0].shape == (1024, 32)
    assert made == [0, 32]              # nothing kept: a count, then the fill
    del made[:]
    deeper = _tiered_interval(32, 700, 40, 50, False)
    dv_deep = keeps[1]["dv"]
    got, stats = _one_pass(ar, deeper, keeps)
    assert made == [32, 64]             # refused at the kept depth, then made
    assert stats == {"onepass": 1, "fresh_bytes": 1024 * 64 * 4}
    assert got[0][0].shape == (1024, 64) and keeps[1]["dv"] is dv_deep
    _same_tiers(got, _parents_tiers(
        arena_mod.DigestArena(capacity=CAPACITY), deeper, ({}, {})))
    # and a shallower one after it: filled at the kept depth, which is
    # not the parent's shape for it, so once more at that
    del made[:]
    got, stats = _one_pass(ar, shallow, keeps)
    assert made == [64, 32] and stats["onepass"] == 1
    _same_tiers(got, _parents_tiers(
        arena_mod.DigestArena(capacity=CAPACITY), shallow, ({}, {})))
    # the steady case is one call
    del made[:]
    _one_pass(ar, _tiered_interval(33, 650, 44, 31, False), keeps)
    assert made == [32]


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


@pytest.mark.parametrize("kind", [
    "engine_absent", "float64_eval", "negative_row", "row_past_capacity",
    "row_not_touched", "deep_out_of_order"])
def test_what_the_one_pass_declines_is_built_as_the_parent_built_it(
        kind, monkeypatch, caplog):
    """No engine, a dtype the native fill would round, corrupt staging:
    `build_tiers` writes nothing and says so, and `_build_tiers` answers
    with the parent's sequence — or its exception."""
    part = _spoil(kind, _tiered_interval(41, 700, 40, 47, False))

    def attempt(parent):
        agg = _agg(initial_capacity=CAPACITY)
        seeded = _one_pass(agg.digests, _tiered_interval(
            42, 900, 60, 60, False), agg._tier_operands)[0]
        held = [a.copy() for tier in seeded for a in tier if a is not None]
        if kind == "float64_eval":
            agg.digests.eval_dtype = np.float64
        with monkeypatch.context() as m:
            if kind == "engine_absent":
                _no_engine(m)
            if parent:
                m.setattr(arena_mod.DigestArena, "build_tiers",
                          lambda *a, **k: None)
            else:
                # declined before a cell was written
                assert _one_pass(agg.digests, part,
                                 agg._tier_operands)[0] is None
                now = [a for tier in seeded for a in tier if a is not None]
                assert all(np.array_equal(a, b) for a, b in zip(now, held))
            try:
                tiers = agg._build_tiers(part)
            except IndexError as e:         # the numpy builder's own answer
                return type(e)
        assert agg.digests.take_build_stats()["onepass"] == 0
        return [t["dense"] for t in tiers]

    got, want = attempt(False), attempt(True)
    if isinstance(want, type):
        assert got is want
    else:
        assert len(got) == len(want)
        for g, w in zip(got, want):
            _same_tiers([g, g], [w, w])
    if kind in ("negative_row", "row_past_capacity"):
        assert len(got) == 1 and "out-of-bounds" in caplog.text


def test_a_forwarding_locals_export_is_unaffected_by_the_next_build():
    """A forwarding local keeps each tier's operands on the device for
    the digest export, which runs at emit: build N+1 between flush N's
    dispatch and its emit must not reach them — where device_put aliased
    a kept buffer, the tier's dict let go of it."""
    def run(seeded):
        agg = MetricAggregator(percentiles=PCTS, is_local=True,
                               initial_capacity=2048)
        if seeded:
            for ops, u_pad, names in zip(
                    agg._tier_operands, (2048, 512),
                    (("dv",), ("dv", "dw"))):
                for name in names:
                    ops[name] = _aligned((u_pad, 64 if u_pad == 2048
                                          else 512), np.float32)
                ops["depths"] = _aligned((u_pad,), np.int16)
                ops["filled"] = True
        pends = []
        for seed in (81, 82):
            keys, vals = _zipf_interval(seed)
            with agg.lock:
                row_of = {k: agg.digests.row_for(
                    MetricKey(f"t.{k}", "timer", ""), MetricScope.MIXED, [])
                    for k in np.unique(keys).tolist()}
                agg.digests.sample_batch(
                    np.asarray([row_of[k] for k in keys.tolist()], np.int64),
                    vals, np.ones(len(vals)))
            pends.append(agg.flush_dispatch(is_local=True))
            seg = agg.last_flush_segments
            assert seg["build_onepass"] == 1 and seg["dense_tiers"] == 2
            if seeded and seed == 81:
                if _device_put_aliases():
                    assert "dv" not in agg._tier_operands[0]
                    assert "dw" not in agg._tier_operands[1]
        out = _export(pends[0].emit())
        pends[1].emit()
        return out

    want = run(False)
    assert len(want) > 1000
    assert run(True) == want


def test_row_fields_of_a_served_tiered_flush():
    """The timeline row and /debug/vars of a served node's tiered
    flushes: `build_onepass` 1 beside `dense_tiers` 2, and nothing
    allocated from the second flush on."""
    from veneur_tpu import config as config_mod
    from veneur_tpu import http_api
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import ChannelMetricSink

    srv = Server(config_mod.Config(
        statsd_listen_addresses=[], interval=60.0, percentiles=PCTS,
        hostname="tier-row-test", native_ingest=False,
        arena_initial_capacity=2048),
        extra_metric_sinks=[ChannelMetricSink()])
    try:
        srv.start()
        for i, seed in enumerate((91, 92, 93)):
            _feed(srv.aggregator, *_zipf_interval(seed))
            srv.flush()
            assert srv.egress.settle(timeout_s=20.0)
            row = srv.flush_timeline.snapshot()[-1]
            assert row["dense_tiers"] == 2 and row["build_onepass"] == 1
            assert (row["build_fresh_bytes"] == 0) == (i > 0)
        stats = http_api.debug_vars(srv)["hot_lane"]
        assert stats["build_onepass"] == 1 and stats["dense_tiers"] == 2
        assert stats["build_fresh_bytes"] == 0
    finally:
        srv.shutdown()


# -- the served path: a node configured for it boots with its programs -------

def test_a_node_boots_with_its_programs_and_serves_a_zipf_interval():
    """`prewarm_flush_shapes` on an unmeshed node: start() returns with
    the closed list launched (no thread compiles beside the live server
    any more), and a Zipf interval sent over UDP lands on the timeline
    row and in /debug/vars with nothing compiled after the boot but the
    small buckets of the server's own first flush."""
    import socket
    import time

    from veneur_tpu import config as config_mod
    from veneur_tpu import http_api
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import ChannelMetricSink

    sink = ChannelMetricSink()
    srv = Server(config_mod.Config(
        statsd_listen_addresses=["udp://127.0.0.1:0"], interval=60.0,
        percentiles=PCTS, hostname="hot-lane-test",
        prewarm_flush_shapes=True, prewarm_depths=[64],
        arena_initial_capacity=2048), extra_metric_sinks=[sink])
    try:
        srv.start()
        agg = srv.aggregator
        boot = set(agg._compiled_shapes)
        assert srv.prewarm_stats["programs"] == agg.compile_events \
            == len(boot) > 0
        assert {("hot_compress", TILE), ("deep_tier", (512, 512), True),
                ((2048, 64), True, True)} <= boot
        assert any(s["name"] == "server.prewarm"
                   for s in srv.flight_recorder.snapshot())
        assert not [t for t in srv._threads if t.name == "flush-prewarm"]
        keys, vals = _zipf_interval(61)
        lines = [b"t.%d:%.3f|ms" % (k, v)
                 for k, v in zip(keys.tolist(), vals.tolist())]
        sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        addr = tuple(srv.statsd_addrs[0][1])
        for i in range(0, len(lines), 40):
            sock.sendto(b"\n".join(lines[i:i + 40]), addr)
            if i % 4000 == 0:
                time.sleep(0.01)        # loopback: do not outrun the reader
        sock.close()
        give_up = time.time() + 30
        while time.time() < give_up:
            srv._drain_native()
            if srv.native.engine.totals()[0] >= len(lines):
                break
            time.sleep(0.05)
        assert srv.native.engine.totals()[0] == len(lines)
        srv.flush()
        assert srv.egress.settle(timeout_s=20.0)
        row = srv.flush_timeline.snapshot()[-1]
        assert row["dense_tiers"] == 2 and row["hot_keys"] >= 5
        assert row["hot_compress_launches"] >= 1
        assert row["hot_compress_held_ms"] > 0
        assert row["dense_elems"] == 2048 * 64 + 512 * 512
        stats = http_api.debug_vars(srv)
        assert stats["hot_lane"]["hot_keys"] == row["hot_keys"]
        assert stats["hot_lane"]["dense_tiers"] == 2
        assert stats["prewarm_programs"] == srv.prewarm_stats["programs"]
        # the interval's own programs were the boot's
        new = set(agg._compiled_shapes) - boot
        assert not [k for k in new if k[0] in ("hot_compress", "deep_tier")
                    or k == ((2048, 64), True, True)], new
        by = {m.name: m.value for batch in list(sink.queue.queue)
              for m in batch}
        hot = int(np.bincount(keys).argmax())
        assert by[f"t.{hot}.count"] == np.bincount(keys).max()
    finally:
        srv.shutdown()
