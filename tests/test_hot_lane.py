"""The hot-key lane: a node whose timer keys are Zipfian.

(a) `serving.partial_digests` at the lane's one tile shape against the
    float64 compress the benchmark keeps (benchmark/reference/
    tdigest_compress.py — it imports nothing of the program), centroid
    for centroid.
(b) An aggregator fed a Zipf interval in shuffled ticks: shallow keys are
    numpy's hazen percentile, hot keys sit inside the rank limits the
    benchmark's cell holds them to, count / min / max are exact, and the
    tiered flush answers what the single-operand flush (`build_dense`
    over every row) answers on the same staged points.
(c) The set of shapes a flush and a pre-reduce may launch is closed: the
    enumeration below, and after the boot's launches a second Zipf seed
    compiles nothing.
(d) How the tiers' operands are built — one native pass into buffers the
    arena keeps, bit-equal to the numpy reference — is
    tests/test_dense_build.py's, beside the single operand's.
"""

import importlib.util
import os

import numpy as np
import pytest

from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.aggregator import MetricAggregator
from veneur_tpu.parallel import serving
from veneur_tpu.samplers.metric_key import MetricKey, MetricScope
from veneur_tpu.sketches import tdigest as td

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
    """The same staged points through the plain form — build_dense over
    every touched row, one weighted [2048, 512] operand — answer the
    same numbers."""
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
