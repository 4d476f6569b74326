"""Worker/flush-core tests, porting the semantics of the reference's
`worker_test.go` and `flusher_test.go`: scope dispatch, local vs global
flush duality, sampler math, import-merge correctness, interval reset."""

import numpy as np
import pytest

from veneur_tpu.core.aggregator import MetricAggregator
from veneur_tpu.samplers import samplers as sm
from veneur_tpu.samplers.metric_key import MetricScope, UDPMetric
from veneur_tpu.samplers.parser import Parser


def mk(name, mtype, value, rate=1.0, tags=(), scope=MetricScope.MIXED):
    m = UDPMetric(name=name, type=mtype, value=value, sample_rate=rate,
                  scope=scope)
    m.update_tags(list(tags), None)
    return m


def agg(**kw):
    kw.setdefault("percentiles", [0.5, 0.9])
    return MetricAggregator(**kw)


def by_name(metrics):
    return {m.name: m for m in metrics}


def test_counter_accumulates_and_rate_normalizes():
    a = agg()
    a.process_metric(mk("c", "counter", 10))
    a.process_metric(mk("c", "counter", 1, rate=0.1))
    res = a.flush(is_local=True)
    m = by_name(res.metrics)["c"]
    assert m.value == 20.0  # 10 + 1/0.1
    assert m.type == sm.COUNTER


def test_gauge_last_write_wins():
    a = agg()
    a.process_metric(mk("g", "gauge", 1))
    a.process_metric(mk("g", "gauge", 42))
    res = a.flush(is_local=True)
    assert by_name(res.metrics)["g"].value == 42.0


def test_interval_reset():
    a = agg()
    a.process_metric(mk("c", "counter", 5))
    a.flush(is_local=True)
    res = a.flush(is_local=True)
    assert res.metrics == []  # untouched keys are not re-emitted


def test_histogram_local_flush_aggregates_no_percentiles():
    """Local flush of a mixed histo: aggregates from local scalars,
    digest forwarded, no percentiles (flusher.go:57-74)."""
    a = agg()
    for v in [1.0, 2.0, 3.0, 4.0]:
        a.process_metric(mk("h", "histogram", v, tags=("t:1",)))
    res = a.flush(is_local=True)
    names = by_name(res.metrics)
    assert names["h.min"].value == 1.0
    assert names["h.max"].value == 4.0
    assert names["h.count"].value == 4.0
    assert names["h.count"].type == sm.COUNTER
    assert not any(".50percentile" in n for n in names)
    # digest was forwarded
    fwd = [f for f in res.forward if f.name == "h"]
    assert len(fwd) == 1
    assert fwd[0].kind == "histogram"
    assert fwd[0].scope == MetricScope.MIXED
    assert fwd[0].digest_min == 1.0
    assert fwd[0].digest_max == 4.0
    assert sum(fwd[0].digest_weights) == pytest.approx(4.0)


def test_histogram_global_flush_percentiles():
    a = agg()
    for v in np.random.default_rng(0).random(1000):
        a.process_metric(mk("h", "histogram", float(v)))
    res = a.flush(is_local=False)
    names = by_name(res.metrics)
    assert names["h.50percentile"].value == pytest.approx(0.5, abs=0.05)
    assert names["h.90percentile"].value == pytest.approx(0.9, abs=0.05)
    # mixed histo on global: local-sample aggregates present (samples
    # arrived over UDP here), min/max from local scalars
    assert names["h.min"].value >= 0
    assert res.forward == []


def test_local_only_histogram_full_percentiles_locally():
    a = agg()
    for v in [1.0, 2.0, 3.0]:
        a.process_metric(mk("h", "histogram", v,
                            scope=MetricScope.LOCAL_ONLY))
    res = a.flush(is_local=True)
    names = by_name(res.metrics)
    assert "h.50percentile" in names
    assert res.forward == []  # local-only never forwarded


def test_global_only_histogram_not_emitted_locally():
    a = agg()
    a.process_metric(mk("h", "histogram", 1.0,
                        scope=MetricScope.GLOBAL_ONLY))
    res = a.flush(is_local=True)
    assert res.metrics == []
    assert len(res.forward) == 1
    assert res.forward[0].scope == MetricScope.GLOBAL_ONLY


def test_timer_kind_preserved_in_forward():
    a = agg()
    a.process_metric(mk("t", "timer", 5.0))
    res = a.flush(is_local=True)
    assert res.forward[0].kind == "timer"


def test_set_local_vs_global_flush():
    a = agg()
    for v in ("a", "b", "c", "a"):
        a.process_metric(mk("s", "set", v))
    res = a.flush(is_local=True)
    assert res.metrics == []  # mixed sets have no local part
    assert len(res.forward) == 1
    assert res.forward[0].kind == "set"

    b = agg()
    for v in ("a", "b", "c", "a"):
        b.process_metric(mk("s", "set", v))
    res = b.flush(is_local=False)
    m = by_name(res.metrics)["s"]
    assert m.value == 3.0
    assert m.type == sm.GAUGE


def test_local_only_set_flushed_locally():
    a = agg()
    for v in ("x", "y"):
        a.process_metric(mk("s", "set", v, scope=MetricScope.LOCAL_ONLY))
    res = a.flush(is_local=True)
    assert by_name(res.metrics)["s"].value == 2.0


def test_global_counter_forwarded_not_emitted():
    a = agg()
    a.process_metric(mk("c", "counter", 7, scope=MetricScope.GLOBAL_ONLY))
    res = a.flush(is_local=True)
    assert res.metrics == []
    assert res.forward[0].counter_value == 7


def test_status_check_flush():
    a = agg()
    m = mk("svc", "status", 1.0)
    m.message = "warn!"
    m.hostname = "host1"
    a.process_metric(m)
    res = a.flush(is_local=True)
    sc = by_name(res.metrics)["svc"]
    assert sc.type == sm.STATUS
    assert sc.value == 1.0
    assert sc.message == "warn!"
    assert sc.hostname == "host1"


def test_import_counter_gauge():
    g = agg()
    g.import_metric(sm.ForwardMetric(
        name="c", tags=[], kind="counter", scope=MetricScope.GLOBAL_ONLY,
        counter_value=5))
    g.import_metric(sm.ForwardMetric(
        name="c", tags=[], kind="counter", scope=MetricScope.GLOBAL_ONLY,
        counter_value=3))
    g.import_metric(sm.ForwardMetric(
        name="g", tags=[], kind="gauge", scope=MetricScope.MIXED,
        gauge_value=9.0))
    res = g.flush(is_local=False)
    names = by_name(res.metrics)
    assert names["c"].value == 8.0
    assert names["g"].value == 9.0


def test_import_rejects_local():
    g = agg()
    with pytest.raises(ValueError):
        g.import_metric(sm.ForwardMetric(
            name="h", tags=[], kind="histogram",
            scope=MetricScope.LOCAL_ONLY))


def test_local_to_global_histogram_roundtrip():
    """The core distributed flow (server_test.go TestLocalServerMixedMetrics):
    local instances sample, forward digests; global merges and reports
    accurate percentiles."""
    rng = np.random.default_rng(1)
    all_data = []
    g = agg()
    for host in range(4):
        local = agg()
        data = rng.gamma(2, 50, 2000)
        all_data.append(data)
        for v in data:
            local.process_metric(mk("api.latency", "timer", float(v),
                                    tags=("env:prod",)))
        res = local.flush(is_local=True)
        assert res.metrics and res.forward
        for fm in res.forward:
            g.import_metric(fm)
    gres = g.flush(is_local=False)
    names = by_name(gres.metrics)
    ref = np.concatenate(all_data)
    assert names["api.latency.50percentile"].value == pytest.approx(
        np.quantile(ref, 0.5), rel=0.05)
    assert names["api.latency.90percentile"].value == pytest.approx(
        np.quantile(ref, 0.9), rel=0.05)
    assert names["api.latency.50percentile"].tags == ["env:prod"]
    # global flush of a mixed digest without local samples: no local
    # aggregates (the sparse-emission guards, samplers.go:359-370)
    assert "api.latency.min" not in names
    assert "api.latency.count" not in names


def test_local_to_global_set_roundtrip():
    g = agg()
    for host in range(3):
        local = agg()
        for i in range(1000):
            local.process_metric(
                mk("users", "set", f"host{host}-user{i % 500}"))
        res = local.flush(is_local=True)
        for fm in res.forward:
            g.import_metric(fm)
    gres = g.flush(is_local=False)
    # 3 hosts x 500 unique each, no overlap
    assert by_name(gres.metrics)["users"].value == pytest.approx(
        1500, rel=0.05)


def test_import_min_max_exact():
    """Imported digest min/max must come from wire scalars, not centroid
    means (which are interior)."""
    local = agg()
    for v in [0.001, 5.0, 1000.0]:
        local.process_metric(mk("h", "histogram", v))
    fwd = local.flush(is_local=True).forward
    g = agg(aggregates=sm.HistogramAggregates(
        sm.Aggregate.MIN | sm.Aggregate.MAX))
    for fm in fwd:
        g.import_metric(fm)
    # mixed scope + no local samples on global -> min/max suppressed; use a
    # GLOBAL_ONLY import instead to check digest-backed values
    g2 = agg(aggregates=sm.HistogramAggregates(
        sm.Aggregate.MIN | sm.Aggregate.MAX))
    for fm in fwd:
        fm.scope = MetricScope.GLOBAL_ONLY
        g2.import_metric(fm)
    names = by_name(g2.flush(is_local=False).metrics)
    assert names["h.min"].value == pytest.approx(0.001)
    assert names["h.max"].value == pytest.approx(1000.0)


def test_unique_timeseries_counting():
    a = agg(count_unique_timeseries=True)
    for i in range(100):
        a.process_metric(mk(f"m{i % 10}", "counter", 1))
    assert a.unique_ts.estimate() == pytest.approx(10, abs=2)


def test_parser_to_aggregator_pipeline():
    """End-to-end: DogStatsD bytes -> parser -> aggregator -> flush."""
    p = Parser()
    a = agg()
    packets = [b"api.hits:1|c|#route:/home", b"api.hits:1|c|#route:/home",
               b"api.lat:3.5:4.5|ms|#route:/home",
               b"api.users:alice|s", b"temp:70.5|g"]
    for pk in packets:
        p.parse_metric(pk, a.process_metric)
    res = a.flush(is_local=False)
    names = by_name(res.metrics)
    assert names["api.hits"].value == 2.0
    assert names["api.hits"].tags == ["route:/home"]
    assert names["api.lat.50percentile"].value == pytest.approx(4.0, abs=0.5)
    assert names["api.users"].value == 1.0
    assert names["temp"].value == 70.5


def test_arena_growth():
    a = agg()
    for i in range(3000):  # exceeds initial capacity 1024
        a.process_metric(mk(f"m{i}", "counter", 1))
    res = a.flush(is_local=True)
    assert len(res.metrics) == 3000


def test_idle_gc():
    from veneur_tpu.core import arena as am
    a = agg()
    a.process_metric(mk("once", "counter", 1))
    a.flush(is_local=True)
    for _ in range(am.IDLE_GC_INTERVALS + 1):
        a.flush(is_local=True)
    assert len(a.counters.kdict) == 0


def test_hot_key_sync_bounded_launches():
    """A key receiving tens of thousands of samples per interval must not
    blow up the flush dense matrix: pre-reduction collapses the backlog
    into <= C weighted points per deep row in O(groups) device calls, and
    quantiles stay accurate."""
    import numpy as np

    from veneur_tpu.core import arena as arena_mod
    from veneur_tpu.parallel import serving
    from veneur_tpu.samplers.metric_key import MetricKey

    calls = {"partial": 0}
    real_partial = serving.partial_digests

    def partial_counting(*a, **k):
        calls["partial"] += 1
        return real_partial(*a, **k)

    agg = MetricAggregator(percentiles=[0.5, 0.99])
    rng = np.random.default_rng(21)
    hot = rng.gamma(2.0, 10.0, 50_000)
    key_hot = MetricKey("hot.lat", "histogram", "")
    key_cold = MetricKey("cold.lat", "histogram", "")
    with agg.lock:
        row_h = agg.digests.row_for(key_hot, MetricScope.LOCAL_ONLY, [])
        row_c = agg.digests.row_for(key_cold, MetricScope.LOCAL_ONLY, [])
        agg.digests.sample_batch(
            np.full(len(hot), row_h), hot, np.ones(len(hot)))
        agg.digests.sample_batch(
            np.full(10, row_c), np.arange(10.0), np.ones(10))

    try:
        serving.partial_digests = partial_counting
        agg.digests.sync()
    finally:
        serving.partial_digests = real_partial

    assert calls["partial"] >= 1          # the deep row pre-reduced
    # backlog collapsed: the flush dense depth is bounded by the
    # pre-reduction output, not the 50k raw samples
    assert int(agg.digests._depth.max()) <= agg.digests.ccap
    assert int(agg.digests._depth[row_c]) == 10  # shallow row untouched
    res = agg.flush(is_local=False)
    by = {m.name: m.value for m in res.metrics}
    p99 = np.percentile(hot, 99)
    assert abs(by["hot.lat.99percentile"] - p99) / p99 < 0.02
    p50 = np.percentile(hot, 50)
    assert abs(by["hot.lat.50percentile"] - p50) / p50 < 0.02
    assert by["hot.lat.count"] == 50_000.0
    assert by["cold.lat.count"] == 10.0


def test_hot_key_mixed_with_many_shallow_rows():
    """Shallow-row crowds next to a deep row must not inflate the dense
    staging matrices (both axes are budget-bounded), and results must stay
    exact for counters of shape and accurate for quantiles."""
    import numpy as np

    from veneur_tpu.samplers.metric_key import MetricKey

    agg = MetricAggregator(percentiles=[0.5, 0.99])
    rng = np.random.default_rng(31)
    deep = rng.gamma(2.0, 10.0, 40_000)
    with agg.lock:
        rows = []
        for i in range(300):
            k = MetricKey(f"shallow.{i}", "histogram", "")
            rows.append(agg.digests.row_for(k, MetricScope.LOCAL_ONLY, []))
        deep_row = agg.digests.row_for(
            MetricKey("deep.lat", "histogram", ""),
            MetricScope.LOCAL_ONLY, [])
        # 700 samples per shallow row -> over HOT_WAVE_THRESHOLD waves
        for row in rows:
            vals = rng.normal(100.0, 5.0, 700)
            agg.digests.sample_batch(
                np.full(700, row), vals, np.ones(700))
        agg.digests.sample_batch(
            np.full(len(deep), deep_row), deep, np.ones(len(deep)))
    res = agg.flush(is_local=False)
    by = {m.name: m.value for m in res.metrics}
    p99 = np.percentile(deep, 99)
    assert abs(by["deep.lat.99percentile"] - p99) / p99 < 0.02
    assert by["deep.lat.count"] == 40_000.0
    for i in range(300):
        assert by[f"shallow.{i}.count"] == 700.0


def test_empty_imported_digest_does_not_crash_flush():
    """A forwarded GLOBAL_ONLY histogram with an empty digest (zero
    count) must flush NaN-valued aggregates, not abort the interval with
    ZeroDivisionError."""
    import math

    g = MetricAggregator(
        percentiles=[0.5],
        aggregates=sm.parse_aggregates(["avg", "hmean", "count"]))
    g.import_metric(sm.ForwardMetric(
        name="empty.h", tags=[], kind="histogram",
        scope=MetricScope.GLOBAL_ONLY, digest_means=[], digest_weights=[],
        digest_min=float("inf"), digest_max=float("-inf"), digest_rsum=0.0))
    g.import_metric(sm.ForwardMetric(
        name="ok.c", tags=[], kind="counter",
        scope=MetricScope.GLOBAL_ONLY, counter_value=5))
    res = g.flush(is_local=False)
    by = {m.name: m.value for m in res.metrics}
    assert by["ok.c"] == 5.0        # the rest of the flush survived
    assert math.isnan(by["empty.h.avg"])
    assert math.isnan(by["empty.h.hmean"])


def test_arena_initial_capacity_presizing():
    """arena_initial_capacity pre-sizes every family (rounded to a power
    of two) so big deployments skip growth copies."""
    a = MetricAggregator(initial_capacity=5000)
    assert a.digests.capacity == 8192
    assert a.counters.capacity == 8192
    assert a.sets.capacity == 8192
    # sets are register-heavy (16 KiB/lane/row at p=14): by default they
    # follow arena_initial_capacity only up to 8192 rows, and their own
    # knob overrides in either direction
    b = MetricAggregator(initial_capacity=20_000)
    assert b.digests.capacity == 2 ** 15
    assert b.sets.capacity == 8192
    c = MetricAggregator(initial_capacity=20_000,
                         set_initial_capacity=2048)
    assert c.sets.capacity == 2048
    d = MetricAggregator(set_initial_capacity=20_000)
    assert d.sets.capacity == 2 ** 15
    a.process_metric(mk("c", "counter", 1))
    res = a.flush(is_local=False)
    assert by_name(res.metrics)["c"].value == 1.0


def test_hll_legacy_migration_lane():
    """Rolling-upgrade mixed fleet (hll_legacy_migration): legacy 'VH'
    payloads carry blake2b-hashed members that land on different
    registers than metro-hashed ones, so hash-mixing inflates the union.
    The migration lane keeps them separate and emits max(primary,
    legacy) — bounded error for the upgrade window."""
    import hashlib

    from veneur_tpu.sketches import hll

    members = [f"user-{i}".encode() for i in range(20_000)]

    # the legacy half of the fleet: pre-metro build, blake2b member hash
    legacy_regs = np.zeros(1 << 14, np.uint8)
    hs = np.fromiter(
        (int.from_bytes(hashlib.blake2b(m, digest_size=8).digest(), "big")
         for m in members), np.uint64, len(members))
    idx, rank = hll.split_hashes(hs)
    np.maximum.at(legacy_regs, idx, rank)
    legacy_payload = b"VH" + bytes([1, 14, 0]) + legacy_regs.tobytes()

    # the upgraded half: metro-hashed axiomhq payload, SAME members
    sk = hll.HLLSketch()
    sk.insert_batch(members)
    metro_payload = sk.marshal()

    def run(migration: bool) -> float:
        g = agg(is_local=False, hll_legacy_migration=migration)
        for payload in (metro_payload, legacy_payload):
            g.import_metric(sm.ForwardMetric(
                name="users", tags=[], kind=sm.TYPE_SET,
                scope=MetricScope.MIXED, hll=payload))
        res = g.flush(is_local=False)
        return by_name(res.metrics)["users"].value

    assert run(True) == pytest.approx(20_000, rel=0.05)
    inflated = run(False)
    assert inflated > 20_000 * 1.5  # the documented hazard, for contrast


def test_nonuniform_counts_sums_keep_host_f64_precision():
    """ADVICE r5 follow-up: non-uniform (weighted-staging) intervals
    must source .count/.sum from the exact f64 host accumulators
    (d_weight/d_sum) like uniform intervals do — not from the device's
    f32 readback — so a series' reported precision cannot shift when
    staging flips uniform/non-uniform between intervals."""
    from veneur_tpu.samplers.metric_key import MetricKey

    g = agg(is_local=False,
            aggregates=sm.parse_aggregates(["count", "sum"]))
    # weights force the general (non-uniform) network; the totals are
    # chosen to be exactly representable in f64 but NOT in f32
    # (16777219 is odd and > 2^24; 16777222.5 needs sub-2 spacing)
    big = 16_777_217.0      # 2^24 + 1
    with g.lock:
        row = g.digests.row_for(
            MetricKey("adv.h", sm.TYPE_HISTOGRAM, ""),
            MetricScope.GLOBAL_ONLY, [])
        g.digests.sample_batch(
            np.full(3, row, np.int64),
            np.asarray([1.0, 2.0, 3.5]),
            np.asarray([big, 1.0, 1.0]))
    assert g.digests.staged_uniform is False
    res = g.flush(is_local=False)
    by = by_name(res.metrics)
    assert by["adv.h.count"].value == big + 2.0          # 16777219.0
    assert by["adv.h.sum"].value == big * 1.0 + 2.0 + 3.5
    # the same totals in f32 would have rounded
    assert float(np.float32(big + 2.0)) != big + 2.0


@pytest.mark.parametrize("arena_cls", ["DigestArena", "MomentsArena",
                                       "CompactorArena"])
@pytest.mark.parametrize("local_between", [False, True])
def test_merge_digest_batch_is_merge_digest_in_a_loop(arena_cls,
                                                      local_between):
    """One staging call for a payload's digests leaves what a
    merge_digest call per digest leaves — points in the same order,
    the exact scalars, the imported points NOT local (every l_*
    accumulator and _sync_extra see them so) — also with a chunk of
    local samples staged in between, and in the arenas that inherit
    the staging."""
    from veneur_tpu.core import arena as arena_mod

    rng = np.random.default_rng(11)
    rows = np.array([3, 7, 3, 0, 9, 7], np.int64)     # rows repeat
    counts = np.array([4, 0, 2, 5, 1, 3], np.int64)   # an empty digest
    means = rng.gamma(2.0, 10.0, int(counts.sum()))
    weights = rng.integers(1, 5, len(means)).astype(np.float64)
    dmin = rng.uniform(0.0, 1.0, len(rows))
    dmax = rng.uniform(90.0, 99.0, len(rows))
    drsum = rng.uniform(0.0, 2.0, len(rows))
    dmin[2] = np.nan              # off the wire; must not stick
    local = (np.array([0, 3, 3], np.int64), np.array([5.0, 6.0, 7.0]),
             np.array([1.0, 1.0, 2.0]))

    def build(batch: bool):
        a = getattr(arena_mod, arena_cls)(capacity=16)
        extra = []
        a._sync_extra = (lambda r, v, w, loc, orig=a._sync_extra:
                         (extra.append(loc.copy()), orig(r, v, w, loc)))
        for half in (slice(0, 3), slice(3, 6)):
            lo = int(counts[:half.start].sum())
            hi = lo + int(counts[half].sum())
            if batch:
                a.merge_digest_batch(rows[half], counts[half],
                                     means[lo:hi], weights[lo:hi],
                                     dmin[half], dmax[half], drsum[half])
            else:
                off = lo
                for i in range(half.start, half.stop):
                    n = int(counts[i])
                    a.merge_digest(int(rows[i]), means[off:off + n],
                                   weights[off:off + n], float(dmin[i]),
                                   float(dmax[i]), float(drsum[i]))
                    off += n
            if local_between and half.start == 0:
                a.sample_batch(*local)
                a.sync()
        # what the sync in between has not consolidated yet
        assert a.staged_count() == int(
            counts[3 if local_between else 0:].sum())
        a.sync()
        uniform = a.staged_uniform
        out = {"staged": a.take_staged(), "uniform": uniform,
               "local_flags": np.concatenate(extra)}
        for name in a._CKPT_SCALARS:
            out[name] = getattr(a, name).copy()
        return out

    got, want = build(True), build(False)
    assert got["uniform"] is want["uniform"] is False
    for name, value in want.items():
        if name == "staged":
            for g, w in zip(got[name], value):
                assert g.dtype == w.dtype and np.array_equal(g, w)
        elif name != "uniform":
            assert np.array_equal(got[name], value, equal_nan=True), name
    n_local = 3 if local_between else 0
    assert int(got["local_flags"].sum()) == n_local
    assert got["l_weight"].sum() == (4.0 if local_between else 0.0)
    assert not np.isnan(got["d_min"]).any()
