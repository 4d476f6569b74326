"""Compile-churn hardening (VERDICT r3 #3): every new (keys, depth) pow2
bucket compiles a fresh flush program; prewarm + the persistent cache keep
that out of production flush intervals, the counters make it observable,
and the watchdog knows a compile from a hang."""

import time

import numpy as np

from veneur_tpu.core.aggregator import MetricAggregator
from veneur_tpu.samplers import samplers as sm
from veneur_tpu.samplers.metric_key import MetricKey, MetricScope


def _stage(agg, n_keys: int, samples_per_key: int = 1) -> None:
    rows = np.empty(n_keys, np.int64)
    for i in range(n_keys):
        rows[i] = agg.digests.row_for(
            MetricKey(f"ramp.k{i}", sm.TYPE_HISTOGRAM, ""),
            MetricScope.GLOBAL_ONLY, [])
    all_rows = np.tile(rows, samples_per_key)
    vals = np.random.default_rng(1).gamma(
        2.0, 10.0, n_keys * samples_per_key)
    with agg.lock:
        agg.digests.sample_batch(
            all_rows, vals, np.ones(len(all_rows)))
        agg.digests.touched[rows] = True


def test_cardinality_ramp_compile_events_tracked():
    agg = MetricAggregator(percentiles=[0.5], is_local=False,
                           initial_capacity=4096)
    _stage(agg, 100)
    agg.flush(is_local=False)
    assert agg.compile_events == 1          # first bucket
    assert agg.compile_seconds_total > 0
    _stage(agg, 100)
    agg.flush(is_local=False)
    assert agg.compile_events == 1          # same bucket: cache hit
    _stage(agg, 1000)                       # cardinality ramp
    agg.flush(is_local=False)
    assert agg.compile_events == 2          # new pow2 key bucket
    _stage(agg, 1000, samples_per_key=3)    # deeper staging
    agg.flush(is_local=False)
    assert agg.compile_events == 3          # new depth bucket


def test_prewarm_makes_ramp_compile_free():
    """A ramp across prewarmed buckets must never pay a compile inside
    flush — the soak criterion, scaled to CI."""
    agg = MetricAggregator(percentiles=[0.5], is_local=False,
                           initial_capacity=1024)
    warmed = agg.prewarm([1], max_keys=1024, min_keys=128)
    # 4 key buckets (128..1024) x 5 production programs per bucket:
    # the depth-vector uniform flush and the general weighted flush
    # for the digest family, plus the moments and compactor read-offs
    # (wire payloads route into their arenas on any tier, so every
    # family's programs prewarm too), and the set estimate at the set
    # arena's capacity
    assert warmed == 21
    base = agg.compile_events
    for n in (128, 200, 400, 900, 1024):    # ramp within the buckets
        _stage(agg, n)
        t0 = time.perf_counter()
        res = agg.flush(is_local=False)
        assert len(res.metrics)
        assert agg.compile_events == base   # zero compiles in-flush
    # ... and the guard flag is idle between flushes
    assert not agg.compile_in_progress.is_set()


def test_watchdog_holds_fire_during_compile():
    from tests.test_server import make_config
    from veneur_tpu.core.server import Server

    cfg = make_config(flush_watchdog_missed_flushes=2, interval=0.05)
    srv = Server(cfg)
    fired = []
    srv.shutdown_hook = lambda: fired.append(True)
    srv.last_flush_unix = time.time() - 10      # long overdue...
    srv.aggregator.compile_in_progress.set()    # ...but compiling
    srv.start()
    time.sleep(0.5)
    assert not fired                            # held fire
    srv.aggregator.compile_in_progress.clear()  # compile done, still no
    deadline = time.time() + 2                  # flush: now it kills
    while time.time() < deadline and not fired:
        time.sleep(0.02)
    srv.shutdown()
    assert fired
