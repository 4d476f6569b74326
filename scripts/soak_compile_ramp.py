"""Cardinality-ramp soak: no flush interval may ever block on an XLA
compile (VERDICT r3 #3 "Done" criterion, scaled to the real device).

Ramps live cardinality 1k -> 1M keys across flush ticks against a
prewarmed server-shaped aggregator and reports, per flush: keys, wall
ms, whether a compile happened inside the flush, and the compile guard's
totals.  Exit code 1 if any post-prewarm flush paid an in-flush compile
or exceeded the interval budget because of one.

Usage: python scripts/soak_compile_ramp.py [max_keys] [interval_s]
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from veneur_tpu.core.aggregator import MetricAggregator  # noqa: E402
from veneur_tpu.samplers import samplers as sm  # noqa: E402
from veneur_tpu.samplers.metric_key import (  # noqa: E402
    MetricKey, MetricScope)


def main() -> int:
    from veneur_tpu.util import compile_cache
    compile_cache.enable(min_compile_secs=0.0)

    max_keys = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    interval = float(sys.argv[2]) if len(sys.argv) > 2 else 10.0
    samples_per_key = 4

    agg = MetricAggregator(percentiles=[0.5, 0.9, 0.99], is_local=False,
                           initial_capacity=max_keys)
    t0 = time.perf_counter()
    warmed = agg.prewarm([samples_per_key], max_keys=max_keys,
                         min_keys=1024)
    print(f"prewarm: {warmed} buckets in "
          f"{time.perf_counter() - t0:.1f}s "
          f"({agg.compile_seconds_total:.1f}s compiling)", flush=True)
    base_events = agg.compile_events

    rng = np.random.default_rng(7)
    rows_cache: dict[int, np.ndarray] = {}

    def stage(n_keys: int) -> None:
        rows = rows_cache.get(n_keys)
        if rows is None:
            rows = np.empty(n_keys, np.int64)
            for i in range(n_keys):
                rows[i] = agg.digests.row_for(
                    MetricKey(f"ramp.k{i}", sm.TYPE_HISTOGRAM, ""),
                    MetricScope.GLOBAL_ONLY, [])
            rows_cache[n_keys] = rows
        all_rows = np.tile(rows, samples_per_key)
        vals = rng.gamma(2.0, 10.0, len(all_rows))
        with agg.lock:
            agg.digests.sample_batch(all_rows, vals,
                                     np.ones(len(all_rows)))
            agg.digests.touched[rows] = True

    failures = 0
    n = 1024
    while n <= max_keys:
        stage(n)
        ev_before = agg.compile_events
        t0 = time.perf_counter()
        res = agg.flush(is_local=False)
        wall = time.perf_counter() - t0
        compiled = agg.compile_events - ev_before
        blocked = compiled > 0
        status = "COMPILED-IN-FLUSH" if blocked else "ok"
        if blocked or (wall > interval and compiled):
            failures += 1
        print(f"keys={n:>8} flush={wall * 1e3:8.1f} ms "
              f"metrics={len(res.metrics):>8} {status}", flush=True)
        n *= 2
    print(f"ramp done: {agg.compile_events - base_events} in-flush "
          f"compiles after prewarm; {failures} failures", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
