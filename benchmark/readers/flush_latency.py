"""End-to-end reader: interval close to sink.  Per flush of the window,
from its scheduled tick to the moment its batch left the sink's queue
(metrics.flush_latencies_ms), over ALL the window's flushes.

args: `q`: the percentile (50 = median); `min_samples`: report nothing
with fewer flushes than this (a 95th percentile of 20 is the maximum).
"""


def read(ctx: dict, q: float, min_samples: int = 1):
    xs = ctx["flush_ms"]
    if len(xs) < max(1, min_samples):
        return None
    return ctx["metrics_mod"].percentile(xs, q)
