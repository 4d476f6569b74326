"""Plain reference for generator kind `forward_stream`: what a global must
emit for the digests its locals STREAMED (`SendMetricsV2`, one message a
metric).  The wire differs from kind `forward`; the answer does not — the
same seeded digests merge to the same 256 centroids a key — so this is
`reference/forward.py` itself, loaded by path and not copied: `plan`
(which percentile names to keep: `sampled_keys` of the mix's keys) and
`compare` (p50 / p90 / p99 against `reference/tdigest_rule.py` in numpy
float64 over the merged centroids, span-normalised, and against the true
hazen percentiles of the samples behind them; sampled and percentile
metrics missing).  The exact comparisons of a forwarding cell
(`intervals_with_wrong_import_count`, `import_errors_or_duplicates`,
`late_or_failed_forwards`, compile events) are `run.py`'s, from the
generator's `ledger` and the flush timeline.  numpy only; imports
nothing of the program.
"""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _forward():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_forward", os.path.join(HERE, "forward.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_fwd = _forward()
plan = _fwd.plan
compare = _fwd.compare
