"""Reader over the load generator's own per-interval reports: digests of
one interval over (last ack - first send), on the generator's clock —
the import path seen from outside.  Median over the window's intervals;
nothing where the generator forwards no digests.
"""

import statistics


def read(ctx: dict):
    rates = ctx["metrics_mod"].import_rates(
        [r for r in ctx.get("loadgen_reports", []) if "ack_s" in r])
    if not rates:
        return None
    return statistics.median(rates)
