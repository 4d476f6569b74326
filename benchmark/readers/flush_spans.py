"""Reader over the flight recorder's `flush.seg.*` spans of the window's
flushes (host clock, recorded by the program around each flush segment).

args: `spans`: the span names summed per flush.  Returns the median over
the window's flushes in ms, or nothing where no flush carried them.
"""

import statistics


def read(ctx: dict, spans: list):
    per_flush = [sum(f[s] for s in spans) for f in ctx.get("flush_spans", [])
                 if all(s in f for s in spans)]
    if not per_flush:
        return None
    return statistics.median(per_flush)
