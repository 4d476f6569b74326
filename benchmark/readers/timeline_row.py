"""Reader over the flush timeline's rows of the window's flushes (the
program's own per-flush record, `/debug/flush_timeline`: what the flush
measured and the interval ledger it closed).

args: `field`: the row's field; `per`: a second field that divides it, per
flush (flushes where it is zero are skipped); `scale`: a factor on the
result.  Returns the median over the window's flushes whose row has the
field(s), or nothing where no row has them (a program without that field).
"""

import statistics


def read(ctx: dict, field: str, per: str = None, scale: float = 1.0):
    values = []
    for w in ctx.get("window", []):
        row = w.get("row") or {}
        v = row.get(field)
        if not isinstance(v, (int, float)):
            continue
        if per is not None:
            d = row.get(per)
            if not isinstance(d, (int, float)) or d <= 0:
                continue
            v = v / d
        values.append(v * scale)
    if not values:
        return None
    return statistics.median(values)
