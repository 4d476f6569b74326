"""Reader over the native engine's monotonic stage counters
(`native.stage_stats()["totals"]`), taken at the window's two ends.

args: `stages`: the stages whose nanoseconds are summed; `per`: [stage,
counter] whose increase divides them.  Returns ns per unit, or nothing
where the cell has no native UDP engine or nothing was staged.
"""


def read(ctx: dict, stages: list, per: list):
    a, b = ctx.get("stage_before"), ctx.get("stage_after")
    if not a or not b:
        return None
    units = b[per[0]][per[1]] - a[per[0]][per[1]]
    if units <= 0:
        return None
    ns = sum(b[s]["ns"] - a[s]["ns"] for s in stages)
    return ns / units
