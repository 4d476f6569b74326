"""Reader over the profiler trace's device PROGRAMS (see trace_reduce.py):
time the first device spent in the programs named, per traced flush.

`ctx["trace"]["programs"]` holds the traced window's ten longest programs
of the first device as [name, launches, seconds summed over the window];
a name is the jitted function's (`jit_set_estimate_plane(...)`).  The
traced window holds as many intervals as flushes, so "per flush" is also
"per interval" for programs the interval's import launches.

args: `programs`: substrings of the program names summed; `hbm_share_of`:
report instead the share of the device's peak HBM bandwidth (peaks.json)
those launches reached, the bytes of one launch computed by
`rows_bytes` from a field of the flush timeline's row:
{"rows_from_bytes_field": a row field holding bytes_per_row_out x rows,
"bytes_per_row_in", "bytes_per_row_out"}.

Returns nothing without a trace, without a traced flush, where no such
program is among those kept (a program that never launches them), or —
for the share — where no row has the field.
"""

import statistics


def rows_bytes(rows: float, bytes_per_row_in: int,
               bytes_per_row_out: int) -> float:
    """What one launch of a row-wise reduction must move through HBM:
    every row read once, its answer written once."""
    return rows * (bytes_per_row_in + bytes_per_row_out)


def read(ctx: dict, programs: list, hbm_share_of: dict = None):
    tr = ctx.get("trace")
    if not tr:
        return None
    flushes = len(tr.get("kernel_ms_per_flush") or [])
    mine = [(launches, t) for name, launches, t in tr.get("programs") or []
            if any(p in name for p in programs)]
    seconds = sum(t for _launches, t in mine)
    if not flushes or seconds <= 0:
        return None
    if hbm_share_of is None:
        return seconds * 1e3 / flushes
    field = hbm_share_of["rows_from_bytes_field"]
    per_row_out = hbm_share_of["bytes_per_row_out"]
    rows = [w["row"][field] / per_row_out for w in ctx.get("window", [])
            if isinstance((w.get("row") or {}).get(field), (int, float))]
    if not rows:
        return None
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    moved = sum(launches for launches, _t in mine) * rows_bytes(
        statistics.median(rows), hbm_share_of["bytes_per_row_in"],
        per_row_out)
    return ctx["kernel_bytes_mod"].hbm_share_percent(
        moved, seconds, ctx["peaks"][kind]["hbm_bytes_per_s"])
