"""Reader over the profiler trace of the window's first flushes (see
trace_reduce.py): device-side time of the operations each flush ran.

args: `what`: "ms" = median over the traced flushes of the union of the
flush's device operations; "hbm_share" = the bytes the flush's kernels
must move (kernel_bytes.py, from the operand and result shapes of each
kernel operation in the trace) over the device's peak HBM bandwidth
(peaks.json, keyed by device_kind; an unknown kind is an error), as a
share of that time.  Returns nothing without a trace (a `--trace 0` run,
or no device plane).
"""

import statistics


def read(ctx: dict, what: str):
    tr = ctx.get("trace")
    if not tr or not tr.get("kernel_ms_per_flush"):
        return None
    flushes = [(ms, ops) for ms, ops in zip(tr["kernel_ms_per_flush"],
                                            tr["ops_per_flush"]) if ms > 0]
    if len(flushes) < 3:
        return None
    if what == "ms":
        return statistics.median(ms for ms, _ops in flushes)
    peaks = ctx["peaks"]
    kind = ctx["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    kb = ctx["kernel_bytes_mod"]
    shares = [kb.hbm_share_percent(
        sum(kb.op_bytes(n) for n in ops if kb.is_kernel(n)), ms / 1e3,
        peaks[kind]["hbm_bytes_per_s"]) for ms, ops in flushes]
    return statistics.median(shares)
