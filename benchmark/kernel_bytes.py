"""Bytes a flush's kernels have to move through HBM, from the shapes in
the device trace: each kernel operation (`custom-call ...
custom_call_target="tpu_custom_call"`) reads every operand once and writes
its result once.  The shapes are parsed from the operation's HLO text,
which the profiler records as the event's name:

    %uniform_eval.1 = f32[4,32768]{...} custom-call(f32[32768,8]{...} %copy,
        s32[1,32768]{...} %bitcast.3, f32[1,4]{...} %bitcast.4), ...

That is the algorithm's minimum, not what the compiled program moves: the
layout copies and converts around the kernel are time (they count in
`flush_kernel_ms`) but not bytes, so they lower the share, as they should.
"""

from __future__ import annotations

import re

DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1}
KERNEL_MARK = "tpu_custom_call"
_SHAPE = re.compile(r"\b(" + "|".join(DTYPE_BYTES) + r")\[([\d,]*)\]")
_SHORT = re.compile(r"^%?([\w.\-]+) = .*? ([\w\-]+)\(")


def is_kernel(op_text: str) -> bool:
    return KERNEL_MARK in op_text


def op_bytes(op_text: str) -> int:
    """Result + operands of one operation, from its HLO text (the part
    before the attributes: `name = result opcode(operands)`)."""
    head = op_text.split("), ", 1)[0]
    total = 0
    for dtype, dims in _SHAPE.findall(head):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def short_name(op_text: str) -> str:
    """`uniform_eval.1 (custom-call)` for the breakdown."""
    m = _SHORT.match(op_text)
    if not m:
        return op_text[:64]
    return f"{m.group(1)} ({m.group(2)})"


def hbm_share_percent(bytes_moved: float, kernel_s: float,
                      hbm_bytes_per_s: float) -> float:
    """Least time the chip could take to move the bytes, over the time the
    operations took: a roofline share, bounded by bytes."""
    if kernel_s <= 0:
        raise ValueError("no kernel time")
    return 100.0 * (bytes_moved / hbm_bytes_per_s) / kernel_s
