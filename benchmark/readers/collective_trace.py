"""Reader over the profiler trace's device operations (see
trace_reduce.py): time the first device spent in collective operations,
per flush.

`ctx["trace"]["device_ops"]` holds the traced window's ten longest
operations of the first device as [name, seconds summed over the window];
a name is the operation's HLO text (`%all-to-all.1 = f32[...]
all-to-all(...)`) or, where the profiler records no more, its bare name.
The five collective opcodes are summed (their `-start` / `-done` halves
with them) and divided by the number of traced flushes.  Returns ms per
flush, or nothing without a trace, without a traced flush, or where no
collective is among the operations kept (a mesh-less program, or one
whose collectives all fell outside the ten longest).
"""

import re

COLLECTIVES = ("all-to-all", "all-reduce", "all-gather",
               "collective-permute", "reduce-scatter")
# `%name = shape opcode(operands...`: the shape is one token, or a tuple
_OP = re.compile(r"^%?([\w.\-]+) = (?:\(.*?\)|\S+) ([\w\-]+)\(")


def is_collective(op_text: str) -> bool:
    """By the operation's own name and opcode, never by its operands: a
    copy OF an all-to-all's result is a copy."""
    m = _OP.match(op_text)
    words = m.groups() if m else (op_text.split("(", 1)[0],)
    return any(c in w for w in words for c in COLLECTIVES)


def read(ctx: dict):
    tr = ctx.get("trace")
    if not tr:
        return None
    flushes = len(tr.get("kernel_ms_per_flush") or [])
    seconds = [t for name, t in tr.get("device_ops") or []
               if is_collective(name)]
    if not flushes or not seconds:
        return None
    return sum(seconds) * 1e3 / flushes
