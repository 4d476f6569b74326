#!/usr/bin/env python3
"""Drives run.py's `run_cell` with the arena's row lifecycle broken
underneath: a row the idle GC frees keeps the state it held at its last
flush — `reset_rows` at that cut is undone when `end_interval` puts the row
on the free list — so the name that is born into it later starts with
another name's count, minimum, maximum and sum.  The engine's line counts
never knew.  Takes run.py's arguments; used by test_udp_churn.py with
--rehearse."""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))


def break_the_arena():
    from veneur_tpu.core.arena import DigestArena, _ArenaBase

    real_reset, real_end = DigestArena.reset_rows, _ArenaBase.end_interval

    def remembering_reset(self, rows):
        held = self.__dict__.setdefault("_held_at_last_flush", {})
        for name, _ in self._COLUMNS:
            col = getattr(self, name)
            for r in rows.tolist():
                held.setdefault(r, {})[name] = col[r].copy()
        real_reset(self, rows)

    def dirty_end(self):
        before = len(self._free)
        n = real_end(self)
        held = self.__dict__.get("_held_at_last_flush")
        if held:
            for r in self._free[before:]:
                for name, value in held.pop(r, {}).items():
                    getattr(self, name)[r] = value
        return n

    DigestArena.reset_rows = remembering_reset
    DigestArena.end_interval = dirty_end


if __name__ == "__main__":
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    ap = run.arg_parser()
    args = ap.parse_args()
    rc = 1
    try:
        break_the_arena()
        print(json.dumps(run.run_cell(args)), flush=True)
        rc = 0
    except BaseException:      # noqa: BLE001 - report, then leave
        import traceback
        traceback.print_exc()
    sys.stdout.flush()
    os._exit(rc)
