#!/usr/bin/env python3
"""Drives run.py's `run_cell` with the hot-key lane broken underneath: the
pre-reduce drops every second tile it launches — the compress answers
all-zero weights, so what the tile held is gone from the key's digest,
while the host's exact scalars (`.count`, `min`, `max`) never knew.  Takes
run.py's arguments; used by test_udp_zipf.py with --rehearse."""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

spec = importlib.util.spec_from_file_location(
    "bench_run", os.path.join(BENCH, "run.py"))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)


def break_the_lane():
    from veneur_tpu.core.arena import DigestArena

    real = DigestArena._hot_compress
    launches = [0]

    def dropping(self, dv, dw):
        pm, pw = real(self, dv, dw)
        launches[0] += 1
        return (pm, pw * 0) if launches[0] % 2 == 0 else (pm, pw)

    DigestArena._hot_compress = dropping


if __name__ == "__main__":
    ap = run.arg_parser()
    args = ap.parse_args()
    rc = 1
    try:
        break_the_lane()
        print(json.dumps(run.run_cell(args)), flush=True)
        rc = 0
    except BaseException:      # noqa: BLE001 - report, then leave
        import traceback
        traceback.print_exc()
    sys.stdout.flush()
    os._exit(rc)
