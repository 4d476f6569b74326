#!/usr/bin/env python3
"""Runs run.py's `run_cell` and stores what the metric readers were handed
(`ctx`, its JSON-able parts) in the file `BENCH_DUMP_CTX` names.  Takes
run.py's arguments.  `data/ctx_before_pr26.*.json` were made with it from
`--rehearse --trace 1` runs in a checkout of the commit before PR 26 (rows
and spans without any of that PR's names), with this PR's `benchmark/` and
`BENCHMARK.json` laid over it; test_readers_absent.py reads them."""

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

KEEP = ("interval_s", "traffic", "loadgen_reports", "flush_ms", "forward_ms",
        "cpu_seconds", "lines_accounted", "setup_s", "stage_before",
        "stage_after", "device", "trace", "flush_spans")
WINDOW_KEEP = ("interval", "tick", "taken", "row", "n")


def dumping(load_module):
    done = []

    def load(folder, name):
        mod = load_module(folder, name)
        if folder != "readers" or done:
            return mod
        real = mod.read

        def read(ctx, **args):
            if not done:
                done.append(True)
                out = {k: ctx[k] for k in KEEP if k in ctx}
                out["window"] = [{k: w[k] for k in WINDOW_KEEP if k in w}
                                 for w in ctx["window"]]
                with open(os.environ["BENCH_DUMP_CTX"], "w") as f:
                    json.dump(out, f, indent=1, sort_keys=True)
            return real(ctx, **args)

        mod.read = read
        return mod

    return load


if __name__ == "__main__":
    run.load_module = dumping(run.load_module)
    args = run.arg_parser().parse_args()
    rc = 1
    try:
        print(json.dumps(run.run_cell(args)), flush=True)
        rc = 0
    except BaseException:      # noqa: BLE001 - report, then leave
        import traceback
        traceback.print_exc()
    sys.stdout.flush()
    os._exit(rc)
