#!/usr/bin/env python3
"""Several runs of benchmark/run.py in one call, one after the other (one
process may hold the chip), with their result lines and the lines compared
kept under chiprun_out/bench/<out>.jsonl and a summary of each metric's
spread (metrics.spread: quartile distance over the median).

    python3 benchmark/tools/runs.py --out fanout_set1 --workload node1.fanout \
        --seeds 101,102,103,104,105,106 [--seconds 40] [--trace 0] \
        [-- extra arguments handed to run.py, e.g. --traffic-override samples_per_key=16]

It is the builder's tool for sweeps, probes, controls and the two sets of
six; the driver's check calls run.py itself.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def metrics_module():
    spec = importlib.util.spec_from_file_location(
        "bench_metrics", os.path.join(os.path.dirname(HERE), "metrics.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--trace", default="0")
    ap.add_argument("extra", nargs="*")
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", "bench")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{args.out}.jsonl")
    results = []
    for seed in args.seeds.split(","):
        cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
               "--workload", args.workload, "--seed", seed,
               "--trace", args.trace] + args.extra
        if args.seconds:
            cmd += ["--seconds", args.seconds]
        t0 = time.time()
        p = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
        lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
        last = json.loads(lines[-1]) if lines and p.returncode == 0 else None
        rec = {"seed": int(seed), "rc": p.returncode,
               "wall_s": round(time.time() - t0, 1), "result": last,
               "lines": [json.loads(ln) for ln in lines[:-1]],
               "stderr_tail": p.stderr[-1500:] if p.returncode else ""}
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        results.append(rec)
        short = {k: round(v["value"], 4)
                 for k, v in (last or {}).get("metrics", {}).items()}
        bad = [c for c in rec["lines"] if c.get("ok") is False
               or "problem" in c]
        print(json.dumps({"seed": int(seed), "rc": p.returncode,
                          "wall_s": rec["wall_s"],
                          "correct": (last or {}).get("correct"),
                          "failed": (last or {}).get("failed"),
                          "metrics": short, "not_ok": bad[:6]}), flush=True)
        if p.returncode:
            print(p.stderr[-1500:], flush=True)
    good = [r["result"] for r in results if r["result"]]
    names = sorted({n for r in good for n in r["metrics"]})
    for n in names:
        vals = [r["metrics"][n]["value"] for r in good if n in r["metrics"]]
        if len(vals) >= 2:
            print(json.dumps({
                "metric": n, "n": len(vals),
                "median": statistics.median(vals), "min": min(vals),
                "max": max(vals),
                "spread": (metrics_module().spread(vals)
                           if len(vals) >= 3 else None)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
