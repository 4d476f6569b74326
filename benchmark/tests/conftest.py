"""The benchmark's own tests: `python3 -m pytest benchmark/tests -q` from
the repository's root, on the CPU.  Not part of the repository's tier-1
suite (`tests/`)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load(*parts):
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "benchtest_" + "_".join(parts).replace(".py", "").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_rehearsal(workload, *extra, seed=7, seconds=4, trace=0,
                  script="run.py"):
    """One `--rehearse` run in a process of its own (it ends in os._exit);
    returns (exit code, every JSON line printed)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace), "--rehearse", *extra],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600)
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    return p.returncode, lines, p.stderr


@pytest.fixture(scope="session")
def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
