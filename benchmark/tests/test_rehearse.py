"""`--rehearse` end to end, each cell, on the CPU: the whole control flow
of a run (server boot, generator child, warm intervals, window, comparison
with the reference, result line).  A rehearsal is never `correct`."""

import pytest

from conftest import run_rehearsal

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def check(bench_json, workload, trace, *extra):
    rc, lines, err = run_rehearsal(workload, *extra, trace=trace)
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert KEYS <= set(last)
    assert last["correct"] is False            # a rehearsal never passes
    assert last["device"]["platform"] == "cpu"
    verdict = [ln for ln in lines if ln.get("info") == "verdict"][0]
    bad = [ln for ln in lines if ln.get("ok") is False or "problem" in ln]
    assert verdict["comparisons_ok"], bad
    assert last["failed"] == 0 and last["attempted"] > 0
    which = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in bench_json[which]
              if workload in (m.get("workloads") or [workload])}
    # what only a device trace can give is left out on the CPU
    on_cpu = {n for n in listed if "kernel" not in n}
    assert on_cpu <= set(last["metrics"]) <= listed
    return last


@pytest.mark.parametrize("workload", ["node1.fanout", "fleet8.steady"])
def test_rehearse_end_to_end(bench_json, workload):
    last = check(bench_json, workload, trace=0)
    assert "setup_s" in last["metrics"] and len(last["metrics"]) >= 2


def test_rehearse_another_mix_is_one_data_file(bench_json):
    """`traffic/udp-rest.json` (the node-at-rest mix, kept for the cell
    PERF.md section 7 lists) runs through the same harness and generator."""
    last = check(bench_json, "node1.fanout", 0, "--traffic-file", "udp-rest")
    assert last.get("probe") is True


@pytest.mark.parametrize("workload", ["node1.fanout", "fleet8.steady"])
def test_rehearse_traced(bench_json, workload):
    last = check(bench_json, workload, trace=1)
    assert "busy_s" in last["device"] and "window_s" in last["device"]


def test_no_accelerator_no_result():
    """Without --rehearse, a machine with no TPU prints no result line."""
    import os
    import subprocess
    import sys

    from conftest import BENCH, ROOT
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "node1.fanout", "--seed", "1", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert p.returncode != 0
    assert not any('"correct"' in ln for ln in p.stdout.splitlines())
