"""`mesh4.steady`: its rehearsal on the CPU's virtual devices, and the
collective reader on hand-made contexts.

The cell's server is meshed over four devices, so its rehearsal needs
`XLA_FLAGS=--xla_force_host_platform_device_count=4`;
`conftest.run_rehearsal` drops `XLA_FLAGS` (the other cells run on one
device), so this file starts its own subprocess and holds it to
`test_rehearse.check`'s conditions.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, load
from test_rehearse import KEYS

CELL = "mesh4.steady"


def rehearse(trace: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2800000028", "--seconds", "8", "--trace", str(trace),
         "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600)
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    return p.returncode, lines, p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearse_mesh4(bench_json, trace):
    rc, lines, err = rehearse(trace)
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert KEYS <= set(last)
    assert last["correct"] is False            # a rehearsal never passes
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == 4
    verdict = [ln for ln in lines if ln.get("info") == "verdict"][0]
    bad = [ln for ln in lines if ln.get("ok") is False or "problem" in ln]
    assert verdict["comparisons_ok"], bad
    assert last["failed"] == 0 and last["attempted"] > 0
    which = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in bench_json[which]
              if CELL in (m.get("workloads") or [CELL])}
    # what only a device trace can give is left out on the CPU
    on_cpu = {n for n in listed
              if "kernel" not in n and n != "flush_collective_ms"}
    assert on_cpu <= set(last["metrics"]) <= listed
    if not trace:
        assert set(last["metrics"]) == {"flush_p50_ms", "setup_s"}
        return
    assert "busy_s" in last["device"] and "window_s" in last["device"]
    # the row says what mesh it ran on, and what its collectives move
    window = [ln for ln in lines if ln.get("info") == "window"][0]
    dense = [s for s in window["compiled_shapes"] if s.startswith("(((128,")]
    assert dense, window["compiled_shapes"]
    assert last["metrics"]["collective_bytes"]["value"] > 64 << 20


def ctx_with(device_ops, flushes):
    return {"trace": {"device_ops": device_ops,
                      "kernel_ms_per_flush": [0.5] * flushes,
                      "ops_per_flush": [[] for _ in range(flushes)]}}


A2A = ("%all-to-all.3 = f32[2,32768,32]{2,1,0} all-to-all(f32[2,65536,16]"
       "{2,1,0} %stack), replica_groups={{0,1},{2,3}}")
PMAX = ("%all-reduce.1 = (u8[67125248]{0}, f32[65536,2]{1,0}) all-reduce("
        "u8[67125248]{0} %concatenate, f32[65536,2]{1,0} %reduce), "
        "channel_id=2")
KERNEL = ('%uniform_eval.1 = f32[4,32768]{1,0} custom-call(f32[32768,32]'
          '{1,0} %copy.5), custom_call_target="tpu_custom_call"')
COPY_OF = ("%copy.5 = f32[32768,32]{1,0} copy(f32[32768,32]{1,0} "
           "%all-to-all.3)")


def test_collective_trace_reader():
    reader = load("readers", "collective_trace.py")
    assert reader.read({"trace": None}) is None             # --trace 0
    assert reader.read({}) is None
    # a mesh-less program: kernels and copies, no collective
    assert reader.read(ctx_with([[KERNEL, 3e-4], [COPY_OF, 1e-4]], 3)) is None
    # no traced flush to divide by
    assert reader.read(ctx_with([[A2A, 3e-4]], 0)) is None
    got = reader.read(ctx_with(
        [[PMAX, 9e-4], [KERNEL, 6e-4], [A2A, 3e-4], [COPY_OF, 1e-4]], 3))
    assert got == pytest.approx((9e-4 + 3e-4) * 1e3 / 3)
    # where the profiler keeps bare names
    assert reader.read(ctx_with([["all-reduce-start.2", 2e-4],
                                 ["fusion.7", 5e-4]], 2)) == \
        pytest.approx(0.1)
