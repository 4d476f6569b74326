"""ASan/UBSan build arms for the native ingest engine, alongside the
TSan driver in test_profiling.py — the full sanitizer matrix the
`scripts/native_sanitize.sh` runner drives.

One driver binary (native/stage_tsan_driver.cpp) serves every arm:
phase 1 is the concurrent stage-counter workload (the TSan story),
phases 2-3 are single-threaded wire fuzz (vn_route / vn_import_scan
truncation + bit-flip sweeps; for vn_import_scan's descent into a
forwarded t-digest, a list of digests decoded intact, truncated at
every cut and mutated at every byte) and vn_build_tiers boundary abuse,
as one operand and as two tiers — the memory-safety surface ASan/UBSan exist for.  The UBSan arm is what
caught the vn_route chunk_max=0 division by zero (now guarded:
degenerate routing args return null, the Python-fallback contract).
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SOURCES = [os.path.join(REPO, "native", "stage_tsan_driver.cpp"),
            os.path.join(REPO, "native", "ingest_engine.cpp")]
_FLAGS = ["-O1", "-g", "-std=c++17", "-pthread",
          "-Wall", "-Wextra", "-Werror", "-fno-sanitize-recover=all"]


def _build(tmp_path, sanitize: str):
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    binary = tmp_path / f"driver_{sanitize.replace(',', '_')}"
    build = subprocess.run(
        ["g++", f"-fsanitize={sanitize}", *_FLAGS, *_SOURCES,
         "-o", str(binary)],
        capture_output=True, text=True)
    if build.returncode != 0 and "sanitize" in build.stderr:
        pytest.skip(f"{sanitize} unavailable: {build.stderr[-200:]}")
    assert build.returncode == 0, build.stderr
    return binary


def _run(binary, env_extra, iters=None):
    env = dict(os.environ, **env_extra)
    if iters is not None:
        env["VN_SAN_ITERS"] = str(iters)
        env["VN_SAN_THREADS"] = "2"
    run = subprocess.run([str(binary)], capture_output=True, text=True,
                         timeout=600, env=env)
    sys.stderr.write(run.stderr[-2000:])
    return run


def test_native_asan_ubsan_smoke(tmp_path):
    """Tier-1: the combined address+undefined arm builds and the
    reduced driver workload (incl. the full fuzz phases, which do not
    scale with VN_SAN_ITERS) runs clean."""
    binary = _build(tmp_path, "address,undefined")
    run = _run(binary, {"ASAN_OPTIONS": "detect_leaks=1"}, iters=1000)
    assert "ERROR: AddressSanitizer" not in run.stderr
    assert "runtime error" not in run.stderr
    assert run.returncode == 0, run.stderr[-2000:]


@pytest.mark.slow
def test_stage_driver_under_asan(tmp_path):
    binary = _build(tmp_path, "address")
    run = _run(binary, {"ASAN_OPTIONS": "detect_leaks=1"})
    assert "ERROR: AddressSanitizer" not in run.stderr
    assert run.returncode == 0, run.stderr[-2000:]


@pytest.mark.slow
def test_stage_driver_under_ubsan(tmp_path):
    binary = _build(tmp_path, "undefined")
    run = _run(binary, {"UBSAN_OPTIONS": "print_stacktrace=1"})
    assert "runtime error" not in run.stderr
    assert run.returncode == 0, run.stderr[-2000:]


@pytest.mark.slow
def test_sanitize_matrix_runner(tmp_path):
    """scripts/native_sanitize.sh drives the same matrix end-to-end
    (asan + ubsan here; the tsan arm is covered by test_profiling)."""
    if shutil.which("g++") is None or shutil.which("bash") is None:
        pytest.skip("no g++/bash")
    run = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "native_sanitize.sh"),
         "asan", "ubsan"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, VN_SAN_BUILD_DIR=str(tmp_path),
                 VN_SAN_ITERS="4000"))
    sys.stderr.write(run.stdout[-1000:] + run.stderr[-1000:])
    assert run.returncode == 0
    assert run.stdout.count("PASS") == 2
