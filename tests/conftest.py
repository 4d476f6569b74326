"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's approach of running "distributed" tests in-process
(SURVEY.md §4): instead of loopback gRPC between real hosts, multi-device
sharding tests run on 8 emulated CPU devices.  Must set env vars before jax
is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("VENEUR_TPU_TEST", "1")
# grpc's C core logs transport INFO lines (GOAWAY on channel teardown)
# straight to stderr, which interleaves into pytest's progress output
# mid-line — harmless but it corrupts dot-counting CI heuristics
os.environ.setdefault("GRPC_VERBOSITY", "ERROR")

# Tests run on the CPU backend: forced by the environment variable above
# and by the config flag, so a JAX that was already imported with another
# default cannot put them on an accelerator.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache: without it every pytest process cold-compiles
# the flush kernels (~seconds each), which makes timing-sensitive
# forwarding/server tests flaky under contention.
from veneur_tpu.util import compile_cache  # noqa: E402

compile_cache.enable(min_compile_secs=0.0)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running builds/soaks (tier-1 runs -m 'not slow')")

