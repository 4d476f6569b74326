"""Self-profiling subsystem: host CPU profiler, data-plane stage
accounting, and the flush timeline.

The observability layer the reference exposes as its `/debug/pprof` suite
(`server.go:1366-1383`, SURVEY §5.1), rebuilt for this runtime's three
hot planes:

  * **Host CPU** (`profiling/cpu.py`): a sampling profiler behind
    `/debug/pprof/profile?seconds=N` — py-spy subprocess when the binary
    is present (samples the interpreter AND native frames), else an
    in-process `sys._current_frames()` sampler — returning folded-stack
    text ready for `flamegraph.pl` / speedscope.
  * **C++ data plane** (`native/ingest_engine.cpp` stage counters, bound
    in `veneur_tpu/ingest`): per-thread, per-stage packet and nanosecond
    counters over recvmmsg -> parse -> intern -> stage -> drain,
    surfaced as monotonic counters under `/debug/vars` and driven to
    saturation by `scripts/ingest_ceiling.py`.
  * **Flush path** (`profiling/timeline.py`): a fixed-size ring of
    structured per-flush records (interval id, segment milliseconds,
    key/device counts, bytes moved) queryable at
    `/debug/flush_timeline`, so the segment decomposition the bench
    emits is observable on a live server.

Everything here is stdlib-only and safe to import from the server's hot
path; the expensive pieces (py-spy, the sampler thread) run only while a
profile request is in flight.
"""

from veneur_tpu.profiling.cpu import CpuProfiler, profile_cpu
from veneur_tpu.profiling.timeline import FlushRecord, FlushTimeline

# Data-plane stage names, in pipeline order.  The first four are
# per-reader-thread (the C++ engine accounts them per thread); drain is
# engine-level (it runs on the Python drainer thread).
STAGES = ("recvmmsg", "parse", "intern", "stage", "drain")

# The unit each stage counts in (its counter key next to "ns").  Drain
# additionally reports "calls" (consolidation passes).  Consumers
# (ingest.stage_stats, scripts/ingest_ceiling.py) are
# table-driven off this so a stage rename/addition has one home.
STAGE_UNITS = {"recvmmsg": "packets", "parse": "packets",
               "intern": "calls", "stage": "values", "drain": "packets"}

__all__ = ["CpuProfiler", "profile_cpu", "FlushRecord", "FlushTimeline",
           "STAGES", "STAGE_UNITS"]
