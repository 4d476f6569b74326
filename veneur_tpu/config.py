"""Server configuration: YAML + template + environment overrides.

Mirrors `config.go:12-134` (field set and defaults) and the generic loader
`util/config/config.go:16-63`: the file is template-expanded (env vars via
$NAME / ${NAME}, the Python analog of the Go text/template pass), parsed as
YAML (with optional strict unknown-field rejection), then overridden by
VENEUR_* environment variables (envconfig equivalent).
"""

from __future__ import annotations

import os
import re
import socket
from dataclasses import dataclass, field, fields
from typing import Any, Optional

import yaml

from veneur_tpu import sinks as sink_mod
from veneur_tpu.util.matcher import Matcher, matcher_from_config


def parse_duration(v: Any) -> float:
    """Go-style duration ("10s", "50ms", "1m30s") -> seconds.

    Raises ValueError on anything that isn't a number or a duration
    string (time.ParseDuration errors on malformed input too).
    """
    if isinstance(v, bool) or not isinstance(v, (int, float, str)):
        raise ValueError(f"invalid duration: {v!r}")
    if isinstance(v, (int, float)):
        return float(v)
    s = v.strip()
    if re.fullmatch(r"[0-9.]+", s):
        return float(s)
    units = {"ns": 1e-9, "us": 1e-6, "µs": 1e-6, "ms": 1e-3,
             "s": 1.0, "m": 60.0, "h": 3600.0}
    matched = re.fullmatch(r"(?:[0-9.]+(?:ns|us|µs|ms|s|m|h))+", s)
    if not matched:
        raise ValueError(f"invalid duration: {v!r}")
    total = 0.0
    for num, unit in re.findall(r"([0-9.]+)(ns|us|µs|ms|s|m|h)", s):
        total += float(num) * units[unit]
    return total


@dataclass
class SinkRoutingConfig:
    """metric_sink_routing entry (config.go:78-87)."""
    name: str = ""
    match: list[Matcher] = field(default_factory=list)
    matched: list[str] = field(default_factory=list)
    not_matched: list[str] = field(default_factory=list)

    @classmethod
    def from_dict(cls, d: dict) -> "SinkRoutingConfig":
        sinks = d.get("sinks", {})
        return cls(
            name=d.get("name", ""),
            match=[matcher_from_config(m) for m in d.get("match", [])],
            matched=sinks.get("matched", []),
            not_matched=sinks.get("not_matched", []))


@dataclass
class SourceSpec:
    kind: str
    name: str = ""
    config: dict = field(default_factory=dict)
    tags: list[str] = field(default_factory=list)


@dataclass
class Config:
    """Server configuration (config.go:12-112)."""
    # listeners
    statsd_listen_addresses: list[str] = field(default_factory=list)
    ssf_listen_addresses: list[str] = field(default_factory=list)
    grpc_listen_addresses: list[str] = field(default_factory=list)
    http_address: str = ""
    grpc_address: str = ""          # gRPC import (global tier)
    forward_address: str = ""       # set => this is a LOCAL instance
    forward_timeout: float = 0.0    # 0 => max(interval, 10s)
    # parallel SendMetricsV2 streams per forward flush for big batches
    # (a single python-grpc client stream caps at ~20k msgs/s)
    forward_streams: int = 8
    # bounded forward retries (forward/client.py RetryPolicy): retries
    # BEYOND the first attempt, with exponential backoff + jitter from
    # forward_retry_backoff; exhausted retries are accounted in
    # forward.dropped_total / /debug/vars, never silent
    forward_max_retries: int = 2
    forward_retry_backoff: float = 0.05   # base backoff ("50ms", doubles)
    # DEADLINE_EXCEEDED joins the retry-safe forward status codes.  A
    # deadline is AMBIGUOUS (the peer may have imported the chunk after
    # the client gave up — a SIGSTOP'd or GC-paused global thaws and
    # keeps going), so this is only safe when the forward peer is a
    # ledger-bearing global of THIS framework (direct local->global
    # fleets): every V1 chunk carries its stable identity and the
    # global's dedup ledger merges re-delivery exactly once.  Leave it
    # off when forwarding through a proxy (the proxy re-shards without
    # a ledger, so re-delivery could double-count).
    forward_deadline_retry_safe: bool = False
    # crash durability (forward/spool.py + core/checkpoint.py).
    # spool_dir != "": when the bounded retries exhaust, provably-
    # chunked V1 payloads spill to an on-disk segment spool (length-
    # prefixed, CRC32-per-record) and a background replayer re-delivers
    # them oldest-first when the destination recovers — under the SAME
    # chunk identity, so the global's dedup ledger merges each chunk
    # exactly once even across crashes on either side.  Bounded by
    # spool_max_bytes / spool_max_age; expiry is visibly-accounted loss
    # (/debug/vars -> spool, forward.spool.* self-metrics), never
    # silent.
    spool_dir: str = ""                  # "" = spool off
    spool_max_bytes: int = 64 * 1024 * 1024
    spool_max_age: float = 600.0         # oldest record kept ("10m")
    spool_fsync: str = "rotate"          # always | rotate | never
    spool_replay_interval: float = 0.5   # replay tick ("500ms")
    spool_segment_max_bytes: int = 4 * 1024 * 1024
    # per-source identity window of the global tier's dedup ledger
    spool_dedup_window: int = 4096
    # egress data plane (veneur_tpu/egress/): sink fan-out runs on
    # bounded per-sink queues + worker lanes off the flush critical
    # path.  Each metric sink gets a circuit breaker
    # (egress_breaker_threshold consecutive failures trip it open;
    # cooldown egress_breaker_reset, doubling per trip) and bounded
    # retries with seeded backoff; when retries exhaust — or the
    # breaker is open — the filtered payload spills to that sink's own
    # durable spool under egress_spool_dir ("" = drop with accounting
    # instead) and a background replayer re-delivers once the backend
    # recovers.  The ledger (spilled == replayed + expired + dropped +
    # pending) surfaces at /debug/vars -> egress and as egress.*
    # self-metrics.
    egress_queue_depth: int = 128        # intervals buffered per sink
    egress_max_retries: int = 2          # retries beyond first attempt
    egress_retry_backoff: float = 0.05   # base backoff ("50ms", doubles)
    egress_retry_seed: int = 0           # seeded jitter (chaos replay)
    egress_breaker_threshold: int = 3    # consecutive failures to trip
    egress_breaker_reset: float = 5.0    # cooldown before half-open probe
    egress_spool_dir: str = ""           # "" = egress spool off
    egress_spool_max_bytes: int = 64 * 1024 * 1024
    egress_spool_max_age: float = 600.0  # oldest record kept ("10m")
    egress_spool_replay_interval: float = 0.5
    # checkpoint_dir != "": periodic (checkpoint_interval > 0) and
    # shutdown snapshots of every arena — dense registers, key tables,
    # staged digest points, cardinality quota state, the dedup ledger —
    # to an atomic-rename file; on boot the server restores and resumes
    # the interval, so a hard crash loses at most one checkpoint period
    # of ingest instead of everything.
    checkpoint_dir: str = ""             # "" = checkpointing off
    checkpoint_interval: float = 0.0     # 0 = shutdown/manual only
    stats_address: str = ""         # self-metrics statsd target

    # aggregation
    interval: float = 10.0
    percentiles: list[float] = field(default_factory=list)
    aggregates: list[str] = field(default_factory=lambda: ["min", "max", "count"])
    tdigest_compression: float = 100.0
    # sketch-family dispatch (core/aggregator.py): per-key choice of
    # the histogram/timer sketch — "tdigest" (default; centroid sets,
    # sort-network flush), "moments" (fixed-size moment vectors, dense
    # segmented-sum flush + maxent solver — a fundamentally cheaper
    # merge for high-cardinality/low-accuracy tiers) or "compactor"
    # (relative-error adaptive-compactor ladders, batched Pallas
    # compaction — provable rank-error envelopes where the empirical
    # families only measure theirs; error envelopes per family are
    # committed in analysis/tdigest_accuracy.csv).  Rules match at
    # ingest, first hit wins; each entry is {match: <name glob>,
    # family: ...} or {tenant: <tenant-tag value>, family: ...}.
    # Imports route by the wire payload itself, so tiers with
    # different rules still merge every sketch into its own family.
    # Mesh policy is per family: moments shards its maxent solve over
    # the key axis (single-process meshes), compactor is single-device
    # only.
    sketch_family_default: str = "tdigest"
    sketch_family_rules: list = field(default_factory=list)
    # power-sum order k of the moments vector (6 + 2k doubles per key;
    # every tier of a fleet must agree — vectors of different k refuse
    # to merge)
    sketch_moments_k: int = 8
    # adaptive-compactor ladder geometry (sketches/compactor.py): cap
    # is the per-level buffer capacity (a power of two in [8, 256];
    # 0 = built-in default), levels the ladder height (0 = default),
    # seed the stride-select coin seed.  Every tier of a fleet must
    # agree on all three — the importer prechecks and refuses
    # mismatched ladders rather than merging garbage.
    sketch_compactor_cap: int = 0
    sketch_compactor_levels: int = 0
    sketch_compactor_seed: int = 0
    set_precision: int = 14
    # live query plane (veneur_tpu/query/): each histogram arena keeps
    # a bounded ring of query_window_slots per-interval mergeable
    # sub-sketches, rotated at the flush cut, and GET /query fuses the
    # slots covering a requested window on read — windowed quantiles
    # between flushes ("p99 over the last 30 s, now").  0 disables the
    # plane (and /query answers 404).  query_slot_seconds is the
    # nominal slot duration for window->slot conversion and the
    # documented staleness bound (answers cover data up to the last
    # completed cut, <= 1 slot behind now); 0 = follow `interval`.
    # OPT-IN (default 0 = off): each slot holds references to its
    # interval's staged digest points, so an enabled ring retains up
    # to query_window_slots intervals of staged samples — a real
    # memory cost at high rates that a deployment must choose, not
    # inherit (8 is the recommended enabled value; see example.yaml).
    query_window_slots: int = 0
    query_slot_seconds: float = 0.0
    # multi-resolution retention (veneur_tpu/retention/): every flush
    # cut additionally compacts into a finest-first ladder of coarser
    # bucket tiers (each entry {seconds: <bucket width>, buckets:
    # <ring capacity>[, name: <label>]}), kept mergeable by
    # construction for all three sketch families; `GET
    # /query?since=&step=` then answers bucketed ranges from whichever
    # tier covers the window.  Requires the live query plane
    # (query_window_slots > 0) — the tiers compact the same flush-cut
    # snapshots the window ring holds.  Empty = retention off.
    retention_tiers: list = field(default_factory=list)
    # retention_dir != "": buckets evicted from the COARSEST in-memory
    # tier spill to CRC-framed tier segments (the ForwardSpool disk
    # format) and survive kill -9 — re-indexed on boot, queryable like
    # in-memory buckets.  Bounded by retention_max_bytes /
    # retention_max_age (0 = bytes budget only); expiry is visibly-
    # accounted loss (/debug/vars -> retention), never silent.
    retention_dir: str = ""              # "" = disk spill off
    retention_max_bytes: int = 256 * 1024 * 1024
    retention_max_age: float = 0.0       # oldest bucket kept ("30d")
    # evaluate t-digest flush quantiles in float64 (the reference's
    # merging_digest.go float64 semantics): keeps integer exactness for
    # values past 2^24 (epoch stamps, byte counters) at the cost of
    # emulated-f64 device math (no Pallas fast path, slower flush).
    # Single-device tiers only; sets jax_enable_x64 process-wide.
    digest_float64: bool = False
    # stage dense digest VALUES as bfloat16: halves the flush's dominant
    # host->device bytes at ~2^-8 relative quantile rounding (within the
    # t-digest accuracy envelope; weights/totals stay exact).  Mutually
    # exclusive with digest_float64.
    digest_bf16_staging: bool = False
    # initial arena rows (metric keys) per sampler family; arenas grow by
    # doubling, but each growth copies device tensors — size for the
    # expected live cardinality up front on big deployments (0 = default)
    arena_initial_capacity: int = 0
    # set (HLL) rows are register-heavy (2^set_precision bytes per lane =
    # 16 KiB at p=14): size the set arena for its OWN expected cardinality.
    # 0 = follow arena_initial_capacity up to 8192 rows (128 MiB/lane);
    # sets grow on demand past the pre-size either way.  Above the
    # arena's default of 1,024 rows the value also says where an
    # unmeshed tier keeps the registers: ON THE DEVICE, as one resident
    # lane (65,536 rows = 1 GiB of HBM) that forwarded sketches are
    # unioned into during the interval and that the flush estimates in
    # place — that many registers cannot be copied under the lock,
    # uploaded and read back every flush.  The lane programs are
    # launched once at boot, before the server listens.  At the default
    # size the registers stay on the host (a flush uploads a copy of
    # the touched rows).
    set_arena_initial_capacity: int = 0
    # cardinality defense (core/cardinality.py): per-tenant key budget.
    # 0 disables.  With a budget set, every metric key carrying the
    # tenant tag (cardinality_tenant_tag, "tenant:<t>" by default)
    # counts against its tenant; once a tenant's distinct-key count
    # crosses the budget, the long tail folds into one mergeable rollup
    # sketch per (tenant, type) — emitted as `veneur.rollup.<type>`
    # with the reserved `veneur_rollup:true` tag so downstream can tell
    # degraded data from exact data.  Eviction is deterministic
    # (cardinality_seed, count-ordered); quota state is visible at
    # /debug/vars -> cardinality and as cardinality.* self-metrics.
    # Untenanted keys (self-telemetry included) are never budgeted.
    cardinality_key_budget: int = 0
    cardinality_tenant_tag: str = "tenant"
    cardinality_seed: int = 0
    # sketch family of the guard's histogram/timer tail rollups:
    # "moments" folds an over-budget tenant's tail into one moments
    # vector per (tenant, type) instead of a t-digest — same exact
    # cross-tier count/sum conservation, fixed-size state, and the
    # merge stays elementwise at every tier (the guard is the first
    # production consumer of the family dispatch)
    cardinality_rollup_family: str = "tdigest"
    # group-by sketch cubes (veneur_tpu/cubes/): each entry declares one
    # group-by dimension — a tag-name list (`[region, endpoint]`) or a
    # dict `{tags: [...], match: "api.*"}` gating it to matching metric
    # names.  Every histogram/timer sample carrying ALL of a dimension's
    # tag names is mirrored into a per-group rollup row (an ordinary
    # mergeable arena key tagged `veneur_cube:true`, tag values joined
    # SORTED), served by `/query?group_by=...`.  Empty list disables.
    cube_dimensions: list = field(default_factory=list)
    # per-dimension live-group budget (cardinality-guard pattern): the
    # over-budget tail degrades into one accounted `veneur.cube.other`
    # row per (dimension, type) — visible loss, never silent — while
    # space-saving candidates track demoted groups for promotion at
    # interval end.  Required > 0 when cube_dimensions is set.
    cube_group_budget: int = 0
    # deterministic tie-break seed for cube eviction/promotion ranks and
    # the top-k ranking (the cardinality_seed of the cube plane)
    cube_seed: int = 0
    # rolling-upgrade migration lane for sets: merge legacy 'VH'
    # (blake2b-hashed) HLL imports into a side lane and emit
    # max(primary, legacy) instead of hash-mixing the registers (which
    # inflates union estimates up to ~2x); enable on global tiers while
    # any forwarding host still runs a pre-metro build
    hll_legacy_migration: bool = False
    count_unique_timeseries: bool = False
    # device mesh for the sharded serving flush (veneur_tpu/parallel/):
    # 0 devices = single-device lanes; replicas 0 = auto (2 when even)
    mesh_devices: int = 0
    mesh_replicas: int = 0
    ingest_lanes: int = 0           # 0 = auto (2 per replica)
    # multi-host (DCN) scaling: join a jax.distributed cluster before mesh
    # construction so the mesh spans every host's chips
    # (parallel/multihost.py; replica groups stay intra-host on ICI)
    distributed_coordinator: str = ""     # "host:port"; "" = single host
    distributed_num_processes: int = 0    # 0 = auto-detect
    distributed_process_id: int = -1      # -1 = auto-detect

    # ingest
    num_workers: int = 1
    num_readers: int = 1
    # native C++ data plane for UDP DogStatsD (recvmmsg readers + batch
    # parser + columnar staging, native/ingest_engine.cpp); a server
    # whose engine cannot be built or loaded refuses to start — false
    # asks for the Python packet path
    native_ingest: bool = True
    # native data-plane tuning (engine defaults when 0 / "auto"):
    #   ingest_reader_shards   SO_REUSEPORT sockets + native reader threads
    #                          (0 = num_readers)
    #   ingest_reader_pinning  pin reader i to cpu i % cpu_count
    #   ingest_reader_batch    packets per receive burst
    #   ingest_simd            tokenizer/hash dispatch: auto|scalar|sse2|avx2
    #   ingest_backend         receive syscall path: auto|recvmmsg|io_uring
    #                          (auto probes io_uring, falls back)
    #   ingest_ring_slots      SPSC staging slots per reader (pow2)
    ingest_reader_shards: int = 0
    ingest_reader_pinning: bool = False
    ingest_reader_batch: int = 0
    ingest_simd: str = "auto"
    ingest_backend: str = "auto"
    ingest_ring_slots: int = 0
    ingest_drain_interval: float = 0.0  # 0 = auto (min(interval/10, 0.5s))
    # sync staged samples into device lanes on every drain tick instead
    # of all at once during the flush snapshot (P7: pipelined flush vs
    # ingest — spreads device work across the interval).  Rides the
    # native drain loop, so it has no effect on the Python fallback
    # ingest path (which stages at flush only).
    eager_device_sync: bool = True
    # intern-table GC threshold (distinct metric identities in the engine)
    intern_gc_threshold: int = 1_000_000
    num_span_workers: int = 1
    metric_max_length: int = 4096
    trace_max_length_bytes: int = 16 * 1024 * 1024
    read_buffer_size_bytes: int = 2 * 1024 * 1024
    span_channel_capacity: int = 100

    # identity/tags
    hostname: str = ""
    omit_empty_hostname: bool = False
    extend_tags: list[str] = field(default_factory=list)
    tags_exclude: list[str] = field(default_factory=list)

    # behavior
    flush_on_shutdown: bool = False
    flush_watchdog_missed_flushes: int = 0
    synchronize_with_interval: bool = False
    # XLA compile-churn hardening: every new (keys, depth) pow2 bucket
    # compiles a fresh flush program (tens of seconds at high
    # cardinality).  The persistent cache makes recompiles across
    # restarts near-free (on TPU backends; "" places it at
    # <checkout>/.jax_cache, and JAX_COMPILATION_CACHE_DIR in the
    # environment wins over any value here — util/compile_cache.py);
    # prewarm: before it opens a listener, an unmeshed node LAUNCHES,
    # once each on zeros, the closed list of programs a steady interval
    # of its arena pre-size needs (MetricAggregator.prewarm_launch: the
    # flush at the pre-size's two row buckets at prewarm_depths, both
    # forms, the deep tier and the hot-key compress of a skewed
    # interval), so that interval never pays a
    # compile inside a flush or under the aggregator lock; a bucket
    # below those compiles in its first flush.  With a device mesh
    # (mesh_devices > 0) one compile takes tens of seconds, and the tier
    # is a global sized by its configuration: prewarm then compiles the
    # meshed flush program at the bucket of arena_initial_capacity (at
    # prewarm_depths, first), the set-lane kernels, and the bucket an
    # interval of the server's own telemetry lands in — nothing between
    # — and does so before the server opens a listener, not beside it.
    # Compile events surface as
    # flush.compile_events_total / flush.compile_seconds self-metrics,
    # /debug/vars carries prewarm_programs / prewarm_seconds,
    # and the flush watchdog is compile-aware (a first-bucket compile is
    # not a hang).
    compilation_cache_dir: str = ""
    prewarm_flush_shapes: bool = False
    prewarm_depths: list[int] = field(default_factory=lambda: [4, 32])
    # global-tier flushes >= chunks*8192 dense rows split into this many
    # row chunks so chunk i+1's host->device upload overlaps chunk i's
    # evaluation (1 disables; non-power-of-two values round down to the
    # nearest power of two, since only pow2 chunk counts tile the
    # pow2-padded row space)
    flush_upload_chunks: int = 2
    # meshed flushes place each device's staged blocks directly on their
    # owning device (pre-sharded staging) instead of one process-wide
    # device_put funnel; off reverts to the funnel (A/B + debugging)
    flush_presharded_staging: bool = True
    # device-resident arenas + asynchronous delta flush (ROADMAP #2):
    # sketch registers for the digest/moments/set families stay in HBM
    # across intervals; ingest keeps accumulating the host-side staged
    # COO (still the checkpoint/forwarding source of truth) and streams
    # fixed-size delta chunks to the device DURING the interval, so the
    # flush critical path degenerates to merge-eval + readback — upload
    # cost is amortized into the interval instead of paid at the p99.
    # Unmeshed (global single-device) tiers only; meshed tiers already
    # hold set/counter registers device-resident and ignore the gate.
    # The SET family alone is resident without this flag wherever
    # set_arena_initial_capacity pre-sized its arena (above); with the
    # flag it is resident at any size.
    flush_resident_arenas: bool = False
    # granularity of the delta machinery (0 = defaults).  In the chunked
    # host-staged pipeline this is dense ROWS per upload chunk (overrides
    # the flush_upload_chunks even split); in resident mode it is staged
    # POINTS per streamed delta chunk.  Rounded down to a power of two.
    flush_delta_chunk_keys: int = 0
    # in-flight window of the chunked upload pipeline: how many chunks may
    # be dispatched-but-unfetched before the host blocks (the host<->HBM
    # analog of the _dma_pipeline double buffer; 2 = classic double
    # buffering, higher trades pinned-buffer memory for slack)
    flush_delta_nbuf: int = 2
    # tri-state override of the resident DEVICE-ASSEMBLY half: None
    # (default) follows serving.resident_link_ok — on PJRT:CPU there is
    # no host<->device link to amortize, so digest/moments assembly
    # auto-degrades to the staged chunk-pipelined flush (the resident
    # SET lanes stay active everywhere).  True forces device assembly
    # regardless of backend (the CI conservation cells + bit-parity
    # tests); False forces the staged path even on a real accelerator.
    flush_resident_device_assembly: Optional[bool] = None
    debug: bool = False
    enable_profiling: bool = False
    # profiling subsystem (veneur_tpu/profiling/): the /debug/pprof
    # suite, the flush-timeline ring, and the data-plane stage counters.
    # The CPU profile endpoint is gated by enable_profiling (above);
    # stage counters and the flush timeline are always on (their hot-path
    # cost is a handful of TSC reads per burst / one dict per flush).
    profiling_cpu_hz: int = 100          # sampling rate (samples/s)
    profiling_cpu_max_seconds: float = 60.0  # per-request duration cap
    profiling_timeline_capacity: int = 512   # flush records in the ring
    profiling_use_pyspy: bool = True     # py-spy subprocess when on PATH
    # self-tracing flight recorder (veneur_tpu/trace/recorder.py): every
    # flush interval becomes a distributed trace over the pipeline's own
    # SSF span plane — root flush span, segment children, per-attempt
    # forward spans, context propagated over gRPC metadata to the proxy
    # and global tiers.  The bounded span ring is ALWAYS on (served at
    # /debug/trace); trace_flush_sample_rate gates how many intervals
    # get the full treatment (deterministic seeded head sampling, so
    # every tier configured alike samples the same intervals), and
    # trace_flush_enabled=False turns interval tracing off entirely
    # (the ring still records externally-submitted spans).
    trace_flush_enabled: bool = True
    trace_flush_sample_rate: float = 1.0
    trace_seed: int = 0
    trace_ring_capacity: int = 512
    http_quit: bool = False
    http_config_endpoint: bool = False
    # operator-driven flush/checkpoint: POST /flush and POST /checkpoint
    # on the HTTP API run one synchronous flush / checkpoint.  The
    # process-separated testbed drives intervals through these instead
    # of wall-clock tickers (explicit interval boundaries are what make
    # exact cross-process conservation assertable); production keeps
    # them off — an unauthenticated flush trigger is a DoS lever.
    http_flush_endpoint: bool = False
    # boot-from-YAML port readback: after the listeners bind, the entry
    # point writes a JSON file {statsd: [...], grpc: N, http: N} of the
    # RESOLVED addresses (tempfile + atomic rename).  Every listener can
    # then bind port 0 — a supervising harness (testbed/proccluster.py)
    # reads real ports back instead of assuming fixed ones, so parallel
    # CI runs cannot flake on EADDRINUSE.  "" = no file.
    port_file: str = ""
    # accepted for reference-config compatibility; Go-runtime-specific
    # knobs with no Python analog (profiling here is /debug/profile)
    mutex_profile_fraction: int = 0
    block_profile_rate: int = 0
    sentry_dsn: str = ""

    # span/indicator
    indicator_span_timer_name: str = ""
    objective_span_timer_name: str = ""

    # TLS (statsd TCP listener)
    tls_key: str = ""
    tls_certificate: str = ""
    tls_authority_certificate: str = ""

    # features
    enable_metric_sink_routing: bool = False
    diagnostics_metrics_enabled: bool = False

    # plugins
    metric_sinks: list[sink_mod.SinkSpec] = field(default_factory=list)
    span_sinks: list[sink_mod.SinkSpec] = field(default_factory=list)
    sources: list[SourceSpec] = field(default_factory=list)
    metric_sink_routing: list[SinkRoutingConfig] = field(default_factory=list)

    # scope coercion of self-emitted metrics (veneur_metrics_scopes)
    veneur_metrics_scopes: dict[str, str] = field(default_factory=dict)
    veneur_metrics_additional_tags: list[str] = field(default_factory=list)

    def __post_init__(self) -> None:
        # accept plain dicts for sink specs so Config can be constructed
        # directly with the same shapes the YAML loader accepts
        self.metric_sinks = [
            s if isinstance(s, sink_mod.SinkSpec)
            else sink_mod.SinkSpec.from_dict(s) for s in self.metric_sinks]
        self.span_sinks = [
            s if isinstance(s, sink_mod.SinkSpec)
            else sink_mod.SinkSpec.from_dict(s) for s in self.span_sinks]

    def apply_defaults(self) -> None:
        """config.go:114-134."""
        if not self.aggregates:
            self.aggregates = ["min", "max", "count"]
        if not self.hostname and not self.omit_empty_hostname:
            self.hostname = socket.gethostname()
        if self.interval <= 0:
            self.interval = 10.0
        if self.forward_timeout < 0:
            self.forward_timeout = 0.0
        if self.forward_max_retries < 0:
            self.forward_max_retries = 0
        if self.forward_retry_backoff < 0:
            self.forward_retry_backoff = 0.0
        if self.spool_fsync not in ("always", "rotate", "never"):
            raise ValueError(
                f"spool_fsync must be always|rotate|never, "
                f"got {self.spool_fsync!r}")
        if self.egress_queue_depth <= 0:
            self.egress_queue_depth = 128
        if self.egress_max_retries < 0:
            self.egress_max_retries = 0
        if self.egress_retry_backoff < 0:
            self.egress_retry_backoff = 0.0
        if self.egress_breaker_threshold < 1:
            self.egress_breaker_threshold = 1
        if self.egress_breaker_reset < 0:
            self.egress_breaker_reset = 0.0
        if self.egress_spool_replay_interval <= 0:
            self.egress_spool_replay_interval = 0.5
        if self.query_window_slots < 0:
            self.query_window_slots = 0
        if self.query_slot_seconds < 0:
            self.query_slot_seconds = 0.0
        if self.retention_max_bytes <= 0:
            self.retention_max_bytes = 256 * 1024 * 1024
        if self.retention_max_age < 0:
            self.retention_max_age = 0.0
        if self.retention_tiers:
            if self.query_window_slots <= 0:
                raise ValueError(
                    "retention_tiers requires the live query plane "
                    "(query_window_slots > 0): the tiers compact the "
                    "same flush-cut snapshots the window ring holds")
            prev = 0.0
            for t in self.retention_tiers:
                if not isinstance(t, dict):
                    raise ValueError(
                        f"bad retention tier {t!r}: need "
                        "{seconds: <width>, buckets: <capacity>}")
                secs = float(t.get("seconds", 0))
                if secs <= prev:
                    raise ValueError(
                        "retention_tiers must be finest-first with "
                        f"strictly increasing seconds (got {secs} "
                        f"after {prev})")
                if int(t.get("buckets", 8)) < 1:
                    raise ValueError(
                        f"retention tier {t!r}: buckets must be >= 1")
                prev = secs
        elif self.retention_dir:
            raise ValueError(
                "retention_dir without retention_tiers: the spill "
                "store holds tier evictions — configure the tier "
                "ladder or drop the directory")
        if self.metric_max_length <= 0:
            self.metric_max_length = 4096
        if self.ingest_reader_shards < 0:
            self.ingest_reader_shards = 0
        if self.ingest_reader_batch < 0:
            self.ingest_reader_batch = 0
        if self.ingest_ring_slots < 0:
            self.ingest_ring_slots = 0
        if self.ingest_simd not in ("auto", "scalar", "sse2", "avx2"):
            raise ValueError(
                f"ingest_simd must be auto|scalar|sse2|avx2, "
                f"got {self.ingest_simd!r}")
        if self.ingest_backend not in ("auto", "recvmmsg", "io_uring"):
            raise ValueError(
                f"ingest_backend must be auto|recvmmsg|io_uring, "
                f"got {self.ingest_backend!r}")
        if self.read_buffer_size_bytes <= 0:
            self.read_buffer_size_bytes = 2 * 1024 * 1024
        if self.span_channel_capacity <= 0:
            self.span_channel_capacity = 100
        if self.digest_bf16_staging and self.digest_float64:
            raise ValueError(
                "digest_bf16_staging contradicts digest_float64 "
                "(half- vs double-precision staging); drop one")
        if self.digest_bf16_staging and self.mesh_devices:
            raise ValueError(
                "digest_bf16_staging is unsupported with a device mesh "
                "(the meshed flush program is f32-native); drop one")
        _FAMS = ("tdigest", "moments", "compactor")
        for fam in (self.sketch_family_default,
                    self.cardinality_rollup_family):
            if fam not in _FAMS:
                raise ValueError(
                    f"unknown sketch family {fam!r} "
                    "(tdigest | moments | compactor)")
        for rule in self.sketch_family_rules:
            if not isinstance(rule, dict) \
                    or rule.get("family", "moments") not in _FAMS \
                    or not (rule.get("match") or rule.get("tenant")):
                raise ValueError(
                    f"bad sketch_family rule {rule!r}: need "
                    "{match: <glob> | tenant: <t>, family: "
                    "tdigest|moments|compactor}")
        if self.sketch_moments_k < 2 or self.sketch_moments_k > 16:
            raise ValueError(
                f"sketch_moments_k {self.sketch_moments_k} out of "
                "range [2, 16] (the maxent solve conditions past 16)")
        cap = self.sketch_compactor_cap
        if cap and (cap < 8 or cap > 256 or cap & (cap - 1)):
            raise ValueError(
                f"sketch_compactor_cap {cap} must be a power of two "
                "in [8, 256] (or 0 for the built-in default)")
        lv = self.sketch_compactor_levels
        if lv and (lv < 4 or lv > 32):
            raise ValueError(
                f"sketch_compactor_levels {lv} out of range [4, 32] "
                "(or 0 for the built-in default)")
        fams_in_play = {self.sketch_family_default}
        fams_in_play.update(rule.get("family", "moments")
                            for rule in self.sketch_family_rules)
        if self.cardinality_key_budget > 0:
            fams_in_play.add(self.cardinality_rollup_family)
        if "compactor" in fams_in_play and self.mesh_devices:
            raise ValueError(
                "the compactor sketch family is unsupported with a "
                "device mesh (mesh_devices > 0): its batched "
                "compaction program is single-device — drop one")
        if self.cube_group_budget < 0:
            self.cube_group_budget = 0
        if self.cube_dimensions:
            # validate at boot (identity rules live in cubes/cube.py);
            # a malformed dimension must fail loudly here, not at the
            # first matching sample
            from veneur_tpu.cubes import parse_dimensions
            parse_dimensions(self.cube_dimensions)
            if self.cube_group_budget <= 0:
                raise ValueError(
                    "cube_dimensions requires cube_group_budget > 0: "
                    "an unbounded cube is a cardinality explosion by "
                    "construction (set a budget; overflow degrades "
                    "into the accounted veneur.cube.other row)")
        if self.digest_float64 and self.mesh_devices:
            # config-level rejection (not a deep aggregator error): the
            # meshed flush program is f32-native — hi/lo counter planes,
            # f32 staged digests — and device f64 is emulated; run f64
            # digest evaluation on an unmeshed tier instead
            raise ValueError(
                "digest_float64 is unsupported with a device mesh "
                "(mesh_devices > 0); f64 digest evaluation is "
                "single-device only — drop one of the two options")

    @property
    def is_local(self) -> bool:
        """Server.IsLocal (server.go:1440-1442): local iff forwarding."""
        return self.forward_address != ""


_LIST_FIELDS_OF_FLOAT = {"percentiles"}
# fields accepting Go-style duration strings ("10s", "500ms")
_DURATION_FIELDS = {"interval", "forward_timeout", "ingest_drain_interval",
                    "forward_retry_backoff", "spool_max_age",
                    "spool_replay_interval", "checkpoint_interval",
                    "egress_retry_backoff", "egress_breaker_reset",
                    "egress_spool_max_age",
                    "egress_spool_replay_interval",
                    "query_slot_seconds", "retention_max_age"}


def _coerce(key: str, value: Any) -> Any:
    if key in _DURATION_FIELDS:
        return parse_duration(value)
    if key in _LIST_FIELDS_OF_FLOAT:
        return [float(x) for x in value]
    return value


def load_config_dict(data: dict, strict: bool = False,
                     apply_defaults: bool = True) -> Config:
    cfg = Config()
    known = {f.name for f in fields(Config)}
    for key, value in (data or {}).items():
        if key == "features":
            for fk, fv in (value or {}).items():
                if fk == "enable_metric_sink_routing":
                    cfg.enable_metric_sink_routing = bool(fv)
                elif fk == "diagnostics_metrics_enabled":
                    cfg.diagnostics_metrics_enabled = bool(fv)
                elif strict:
                    raise ValueError(f"unknown config field features.{fk}")
            continue
        if key == "http":
            cfg.http_config_endpoint = bool((value or {}).get("config"))
            continue
        if key == "metric_sinks":
            cfg.metric_sinks = [sink_mod.SinkSpec.from_dict(d) for d in value]
            continue
        if key == "span_sinks":
            cfg.span_sinks = [sink_mod.SinkSpec.from_dict(d) for d in value]
            continue
        if key == "sources":
            cfg.sources = [SourceSpec(**d) for d in value]
            continue
        if key == "metric_sink_routing":
            cfg.metric_sink_routing = [
                SinkRoutingConfig.from_dict(d) for d in value]
            continue
        if key not in known:
            if strict:
                raise ValueError(f"unknown config field {key!r}")
            continue
        setattr(cfg, key, _coerce(key, value))
    if apply_defaults:
        cfg.apply_defaults()
    return cfg


_ENV_PREFIX = "VENEUR_"


def _env_overrides(cfg: Config, environ: dict[str, str]) -> None:
    """envconfig-style overrides: VENEUR_<FIELDNAME> (util/config:57-60)."""
    for f in fields(Config):
        env_key = _ENV_PREFIX + f.name.replace("_", "").upper()
        alt_key = _ENV_PREFIX + f.name.upper()
        raw = environ.get(env_key, environ.get(alt_key))
        if raw is None:
            continue
        cur = getattr(cfg, f.name)
        if isinstance(cur, bool):
            setattr(cfg, f.name, raw.lower() in ("1", "true", "yes"))
        elif isinstance(cur, int):
            setattr(cfg, f.name, int(raw))
        elif isinstance(cur, float):
            setattr(cfg, f.name, parse_duration(raw)
                    if f.name in _DURATION_FIELDS else float(raw))
        elif isinstance(cur, list):
            items = [x for x in raw.split(",") if x]
            if f.name in _LIST_FIELDS_OF_FLOAT:
                setattr(cfg, f.name, [float(x) for x in items])
            else:
                setattr(cfg, f.name, items)
        elif isinstance(cur, str):
            setattr(cfg, f.name, raw)


def read_config(path: str, strict: bool = False,
                environ: Optional[dict[str, str]] = None) -> Config:
    """File -> template expansion -> YAML -> env override
    (util/config/config.go:16-63)."""
    environ = environ if environ is not None else dict(os.environ)
    with open(path) as f:
        raw = f.read()
    # template pass: $NAME / ${NAME} env expansion
    raw = _expand(raw, environ)
    data = yaml.safe_load(raw) or {}
    # env overrides must land before defaults are computed so flags like
    # VENEUR_OMITEMPTYHOSTNAME can affect default derivation
    cfg = load_config_dict(data, strict=strict, apply_defaults=False)
    _env_overrides(cfg, environ)
    cfg.apply_defaults()
    return cfg


def _expand(text: str, environ: dict[str, str]) -> str:
    def repl(m):
        name = m.group(1) or m.group(2)
        return environ.get(name, m.group(0))
    return re.sub(r"\$(?:\{(\w+)\}|(\w+))", repl, text)


def redacted_fields(cfg_obj, secret_fields: set, redact: bool = True) -> dict:
    """Dataclass config dump with the named secret fields redacted
    (util/string_secret.go:13-36); shared by the server and proxy config
    endpoints so redaction semantics cannot drift between them."""
    out = {}
    for f in fields(type(cfg_obj)):
        v = getattr(cfg_obj, f.name)
        if redact and f.name in secret_fields and v:
            v = "REDACTED"
        if isinstance(v, list) and v and not isinstance(
                v[0], (str, int, float)):
            v = [str(x) for x in v]
        out[f.name] = v
    return out


def redacted_dict(cfg: Config, redact: bool = True) -> dict:
    """Server config dump; redact=False is the -print-secrets escape
    hatch."""
    return redacted_fields(cfg, {"sentry_dsn", "tls_key"}, redact)
