"""The veneur_tpu server: listeners, flush ticker, sink fan-out, watchdog.

Composition root mirroring the reference `Server`
(`server.go:106-174,462-868`): DogStatsD listeners (UDP with SO_REUSEPORT
multi-reader parallelism as in `networking.go:54-107`/`socket_linux.go`,
TCP with optional TLS client-cert auth, UNIX datagram/stream), the interval
flush ticker with per-flush deadline, metric-sink fan-out handed to the
async egress data plane (central filtering per `flusher.go:115-247` runs
on the per-sink lanes, veneur_tpu/egress/), event/service-check handling
(`server.go:942-993`), the flush watchdog (`server.go:877-912`), and
pluggable sources/sinks/forwarder.

The aggregation core is the batched MetricAggregator (one arena set instead
of N worker goroutines; the key-shard parallelism lives on the device mesh,
see veneur_tpu/parallel/).
"""

from __future__ import annotations

import collections
import concurrent.futures
import logging
import os
import socket
import ssl
import struct
import threading
import time
from typing import Callable, Optional

from veneur_tpu import config as config_mod
from veneur_tpu import sinks as sink_mod
from veneur_tpu.core.aggregator import (ROW_ONLY_SEGMENT_KEYS,
                                        MetricAggregator)
from veneur_tpu.profiling.timeline import FlushTimeline
from veneur_tpu.samplers import parser as parser_mod
from veneur_tpu.samplers import samplers as sm
from veneur_tpu.sketches import hll as hll_mod
from veneur_tpu.util import matcher as matcher_mod
from veneur_tpu.util import netaddr
from veneur_tpu.util import tagging

logger = logging.getLogger("veneur_tpu.server")


def parse_listen_addr(addr: str) -> tuple[str, str]:
    """'udp://host:port' -> (scheme, rest); bare 'host:port' -> udp."""
    if "://" in addr:
        scheme, rest = addr.split("://", 1)
        return scheme, rest
    return "udp", addr


def _split_hostport(rest: str) -> tuple[str, int]:
    """host:port with RFC-3986 bracketed IPv6 support; unbracketed IPv6
    literals fail loudly (util/netaddr.py, the reference's ResolveAddr
    dialect)."""
    return netaddr.split_hostport(rest)


def _sock_family(host: str) -> int:
    return netaddr.family(host)


class _SpanSinkWorker:
    """One span sink's bounded queue + drain thread(s).

    The isolation analog of the reference SpanWorker's per-sink goroutine
    with a 9s ingest timeout (`worker.go:603-652`): each sink drains its
    own queue, so a hung or slow sink blocks only itself — its queue fills
    and further spans are dropped with accounting, while every other sink
    keeps receiving.  Per-sink cumulative ingest time backs the
    `sink.span_ingest_total_duration_ns` metric (worker.go:647-652)."""

    def __init__(self, sink, capacity: int, n_threads: int,
                 shutdown: threading.Event, excluded_tags=None):
        import queue as queue_mod
        self.sink = sink
        # tags_exclude keys stripped from spans before this sink sees them
        # (setSinkExcludedTags covers span sinks too, server.go:1456-1463)
        self.excluded_tags = excluded_tags or None
        self.queue: "queue_mod.Queue" = queue_mod.Queue(maxsize=capacity)
        self.dropped = 0
        self.ingested = 0
        self.errors = 0
        self.ingest_duration_ns = 0
        self._reported = (0, 0, 0, 0)
        self._shutdown = shutdown
        self.threads = []
        for i in range(max(1, n_threads)):
            t = threading.Thread(
                target=self._run, daemon=True,
                name=f"span-sink-{sink.name()}-{i}")
            t.start()
            self.threads.append(t)

    def submit(self, span) -> None:
        try:
            self.queue.put_nowait(span)
        except Exception:
            self.dropped += 1

    def interval_stats(self) -> tuple[int, int, int, int]:
        """(ingested, dropped, errors, duration_ns) since last call."""
        cur = (self.ingested, self.dropped, self.errors,
               self.ingest_duration_ns)
        delta = tuple(c - p for c, p in zip(cur, self._reported))
        self._reported = cur
        return delta

    def _run(self) -> None:
        import queue as queue_mod
        while not self._shutdown.is_set():
            try:
                span = self.queue.get(timeout=0.1)
            except queue_mod.Empty:
                continue
            if self.excluded_tags and any(
                    k in self.excluded_tags for k in span.tags):
                # copy-on-strip: the same span object fans out to the
                # other sinks, which may not share this exclusion
                # (SSFSpan.tags is a map<string,string>)
                stripped = type(span)()
                stripped.CopyFrom(span)
                for k in list(stripped.tags):
                    if k in self.excluded_tags:
                        del stripped.tags[k]
                span = stripped
            t0 = time.perf_counter_ns()
            try:
                self.sink.ingest(span)
                self.ingested += 1
            except Exception as e:
                self.errors += 1
                logger.warning("span sink %s ingest error: %s",
                               self.sink.name(), e)
            finally:
                self.ingest_duration_ns += time.perf_counter_ns() - t0


class _IngestShim:
    """sources.Ingest implementation handed to every source
    (the `ingest` shim, server.go:328-355)."""

    def __init__(self, server: "Server"):
        self._server = server

    def ingest_metric(self, m) -> None:
        self._server.aggregator.process_metric(m)

    def ingest_metric_proto(self, fm) -> None:
        self._server.aggregator.import_metric(fm)


class Server:
    def __init__(self, cfg: config_mod.Config,
                 extra_metric_sinks: Optional[list] = None,
                 extra_span_sinks: Optional[list] = None,
                 forwarder: Optional[Callable[[list[sm.ForwardMetric]], None]] = None):
        self.config = cfg
        self.extend_tags = tagging.ExtendTags(cfg.extend_tags)
        self.parser = parser_mod.Parser(self.extend_tags)
        # device mesh: the sharded serving flush runs over (shard, replica)
        # when mesh_devices is set (the multi-chip production path).  With
        # a distributed coordinator configured, join the multi-host
        # cluster FIRST so the mesh spans every host's chips (DCN story:
        # parallel/multihost.py).
        self.mesh = None
        from veneur_tpu.parallel import multihost
        # cluster join MUST precede any backend initialization (including
        # the default_backend() probe below)
        multihost.maybe_init_from_config(cfg)  # no-op without coordinator
        # persistent XLA compile cache: recompiles of known flush
        # buckets across process restarts become disk hits instead of
        # multi-second (or, at 1M keys, minute-scale) compiles.
        # TPU-backend only: XLA:CPU AOT cache entries are machine-
        # feature-specific and can SIGILL when reloaded on a different
        # host generation.  util/compile_cache.py places the directory
        # (JAX_COMPILATION_CACHE_DIR wins over the config).  No minimum
        # compile time: a flush bucket that compiles in a fraction of a
        # second still costs an uncovered in-flush compile on every boot.
        import jax as _jax
        logger.info("jax backend %s, %d device(s)",
                    _jax.default_backend(), _jax.device_count())
        if _jax.default_backend() == "tpu":
            from veneur_tpu.util import compile_cache
            compile_cache.enable(cfg.compilation_cache_dir,
                                 min_compile_secs=0.0)
        if cfg.mesh_devices > 0:
            from veneur_tpu.parallel import mesh as mesh_mod
            self.mesh = mesh_mod.make_mesh(
                cfg.mesh_devices, cfg.mesh_replicas or None)
        self.aggregator = MetricAggregator(
            percentiles=list(cfg.percentiles),
            aggregates=sm.parse_aggregates(cfg.aggregates),
            compression=cfg.tdigest_compression,
            set_precision=cfg.set_precision,
            count_unique_timeseries=cfg.count_unique_timeseries,
            mesh=self.mesh,
            ingest_lanes=cfg.ingest_lanes or None,
            is_local=cfg.is_local,
            initial_capacity=cfg.arena_initial_capacity,
            set_initial_capacity=cfg.set_arena_initial_capacity,
            hll_legacy_migration=cfg.hll_legacy_migration,
            digest_float64=cfg.digest_float64,
            digest_bf16_staging=cfg.digest_bf16_staging,
            flush_upload_chunks=cfg.flush_upload_chunks,
            flush_presharded_staging=cfg.flush_presharded_staging,
            flush_resident_arenas=cfg.flush_resident_arenas,
            flush_delta_chunk_keys=cfg.flush_delta_chunk_keys,
            flush_delta_nbuf=cfg.flush_delta_nbuf,
            resident_device_assembly=cfg.flush_resident_device_assembly,
            cardinality_key_budget=cfg.cardinality_key_budget,
            cardinality_tenant_tag=cfg.cardinality_tenant_tag,
            cardinality_seed=cfg.cardinality_seed,
            sketch_family_default=cfg.sketch_family_default,
            sketch_family_rules=list(cfg.sketch_family_rules),
            sketch_moments_k=cfg.sketch_moments_k,
            sketch_compactor_cap=cfg.sketch_compactor_cap,
            sketch_compactor_levels=cfg.sketch_compactor_levels,
            sketch_compactor_seed=cfg.sketch_compactor_seed,
            cardinality_rollup_family=cfg.cardinality_rollup_family,
            query_window_slots=cfg.query_window_slots,
            query_slot_seconds=(cfg.query_slot_seconds
                                or cfg.interval),
            cube_dimensions=list(cfg.cube_dimensions),
            cube_group_budget=cfg.cube_group_budget,
            cube_seed=cfg.cube_seed,
            retention_tiers=list(cfg.retention_tiers),
            retention_dir=(os.path.expanduser(cfg.retention_dir)
                           if cfg.retention_dir else ""),
            retention_max_bytes=cfg.retention_max_bytes,
            retention_max_age_s=cfg.retention_max_age,
            # lazy: self.statsd is created at start(); the timeline
            # resolves the client per emission via scopedstatsd.ensure
            retention_statsd_fn=lambda: self.statsd)
        self.forwarder = forwarder

        # sinks: configured kinds + directly injected instances
        self.metric_sinks: list[tuple[sink_mod.SinkSpec, object]] = []
        for spec in cfg.metric_sinks:
            self.metric_sinks.append(
                (spec, sink_mod.create_metric_sink(spec, cfg)))
        for s in (extra_metric_sinks or []):
            self.metric_sinks.append(
                (sink_mod.SinkSpec(kind=s.kind(), name=s.name()), s))
        self.span_sinks: list[object] = []
        for spec in cfg.span_sinks:
            self.span_sinks.append(sink_mod.create_span_sink(spec, cfg))
        self.span_sinks.extend(extra_span_sinks or [])

        # metric extraction from spans is always installed
        # (ssfmetrics, server.go:645-657)
        from veneur_tpu.sinks.ssfmetrics import MetricExtractionSink
        self.metric_extraction = MetricExtractionSink(
            self.parser, self.aggregator.process_metric,
            indicator_timer_name=cfg.indicator_span_timer_name,
            objective_timer_name=cfg.objective_span_timer_name)
        self.span_sinks.append(self.metric_extraction)

        # self-tracing flight recorder (veneur_tpu/trace/recorder.py):
        # an always-on bounded ring of finished spans, installed as a
        # span sink so everything on the span plane — the server's own
        # flush traces included — is queryable at /debug/trace
        from veneur_tpu.trace import recorder as trace_rec
        self.flight_recorder = trace_rec.FlightRecorder(
            cfg.trace_ring_capacity)
        self.span_sinks.append(self.flight_recorder)
        # per-interval distributed tracing: the deterministic seeded
        # sampler decides which flush intervals get the full treatment
        # (segment children, per-attempt forward spans, gRPC metadata
        # propagation); None = interval tracing off
        self.trace_sampler = (
            trace_rec.DeterministicSampler(cfg.trace_flush_sample_rate,
                                           cfg.trace_seed)
            if cfg.trace_flush_enabled else None)
        # live query plane (veneur_tpu/query/): the /query read path
        # over the aggregator's window rings.  The engine exists even
        # with the rings disabled so /query answers a clean 404.
        from veneur_tpu.query.engine import QueryEngine
        self.query = QueryEngine(
            self.aggregator, recorder=self.flight_recorder,
            statsd_fn=lambda: self.statsd,
            tier="local" if cfg.is_local else "global",
            hostname=cfg.hostname)
        # trace ids imported since the last flush (global tier): the
        # flush root span tags them so the cross-tier assembler can join
        # this global flush onto each settled local interval's trace
        self._imported_traces: set = set()
        self._imported_traces_lock = threading.Lock()

        # event/service-check accumulation (EventWorker, worker.go:491-536)
        self._events: list[parser_mod.SSFSample] = []
        self._events_lock = threading.Lock()

        # span pipeline: per-sink bounded queues, each drained by its own
        # worker thread(s) (SpanChan + SpanWorker with per-sink isolation,
        # worker.go:539-654)
        self.span_workers: list[_SpanSinkWorker] = []
        self.ssf_received = 0

        # self-telemetry loops back into our own span pipeline
        # (trace.NewChannelClient, server.go:518-521)
        from veneur_tpu import trace as trace_mod
        self.trace_client = trace_mod.new_channel_client(self.handle_span)

        # pluggable pull/push sources (sources/sources.go, wired like
        # createSources server.go:660-670); each gets the ingest shim at
        # start (server.go:328-355 — here the aggregator shards internally)
        from veneur_tpu import sources as sources_mod
        self.sources: list = [sources_mod.create_source(spec, cfg)
                              for spec in cfg.sources]
        self.ingest_shim = _IngestShim(self)
        self.statsd = None        # self-metrics client (stats_address)
        self.diagnostics = None   # runtime stats loop
        # opt-in runtime lock witness (analysis/witness.py): set a
        # LockWitness BEFORE start() and the named locks are wrapped to
        # record acquisition-order edges for the static cross-check
        self.lock_witness = None

        # crash durability (core/checkpoint.py + forward/spool.py):
        # the dedup ledger exists whenever this instance imports (its
        # state rides the checkpoint, so replayed chunks merge exactly
        # once across a receiver crash); checkpoint_stats is the
        # /debug/vars -> checkpoint ledger
        self.dedup = None
        if cfg.grpc_address:
            from veneur_tpu.sources.proxy import DedupLedger
            self.dedup = DedupLedger(cfg.spool_dedup_window)
        self.checkpoint_stats = {
            "enabled": bool(cfg.checkpoint_dir),
            "writes": 0, "restores": 0, "errors": 0,
            # checkpoints skipped at boot because a later flush had
            # already delivered their arena contents (flush marker)
            "stale_skips": 0,
            "last_bytes": 0, "last_unix": 0.0,
            # age of the restored checkpoint at boot (how much ingest
            # the crash window could have cost), 0 on a cold start
            "age_ms": 0.0,
        }
        self._checkpoint_write_lock = threading.Lock()
        # set by crash() (the testbed's simulated kill -9): shutdown
        # skips the final flush, the checkpoint write and the spool
        # drain — in-memory state is dropped, disk state is kept
        self._crashed = False
        self._listeners: list[socket.socket] = []
        # (lockfile path, open file) pairs guarding unix socket paths
        self._socket_locks: list[tuple[str, object]] = []
        # set by request_graceful_restart (SIGUSR2)
        self._graceful_restart = False
        # datagram readers stop on THIS event, not _shutdown: a graceful
        # restart sets _shutdown to unblock serve() but must keep readers
        # alive through the drain grace so the queued tail is consumed
        self._readers_stop = threading.Event()
        self._legacy_hll_reported = 0
        self._compiles_reported = (0, 0.0)
        self._threads: list[threading.Thread] = []
        self._shutdown = threading.Event()
        # the pool now carries only forward submissions — sink fan-out
        # moved to the egress data plane's per-sink lanes (below)
        self._flush_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.FORWARD_MAX_IN_FLIGHT + 2,
            thread_name_prefix="flush")
        self.last_flush_unix = time.time()
        self.flush_count = 0
        self._flush_serial = threading.Lock()
        # profiling subsystem: per-flush structured records, served at
        # /debug/flush_timeline (veneur_tpu/profiling/timeline.py)
        self.flush_timeline = FlushTimeline(cfg.profiling_timeline_capacity)
        # what the boot-time prewarm (_prewarm) compiled and how long it
        # took: /debug/vars -> prewarm_programs, prewarm_seconds
        self.prewarm_stats = {"programs": 0, "seconds": 0.0}
        # tags_exclude rules: "key" (every sink) or "key|sink1|sink2"
        # (those sinks only) — setSinkExcludedTags, server.go:660,1456-1463
        self._tags_exclude_global: set[str] = set()
        self._tags_exclude_by_sink: dict[str, set[str]] = {}
        for rule in cfg.tags_exclude:
            parts = str(rule).split("|")
            key = parts[0]
            if not key:
                continue
            if len(parts) > 1:
                for sink_name in parts[1:]:
                    if sink_name:
                        self._tags_exclude_by_sink.setdefault(
                            sink_name, set()).add(key)
            else:
                self._tags_exclude_global.add(key)
        # egress data plane (veneur_tpu/egress/): bounded per-sink
        # queues + worker lanes that take the whole sink fan-out —
        # filtering, serialization, HTTP, retries, spool spill — off
        # the flush critical path.  _flush_body_locked just enqueues.
        from veneur_tpu.egress import EgressPlane
        from veneur_tpu.forward.client import RetryPolicy as _RetryPolicy
        self.egress = EgressPlane(
            interval_s=cfg.interval,
            queue_depth=cfg.egress_queue_depth,
            retry=_RetryPolicy(
                attempts=cfg.egress_max_retries + 1,
                backoff_base_s=cfg.egress_retry_backoff,
                seed=cfg.egress_retry_seed),
            breaker_threshold=cfg.egress_breaker_threshold,
            breaker_reset_s=cfg.egress_breaker_reset,
            spool_dir=(os.path.expanduser(cfg.egress_spool_dir)
                       if cfg.egress_spool_dir else ""),
            spool_max_bytes=cfg.egress_spool_max_bytes,
            spool_max_age_s=cfg.egress_spool_max_age,
            spool_fsync=cfg.spool_fsync,
            spool_replay_interval_s=cfg.egress_spool_replay_interval,
            routing_enabled=cfg.enable_metric_sink_routing,
            excluded_tags_for=self._excluded_tags_for,
            recorder=self.flight_recorder,
            statsd_fn=lambda: self.statsd,
            timeline=self.flush_timeline)
        for spec, sink in self.metric_sinks:
            self.egress.add_metric_sink(spec, sink)
        for sink in self.span_sinks:
            self.egress.add_span_sink(sink)
        # last-reported egress totals (egress.* per-interval deltas)
        self._egress_reported: dict = {}
        # per-protocol received-packet tallies, drained each flush into
        # listen.received_per_protocol_total (flusher.go:280,455-475).
        # Plain int increments; GIL-atomic enough for telemetry.  Batch
        # adds from the native drain and flush()'s swap take _proto_lock
        # (a lost batch add is thousands of packets, not one).
        self.proto_received: collections.Counter = collections.Counter()
        self._proto_lock = threading.Lock()
        # last-reported native parse-error/too-long totals (flush deltas)
        self._native_err_reported = (0, 0)
        # which queue overflowed (read once per flush, _ingest_overflow):
        # the last interval's reading for /debug/vars, and the monotonic
        # totals its deltas are taken from
        self.ingest_overflow: dict = {}
        self._overflow_totals = (0, 0)
        # host-path loss counters (the no-silent-loss ledger for lines
        # the PYTHON paths discard — the native plane keeps its own):
        # unparseable statsd lines, unparseable SSF datagrams, span-sink
        # ingest raises, and import-edge failures; drained each flush
        # into listen.parse_errors_total / worker.span.* /
        # import.errors_total deltas
        self.parse_errors = 0
        self.ssf_parse_errors = 0
        self.span_ingest_errors = 0
        self._host_err_reported = (0, 0, 0)
        self._import_err_reported = 0
        # Bounded-concurrency forwarding: the reference gives each flush its
        # own goroutine with a one-interval ctx deadline (flusher.go:81-86),
        # so in-flight forwards are implicitly bounded by deadline/interval.
        # With the deadline floored at 10s (see start()), we bound explicitly
        # instead: up to FORWARD_MAX_IN_FLIGHT concurrent streams, and drop
        # the batch when all slots are stalled (UDP-heritage loss model).
        self._forward_slots = threading.BoundedSemaphore(
            self.FORWARD_MAX_IN_FLIGHT)
        self.forward_dropped = 0
        # last-reported forward-client (retries, dropped) totals, for
        # per-interval forward.retries_total/forward.dropped_total deltas
        self._forward_client_reported = (0, 0)
        # last-reported spool ledger totals (forward.spool.* deltas)
        self._spool_reported: dict = {}
        # accepted stream connections, closed on shutdown so reader
        # threads blocked in recv are unblocked
        self._stream_conns: set = set()
        self._stream_conns_lock = threading.Lock()
        # resolved addresses (after binding port 0)
        self.statsd_addrs: list[tuple[str, object]] = []
        self.ssf_addrs: list[tuple[str, object]] = []
        self.grpc_import = None
        # edge gRPC ingest listeners (grpc_listen_addresses)
        self.grpc_ingest_listeners: list = []
        # native ingest data plane (created in start(); None = Python path)
        self.native = None
        self.shutdown_hook: Callable[[], None] = lambda: os._exit(2)

    @property
    def is_local(self) -> bool:
        return self.config.is_local

    def resolved_ports(self) -> dict:
        """The ACTUAL bound addresses after start() — what a
        supervising harness needs when every listener bound port 0
        (config.port_file; cli/veneur.py writes this dict as JSON)."""
        return {
            "statsd": [[scheme, list(addr) if isinstance(addr, tuple)
                        else str(addr)]
                       for scheme, addr in self.statsd_addrs],
            "grpc": (self.grpc_import.port
                     if self.grpc_import is not None else 0),
            "hostname": self.config.hostname,
        }

    # -- ingestion handlers (server.go:942-1011) ---------------------------

    def handle_metric_packet(self, packet: bytes) -> None:
        """Dispatch one line: event / service check / metric."""
        if not packet:
            return
        try:
            if packet.startswith(b"_e{"):
                sample = self.parser.parse_event(packet)
                with self._events_lock:
                    self._events.append(sample)
            elif packet.startswith(b"_sc"):
                m = self.parser.parse_service_check(packet)
                self.aggregator.process_metric(m)
            else:
                self.parser.parse_metric(
                    packet, self.aggregator.process_metric)
        except parser_mod.ParseError as e:
            # visible loss: joins listen.parse_errors_total
            # (protocol:python) at the next interval accounting.
            # Locked: several reader threads hit this path, and the
            # loss ledger itself must not lose increments.
            with self._proto_lock:
                self.parse_errors += 1
            logger.debug("could not parse packet %r: %s", packet[:64], e)

    def process_packet_buffer(self, buf: bytes) -> None:
        """Newline-split a datagram (processMetricPacket,
        server.go:1109-1133)."""
        if len(buf) > self.config.metric_max_length:
            logger.debug("packet too long (%d bytes)", len(buf))
            return
        for line in buf.split(b"\n"):
            if line:
                self.handle_metric_packet(line)

    # -- listeners (networking.go) ----------------------------------------

    def start(self) -> None:
        # restore from the crash checkpoint FIRST — before any
        # listener, import server or drain thread can race the arena
        # rebuild (the arenas must be fresh for restore_state)
        if self.config.checkpoint_dir:
            self._maybe_restore_checkpoint()
        if self.config.prewarm_flush_shapes and self.mesh is not None:
            # a meshed global compiles its programs BEFORE it opens a
            # listener or ticks.  One meshed compile is ~20 s cold; a
            # global that took forwards meanwhile flushed late, the late
            # flush held two intervals (twice the depth: a new bucket,
            # another 20 s), ticks cut 14 s imports in half (partial key
            # sets: more buckets), and it took 19 intervals to come
            # back (PERF.md section 6, PR 28: 15 compiles, 282 s).  A
            # sender that cannot connect yet retries or spools; ~45 s
            # from a cold compile cache, ~1.5 s from a warm one.
            self._prewarm()
        elif self.config.prewarm_flush_shapes:
            # an unmeshed node launches, before it listens, the closed
            # list of programs a steady interval of its deployment can
            # need — the flush at the arena pre-size's row buckets, the
            # deep tier and the hot-key compress of a skewed interval
            # (MetricAggregator.prewarm_launch) — for
            # the meshed boot's reason: the hot-key compress first
            # launches under the aggregator lock on the drain thread,
            # and a compile there is a late flush.  Nothing compiles
            # beside the live server any more: the pow2 sweep of every
            # bucket below (MetricAggregator.prewarm, ahead-of-time)
            # used to run as a thread from here, held a core for
            # minutes from a cold cache, and marked shapes compiled
            # that their first live launch compiled again; a bucket the
            # list lacks compiles in its first flush, under the guard.
            self._prewarm_launch()
            if self.aggregator.sets.resident:
                self._prewarm_set_lanes()
        elif self.aggregator.sets.resident:
            # an unmeshed arena whose set registers live on the device
            # launches its lane programs — a closed set, sized by the
            # arena's capacity (SetArena.prewarm_lanes) — on a scratch
            # plane before it listens, for the meshed boot's reason: at
            # 1 GiB of registers each compiles for seconds, and a first
            # launch inside an interval is a late flush
            self._prewarm_set_lanes()
        has_udp_statsd = any(
            parse_listen_addr(a)[0] == "udp"
            for a in self.config.statsd_listen_addresses)
        if self.config.native_ingest and has_udp_statsd:
            # the C++ edge data plane (UDP readers + parser + staging);
            # the Python chain stays as the slow path for what the
            # engine hands back (events, service checks).  Only built
            # when a UDP listener exists to feed it — TCP/unix-only
            # configs skip the engine (and its first-run g++ compile).
            # An engine that cannot be built or loaded is a BOOT ERROR:
            # a server asked for the native data plane must not carry
            # on through the Python packet path at a fraction of the
            # rate.  `native_ingest: false` is how to ask for that path.
            from veneur_tpu.ingest import NativeIngest
            self.native = NativeIngest(
                self.aggregator,
                max_packet=self.config.metric_max_length,
                implicit_tags=list(self.config.extend_tags),
                on_other=self.handle_metric_packet,
                simd=self.config.ingest_simd,
                backend=self.config.ingest_backend,
                batch=self.config.ingest_reader_batch,
                ring_slots=self.config.ingest_ring_slots)
        for sspec, sink in self.metric_sinks:
            sink.start(None)
        for sink in self.span_sinks:
            sink.start(None)
        # spin up the egress lanes (sinks are started; the lanes may
        # immediately replay any spool records a crash left behind)
        self.egress.start()
        for addr in self.config.statsd_listen_addresses:
            self._start_statsd(addr)
        for addr in self.config.ssf_listen_addresses:
            self._start_ssf(addr)
        for addr in self.config.grpc_listen_addresses:
            self._start_grpc_ingest(addr)
        for sink in self.span_sinks:
            self.span_workers.append(_SpanSinkWorker(
                sink, self.config.span_channel_capacity,
                self.config.num_span_workers, self._shutdown,
                excluded_tags=self._excluded_tags_for(sink.name())))
        if self.config.grpc_address:
            # global tier: gRPC import source (server.go:673-682)
            from veneur_tpu.sources.proxy import GrpcImportServer

            def _import_counted(fm):
                self.proto_received["grpc"] += 1
                self.aggregator.import_metric(fm)

            def _import_payload_counted(payload, stream=None):
                # a V1 RPC's MetricList, or a chunk of a V2 stream
                # (`stream`: sources.proxy.StreamChunk)
                ok, failed = self.aggregator.import_payload(payload,
                                                            stream)
                with self._proto_lock:
                    self.proto_received["grpc"] += ok
                return ok, failed

            self.grpc_import = GrpcImportServer(
                self.config.grpc_address,
                _import_counted,
                ingest_span=self._grpc_span_counted,
                handle_packet=self._grpc_packet_counted,
                import_payload=_import_payload_counted,
                trace_hook=self._record_import_span,
                dedup=self.dedup)
            self.grpc_import.start()
        if self.config.forward_address and self.forwarder is None:
            # local tier: persistent forward connection (server.go:810-828)
            from veneur_tpu.forward.client import ForwardClient, RetryPolicy
            spool = None
            if self.config.spool_dir:
                from veneur_tpu.forward.spool import ForwardSpool
                spool = ForwardSpool(
                    os.path.expanduser(self.config.spool_dir),
                    max_bytes=self.config.spool_max_bytes,
                    max_age_s=self.config.spool_max_age,
                    fsync=self.config.spool_fsync,
                    segment_max_bytes=self.config.spool_segment_max_bytes,
                    replay_interval_s=self.config.spool_replay_interval)
            # The reference bounds each forward by one flush interval
            # (flusher.go:516-591).  Here at most FORWARD_MAX_IN_FLIGHT
            # forwards run concurrently (later flushes drop theirs once the
            # semaphore is exhausted — see flush()), so the deadline can be
            # floored at the reference's default interval without unbounded
            # pileup; sub-second test intervals would otherwise starve a
            # cold-start peer mid-stream.  Transient failures retry under
            # the config-driven bounded policy (exhaustion is accounted in
            # forward.dropped_total / /debug/vars).
            self.forwarder = ForwardClient(
                self.config.forward_address,
                timeout_s=self.config.forward_timeout
                or max(self.config.interval, 10.0),
                max_streams=self.config.forward_streams,
                retry=RetryPolicy(
                    attempts=self.config.forward_max_retries + 1,
                    backoff_base_s=self.config.forward_retry_backoff),
                spool=spool, source=self.config.hostname,
                trace_recorder=self.flight_recorder,
                deadline_retry_safe=self.config
                .forward_deadline_retry_safe)
        if self.lock_witness is not None:
            # testbed/dryrun lock witness (analysis/witness.py): wrap
            # the named locks NOW — native plane and forwarder exist,
            # none of the contending threads (ticker, drain loop,
            # watchdog, prewarm) have spawned yet, so no lock is
            # replaced while another thread can hold it
            from veneur_tpu.analysis import witness as witness_mod
            witness_mod.install_server(self, self.lock_witness)
        if self.config.flush_watchdog_missed_flushes > 0:
            t = threading.Thread(target=self._watchdog, daemon=True,
                                 name="flush-watchdog")
            t.start()
            self._threads.append(t)
        if self.config.checkpoint_dir and self.config.checkpoint_interval > 0:
            t = threading.Thread(target=self._checkpoint_loop,
                                 daemon=True, name="checkpoint-loop")
            t.start()
            self._threads.append(t)
        # self-metrics statsd client + runtime diagnostics loop
        # (cmd/veneur/main.go:85-94, diagnostics/diagnostics_metrics.go).
        # A telemetry-witness recorder (analysis/telemetry.py) may have
        # wrapped a pre-start None: the configured client slots in as
        # its inner target so recording composes instead of suppressing.
        if self.config.stats_address and (
                self.statsd is None
                or hasattr(self.statsd, "replace_inner")):
            from veneur_tpu import scopedstatsd
            sc = self.config.veneur_metrics_scopes or {}
            client = scopedstatsd.ScopedClient(
                self.config.stats_address,
                scopes=scopedstatsd.MetricScopes(
                    counter=sc.get("counter", ""),
                    gauge=sc.get("gauge", ""),
                    histogram=sc.get("histogram", ""),
                    set_=sc.get("set", ""),
                    timing=sc.get("timing", "")),
                tags=list(self.config.veneur_metrics_additional_tags))
            if self.statsd is None:
                self.statsd = client
            else:
                self.statsd.replace_inner(client)
        if self.config.diagnostics_metrics_enabled:
            from veneur_tpu import diagnostics as diag_mod
            self.diagnostics = diag_mod.Diagnostics(
                self.statsd, interval_s=self.config.interval,
                tags=list(self.config.veneur_metrics_additional_tags),
                # push the data-plane stage totals alongside the runtime
                # stats (reads self.native at call time: safe across the
                # engine's whole lifecycle, {} once it is torn down)
                sources=[
                    lambda: diag_mod.ingest_stage_gauges(self.native),
                    # per-tenant quota/eviction counters (cardinality.*)
                    lambda: diag_mod.cardinality_gauges(self.aggregator),
                ])
            self.diagnostics.start()
        for source in self.sources:
            source.start(self.ingest_shim)
        if self.native is not None:
            t = threading.Thread(target=self._native_drain_loop, daemon=True,
                                 name="ingest-drain")
            t.start()
            self._threads.append(t)

    def _drain_native(self) -> None:
        """Fold the native engine's staged batches into the arenas and
        account the drained datagrams (the coarse-grained analog of the
        reference's per-packet worker channel sends, worker.go:274-290)."""
        if self.native is None:
            return
        batch = self.native.drain_into()
        self._count_drained(batch)

    def _count_drained(self, batch) -> None:
        if batch.packets:
            # under _proto_lock so flush()'s counter swap cannot strand a
            # batch-sized increment on the already-reported Counter
            with self._proto_lock:
                self.proto_received["udp"] += batch.packets

    def _native_drain_loop(self) -> None:
        iv = self.config.ingest_drain_interval or min(
            self.config.interval / 10.0, 0.5)
        while not self._shutdown.wait(iv):
            try:
                self._count_drained(self.native.drain_or_gc(
                    self.config.intern_gc_threshold))
            except Exception:
                logger.exception("native ingest drain failed")
                continue
            if (self.config.eager_device_sync
                    or self.config.flush_resident_arenas):
                # P7 pipelining: push this tick's staged samples into
                # the device lanes NOW so flush-time sync only covers
                # the final partial tick, instead of the whole
                # interval's backlog arriving at the snapshot.  With
                # resident arenas the same tick also STREAMS the
                # consolidated delta chunks into HBM, which is the whole
                # point of the mode — upload amortized into the
                # interval — so the gate is implied by the flag
                try:
                    self.aggregator.sync_staged()
                except Exception:
                    logger.exception("eager device sync failed")

    def stop_serving(self) -> None:
        """Unblock serve() without tearing down (signal-handler safe:
        takes no locks, so it may run while a flush is mid-flight)."""
        self._shutdown.set()

    def request_graceful_restart(self) -> None:
        """Signal-handler-safe SIGUSR2 entry: flag the serve loop to run
        the zero-drop handoff (the einhorn/goji analog of
        server.go:1365-1413)."""
        self._graceful_restart = True
        self._shutdown.set()

    def graceful_restart_drain(self, grace_s: float = 0.5) -> None:
        """Zero-drop restart handoff (server.go:1365-1413 SIGUSR2
        semantics, re-imagined on SO_REUSEPORT): the REPLACEMENT process
        binds the same UDP addresses first (the kernel's reuseport group
        admits it immediately), then this process

          1. connect()s each of its UDP sockets to a blackhole peer —
             atomically steering all NEW datagrams to the replacement's
             sockets while the already-queued tail stays readable;
          2. keeps its readers running for `grace_s` to consume that
             tail;
          3. drains the native engine and runs the final flush
             (flush_on_shutdown path) before tearing down.

        Unix sockets have no reuseport group: their listeners drain and
        close FIRST (flock released immediately), so the replacement can
        bind the path during the grace window — `_bind_unix` retries a
        locked path briefly for exactly this ordering.  A unixgram sender
        hitting the brief gap gets ECONNREFUSED (visible, not silent
        loss), which matches the reference's behavior without einhorn."""
        unix_socks = [s for s in self._listeners
                      if s.family == socket.AF_UNIX
                      and s.type == socket.SOCK_DGRAM]
        for sock in unix_socks:
            # consume whatever is queued, then close + release the lock
            sock.setblocking(False)
            while True:
                try:
                    data = sock.recv(self.config.metric_max_length + 1)
                # vnlint: disable=silent-loss (EWOULDBLOCK is the
                #   drain-until-empty terminator of the shutdown sweep:
                #   no datagram was read, so none can be lost here)
                except (BlockingIOError, OSError):
                    break
                if data:
                    self.handle_metric_packet(data)
            try:
                self._listeners.remove(sock)
                sock.close()
            except (ValueError, OSError):
                pass
        for lock_path, lock_f in self._socket_locks:
            try:
                lock_f.close()
                os.unlink(lock_path)
            except OSError:
                pass
        self._socket_locks = []
        for sock in self._listeners:
            if sock.type != socket.SOCK_DGRAM:
                continue
            if sock.family == socket.AF_UNIX:
                continue
            try:
                # discard port; never actually sent to
                target = ("127.0.0.1", 9) if sock.family == socket.AF_INET \
                    else ("::1", 9)
                sock.connect(target)
            except OSError:
                logger.exception("graceful restart: connect() failed")
        time.sleep(grace_s)      # readers consume the queued tail
        self._drain_native()
        self.shutdown()

    def _bind_unix(self, path: str, socktype: int) -> socket.socket:
        """Bind a unix socket path with the reference's semantics:
        `@`-prefixed paths use the Linux abstract namespace (tested
        server_test.go:477-1053 — no filesystem entry, no unlink), and
        filesystem paths take an exclusive flock on a sidecar lockfile
        before unlinking a possibly-live socket (networking.go:395-408),
        so two servers cannot silently steal each other's path."""
        sock = socket.socket(socket.AF_UNIX, socktype)
        if path.startswith("@"):
            sock.bind("\0" + path[1:])
            return sock
        import fcntl
        lock_f = open(path + ".lock", "w")
        # bounded retry: a replacement started just before the old
        # instance's SIGUSR2 drain releases the lock within the grace
        # window (graceful_restart_drain ordering)
        deadline = time.time() + 1.0
        while True:
            try:
                fcntl.flock(lock_f, fcntl.LOCK_EX | fcntl.LOCK_NB)
                break
            except OSError:
                if time.time() >= deadline:
                    lock_f.close()
                    sock.close()
                    raise RuntimeError(
                        f"socket path {path!r} is locked by another "
                        f"instance")
                time.sleep(0.05)
        self._socket_locks.append((path + ".lock", lock_f))
        if os.path.exists(path):
            os.unlink(path)
        sock.bind(path)
        return sock

    def _start_statsd(self, addr: str) -> None:
        scheme, rest = parse_listen_addr(addr)
        if scheme == "udp":
            host, port = _split_hostport(rest)
            first_sock = None
            # shard count: the flow-sharded native plane can run more
            # reader sockets than the Python fallback's thread count
            n_shards = (self.config.ingest_reader_shards
                        if self.native is not None
                        and self.config.ingest_reader_shards > 0
                        else max(1, self.config.num_readers))
            n_cpus = os.cpu_count() or 1
            for i in range(n_shards):
                sock = socket.socket(_sock_family(host),
                                     socket.SOCK_DGRAM)
                # SO_REUSEPORT kernel load balancing (socket_linux.go:26-28)
                if hasattr(socket, "SO_REUSEPORT"):
                    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
                sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF,
                                self.config.read_buffer_size_bytes)
                if first_sock is None:
                    sock.bind((host, port))
                    first_sock = sock
                    port = sock.getsockname()[1]  # resolve port 0
                else:
                    sock.bind((host, port))
                self._listeners.append(sock)
                if self.native is not None:
                    # C++ reader loop owns this socket's hot path
                    # (io_uring multishot or recvmmsg, runtime-probed)
                    pin = (i % n_cpus
                           if self.config.ingest_reader_pinning else -1)
                    self.native.engine.add_udp_reader(sock.fileno(),
                                                      pin_cpu=pin)
                else:
                    t = threading.Thread(target=self._read_udp, args=(sock,),
                                         daemon=True, name=f"statsd-udp-{i}")
                    t.start()
                    self._threads.append(t)
            self.statsd_addrs.append(("udp", first_sock.getsockname()))
        elif scheme in ("tcp", "tcp+tls"):
            host, port = _split_hostport(rest)
            sock = socket.socket(_sock_family(host), socket.SOCK_STREAM)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind((host, port))
            sock.listen(128)
            self._listeners.append(sock)
            ctx = self._tls_context() if (
                scheme == "tcp+tls" or self.config.tls_key) else None
            t = threading.Thread(target=self._accept_tcp,
                                 args=(sock, ctx, "tcp"),
                                 daemon=True, name="statsd-tcp")
            t.start()
            self._threads.append(t)
            self.statsd_addrs.append(("tcp", sock.getsockname()))
        elif scheme == "unixgram":
            path = rest
            sock = self._bind_unix(path, socket.SOCK_DGRAM)
            self._listeners.append(sock)
            t = threading.Thread(target=self._read_udp,
                                 args=(sock, "unixgram"),
                                 daemon=True, name="statsd-unixgram")
            t.start()
            self._threads.append(t)
            self.statsd_addrs.append(("unixgram", path))
        elif scheme == "unix":
            path = rest
            sock = self._bind_unix(path, socket.SOCK_STREAM)
            sock.listen(128)
            self._listeners.append(sock)
            t = threading.Thread(target=self._accept_tcp,
                                 args=(sock, None, "unix"),
                                 daemon=True, name="statsd-unix")
            t.start()
            self._threads.append(t)
            self.statsd_addrs.append(("unix", path))
        else:
            raise ValueError(f"unknown statsd listener scheme {scheme!r}")

    def _grpc_packet_counted(self, buf: bytes) -> None:
        """dogstatsd bytes over gRPC (DOGSTATSD_GRPC, networking.go:347);
        counted identically on edge and global-tier listeners."""
        with self._proto_lock:
            self.proto_received["dogstatsd-grpc"] += 1
        self.process_packet_buffer(buf)

    def _grpc_span_counted(self, span) -> None:
        """SSF spans over gRPC (SSF_GRPC, networking.go:353)."""
        with self._proto_lock:
            self.proto_received["ssf-grpc"] += 1
        self.handle_span(span)

    def _grpc_server_credentials(self):
        """mTLS credentials for gRPC listeners when the server TLS config
        is set (networking.go:363-374: the reference encrypts the gRPC
        listener with the same tlsConfig as the statsd TCP listener,
        requiring client certs when an authority is configured)."""
        key_set = bool(self.config.tls_key)
        cert_set = bool(self.config.tls_certificate)
        if not key_set and not cert_set:
            return None
        if key_set != cert_set:
            # fail LOUD like the statsd TCP path's load_cert_chain would —
            # a half-configured TLS setup must never bind plaintext
            raise ValueError(
                "tls_key and tls_certificate must both be set for TLS "
                "gRPC listeners (got only one)")
        import grpc as grpc_mod
        with open(self.config.tls_key, "rb") as f:
            key = f.read()
        with open(self.config.tls_certificate, "rb") as f:
            cert = f.read()
        ca = None
        if self.config.tls_authority_certificate:
            with open(self.config.tls_authority_certificate, "rb") as f:
                ca = f.read()
        return grpc_mod.ssl_server_credentials(
            [(key, cert)], root_certificates=ca,
            require_client_auth=ca is not None)

    def _start_grpc_ingest(self, addr: str) -> None:
        """Edge gRPC ingest: SSF SendSpan + raw dogstatsd SendPacket on
        one listener (StartGRPC, networking.go:326-391) — available on
        any instance, unlike grpc_address's global-tier Forward import."""
        from veneur_tpu.sources.proxy import GrpcImportServer

        scheme, rest = parse_listen_addr(addr)
        if scheme not in ("tcp", "grpc"):
            raise ValueError(
                f"unknown grpc listener scheme {scheme!r} in {addr!r}")
        srv = GrpcImportServer(
            rest, import_metric=None,
            ingest_span=self._grpc_span_counted,
            handle_packet=self._grpc_packet_counted,
            server_credentials=self._grpc_server_credentials())
        srv.start()
        self.grpc_ingest_listeners.append(srv)

    def _tls_context(self) -> ssl.SSLContext:
        """TLS with required client certs when an authority is configured
        (server.go:1257-1281)."""
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(self.config.tls_certificate,
                            self.config.tls_key)
        if self.config.tls_authority_certificate:
            ctx.load_verify_locations(self.config.tls_authority_certificate)
            ctx.verify_mode = ssl.CERT_REQUIRED
        return ctx

    def _read_udp(self, sock: socket.socket, proto: str = "udp") -> None:
        # +1 so an oversized datagram still trips the too-long guard
        # instead of being silently truncated into a parseable prefix
        # (the reference allocates metricMaxLength+1, server.go:734).
        bufsize = self.config.metric_max_length + 1
        while not self._readers_stop.is_set():
            try:
                data = sock.recv(bufsize)
            except OSError:
                return
            if data:
                # always through the attribute: flush() swaps in a fresh
                # Counter each interval, so a cached reference would be
                # orphaned after the first drain
                self.proto_received[proto] += 1
                self.process_packet_buffer(data)

    def _accept_tcp(self, sock: socket.socket,
                    ctx: Optional[ssl.SSLContext],
                    proto: str = "tcp") -> None:
        while not self._shutdown.is_set():
            try:
                conn, _ = sock.accept()
            except OSError:
                return
            t = threading.Thread(target=self._read_stream,
                                 args=(conn, ctx, proto), daemon=True)
            t.start()

    # idle timeout for stream connections (the reference arms a read
    # deadline per connection, server.go:1283-1295)
    STREAM_IDLE_TIMEOUT_S = 600.0
    FORWARD_MAX_IN_FLIGHT = 4

    def _track_conn(self, conn) -> None:
        with self._stream_conns_lock:
            self._stream_conns.add(conn)

    def _untrack_conn(self, conn) -> None:
        with self._stream_conns_lock:
            self._stream_conns.discard(conn)

    def _read_stream(self, conn: socket.socket,
                     ctx: Optional[ssl.SSLContext],
                     proto: str = "tcp") -> None:
        max_line = max(65536, self.config.metric_max_length)
        raw_conn = conn
        self._track_conn(raw_conn)
        try:
            conn.settimeout(self.STREAM_IDLE_TIMEOUT_S)
            if ctx is not None:
                conn = ctx.wrap_socket(conn, server_side=True)
            buf = b""
            while not self._shutdown.is_set():
                data = conn.recv(65536)
                if not data:
                    break
                buf += data
                *lines, buf = buf.split(b"\n")
                for line in lines:
                    if line:
                        self.proto_received[proto] += 1
                        self.handle_metric_packet(line)
                if len(buf) > max_line:
                    # a line that never ends: drop the connection rather
                    # than buffer unboundedly (bufio.Scanner's token cap)
                    logger.debug("stream line exceeded %d bytes; closing",
                                 max_line)
                    return
            if buf:
                self.handle_metric_packet(buf)
        # vnlint: disable=silent-loss (connection teardown: every
        #   COMPLETE line was already handled above; only the torn tail
        #   of a dying stream is unreadable, and the peer owns
        #   reconnect-and-resend per the statsd-TCP contract)
        except (ssl.SSLError, OSError, TimeoutError) as e:
            logger.debug("stream connection error: %s", e)
        finally:
            self._untrack_conn(raw_conn)
            try:
                conn.close()
            except OSError:
                pass

    # -- spans (SSF pipeline) ----------------------------------------------

    def handle_trace_packet(self, packet: bytes) -> None:
        """One raw SSFSpan protobuf datagram (HandleTracePacket,
        server.go:1015-1044)."""
        from veneur_tpu import ssf as ssf_mod
        if not packet:
            return
        try:
            span = ssf_mod.parse_ssf(packet)
        except Exception as e:
            # visible loss: joins listen.parse_errors_total
            # (protocol:ssf) at the next interval accounting (locked:
            # concurrent SSF readers share this counter)
            with self._proto_lock:
                self.ssf_parse_errors += 1
            logger.debug("could not parse SSF packet: %s", e)
            return
        self.handle_span(span)

    def handle_span(self, span) -> None:
        """Fan one span out to every span sink's queue (handleSSF,
        server.go:1046-1093 + SpanWorker fan-out, worker.go:603-652);
        a full sink queue drops for that sink only."""
        self.ssf_received += 1
        if self.span_workers:
            for w in self.span_workers:
                w.submit(span)
        else:
            # not started yet (or no sinks): synchronous fallback so tests
            # and pre-start self-telemetry are not silently lost
            self.ingest_span(span)

    @property
    def spans_dropped(self) -> int:
        return sum(w.dropped for w in self.span_workers)

    def ingest_span(self, span) -> None:
        for sink in self.span_sinks:
            try:
                sink.ingest(span)
            except Exception as e:
                # visible loss: this direct path (gRPC SendSpan) has no
                # _SpanSinkWorker error counter in front of it (locked:
                # the gRPC pool runs these handlers concurrently)
                with self._proto_lock:
                    self.span_ingest_errors += 1
                logger.warning("span sink %s ingest error: %s",
                               sink.name(), e)

    def _start_ssf(self, addr: str) -> None:
        """SSF listeners (StartSSF, networking.go:223-319): UDP datagrams
        carry a raw SSFSpan protobuf; unix/tcp streams carry framed
        spans, where any framing error poisons the stream."""
        scheme, rest = parse_listen_addr(addr)
        if scheme == "udp":
            host, port = _split_hostport(rest)
            sock = socket.socket(_sock_family(host), socket.SOCK_DGRAM)
            if hasattr(socket, "SO_REUSEPORT"):
                sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF,
                            self.config.read_buffer_size_bytes)
            sock.bind((host, port))
            self._listeners.append(sock)
            t = threading.Thread(target=self._read_ssf_udp, args=(sock,),
                                 daemon=True, name="ssf-udp")
            t.start()
            self._threads.append(t)
            self.ssf_addrs.append(("udp", sock.getsockname()))
        elif scheme in ("unix", "tcp"):
            if scheme == "unix":
                sock = self._bind_unix(rest, socket.SOCK_STREAM)
                bound = rest
            else:
                host, port = _split_hostport(rest)
                sock = socket.socket(_sock_family(host),
                                     socket.SOCK_STREAM)
                sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                sock.bind((host, port))
                bound = sock.getsockname()
            sock.listen(128)
            self._listeners.append(sock)
            t = threading.Thread(target=self._accept_ssf, args=(sock,),
                                 daemon=True, name=f"ssf-{scheme}")
            t.start()
            self._threads.append(t)
            self.ssf_addrs.append((scheme, bound))
        else:
            raise ValueError(f"unknown SSF listener scheme {scheme!r}")

    def _read_ssf_udp(self, sock: socket.socket) -> None:
        # a UDP datagram can't exceed 64KiB; don't allocate the full
        # (16MiB default) trace_max_length_bytes per recv
        bufsize = min(self.config.trace_max_length_bytes, 65536)
        while not self._readers_stop.is_set():
            try:
                data = sock.recv(bufsize)
            except OSError:
                return
            if data:
                self.proto_received["ssf-udp"] += 1
                self.handle_trace_packet(data)

    def _accept_ssf(self, sock: socket.socket) -> None:
        while not self._shutdown.is_set():
            try:
                conn, _ = sock.accept()
            except OSError:
                return
            t = threading.Thread(target=self._read_ssf_stream,
                                 args=(conn,), daemon=True)
            t.start()

    def _read_ssf_stream(self, conn: socket.socket) -> None:
        from veneur_tpu import ssf as ssf_mod
        self._track_conn(conn)
        try:
            # No idle timeout here: trace clients hold one long-lived SSF
            # stream and may go quiet for arbitrary stretches; closing an
            # idle stream server-side makes the client's next span die on
            # EPIPE (the statsd stream path keeps the timeout for reference
            # parity with server.go:1283-1295, but SSF streams are
            # reconnect-on-error, not reconnect-before-send).
            f = conn.makefile("rb")
            while not self._shutdown.is_set():
                span = ssf_mod.read_ssf(f)
                if span is None:
                    return
                self.proto_received["ssf-stream"] += 1
                self.handle_span(span)
        # vnlint: disable=silent-loss (stream teardown: every parsed
        #   span was counted into proto_received above; a poisoned or
        #   dying stream closes and the SSF client reconnects — no
        #   complete span is dropped here)
        except ssf_mod.FramingError as e:
            # the stream is poisoned; close it (protocol/wire.go:26-28)
            logger.debug("SSF framing error, closing stream: %s", e)
        # vnlint: disable=silent-loss (same teardown contract as the
        #   framing-error arm above: nothing parsed is in flight)
        except OSError:
            pass
        finally:
            self._untrack_conn(conn)
            try:
                conn.close()
            except OSError:
                pass

    # -- crash durability (core/checkpoint.py) -----------------------------

    def _maybe_restore_checkpoint(self) -> None:
        """Boot-time restore: rebuild arenas, resume the interval count
        and refill the dedup ledger from the last committed checkpoint.
        A missing/corrupt file is a cold start, never a boot failure."""
        from veneur_tpu.core import checkpoint as ckpt_mod
        ckpt_dir = os.path.expanduser(self.config.checkpoint_dir)
        loaded = ckpt_mod.read_checkpoint(ckpt_dir)
        if loaded is None:
            return
        meta, arrays = loaded
        marker = ckpt_mod.read_flush_marker(ckpt_dir)
        if (marker is not None and int(marker.get("flush_count", 0))
                > int(meta.get("flush_count", 0))):
            # a flush COMPLETED after this checkpoint was written: its
            # arenas hold data that was already forwarded/emitted, and
            # a revived sender would re-deliver it under a fresh boot
            # nonce the dedup ledger cannot match.  Skip the arena
            # restore (honest crash-window loss: at most the ingest
            # since that flush), but still resume the interval count
            # and the receiver-side dedup ledger.
            self.flush_count = int(marker["flush_count"])
            if self.dedup is not None and meta.get("dedup") is not None:
                self.dedup.restore(meta["dedup"])
            self.checkpoint_stats["stale_skips"] = (
                self.checkpoint_stats.get("stale_skips", 0) + 1)
            logger.warning(
                "checkpoint (interval %s) predates the last completed "
                "flush (interval %s): skipping arena restore to avoid "
                "re-forwarding delivered data; interval count and "
                "dedup ledger resumed",
                meta.get("flush_count"), marker["flush_count"])
            return
        from veneur_tpu.core.arena import CheckpointIncompatible
        try:
            self.aggregator.restore_state(meta["aggregator"], arrays)
            self.flush_count = int(meta.get("flush_count", 0))
            if self.dedup is not None and meta.get("dedup") is not None:
                self.dedup.restore(meta["dedup"])
        except CheckpointIncompatible as e:
            # prechecked BEFORE any mutation: the arenas are still
            # fresh, so continuing as a cold start is safe (the
            # operator changed sketch parameters across the restart)
            logger.warning("checkpoint incompatible with the current "
                           "configuration (%s); cold start", e)
            return
        except Exception:
            # restore failed MID-mutation: the arenas may hold a mix
            # of restored and fresh state — refusing to boot is safer
            # than emitting stale pre-crash data as if newly ingested
            logger.critical("checkpoint restore failed mid-rebuild; "
                            "refusing to run half-restored (delete %s "
                            "to cold-start)",
                            self.config.checkpoint_dir)
            raise
        age_ms = max(0.0, (time.time()
                           - float(meta.get("written_unix", 0.0))) * 1e3)
        self.checkpoint_stats["restores"] += 1
        self.checkpoint_stats["age_ms"] = round(age_ms, 1)
        logger.info(
            "restored checkpoint: interval %d, %d processed / %d "
            "imported, %.0f ms old", self.flush_count,
            self.aggregator.processed, self.aggregator.imported, age_ms)
        # restore is an operational event on the flush timeline, so the
        # crash window is visible next to the flush records it gapped
        self.flush_timeline.record(
            interval=self.flush_count, unix_ts=time.time(),
            total_s=0.0, event="restore", checkpoint_age_ms=age_ms)

    def checkpoint_now(self) -> bool:
        """Write one checkpoint: a coherent (arenas, interval, dedup
        ledger) cut — the ledger's pause gate drains in-flight imports
        and blocks new ones across both snapshots, so a chunk's data
        and its identity can never split across the cut — then the
        atomic tempfile->rename write OUTSIDE every lock.  Returns
        False (with accounting) on disk failure; the previous
        checkpoint stays live either way."""
        from veneur_tpu.core import checkpoint as ckpt_mod
        import contextlib
        t0 = time.perf_counter()
        # fold the C++ engine's staged batches into the arenas first —
        # mid-interval ingest parked in the data plane must be part of
        # the cut, or a crash right after the checkpoint loses it
        self._drain_native()
        with self._checkpoint_write_lock:
            # drain + block imports for the cut: a chunk's data and
            # its ledger identity must land on the same side
            gate = (self.dedup.paused() if self.dedup is not None
                    else contextlib.nullcontext())
            with gate:
                # vnlint: disable=blocking-propagation (the snapshot's
                #   flagged chain is host COO consolidation inside
                #   checkpoint_state; _checkpoint_write_lock only
                #   serializes checkpoint writers — nothing on the
                #   ingest or flush path ever takes it)
                agg_meta, arrays = self.aggregator.checkpoint_state()
                meta = {
                    "aggregator": agg_meta,
                    "flush_count": self.flush_count,
                    "hostname": self.config.hostname,
                    "dedup": (self.dedup.snapshot()
                              if self.dedup is not None else None),
                }
            try:
                nbytes = ckpt_mod.write_checkpoint(
                    os.path.expanduser(self.config.checkpoint_dir),
                    meta, arrays)
            except Exception as e:
                self.checkpoint_stats["errors"] += 1
                logger.error("checkpoint write failed (previous "
                             "checkpoint stays live): %s", e)
                return False
        dur = time.perf_counter() - t0
        self.checkpoint_stats["writes"] += 1
        self.checkpoint_stats["last_bytes"] = nbytes
        self.checkpoint_stats["last_unix"] = time.time()
        self.flush_timeline.record(
            interval=self.flush_count, unix_ts=time.time(),
            total_s=dur, event="checkpoint", checkpoint_bytes=nbytes)
        return True

    def _checkpoint_loop(self) -> None:
        iv = self.config.checkpoint_interval
        while not self._shutdown.wait(iv):
            try:
                self.checkpoint_now()
            except Exception:
                logger.exception("periodic checkpoint failed")

    def crash(self) -> None:
        """Simulated kill -9 for the crash chaos arms: tear down
        listeners and threads WITHOUT the graceful exits — no final
        flush, no shutdown checkpoint, no spool drain.  Everything
        in memory is dropped; whatever already reached the spool/
        checkpoint directories is what the revived instance gets."""
        self._crashed = True
        self.shutdown()

    # -- flush (flusher.go:26-122) ----------------------------------------

    def flush(self, tick: Optional[float] = None) -> None:
        """One flush interval, traced as a span through the server's own
        pipeline (flusher.go:26-122: Flush is itself a span, and the flush
        path reports the standard self-metrics).  Serialized: callers
        beyond the ticker (tests, /debug/profile, flush_on_shutdown) race
        the non-atomic per-interval counters otherwise.  `tick` is the
        scheduled wall-clock time this flush serves (serve() passes it;
        a flush nobody scheduled has none): the timeline row then says
        how late the flush started (`tick_late_ms`) and, once the metric
        lane is done, when the sink had the batch (`tick_to_sink_ms`)."""
        with self._flush_serial:
            # vnlint: disable=blocking-propagation (_flush_serial
            #   exists to hold the entire flush — device waits
            #   included; ingest threads never contend on it)
            self._flush_locked(tick)
            if self.config.checkpoint_dir:
                # stamp the completed flush: a checkpoint OLDER than
                # this marker must not restore its arenas (the data
                # was delivered; re-forwarding it post-crash would
                # double-count — see checkpoint.write_flush_marker)
                from veneur_tpu.core import checkpoint as ckpt_mod
                try:
                    ckpt_mod.write_flush_marker(
                        os.path.expanduser(self.config.checkpoint_dir),
                        self.flush_count)
                except OSError as e:
                    logger.warning("flush marker write failed: %s", e)

    # bound on the flush root span's imported_traces tag (the tag is
    # operator-facing JSON, not a database; the assembler only needs
    # the ids of the intervals this flush settled)
    IMPORTED_TRACES_TAG_MAX = 64

    def _record_import_span(self, ctxs, n_metrics: int, start_ns: int,
                            transport: str,
                            stream_tags: Optional[dict] = None) -> None:
        """gRPC import trace hook (sources/proxy.py): continue each
        inbound RPC's propagated trace context with one child span
        covering the import, and remember the trace ids so the next
        flush's root span can tag the intervals it settles.  A V2
        stream's span also says how many messages it carried and in
        how many chunks they were imported (`stream_tags`)."""
        from veneur_tpu.trace import recorder as trace_rec
        tags = {"metrics": str(n_metrics), "transport": transport,
                "host": self.config.hostname, **(stream_tags or {})}
        # a batch import (this handler thread's, just made) says where
        # its time went: outside the aggregator lock, waiting for it,
        # holding it
        timing = self.aggregator.take_import_timing()
        if timing is not None and transport == "v1":
            for name, ns in zip(("scan_ms", "lock_wait_ms", "held_ms"),
                                timing):
                tags[name] = f"{ns / 1e6:.3f}"
        for tid, sid in ctxs:
            span = trace_rec.continue_span(
                "global.import", tid, sid, client=self.trace_client,
                tags=dict(tags), start_ns=start_ns)
            span.finish()
        if ctxs:
            with self._imported_traces_lock:
                if len(self._imported_traces) < 4096:
                    self._imported_traces.update(t for t, _ in ctxs)

    # canonical order for the synthesized segment child spans.  The
    # aggregator measures segment DURATIONS, not timestamps (device_s is
    # the residual wait after the overlapped host accounting), so the
    # children are laid end to end from the flush start: their summed
    # extent vs the root's wall is exactly the overlap signal the
    # critical-path table reports.
    _SEGMENT_ORDER = ("snapshot", "build", "layout", "dispatch",
                      "device", "emit")

    def _emit_segment_spans(self, span, flush_start: float) -> None:
        """One child span per measured flush segment (the staging/
        upload/kernel/readback decomposition from last_flush_segments).
        Synthesized children go straight into the flight-recorder ring
        (record_span's proto-free fast path): they exist for trace
        assembly, and the full SSF submission pipeline — built for
        externally-sourced spans — would cost more per flush than the
        segments it annotates."""
        elapsed_ns = int((time.perf_counter() - flush_start) * 1e9)
        t0 = time.time_ns() - elapsed_ns   # wall-clock of flush start
        off = 0
        segs = self.aggregator.last_flush_segments
        for seg_name in self._SEGMENT_ORDER:
            v = segs.get(f"{seg_name}_s")
            if v is None:
                continue
            dur_ns = int(float(v) * 1e9)
            if seg_name == "device":
                win = segs.get("device_window_s")
                if win is not None:
                    # chunked pipeline: the device span's extent is the
                    # device-BUSY window since the first chunk's
                    # dispatch — it reaches BACK over the later chunks'
                    # layout/dispatch children, so sum(flush.seg.*)
                    # exceeding the root wall IS the overlap, visible in
                    # the trace without any derived metric
                    win_ns = int(float(win) * 1e9)
                    child = span.child("flush.seg.device")
                    child.end_ns = t0 + off + dur_ns
                    child.start_ns = child.end_ns - win_ns
                    child.client = None
                    child.finish()
                    self.flight_recorder.record_span(child)
                    self._emit_device_part_spans(child, segs)
                    off += dur_ns
                    continue
            child = span.child(f"flush.seg.{seg_name}")
            child.start_ns = t0 + off
            child.end_ns = child.start_ns + dur_ns
            child.client = None          # ring fast path below
            child.finish()
            self.flight_recorder.record_span(child)
            if seg_name == "snapshot":
                self._emit_snapshot_part_spans(child, segs)
            elif seg_name == "device":
                self._emit_device_part_spans(child, segs)
            off += dur_ns

    # the parts of snapshot_s the aggregator measures (flush_dispatch):
    # the wait for the aggregator lock, then under it the arenas' syncs,
    # the take_staged consolidations and the column copies + reset
    _SNAPSHOT_PARTS = ("lock_wait", "sync", "staged", "columns")
    # the parts of snapshot_columns_s (aggregator.COLUMNS_PART_KEYS)
    _COLUMNS_PARTS = ("cache", "cut", "reset", "end", "rest")
    # a family's share of a columns part is a tag from here up (ms)
    _FAMILY_TAG_MIN_MS = 0.05

    def _lay_spans(self, span, parts) -> dict:
        """Children of `span` laid end to end from its start out of
        measured durations — `parts` is (name, seconds, tags or None)
        each — straight into the flight-recorder ring.  Returns them by
        name."""
        off = span.start_ns
        laid = {}
        for name, seconds, tags in parts:
            child = span.child(name)
            try:
                child.start_ns = off
                child.end_ns = off = off + int(float(seconds) * 1e9)
                if tags:
                    child.tags = tags
                child.client = None
            finally:
                child.finish()
            self.flight_recorder.record_span(child)
            laid[name] = child
        return laid

    def _emit_snapshot_part_spans(self, span, segs: dict) -> None:
        """Grandchildren under flush.seg.snapshot, laid end to end from
        its start (durations are measured, positions are not: take_staged
        runs between column copies), and under flush.seg.snapshot.columns
        its own parts the same way; cut, reset and end carry the
        milliseconds per family as tags.  trace/assembly sums only the
        root's direct children, so these leave the critical-path table
        alone."""
        laid = self._lay_spans(span, [
            (f"flush.seg.snapshot.{part}", segs[f"snapshot_{part}_s"], None)
            for part in self._SNAPSHOT_PARTS
            if f"snapshot_{part}_s" in segs])
        columns = laid.get("flush.seg.snapshot.columns")
        if columns is None:
            return
        by_family = segs.get("columns_by_family") or {}

        def tags(part: str) -> dict:
            out = {fam: f"{s * 1e3:.3f}"
                   for fam, s in by_family.get(part, {}).items()
                   if s * 1e3 >= self._FAMILY_TAG_MIN_MS}
            if part == "end":
                # the rows the idle GC freed, beside its milliseconds
                out.update((f"deaths.{fam}", str(n))
                           for fam, n in by_family.get("deaths",
                                                       {}).items())
            return out

        self._lay_spans(columns, [
            (f"flush.seg.snapshot.columns.{part}",
             segs[f"snapshot_{part}_s"], tags(part))
            for part in self._COLUMNS_PARTS
            if f"snapshot_{part}_s" in segs])

    def _emit_device_part_spans(self, span, segs: dict) -> None:
        """Grandchildren under flush.seg.device, laid end to end from
        its start out of their measured upload/dispatch/drain/wait
        durations: `sets` (the set estimate's register upload, launch
        and wait, aggregator._dispatch_sets), then one span per
        pipelined upload chunk, so a traced interval shows chunk i+1's
        upload riding on top of chunk i's device window."""
        parts = []
        if segs.get("device_sets"):
            parts.append(("sets", segs["device_sets"]))
        parts += [(f"chunk{i}", c)
                  for i, c in enumerate(segs.get("device_chunks") or ())]
        self._lay_spans(span, [
            (f"flush.seg.device.{name}",
             c.get("upload_s", 0.0) + c.get("dispatch_s", 0.0)
             + c.get("drain_s", 0.0) + c.get("wait_s", 0.0),
             {"rows": str(c.get("rows", 0))})
            for name, c in parts])

    def _prewarm(self) -> None:
        """Boot-time compile of a meshed global's flush programs
        (compile-churn hardening; persists via the compilation cache,
        so later boots replay from disk), before start() opens a
        listener: the pre-size's own bucket at `prewarm_depths` first,
        then the set-lane kernels and the bucket of the server's own
        telemetry (MetricAggregator.prewarm).  An unmeshed node's boot
        is _prewarm_launch.  What either did is a `server.prewarm` span
        in the flight recorder and `prewarm_programs` /
        `prewarm_seconds` in /debug/vars."""
        cap = self.config.arena_initial_capacity or 8192
        # prewarm rounds up to the arena's pow2 capacity internally,
        # so the top bucket a ramp can reach is always covered
        self._account_prewarm(
            lambda: self.aggregator.prewarm(
                list(self.config.prewarm_depths), cap,
                stop=self._shutdown),
            "flush prewarm failed; first flushes of each shape will "
            "compile in place")

    def _prewarm_launch(self) -> None:
        """Boot-time launch of an unmeshed node's closed program list
        (MetricAggregator.prewarm_launch), accounted like _prewarm."""
        self._account_prewarm(
            lambda: self.aggregator.prewarm_launch(
                list(self.config.prewarm_depths),
                self.config.arena_initial_capacity or 8192),
            "flush prewarm launch failed; first launches of each "
            "program will compile in place")

    def _prewarm_set_lanes(self) -> None:
        """Boot-time launch of a resident set arena's lane programs,
        accounted like _prewarm."""
        self._account_prewarm(
            self.aggregator.sets.prewarm_lanes,
            "set lane prewarm failed; first launches of each lane "
            "program will compile in place")

    def _account_prewarm(self, compile_programs, on_failure: str) -> None:
        """Run one boot-time compile pass (returns programs compiled)
        under a `server.prewarm` span, into prewarm_stats."""
        from veneur_tpu import trace as trace_mod
        span = trace_mod.Span("server.prewarm",
                              service=self.config.hostname)
        t0 = time.perf_counter()
        try:
            self.prewarm_stats["programs"] += compile_programs()
        except Exception:
            span.error = True
            logger.exception(on_failure)
        finally:
            self.prewarm_stats["seconds"] += time.perf_counter() - t0
            span.tags = {"programs": str(self.prewarm_stats["programs"])}
            span.finish()
            self.flight_recorder.record_span(span)

    def _flush_locked(self, tick: Optional[float] = None) -> None:
        from veneur_tpu import failpoints
        from veneur_tpu import scopedstatsd

        # vnlint: disable=blocking-propagation (deliberate failpoint
        #   edge: the chaos delay arm exists to stall the flush path
        #   itself; disarmed cost is one module-global bool read)
        failpoints.inject("server.flush")
        self.last_flush_unix = time.time()
        statsd = scopedstatsd.ensure(self.statsd)
        interval = self.flush_count + 1
        traced = (self.trace_sampler is not None
                  and self.trace_sampler.sample(interval))
        flush_start = time.perf_counter()
        # the interval's ROOT span: every flush is a distributed trace
        # over the pipeline's own span plane (context propagates through
        # forward metadata -> proxy -> global import).  The with-exit
        # finishes it — error-flagged on an exception — and submission
        # lands it in the flight-recorder ring (/debug/trace).
        with self.trace_client.span(
                "flush", service="veneur_tpu",
                tags={"veneurglobalonly": str(not self.is_local).lower(),
                      "tier": "local" if self.is_local else "global",
                      "interval": str(interval),
                      "host": self.config.hostname,
                      "forward_metrics": "0",
                      "sampled": str(traced).lower()}) as span:
            # vnlint: disable=blocking-propagation (the body IS the
            #   flush — _flush_serial deliberately covers its one
            #   device wait, pending.emit; ingest threads never
            #   contend on _flush_serial, and sink fan-out is a
            #   non-blocking egress-queue handoff.  Same rationale as
            #   the suppression at the wait itself)
            self._flush_body_locked(span, statsd, flush_start, traced,
                                    tick)

    def _flush_body_locked(self, span, statsd, flush_start: float,
                           traced: bool,
                           tick: Optional[float] = None) -> None:
        from veneur_tpu import ssf as ssf_mod

        self._drain_native()
        # swap the imported-trace set out NOW, just before the snapshot:
        # a trace id belongs on THIS flush's imported_traces tag only if
        # its metrics were imported before the snapshot this flush
        # evaluates — imports landing mid-flush are the NEXT flush's to
        # settle (the tag drives the assembler's global-flush join)
        if not self.is_local:
            with self._imported_traces_lock:
                settled_tids, self._imported_traces = (
                    self._imported_traces, set())
        else:
            settled_tids = ()
        # overlapped launch: snapshot + stage + dispatch the device
        # program, then run this interval's host-side self-metric
        # accounting WHILE the kernel executes; pending.emit() — the
        # only device wait — happens once the host work is done.  The
        # try/finally guarantees exactly one emit even if an accounting
        # statsd call raises.
        # vnlint: disable=blocking-propagation (the dispatch's host
        #   staging build + unique-ts estimate run under _flush_serial
        #   by definition — the flush serialization lock covers the
        #   whole flush and is never taken on the ingest path)
        pending = self.aggregator.flush_dispatch(is_local=self.is_local)
        self.flush_count += 1

        try:
            self._flush_interval_accounting(statsd)
        finally:
            # vnlint: disable=sync-under-lock,blocking-propagation (the
            #   emit IS the flush's one deliberate device wait, already
            #   overlapped behind the host-side accounting above;
            #   _flush_serial only serializes flush callers — ticker,
            #   tests, /debug — and is never taken on the ingest path)
            res = pending.emit()

        # worker.metrics_processed_total (worker.go:477)
        statsd.count("worker.metrics_processed_total",
                     res.processed + res.imported)
        # flush.unique_timeseries_total (flusher.go:42-44)
        if res.unique_ts is not None:
            statsd.count("flush.unique_timeseries_total", res.unique_ts,
                         tags=["global_veneur:"
                               + str(not self.is_local).lower()])
        # measured decomposition of the flush that just ran (snapshot/
        # build/layout/dispatch/device/emit + bytes moved) — read after
        # emit so device_s reflects THIS flush, not the last one
        for seg_name, v in list(
                self.aggregator.last_flush_segments.items()):
            if not isinstance(v, (int, float)):
                continue   # structured values (per-chunk stats list)
            if seg_name in ROW_ONLY_SEGMENT_KEYS:
                # the interval ledger's outlet is the timeline row (and
                # flush.seg.snapshot.* spans), not a dozen new series
                continue
            if seg_name.endswith("_s"):
                statsd.timing(f"flush.segment.{seg_name[:-2]}_ms",
                              v * 1e3)
            else:
                statsd.gauge(f"flush.{seg_name}", float(v))
        # sketch-family observability: per-family key counts of the
        # flush that just ran, and the moments solver's worst moment
        # residual (a converged maxent solve sits at ~1e-4; a blowup
        # here is the canary for degenerate moment inputs)
        segs = self.aggregator.last_flush_segments
        statsd.gauge("sketch.keys", float(segs.get("keys_digest", 0)),
                     tags=["family:tdigest"])
        statsd.gauge("sketch.keys", float(segs.get("keys_moments", 0)),
                     tags=["family:moments"])
        if segs.get("keys_moments"):
            statsd.gauge("sketch.moments.solver_resid",
                         float(self.aggregator.last_moments_resid))

        with self._events_lock:
            events, self._events = self._events, []

        # sink routing (flusher.go:97-113)
        if self.config.enable_metric_sink_routing:
            res.metrics.apply_routing(self.config.metric_sink_routing,
                                      matcher_mod.match)

        if self.forwarder is not None and self.is_local and res.forward:
            if self._forward_slots.acquire(blocking=False):
                try:
                    self._flush_pool.submit(
                        self._forward_safely, res.forward, span,
                        traced, self.flush_count)
                    # the assembler requires a complete 3-tier trace
                    # only for intervals whose forward was SUBMITTED
                    # (slot-exhausted drops are accounted, not traced)
                    span.tags["forward_metrics"] = str(len(res.forward))
                except RuntimeError:  # pool shut down mid-flush
                    # the batch never forwards: account it exactly like
                    # the slots-exhausted drop below, not silently
                    self.forward_dropped += len(res.forward)
                    statsd.count("forward.error_total",
                                 len(res.forward),
                                 tags=["cause:pool_shutdown"])
                    self._forward_slots.release()
            else:
                # all forward slots stalled: drop this interval's batch
                # rather than queue unboundedly
                self.forward_dropped += len(res.forward)
                statsd.count("forward.error_total", len(res.forward),
                             tags=["cause:slots_exhausted"])
                logger.warning("%d forwards in flight; dropped %d "
                               "forward metrics",
                               self.FORWARD_MAX_IN_FLIGHT, len(res.forward))
        # sink fan-out: hand the rendered interval to the egress data
        # plane and return — filtering, serialization, HTTP, bounded
        # retries, breaker trips and spool spill all run on per-sink
        # lanes off this lock.  A slow or blackholed backend costs its
        # own lane, never the flush p99 (ROADMAP #8).
        fanout_start_ns = time.time_ns()
        self.egress.submit_interval(
            res.metrics, events, statsd, self.flush_count,
            trace_id=span.trace_id, parent_span_id=span.span_id,
            traced=traced,
            tick_ns=int(tick * 1e9) if tick is not None else 0)
        if traced:
            # segment children (staging/upload/kernel/readback) + the
            # egress handoff, as spans on the interval's own trace.
            # flush.seg.fanout now covers only the ENQUEUE — sink I/O
            # happens on the lanes, visible as flush.sink.<name> spans
            fanout_end_ns = time.time_ns()
            self._emit_segment_spans(span, flush_start)
            fanout = span.child("flush.seg.fanout")
            fanout.start_ns = fanout_start_ns
            fanout.end_ns = fanout_end_ns
            fanout.client = None         # ring fast path, like segments
            fanout.finish()
            self.flight_recorder.record_span(fanout)
        if settled_tids:
            # tag the intervals this global flush settled (bounded), so
            # the assembler can join it onto each local trace
            sample = sorted(settled_tids)[:self.IMPORTED_TRACES_TAG_MAX]
            span.tags["imported_traces"] = ",".join(
                f"{t:x}" for t in sample)
        span.add(ssf_mod.timing(
            "flush.total_duration_ns",
            time.perf_counter() - flush_start))
        # one structured record per flush into the timeline ring: the
        # measured segment decomposition (snapshot/build/layout/dispatch/
        # device/emit + bytes + per-family key counts), the interval id,
        # what the interval carried, and the trace/span ids that make
        # timeline rows cross-link into /debug/trace
        from veneur_tpu.parallel import serving as serving_mod
        self.flush_timeline.record(
            interval=self.flush_count,
            unix_ts=self.last_flush_unix,
            total_s=time.perf_counter() - flush_start,
            segments=self.aggregator.last_flush_segments,
            devices=serving_mod.mesh_device_count(self.mesh),
            # "2x2" = shard x replica; left out mesh-less
            mesh_shape=(None if self.mesh is None else "x".join(
                str(n) for n in self.mesh.devices.shape)),
            processed=res.processed, imported=res.imported,
            metrics_emitted=len(res.metrics),
            forward_metrics=len(res.forward),
            # how late the ticker's flush started; left out of a flush
            # nobody scheduled
            tick_late_ms=(None if tick is None else round(
                (self.last_flush_unix - tick) * 1e3, 3)),
            trace_id=f"{span.trace_id:x}",
            span_id=f"{span.span_id:x}",
            **self._ingest_overflow())

    # getsockopt(SOL_SOCKET, SO_MEMINFO) -> 9 u32; the last is the
    # socket's drop count (sk_drops: the `drops` column of /proc/net/udp)
    _SO_MEMINFO = 55

    def _ingest_overflow(self) -> dict:
        """Which queue overflowed, once per flush, as timeline-row fields
        (and /debug/vars -> ingest_overflow): datagrams the kernel dropped
        at the UDP sockets' receive buffers since the last flush, publishes
        of the native readers that found their ring full (no line is lost
        there: the batch stays with the reader), and the rings' peak
        occupancy as a share of their capacity.  Empty without the native
        UDP plane."""
        if self.native is None:
            return {}
        ring = self.native.ring_stats()
        if ring is None:
            return {}   # engine torn down under us
        full, peak, slots = ring
        drops = 0
        for sock in self._listeners:
            if (sock.type != socket.SOCK_DGRAM
                    or sock.family == socket.AF_UNIX):
                continue
            try:
                drops += struct.unpack("9I", sock.getsockopt(
                    socket.SOL_SOCKET, self._SO_MEMINFO, 36))[8]
            except (OSError, struct.error):
                pass    # closed mid-shutdown, or a kernel without it
        drops0, full0 = self._overflow_totals
        self._overflow_totals = (drops, full)
        out = {"udp_rcvbuf_drops": max(0, drops - drops0),
               "ring_full_stalls": max(0, full - full0),
               "ring_peak_share": round(peak / slots, 4) if slots else 0.0}
        self.ingest_overflow = dict(
            out, udp_rcvbuf_drops_total=drops, ring_full_stalls_total=full)
        return out

    def _flush_interval_accounting(self, statsd) -> None:
        """Host-side per-interval self-metric accounting that does not
        depend on the flush result — run between flush_dispatch() and
        emit() so it overlaps the device kernel."""
        # listen.received_per_protocol_total (flusher.go:280,455-475)
        with self._proto_lock:
            drained, self.proto_received = (self.proto_received,
                                            collections.Counter())
        for proto, n in drained.items():
            statsd.count("listen.received_per_protocol_total", n,
                         tags=[f"protocol:{proto}"])
        if self.native is not None:
            # parse-error/too-long accounting from the native data plane
            mal, tl = self.native.malformed, self.native.too_long
            pm, pt = self._native_err_reported
            if mal > pm:
                statsd.count("listen.parse_errors_total", mal - pm,
                             tags=["protocol:udp"])
            if tl > pt:
                statsd.count("listen.packets_too_long_total", tl - pt,
                             tags=["protocol:udp"])
            self._native_err_reported = (mal, tl)
        # host-path loss deltas (the silent-loss lint's ledger): python
        # parse errors, SSF parse errors, direct span-sink ingest raises
        pe, se, si = (self.parse_errors, self.ssf_parse_errors,
                      self.span_ingest_errors)
        ppe, pse, psi = self._host_err_reported
        if pe > ppe:
            statsd.count("listen.parse_errors_total", pe - ppe,
                         tags=["protocol:python"])
        if se > pse:
            statsd.count("listen.parse_errors_total", se - pse,
                         tags=["protocol:ssf"])
        if si > psi:
            statsd.count("worker.span.ingest_errors_total", si - psi,
                         tags=["sink:direct"])
        self._host_err_reported = (pe, se, si)
        # import-edge failures (sources/proxy.py GrpcImportServer):
        # metrics that arrived at this global but failed to import
        gi = getattr(self, "grpc_import", None)
        if gi is not None:
            ie = getattr(gi, "import_errors", 0)
            if ie > self._import_err_reported:
                statsd.count("import.errors_total",
                             ie - self._import_err_reported)
                self._import_err_reported = ie
        # legacy VH HLL payload accounting (mixed-hash inflation warning
        # lives in sketches/hll.py; the metric makes it monitorable)
        vh_total = hll_mod.legacy_vh_total
        if vh_total > self._legacy_hll_reported:
            statsd.count("listen.legacy_hll_total",
                         vh_total - self._legacy_hll_reported)
            self._legacy_hll_reported = vh_total
        # compile-churn observability: first-bucket XLA compiles this
        # interval (flush-path or prewarm) and their wall seconds
        ce, cs = (self.aggregator.compile_events,
                  self.aggregator.compile_seconds_total)
        if ce > self._compiles_reported[0]:
            statsd.count("flush.compile_events_total",
                         ce - self._compiles_reported[0])
            statsd.timing("flush.compile_duration_ms",
                          (cs - self._compiles_reported[1]) * 1e3)
            self._compiles_reported = (ce, cs)
        # forward retry/drop accounting from the client's bounded retry
        # policy (forward/client.py): interval deltas, so dashboards see
        # retry storms and exhausted-retry drops as they happen
        fw = self.forwarder
        if fw is not None and hasattr(fw, "stats"):
            st = fw.stats()
            pr, pd = self._forward_client_reported
            if st["retries"] > pr:
                statsd.count("forward.retries_total", st["retries"] - pr)
            if st["dropped"] > pd:
                statsd.count("forward.dropped_total", st["dropped"] - pd)
            self._forward_client_reported = (st["retries"], st["dropped"])
        # durable-spool ledger deltas (forward/spool.py): spilled /
        # replayed / expired metric points per interval — expiry is the
        # spool's visibly-accounted loss channel, so it must reach
        # dashboards, not just /debug/vars
        sp = fw.spool_stats() if (fw is not None and
                                  hasattr(fw, "spool_stats")) else None
        if sp is not None:
            prev = self._spool_reported
            for key in ("spilled_points", "replayed_points",
                        "expired_points", "dropped_points"):
                delta = sp[key] - prev.get(key, 0)
                if delta > 0:
                    statsd.count(
                        f"forward.spool.{key.split('_')[0]}_total",
                        delta)
            pending = sp["pending_records"]
            statsd.gauge("forward.spool.pending_records",
                         float(pending))
            self._spool_reported = sp
        # egress data-plane ledger deltas (veneur_tpu/egress/) for the
        # series with no event-site emission: delivered points and the
        # spool's replay/expiry/terminal-drop outcomes.  The failure-
        # side series (egress.retries/spilled/dropped/queue_full) are
        # emitted sink- and cause-tagged at their event sites in the
        # lanes — exactly once per event, never re-summed here.
        eg = self.egress.stats()
        prev_eg = self._egress_reported
        for key in ("flushed", "replayed", "expired", "spool_dropped"):
            delta = eg[key] - prev_eg.get(key, 0)
            if delta > 0:
                statsd.count(f"egress.{key}_total", delta)
        eg_pending = eg["pending"]
        statsd.gauge("egress.pending_records", float(eg_pending))
        self._egress_reported = {
            k: eg[k] for k in ("flushed", "replayed", "expired",
                               "spool_dropped")}
        # straggler classification (flusher.go:553-566 heritage, same
        # per-interval semantics as the old in-lock fan-out deadline):
        # a sink whose CURRENT delivery has been running longer than
        # one interval counts once per interval — which also catches a
        # sink.flush that never returns at all
        for label, lane_st in eg["per_sink"].items():
            if lane_st["busy_for_s"] > self.config.interval:
                statsd.count("flush.stragglers_total", 1,
                             tags=[f"flush:{label}"])
                logger.warning(
                    "flush straggler: sink %s delivery running %.1fs "
                    "(> %.1fs interval)", label,
                    lane_st["busy_for_s"], self.config.interval)
        statsd.count("spans.received_total", self.ssf_received)
        self.ssf_received = 0
        # per-span-sink ingest accounting (worker.go:603-678)
        for w in self.span_workers:
            ingested, dropped, errors, dur_ns = w.interval_stats()
            stags = [f"sink:{w.sink.name()}"]
            statsd.count("worker.span.ingested_total", ingested, tags=stags)
            statsd.count(sink_mod.SPANS_DROPPED_TOTAL, dropped, tags=stags)
            if errors:
                statsd.count("worker.span.ingest_errors_total", errors,
                             tags=stags)
            statsd.timing(sink_mod.SPAN_INGEST_DURATION, dur_ns, tags=stags)

    def _excluded_tags_for(self, sink_name: str):
        """tags_exclude keys applying to this sink (global ∪ sink-scoped);
        None when no rules are configured (fast path)."""
        per_sink = self._tags_exclude_by_sink.get(sink_name)
        if per_sink is None:
            return self._tags_exclude_global or None
        return self._tags_exclude_global | per_sink

    def _forward_safely(self, forward: list[sm.ForwardMetric],
                        parent=None, traced: bool = False,
                        epoch: Optional[int] = None) -> None:
        """Forward with sub-timings on a child span
        (flusher.go:516-576: export/grpc parts + error cause).  When the
        interval is `traced`, the forward client gets the child span as
        trace parent: each attempt becomes its own span and the attempt
        context rides the RPC metadata to the proxy.  `epoch` (the
        flush interval, checkpoint-stable across restarts) becomes the
        interval half of every chunk's exactly-once identity."""
        from veneur_tpu import scopedstatsd
        from veneur_tpu import ssf as ssf_mod
        statsd = scopedstatsd.ensure(self.statsd)
        grpc_start = time.perf_counter()
        fspan = (parent.child("flush.forward") if parent is not None
                 else self.trace_client.span("flush.forward"))
        try:
            fspan.add(
                ssf_mod.gauge("forward.metrics_total",
                              float(len(forward))),
                ssf_mod.count("forward.post_metrics_total",
                              float(len(forward))))
            kwargs = {}
            if epoch is not None and getattr(self.forwarder,
                                             "accepts_epoch", False):
                kwargs["epoch"] = epoch
            if traced and getattr(self.forwarder, "accepts_trace",
                                  False):
                self.forwarder(forward, trace_parent=fspan, **kwargs)
            else:
                self.forwarder(forward, **kwargs)
            fspan.add(ssf_mod.count("forward.error_total", 0))
        except TimeoutError:
            fspan.add(ssf_mod.count("forward.error_total", 1,
                                    tags={"cause": "deadline_exceeded"}))
            statsd.count("forward.error_total", 1,
                         tags=["cause:deadline_exceeded"])
            logger.error("forward deadline exceeded")
        except Exception as e:
            cause = "send"
            msg = str(e)
            # transient connection rebalancing isn't an error worth paging
            # on (flusher.go:556-563)
            if "UNAVAILABLE" in msg or "transport is closing" in msg:
                cause = "transient_unavailable"
            else:
                logger.error("forward failed: %s", e)
            fspan.add(ssf_mod.count("forward.error_total", 1,
                                    tags={"cause": cause}))
            statsd.count("forward.error_total", 1, tags=[f"cause:{cause}"])
        finally:
            wall = time.perf_counter() - grpc_start
            if wall > self.config.interval:
                # the old in-lock fan-out wait classified a forward
                # running past one interval as a straggler; keep the
                # signal, now stamped at completion like the sink lanes
                statsd.count("flush.stragglers_total", 1,
                             tags=["flush:forward"])
                logger.warning("forward straggler: ran %.1fs "
                               "(> %.1fs interval)", wall,
                               self.config.interval)
            fspan.add(ssf_mod.timing(
                "forward.duration_ns", wall, tags={"part": "grpc"}))
            fspan.finish()
            self._forward_slots.release()

    # per-sink delivery (filtering, flushed_metrics accounting, bounded
    # retries, HTTP phase self-metrics) lives on the egress lanes now:
    # veneur_tpu/egress/plane.py SinkLane._deliver_metric /
    # _deliver_span_flush

    # -- lifecycle ---------------------------------------------------------

    def serve(self) -> None:
        """Blocking ticker loop (server.go:830-867)."""
        interval = self.config.interval
        if self.config.synchronize_with_interval:
            now = time.time()
            time.sleep(interval - (now % interval))
        next_tick = time.time() + interval
        while not self._shutdown.is_set():
            timeout = max(0.0, next_tick - time.time())
            if self._shutdown.wait(timeout):
                break
            tick = next_tick
            next_tick += interval
            late = time.time() - next_tick
            if late > 0:
                # the last flush outlasted its interval (a cold XLA
                # compile takes several).  The reference's ticker holds
                # ONE tick for a slow receiver and drops the rest
                # (time.Ticker): this flush is that one, and the next
                # comes on the grid — not every missed tick replayed
                # back to back, a burst of empty flushes off the grid
                next_tick += interval * -(-late // interval)
            try:
                self.flush(tick)
            except Exception as e:
                logger.exception("flush failed: %s", e)

    # longest the watchdog will attribute an overdue flush to an XLA
    # compile before terminating anyway (a guard that never exits is a
    # wedged runtime, which IS the hang class the watchdog exists for)
    COMPILE_GRACE_S = 900.0

    def _watchdog(self) -> None:
        """FlushWatchdog (server.go:877-912): die if flushes stop so a
        supervisor can restart us."""
        interval = self.config.interval
        missed = self.config.flush_watchdog_missed_flushes
        compile_hold_since = None
        while not self._shutdown.is_set():
            if self._shutdown.wait(interval / 2):
                return
            overdue = time.time() - self.last_flush_unix
            if overdue > missed * interval:
                if self.aggregator.compile_in_progress.is_set():
                    # a first-bucket XLA compile is progress, not a hang
                    # (VERDICT r3: a compile stall must not look like
                    # one) — but only for a bounded grace: a compile
                    # that never returns is a wedged device runtime
                    if compile_hold_since is None:
                        compile_hold_since = time.time()
                    held = time.time() - compile_hold_since
                    if held < self.COMPILE_GRACE_S:
                        logger.warning(
                            "flush watchdog: flush %.1fs overdue but an "
                            "XLA compile is in progress (%.0fs); holding "
                            "fire", overdue, held)
                        continue
                    logger.critical(
                        "flush watchdog: compile in progress for %.0fs "
                        "(> %.0fs grace); treating as a hang", held,
                        self.COMPILE_GRACE_S)
                else:
                    compile_hold_since = None
                logger.critical(
                    "flush watchdog: no flush for %.1fs (> %d intervals); "
                    "terminating", overdue, missed)
                self.shutdown_hook()
                return
            else:
                compile_hold_since = None

    def shutdown(self) -> None:
        """server.go:1417-1435.  A crash() teardown skips the graceful
        exits (final flush, shutdown checkpoint, spool drain) — the
        revived instance recovers from disk instead."""
        if self.config.flush_on_shutdown and not self._crashed:
            try:
                self.flush()
            except Exception:
                logger.exception("final flush failed")
        if self.config.checkpoint_dir and not self._crashed:
            # SIGTERM/graceful-exit snapshot: the supervisor's restart
            # resumes from here (cli/veneur.py routes SIGTERM through
            # this path)
            try:
                self.checkpoint_now()
            except Exception:
                logger.exception("shutdown checkpoint failed")
        self._shutdown.set()
        self._readers_stop.set()
        for source in self.sources:
            try:
                source.stop()
            except Exception:
                logger.exception("source stop failed")
        # drain the egress lanes BEFORE the statsd client closes (the
        # final interval's per-sink accounting still needs it) and
        # before sinks close further down.  A crash skips the drain:
        # queued jobs die with the process and the per-sink spools keep
        # their on-disk records for the revived instance's replayers.
        try:
            self.egress.close(drain=not self._crashed,
                              timeout_s=max(2.0, self.config.interval))
        except Exception:
            logger.exception("egress close failed")
        if not self._crashed:
            # flush() no longer waits on its forward future (the old
            # in-lock fan-out wait covered it): give the final
            # interval's in-flight forwards a bounded window to land
            # before the channel is torn down below
            deadline = time.time() + max(2.0, self.config.interval)
            while (time.time() < deadline
                   and self._forward_slots._value
                   < self.FORWARD_MAX_IN_FLIGHT):
                time.sleep(0.02)
        if self.diagnostics is not None:
            self.diagnostics.stop()
        if self.statsd is not None:
            self.statsd.close()
        try:
            self.trace_client.close()
        except Exception:
            pass
        if self.native is not None:
            # join the C++ reader threads BEFORE closing their fds — a
            # recycled fd number must never be readable by a stale reader
            try:
                self.native.stop()
                self.native.close()
            except Exception:
                logger.exception("native ingest shutdown failed")
        for sock in self._listeners:
            try:
                sock.close()
            except OSError:
                pass
        for lock_path, lock_f in self._socket_locks:
            try:
                lock_f.close()
                os.unlink(lock_path)
            except OSError:
                pass
        self._socket_locks = []
        # unblock reader threads parked in recv on accepted streams
        with self._stream_conns_lock:
            conns = list(self._stream_conns)
            self._stream_conns.clear()
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass
        if self.grpc_import is not None:
            self.grpc_import.stop()
        for srv in self.grpc_ingest_listeners:
            try:
                srv.stop()
            except Exception:
                logger.exception("grpc ingest listener stop failed")
        if self.forwarder is not None and hasattr(self.forwarder, "close"):
            try:
                if getattr(self.forwarder, "spool", None) is not None:
                    self.forwarder.close(drain_spool=not self._crashed)
                else:
                    self.forwarder.close()
            except Exception:
                pass
        ret = getattr(self.aggregator, "retention", None)
        if ret is not None:
            # stop the compaction worker first — crash discards its
            # queue (those cuts were never checkpointed) so a dying
            # server can't keep spilling into a directory its revival
            # reopened.  Then graceful exit settles the active tier
            # segment to disk; a crash leaves it as-is — the revived
            # store re-indexes the durable segments (torn tail
            # truncated, CRC-failing records rejected) exactly like
            # the forward spool
            try:
                ret.close(drain=not self._crashed)
            except Exception:
                logger.exception("retention worker close failed")
            if ret.store is not None:
                try:
                    ret.store.close(drain=not self._crashed)
                except Exception:
                    logger.exception("retention store close failed")
        for _, sink in self.metric_sinks:
            if hasattr(sink, "close"):
                try:
                    sink.close()
                except Exception:
                    logger.exception("sink close failed")
        for sink in self.span_sinks:
            if hasattr(sink, "close"):
                try:
                    sink.close()
                except Exception:
                    logger.exception("span sink close failed")
        self._flush_pool.shutdown(wait=False)
