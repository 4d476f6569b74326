"""gRPC import source: the global tier's receive path.

Mirrors `sources/proxy/server.go`: a Forward service whose
`SendMetricsV2` recv-loop feeds each metric into the aggregation core
(`server.go:144-162` -> `ingest.IngestMetricProto` -> worker
`ImportMetric`), registered when `grpc_address` is configured
(`server.go:673-682`).  V2 is the only wire a reference sender speaks
(a Go local opens one client stream per flush and `Send`s one message
a metric, `flusher.go:578-591`; `veneur-proxy` keeps long-lived streams
to every global, `proxy/connect/connect.go:76-227`), and since PR 42 it
is on the columnar path: the handler takes each message as RAW bytes,
frames a stream's messages as `MetricList` wire bytes
(`frame_metric_list`: no parse, no copy of a field) and hands them to
the batch import (`aggregator.import_payload`) in chunks — one native
scan, one aggregator-lock hold and one staging call per chunk, not per
message.  What a stream is promised:

  (a) its response is sent only after every message received on it has
      been imported — an acked forward is in the interval's flush;
  (b) every message is merged exactly once, none dropped;
  (c) a message that fails (malformed bytes, a refused record) fails
      alone: counted in `import_errors`, its chunk and stream go on
      (an import that raises fails its chunk's messages, counted the
      same way, and neither the stream nor the sweeper of (d));
  (d) on a stream that stays open, the import of a received message
      BEGINS within `STREAM_MAX_WAIT_S` of its arrival (sooner when its
      chunk fills), so a proxy's long-lived stream does not carry an
      interval's tail past a cut;
  (e) a message the scan cannot take (a moments / compactor marker, a
      set at another precision, a key the row cache does not know) is
      parsed alone by its byte range, as `import_payload` does for V1.

The service runs on a `grpc.aio` server whose event loop has a thread
of its own: python-grpc's thread-per-RPC server hands every message of
a stream from its one completion-queue thread to the RPC's handler
thread and back (two interpreter-lock hand-offs a message; a trivial
handler takes a fifth of the messages a second the event loop does —
PERF.md section 6, PR 42), and a long-lived stream no longer pins a
worker thread.  Only `SendMetricsV2` is a coroutine; the unary
handlers stay plain functions and run on the server's thread pool, as
do the chunks' imports, so the loop never waits for the aggregator
lock.  The server's HTTP/2 window is fixed (`SERVER_OPTIONS`): a stream
is paced by what the server has taken off it, and what the senders may
park in this process is `STREAM_WINDOW_BYTES` a stream, not an interval.

A record whose `type` disagrees with its value oneof is REJECTED on the
stream as on the V1 batch path (`MetricAggregator._ONEOF_LEGAL_TYPES`);
the per-metric path this handler used until PR 42 merged it by `type`.
A server given only an `import_metric` callable takes the same chunked
path: `_per_metric_payload` stands in for the batch import.

`SendMetrics` (V1) — which the reference leaves UNIMPLEMENTED
(`sources/proxy/server.go:138-142`) — is the fleet-internal batch RPC
this framework's own forwarders and proxies probe first: one
`MetricList` a call.  Both RPCs now reach the same staging.

Also exposes the gRPC ingest listeners for SSF spans and raw dogstatsd
packet bytes (`networking.go:326-391`).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import contextlib
import dataclasses
import logging
import threading
import time
from typing import Callable, Optional

import grpc
from google.protobuf import empty_pb2

from veneur_tpu.forward import convert
from veneur_tpu.protocol import (dogstatsd_grpc_pb2, forward_pb2, metric_pb2,
                                 ssf_grpc_pb2, ssf_pb2)

logger = logging.getLogger("veneur_tpu.sources.proxy")

# A V2 stream's messages are imported in chunks: when this many have
# been received, or this many bytes (a V1 chunk is BATCH_MAX = 2,000
# metrics; the scan's columns and the lock hold scale with the chunk)
STREAM_CHUNK_MSGS = 2048
STREAM_CHUNK_BYTES = 4 << 20
# guarantee (d): the import of a message received on a stream that
# stays open begins at most this long after its arrival.  The sweeper
# looks every STREAM_SWEEP_S and imports what has waited the rest.
STREAM_MAX_WAIT_S = 0.25
STREAM_SWEEP_S = 0.05
# What a sender may have in flight on one stream, ahead of what the
# server has taken off it: a fixed HTTP/2 window, python-grpc's
# bandwidth-delay probe off.  The probe sizes the window from what it
# measures, run by run; fixed, a stream is paced by the import, what a
# sender may park in this process is bounded a stream (a global of a
# thousand locals: 256 MB, not a thousand streams), and the receive is
# no slower (PERF.md section 6, PR 42: -2.5 % on the chip, a fifth of
# the loop's CPU in the sandbox).  A V1 payload of 3.5 MB takes 6 ms
# longer; one of 400 KB the same.
STREAM_WINDOW_BYTES = 256 << 10
SERVER_OPTIONS = (("grpc.http2.bdp_probe", 0),
                  ("grpc.http2.lookahead_bytes", STREAM_WINDOW_BYTES))


def frame_metric_list(messages) -> bytes:
    """The `MetricList` wire bytes of serialized `Metric` messages:
    per message the tag of `repeated Metric metrics = 1` (0x0A), the
    varint of its length and its own bytes — byte for byte what
    `MetricList(metrics=...).SerializeToString()` writes, with no
    message parsed."""
    parts = []
    for raw in messages:
        n = len(raw)
        if n < 0x80:
            parts.append(bytes((0x0A, n)))
        elif n < 0x4000:
            parts.append(bytes((0x0A, (n & 0x7F) | 0x80, n >> 7)))
        else:
            head = bytearray(b"\x0a")
            while n > 0x7F:
                head.append((n & 0x7F) | 0x80)
                n >>= 7
            head.append(n)
            parts.append(bytes(head))
        parts.append(raw)
    return b"".join(parts)


@dataclasses.dataclass(slots=True)
class StreamChunk:
    """What `import_payload` is told of a chunk that came off a V2
    stream: the messages the payload was framed from (a chunk the scan
    refuses is parsed message by message, so one bad message fails
    alone), the time from the first one's arrival to the chunk's cut
    (its handler's wall time in the request iterator) and the time
    framing them, and whether the chunk is its stream's first."""

    messages: list
    recv_ns: int
    frame_ns: int
    first: bool


def _per_metric_payload(import_metric: Callable[[object], None]):
    """An `import_payload` over a bare `import_metric` callable (tests,
    an embedder with no batch import): each message of the payload — a
    V1 `MetricList`, or a stream chunk's own messages — is parsed and
    merged alone, on the pool thread that runs the import."""
    def import_payload(payload, stream=None):
        if stream is not None:
            items, parse = stream.messages, metric_pb2.Metric.FromString
        else:
            items = forward_pb2.MetricList.FromString(payload).metrics
            parse = None
        ok = failed = 0
        for item in items:
            try:
                import_metric(convert.from_pb(parse(item) if parse else item))
                ok += 1
            except Exception as e:
                failed += 1
                logger.error("failed to import a metric: %s", e)
        return ok, failed
    return import_payload


class _OpenStream:
    """One open SendMetricsV2 stream: the messages received and not yet
    imported, and its counts.  Touched on the server's event loop only;
    `lock` is held through a chunk's import, by the handler or by the
    sweeper, so a stream's chunks import in order and its handler's
    last import is the last."""

    __slots__ = ("lock", "pending", "pending_bytes", "first_ns",
                 "imported", "failed", "chunks")

    def __init__(self):
        self.lock = asyncio.Lock()
        self.pending: list = []
        self.pending_bytes = 0
        self.first_ns = 0       # perf_counter_ns: pending[0] arrived
        self.imported = 0
        self.failed = 0
        self.chunks = 0


class DedupLedger:
    """Bounded per-source ledger of imported chunk identities — the
    receiving half of the exactly-once contract (forward/client.py
    CHUNK_ID_KEY).  A chunk delivered both directly and via spool
    replay (an ambiguous timeout, a sender crash mid-ack, a receiver
    crash after import) merges ONCE: the second delivery is recognized
    and skipped.

    Concurrency: `run_once(ident, import_fn)` RESERVES the identity
    under the ledger condition (O(1)), runs the import OUTSIDE it —
    concurrent V1 payloads keep parsing in parallel; only the
    aggregator-lock merge serializes, as before — and un-reserves on
    import failure so a failed delivery can retry.  Reservation at
    entry also makes two concurrent deliveries of the SAME chunk merge
    once.  The checkpoint writer takes `paused()` around its snapshot:
    new imports block and in-flight ones drain first, so a checkpoint
    can never capture a chunk's data without its ledger entry (or vice
    versa) — restore replays stay exact, not approximate.  The window
    is a per-source FIFO (`window` identities, oldest evicted), sized
    far beyond any spool's pending depth."""

    def __init__(self, window: int = 4096):
        self.window = max(16, int(window))
        self._cond = threading.Condition()
        # source -> (deque of idents in arrival order, set for O(1))
        self._sources: dict = {}
        self._active = 0          # imports between reserve and finish
        self._inflight: set = set()   # reserved idents not yet settled
        self._paused = False      # checkpoint cut in progress
        self.recorded = 0
        self.duplicates = 0

    def _seen_locked(self, ident: tuple) -> bool:
        entry = self._sources.get(ident[0])
        return entry is not None and ident in entry[1]

    def _record_locked(self, ident: tuple) -> None:
        entry = self._sources.get(ident[0])
        if entry is None:
            import collections
            entry = self._sources[ident[0]] = (collections.deque(), set())
        dq, seen = entry
        if ident in seen:
            return
        dq.append(ident)
        seen.add(ident)
        if len(dq) > self.window:
            seen.discard(dq.popleft())
        self.recorded += 1

    def _unrecord_locked(self, ident: tuple) -> None:
        entry = self._sources.get(ident[0])
        if entry is None or ident not in entry[1]:
            return
        entry[1].discard(ident)
        try:
            entry[0].remove(ident)
        except ValueError:
            pass
        self.recorded -= 1

    def run_once(self, ident, import_fn):
        """Execute `import_fn()` exactly once per identity.  Returns
        (result, duplicate): on a duplicate the import is skipped and
        result is None.  ident=None (an unidentified sender) always
        imports (still draining through the pause gate so the
        checkpoint cut covers every in-flight import)."""
        with self._cond:
            if ident is None:
                while self._paused:
                    self._cond.wait()
            else:
                # wait out BOTH a checkpoint cut and any in-flight
                # import of this same identity — a duplicate must not
                # be acked as success while the original could still
                # fail (the spool would settle the record and the
                # chunk would be lost silently)
                while self._paused or ident in self._inflight:
                    self._cond.wait()
                if self._seen_locked(ident):
                    # recorded AND no longer in flight = the original
                    # import completed successfully
                    self.duplicates += 1
                    logger.info("dedup: skipping duplicate chunk %s",
                                ident)
                    return None, True
                # reserve NOW: a concurrent duplicate delivery of the
                # same chunk parks on _inflight above
                self._record_locked(ident)
                self._inflight.add(ident)
            self._active += 1
        try:
            result = import_fn()
        except BaseException:
            with self._cond:
                if ident is not None:
                    # failed import: allow the sender's retry/replay
                    self._unrecord_locked(ident)
                    self._inflight.discard(ident)
                self._active -= 1
                self._cond.notify_all()
            raise
        with self._cond:
            if ident is not None:
                self._inflight.discard(ident)
            self._active -= 1
            self._cond.notify_all()
        return result, False

    @contextlib.contextmanager
    def paused(self):
        """The checkpoint cut: block new imports and drain in-flight
        ones, so ledger + aggregator snapshot as one coherent state."""
        with self._cond:
            while self._paused:      # one cut at a time
                self._cond.wait()
            self._paused = True
            while self._active > 0:
                self._cond.wait()
        try:
            yield
        finally:
            with self._cond:
                self._paused = False
                self._cond.notify_all()

    # -- checkpoint plumbing (core/server.py) ------------------------------

    def snapshot(self) -> dict:
        """JSON-able state for the crash checkpoint.  Callers that need
        the import-atomic cut (checkpoint_now) wrap this AND the
        aggregator snapshot in `paused()`."""
        with self._cond:
            return {
                "window": self.window,
                "sources": {
                    src: [[s, int(e), int(i)] for (s, e, i) in dq]
                    for src, (dq, _) in self._sources.items()},
            }

    def restore(self, state: dict) -> None:
        with self._cond:
            for src, idents in (state.get("sources") or {}).items():
                for s, e, i in idents:
                    self._record_locked((str(s), int(e), int(i)))

    def stats(self) -> dict:
        with self._cond:
            return {"recorded": self.recorded,
                    "duplicates": self.duplicates,
                    "sources": len(self._sources),
                    "window": self.window}


class GrpcImportServer:
    """Hosts forwardrpc.Forward (+ optional SSF/dogstatsd ingest) on one
    grpc.Server."""

    def __init__(self, address: str,
                 import_metric: Optional[Callable[[object], None]] = None,
                 ingest_span: Optional[Callable[[object], None]] = None,
                 handle_packet: Optional[Callable[[bytes], None]] = None,
                 max_workers: int = 64,
                 server_credentials: Optional[grpc.ServerCredentials] = None,
                 import_payload: Optional[Callable] = None,
                 trace_hook: Optional[Callable] = None,
                 dedup: Optional[DedupLedger] = None):
        """With import_metric=None the Forward service is omitted — the
        ingest-only shape of `grpc_listen_addresses` edge listeners
        (StartGRPC, networking.go:326-391), vs the global tier's
        `grpc_address` which serves all three.  import_payload, when
        provided, takes the whole V1 MetricList as RAW BYTES in one
        call (native wire scan + single aggregator lock — the
        fleet-rate inbound path), and each chunk of a V2 stream the
        same way with its `StreamChunk` as second argument; when it is
        None, `import_metric` is called message by message in its place.
        trace_hook(ctxs, n_metrics, start_ns, transport,
        stream_tags=None) receives the propagated trace contexts of each import RPC
        (veneur_tpu/trace/recorder.py metadata dialect) so the server
        can continue the sender's flush trace with an import span; a
        V2 stream's span carries `stream_tags` (its messages and chunks)."""
        self.import_metric = import_metric
        if import_payload is None and import_metric is not None:
            import_payload = _per_metric_payload(import_metric)
        self.import_payload = import_payload
        self.ingest_span = ingest_span
        self.handle_packet = handle_packet
        self.trace_hook = trace_hook
        self.dedup = dedup
        self.imported_count = 0
        # metrics that arrived but failed to import (malformed pb,
        # aggregator rejection): visible loss, part of the import-edge
        # ledger (surfaced at /debug/vars -> import_errors_total and as
        # the import.errors_total series)
        self.import_errors = 0
        self._count_lock = threading.Lock()
        # the V2 streams open now, the sweeper task that keeps (d) for
        # them and the chunk imports under way: the event loop's
        self._streams: set = set()
        self._sweep_task: Optional[asyncio.Task] = None
        self._stream_opened = asyncio.Event()
        self._imports: set = set()
        # totals since boot, /debug/vars -> import_stream (the flush
        # timeline's rows carry the same per interval, import_stream_*)
        self._stream_totals = {"rpcs": 0, "msgs": 0, "chunks": 0,
                               "recv_ns": 0, "frame_ns": 0}
        # The unary handlers and every chunk's import run here; a V2
        # stream is a coroutine on the loop and pins no thread.
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="grpc-import")
        self._loop = asyncio.new_event_loop()
        self._loop_thread = threading.Thread(
            target=self._loop.run_forever, daemon=True,
            name="grpc-import-loop")
        self._loop_thread.start()
        self._closed = False
        try:
            self.port = self._on_loop(
                self._build(address, server_credentials))
        except BaseException:
            self._close_loop()
            raise
        # what callers stop a peer hard through (`srv.server.stop(0)`)
        self.server = self
        if self.port == 0:
            # grpc returns 0 instead of raising; fail startup like the
            # reference's net.Listen error path (server.go:673-682)
            self._close_loop()
            raise OSError(f"could not bind gRPC import server to {address}")

    def _on_loop(self, coro):
        """Run a coroutine on the server's event loop; its result."""
        return asyncio.run_coroutine_threadsafe(coro, self._loop).result()

    async def _build(self, address: str, server_credentials) -> int:
        # made on the loop it will serve on
        self._aio = grpc.aio.server(migration_thread_pool=self._pool,
                                    options=SERVER_OPTIONS)
        self._aio.add_generic_rpc_handlers([self._make_handlers()])
        if server_credentials is not None:
            return self._aio.add_secure_port(address, server_credentials)
        return self._aio.add_insecure_port(address)

    # -- service wiring ----------------------------------------------------

    def _make_handlers(self):
        def _trace_ctxs(context):
            """Propagated trace contexts on this RPC, [] when the
            sender is untraced (or no hook is installed)."""
            if self.trace_hook is None:
                return []
            from veneur_tpu.trace import recorder as trace_rec
            return trace_rec.extract_contexts(
                context.invocation_metadata())

        def _chunk_ident(context):
            """The sender's chunk identity on this RPC, or None for an
            unidentified sender (reference veneurs, V2 streams)."""
            from veneur_tpu.forward.client import (CHUNK_ID_KEY,
                                                   parse_chunk_id)
            for entry in (context.invocation_metadata() or ()):
                try:
                    if entry[0] == CHUNK_ID_KEY:
                        return parse_chunk_id(entry[1])
                # vnlint: disable=silent-loss (a malformed metadata
                #   entry only degrades dedup to the unidentified path —
                #   the chunk itself still imports below, nothing drops)
                except (IndexError, TypeError):
                    continue
            return None

        def _import_v1_body(request):
            # RAW bytes straight to the native scan path — no python
            # protobuf materialization on the fleet edge
            count, failed = self.import_payload(bytes(request))
            if failed:
                with self._count_lock:
                    self.import_errors += failed
                logger.error("failed to import %d metrics in a V1 "
                             "batch", failed)
            return count

        def send_metrics(request, context):
            # V1 batch import — the fleet-internal batch RPC.  The
            # reference leaves this UNIMPLEMENTED (sources/proxy/
            # server.go:138-142) and its locals/proxies only speak the
            # V2 stream, so accepting batches here is a strict superset:
            # reference senders are unaffected, while this framework's
            # proxies/forwarders probe V1 and fall back to V2 against
            # reference globals.  Both reach the same batch import
            # (send_metrics_v2 frames a stream's messages into such
            # payloads); what V1 saves is python-grpc's per-message
            # receive — one MetricList carries thousands per RPC — and
            # what it adds is the chunk identity below.
            #
            # A chunk-identity header routes through the dedup ledger:
            # a chunk already imported (delivered pre-crash, or an
            # ambiguous timeout the sender's spool replays) is skipped
            # — merged exactly once — and the RPC still succeeds so the
            # replayer settles the record.
            #
            # server.sigstop_window (delay action) freezes THIS handler
            # for a bounded window — the in-process twin of a SIGSTOP'd
            # global: the RPC neither refuses nor resets, it just
            # hangs past the sender's deadline, and when the window
            # ends the import still completes — so the sender's retry
            # and the thawed original collide at the dedup ledger,
            # which must merge the chunk exactly once.
            from veneur_tpu import failpoints
            failpoints.inject("server.sigstop_window")
            ctxs = _trace_ctxs(context)
            start_ns = time.time_ns()
            if self.dedup is not None:
                count, duplicate = self.dedup.run_once(
                    _chunk_ident(context),
                    lambda: _import_v1_body(request))
                if duplicate:
                    return empty_pb2.Empty()
            else:
                count = _import_v1_body(request)
            with self._count_lock:
                self.imported_count += count
            if ctxs:
                self.trace_hook(ctxs, count, start_ns, "v1")
            return empty_pb2.Empty()

        async def send_metrics_v2(request_iterator, context):
            # the reference's wire (module docstring): messages arrive
            # as raw bytes, wait in `st.pending` and are imported a
            # chunk at a time; the response — the stream's ack — is
            # sent after the last chunk's import returned.  A stream
            # that is cut (cancelled, past its deadline, a lost
            # transport) raises out of the iterator: what was received
            # is imported all the same, by a task of its own, and no
            # ack is sent.
            ctxs = _trace_ctxs(context)
            start_ns = time.time_ns()
            st = _OpenStream()
            self._streams.add(st)
            self._stream_opened.set()
            try:
                async for raw in request_iterator:
                    if not st.pending:
                        st.first_ns = time.perf_counter_ns()
                    st.pending.append(raw)
                    st.pending_bytes += len(raw)
                    if (len(st.pending) >= STREAM_CHUNK_MSGS
                            or st.pending_bytes >= STREAM_CHUNK_BYTES):
                        await self._import_stream_chunk(st)
                await self._import_stream_chunk(st)
            except BaseException:
                self._start_stream_import(st)   # not awaited: no ack
                raise
            finally:
                self._streams.discard(st)
            if ctxs:
                self.trace_hook(ctxs, st.imported, start_ns, "v2",
                                {"messages": str(st.imported + st.failed),
                                 "chunks": str(st.chunks)})
            return empty_pb2.Empty()

        handlers = []
        if self.import_metric is not None:
            forward_handlers = {
                "SendMetrics": grpc.unary_unary_rpc_method_handler(
                    send_metrics,
                    request_deserializer=lambda b: b,
                    response_serializer=empty_pb2.Empty.SerializeToString),
                "SendMetricsV2": grpc.stream_unary_rpc_method_handler(
                    send_metrics_v2,
                    request_deserializer=lambda b: b,
                    response_serializer=empty_pb2.Empty.SerializeToString),
            }
            handlers.append(grpc.method_handlers_generic_handler(
                "forwardrpc.Forward", forward_handlers))

        if self.ingest_span is not None:
            def send_span(request, context):
                self.ingest_span(request)
                return ssf_grpc_pb2.Empty()
            handlers.append(grpc.method_handlers_generic_handler(
                "ssf.SSFGRPC", {
                    "SendSpan": grpc.unary_unary_rpc_method_handler(
                        send_span,
                        request_deserializer=ssf_pb2.SSFSpan.FromString,
                        response_serializer=(
                            ssf_grpc_pb2.Empty.SerializeToString)),
                }))
        if self.handle_packet is not None:
            def send_packet(request, context):
                self.handle_packet(request.packetBytes)
                return dogstatsd_grpc_pb2.Empty()
            handlers.append(grpc.method_handlers_generic_handler(
                "dogstatsd.DogstatsdGRPC", {
                    "SendPacket": grpc.unary_unary_rpc_method_handler(
                        send_packet,
                        request_deserializer=(
                            dogstatsd_grpc_pb2.DogstatsdPacket.FromString),
                        response_serializer=(
                            dogstatsd_grpc_pb2.Empty.SerializeToString)),
                }))

        # grpc.health.v1 Health/Check, always registered (the reference
        # sets SetServingStatus("veneur", SERVING), networking.go:377-384)
        # — k8s gRPC probes expect it.  Hand-rolled protos: request field
        # 1 is the service name; a SERVING response is field 1 varint 1.
        # Unknown service names get NOT_FOUND per the health protocol.
        def health_check(request, context):
            service = ""
            if len(request) >= 2 and request[0] == 0x0A:
                # length is a varint: service names of 128+ bytes use
                # multiple bytes
                n, shift, i = 0, 0, 1
                while i < len(request):
                    b = request[i]
                    n |= (b & 0x7F) << shift
                    i += 1
                    if not b & 0x80:
                        break
                    shift += 7
                service = request[i:i + n].decode(errors="replace")
            if service not in ("", "veneur"):
                context.abort(grpc.StatusCode.NOT_FOUND,
                              f"unknown service {service!r}")
            return b"\x08\x01"
        handlers.append(grpc.method_handlers_generic_handler(
            "grpc.health.v1.Health", {
                "Check": grpc.unary_unary_rpc_method_handler(
                    health_check,
                    request_deserializer=lambda b: b,
                    response_serializer=lambda b: b),
            }))

        class _Multi(grpc.GenericRpcHandler):
            def service(self, handler_call_details):
                for h in handlers:
                    r = h.service(handler_call_details)
                    if r is not None:
                        return r
                return None

        return _Multi()

    # -- the V2 stream's chunks (on the event loop) -------------------------

    def _start_stream_import(self, st: _OpenStream) -> "asyncio.Task":
        """Start the import of what `st` has received and not yet
        imported, as one chunk: a task of its own, so a cancelled RPC
        does not cancel it (what was received is imported, whatever
        becomes of its stream)."""
        task = asyncio.ensure_future(self._import_chunk_task(st))
        self._imports.add(task)
        task.add_done_callback(self._import_done)
        return task

    def _import_stream_chunk(self, st: _OpenStream) -> "asyncio.Future":
        """`_start_stream_import`, for the stream's handler to await."""
        return asyncio.shield(self._start_stream_import(st))

    async def _import_chunk_task(self, st: _OpenStream) -> None:
        # framed here, imported on the thread pool: the loop goes on
        # receiving, this stream's next chunk waits for `st.lock`
        async with st.lock:
            msgs = st.pending
            if not msgs:
                return
            st.pending, st.pending_bytes = [], 0
            t0 = time.perf_counter_ns()
            payload = frame_metric_list(msgs)
            chunk = StreamChunk(msgs, t0 - st.first_ns,
                                time.perf_counter_ns() - t0, st.chunks == 0)
            ok, failed = await self._loop.run_in_executor(
                self._pool, self._import_chunk_body, payload, chunk)
            st.chunks += 1
            st.imported += ok
            st.failed += failed

    def _import_done(self, task: asyncio.Task) -> None:
        self._imports.discard(task)
        if not task.cancelled() and task.exception() is not None:
            logger.error("a V2 stream's chunk failed to import",
                         exc_info=task.exception())

    def _import_chunk_body(self, payload: bytes, chunk: StreamChunk):
        """One chunk's import (a pool thread).  An import that raises
        fails its chunk's messages — counted, like any message that
        fails — and neither its stream nor the sweeper."""
        try:
            if self.dedup is not None:
                # an unidentified sender: nothing to deduplicate, but
                # the import still drains through the checkpoint's
                # pause gate
                (ok, failed), _ = self.dedup.run_once(
                    None, lambda: self.import_payload(payload, chunk))
            else:
                ok, failed = self.import_payload(payload, chunk)
        except Exception:
            logger.exception("a V2 stream's chunk raised in its import")
            return self._count_chunk(chunk, 0, len(chunk.messages))
        return self._count_chunk(chunk, ok, failed)

    def _count_chunk(self, chunk: StreamChunk, ok: int, failed: int):
        with self._count_lock:
            self.imported_count += ok
            self.import_errors += failed
            tot = self._stream_totals
            tot["rpcs"] += chunk.first
            tot["msgs"] += len(chunk.messages)
            tot["chunks"] += 1
            tot["recv_ns"] += chunk.recv_ns
            tot["frame_ns"] += chunk.frame_ns
        if failed:
            logger.error("failed to import %d metrics of a V2 stream's "
                         "chunk of %d", failed, len(chunk.messages))
        return ok, failed

    async def _sweep_streams(self) -> None:
        """Guarantee (d): import what an open stream received more than
        STREAM_MAX_WAIT_S - STREAM_SWEEP_S ago and its handler, waiting
        in the iterator, has not.  The due streams' imports are started,
        not awaited: one held at the aggregator lock or the
        checkpoint's pause gate delays no other stream's (`st.lock`
        orders a stream's own).  Parked while no stream is open."""
        wait_ns = int((STREAM_MAX_WAIT_S - STREAM_SWEEP_S) * 1e9)
        while True:
            if not self._streams:       # parked: an idle server ticks not
                self._stream_opened.clear()
                await self._stream_opened.wait()
            await asyncio.sleep(STREAM_SWEEP_S)
            due = time.perf_counter_ns() - wait_ns
            for st in list(self._streams):
                if st.pending and st.first_ns <= due \
                        and not st.lock.locked():
                    self._start_stream_import(st)

    def stream_stats(self) -> dict:
        """Totals of the V2 stream import since boot (/debug/vars ->
        import_stream): streams that imported a chunk, messages,
        chunks, the handlers' wall time in the request iterator and
        framing, and the streams open now."""
        with self._count_lock:
            tot = dict(self._stream_totals)
        return {"rpcs": tot["rpcs"], "msgs": tot["msgs"],
                "chunks": tot["chunks"],
                "recv_ms": round(tot["recv_ns"] / 1e6, 3),
                "frame_ms": round(tot["frame_ns"] / 1e6, 3),
                "open": len(self._streams)}

    # -- sources.Source lifecycle (sources/sources.go:1-19) ---------------

    def name(self) -> str:
        return "proxy"

    async def _start(self) -> None:
        await self._aio.start()
        if self.import_payload is not None:
            self._sweep_task = asyncio.ensure_future(self._sweep_streams())

    def start(self) -> None:
        self._on_loop(self._start())

    async def _stop(self, grace: Optional[float]) -> None:
        if self._sweep_task is not None:
            self._sweep_task.cancel()
        await self._aio.stop(grace)
        # the cut streams' last imports
        if self._imports:
            await asyncio.wait(set(self._imports), timeout=5.0)

    def _close_loop(self) -> None:
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._loop_thread.join(timeout=5.0)
        if not self._loop.is_running():
            self._loop.close()
        self._pool.shutdown(wait=False)

    def stop(self, grace: Optional[float] = 1.0) -> None:
        """Stop serving (in-flight RPCs get `grace` seconds, then are
        cut) and end the loop's thread.  Blocks until done."""
        if self._closed:
            return
        self._closed = True
        self._on_loop(self._stop(grace))
        self._close_loop()
