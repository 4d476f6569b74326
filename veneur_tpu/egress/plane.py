"""The egress data plane: async sink fan-out off the flush critical path.

The reference fans each flush out to ~15 pluggable sinks inside the
flush goroutine (`flusher.go:97-113`, `sinks/datadog/datadog.go:158`);
this repo's twin used to do the same under `_flush_serial` — one slow
or blackholed backend held the flush serialization lock and became the
new p99 (ROADMAP #8).  This module gives egress the machinery the
forward path already earned:

  * a bounded per-sink queue (`_flush_locked` hands the rendered
    interval over and returns; filtering, serialization and HTTP all
    run on per-sink lane workers),
  * per-sink circuit breakers (egress/breaker.py — the proxy
    destination-set contract) + bounded retries with seeded backoff
    (the forward client's `RetryPolicy`, reused verbatim),
  * durable spill: when a sink's retries exhaust (or its breaker is
    open), the filtered payload is serialized into that sink's own
    `ForwardSpool` segment (forward/spool.py, reused verbatim) and a
    background replayer re-delivers oldest-first once the backend
    recovers — the spool's ledger closure
    (`spilled == replayed + expired + dropped + pending`) surfaces at
    `/debug/vars -> egress`,
  * tracing: on sampled intervals every sink flush becomes a
    `flush.sink.<name>` span on the interval's own trace, with one
    `egress.attempt` child per delivery attempt (a breaker trip is
    causally visible in the critical-path table) and `egress.replay`
    spans continuing the original interval's context across the
    outage.

Failpoint: `egress.sink` fires per metric-lane delivery attempt
(initial and replay), so a chaos arm can blackhole a backend with
error/delay/drop actions and the unit tests can drive the full
degradation chain deterministically.

Job lifetime contract (enforced by the vnlint resource-pairing rule):
a job claimed from a lane queue (`claim_job`) must be settled
(`settle_job`) on every path — delivered, spilled, or dropped with
accounting — so `settle()` (and the flush-on-shutdown drain) can wait
on the pending count without a lost-job leak.
"""

from __future__ import annotations

import gc
import json
import logging
import os
import queue as queue_mod
import random
import re
import threading
import time
from typing import Callable, Optional

from veneur_tpu import failpoints
from veneur_tpu import sinks as sink_mod
from veneur_tpu.egress.breaker import CircuitBreaker
from veneur_tpu.forward.client import RetryPolicy
from veneur_tpu.forward.spool import ForwardSpool, RetryableReplayError
from veneur_tpu.samplers.samplers import InterMetric
from veneur_tpu.trace import recorder as trace_rec

logger = logging.getLogger("veneur_tpu.egress")

# egress spool payload version (the codec below, one record per job)
_PAYLOAD_VERSION = 1


def encode_metrics(metrics) -> bytes:
    """Serialize a filtered metric payload for the durable spool.  The
    sink re-delivery path needs full InterMetric rows back, so the
    codec is a plain JSON row list (routing allowlists are dropped —
    filtering already happened before the spill)."""
    rows = [[m.name, m.timestamp, m.value, list(m.tags), m.type,
             m.message, m.hostname] for m in metrics]
    return json.dumps([_PAYLOAD_VERSION, rows],
                      separators=(",", ":")).encode()


def decode_metrics(body: bytes) -> list[InterMetric]:
    version, rows = json.loads(body.decode())
    if version != _PAYLOAD_VERSION:
        raise ValueError(f"unknown egress payload version {version}")
    return [InterMetric(name=r[0], timestamp=r[1], value=r[2],
                        tags=list(r[3]), type=r[4], message=r[5],
                        hostname=r[6]) for r in rows]


def emit_http_phases(sink, sink_tags, statsd) -> None:
    """Per-POST HTTP phase self-metrics for poster-backed sinks — the
    reference traces DNS/connect/TTFB on every sink POST
    (`http/http.go:23-100`); the poster's tracing adapter records them
    and this emits `sink.http.{connect,ttfb,total}_ms` +
    `sink.http.connections_used_total` by state."""
    poster = getattr(sink, "_poster", None)
    if poster is None or not hasattr(poster, "drain_phase_stats"):
        return
    new_conns = reused = 0
    for rec in poster.drain_phase_stats():
        if rec["reused"]:
            reused += 1
        else:
            new_conns += 1
            statsd.timing("sink.http.connect_ms",
                          rec["connect_ms"], tags=sink_tags)
        statsd.timing("sink.http.ttfb_ms", rec["ttfb_ms"],
                      tags=sink_tags)
        statsd.timing("sink.http.total_ms", rec["total_ms"],
                      tags=sink_tags)
    if new_conns:
        statsd.count("sink.http.connections_used_total", new_conns,
                     tags=sink_tags + ["state:new"])
    if reused:
        statsd.count("sink.http.connections_used_total", reused,
                     tags=sink_tags + ["state:reused"])


class EgressJob:
    """One sink's share of one flush interval."""

    __slots__ = ("metrics", "events", "statsd", "interval",
                 "trace_id", "parent_span_id", "traced", "enqueued_ns",
                 "tick_ns")

    def __init__(self, metrics, events, statsd, interval: int,
                 trace_id: int = 0, parent_span_id: int = 0,
                 traced: bool = False, tick_ns: int = 0):
        self.metrics = metrics
        self.events = events
        self.statsd = statsd
        self.interval = interval
        self.trace_id = trace_id
        self.parent_span_id = parent_span_id
        self.traced = traced
        # wall clock of the handoff onto a lane's queue (SinkLane.submit):
        # where the job's flush.sink.<name> span and its lane.wait start
        self.enqueued_ns = 0
        # wall clock of the scheduled tick this interval's flush served
        # (Server.serve); 0 for a flush nobody scheduled
        self.tick_ns = tick_ns


def _materialized_within(metrics, start_ns: int, end_ns: int):
    """The stamps of the batch's last materialize()
    (samplers.MetricBatch.stamps) when it ran inside [start_ns,
    end_ns]; None for a list, a batch no one materialised, or a
    materialize() of another call."""
    stamps = getattr(metrics, "stamps", None)
    if not stamps or stamps[0] < start_ns or stamps[2] > end_ns:
        return None
    return stamps


def _safe_dirname(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9._-]", "_", name) or "sink"


class SinkLane:
    """One sink's bounded queue, worker thread, breaker and spool."""

    def __init__(self, plane: "EgressPlane", kind: str, spec, sink,
                 spool: Optional[ForwardSpool] = None):
        self.plane = plane
        self.kind = kind                 # "metric" | "span"
        self.spec = spec
        self.sink = sink
        self.name = sink.name()
        self.label = f"{kind}:{self.name}"
        self.sink_tags = [f"sink_name:{self.name}",
                          f"sink_kind:{spec.kind if spec else sink.kind()}"]
        self.queue: queue_mod.Queue = queue_mod.Queue(
            maxsize=plane.queue_depth)
        self.breaker = CircuitBreaker(plane.breaker_threshold,
                                      plane.breaker_reset_s)
        self.spool = spool
        self._rng = random.Random(plane.retry.seed)
        self._spill_seq = 0
        self._stats_lock = threading.Lock()
        self.enqueued = 0            # jobs accepted onto the queue
        self.delivered = 0           # jobs fully delivered
        self.flushed_points = 0      # metric points delivered
        self.retried = 0             # retry attempts taken
        self.errors = 0              # failed delivery attempts
        self.queue_dropped_points = 0  # points dropped on a full queue
        self.dropped_points = 0      # exhausted + spool-less drops
        self.stragglers = 0          # deliveries slower than an interval
        self.busy_since = 0.0        # perf_counter at claim; 0 = idle
        # what the last sink.flush call cost (_account_sink_call)
        self.last_sink_call: dict = {}
        self._thread: Optional[threading.Thread] = None

    def _count(self, field: str, n: int = 1) -> None:
        with self._stats_lock:
            setattr(self, field, getattr(self, field) + n)

    # -- enqueue (the flush path's handoff; never blocks) ------------------

    def submit(self, job: EgressJob) -> bool:
        """Hand one interval's job to this lane.  Returns False (after
        accounting the loss) when the queue is full — a sink that
        cannot keep up drops whole intervals VISIBLY instead of
        wedging the flush ticker."""
        self.plane.job_opened()
        job.enqueued_ns = time.time_ns()
        try:
            self.queue.put_nowait(job)
        except queue_mod.Full:
            self.plane.job_closed()
            # only metric lanes lose actual points on a bounce (span
            # sinks buffer internally; a skipped periodic flush loses
            # nothing) — a phantom point here would pollute the
            # testbed's visible-loss denominator
            pts = len(job.metrics) if self.kind == "metric" else 0
            if pts:
                self._count("queue_dropped_points", pts)
            job.statsd.count("egress.queue_full_total", 1,
                             tags=self.sink_tags)
            logger.warning(
                "egress %s: queue full (%d deep); dropped interval %d "
                "(%d points, accounted)", self.label,
                self.plane.queue_depth, job.interval, pts)
            return False
        self._count("enqueued")
        return True

    # -- worker ------------------------------------------------------------

    def start(self, replayers: bool = True) -> None:
        if replayers and self.spool is not None:
            # the replayer starts HERE (not at construction, and not
            # on a pre-start() lazy submit) so a recovered spool never
            # re-delivers into a sink that has not been start()ed yet;
            # start_replayer is idempotent, so the full start() after
            # a lazy one still arms it
            self.spool.start_replayer(self._replay_deliver)
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"egress-{self.label}")
        self._thread.start()

    def claim_job(self) -> Optional[EgressJob]:
        """Pop the next job (None on an empty poll).  Pairs with
        settle_job on every path — the egress-queue handoff lifetime
        the resource-pairing rule enforces."""
        try:
            return self.queue.get(timeout=0.1)
        except queue_mod.Empty:
            return None

    def settle_job(self, job: Optional[EgressJob]) -> None:
        """Close one claimed job's lifetime (delivered, spilled or
        dropped — the outcome was accounted by the delivery path)."""
        if job is not None:
            self.plane.job_closed()

    def _run(self) -> None:
        while not self.plane.stopping.is_set():
            job = self.claim_job()
            try:
                if job is not None:
                    self._deliver_job(job)
            except Exception:
                # the backstop that keeps the lane alive on a delivery
                # BUG — counted as an error episode so the crash is a
                # visible loss channel.  The points are NOT added to
                # dropped_points here: the delivery path may have
                # already accounted them (flushed or spilled) before
                # the crash, and a double count would break the ledger.
                self._count("errors")
                logger.exception("egress %s: delivery crashed",
                                 self.label)
            finally:
                self.settle_job(job)

    # -- delivery ----------------------------------------------------------

    def _deliver_job(self, job: EgressJob) -> None:
        statsd = job.statsd
        t0 = time.perf_counter()
        with self._stats_lock:
            self.busy_since = t0
        span = None
        if job.traced and job.trace_id:
            # the span covers the sink's whole share of the interval,
            # from the flush's handoff: its first child is the wait on
            # this lane's queue
            claimed_ns = time.time_ns()
            span = trace_rec.continue_span(
                f"flush.sink.{self.name}", job.trace_id,
                job.parent_span_id,
                tags={"sink": self.name, "kind": self.kind,
                      "interval": str(job.interval)},
                start_ns=job.enqueued_ns or claimed_ns)
            if self.kind == "metric":
                self._lane_span(span, "wait", span.start_ns, claimed_ns)
        try:
            if self.kind == "metric":
                self._deliver_metric(job, statsd, span)
            else:
                self._deliver_span_flush(statsd, span)
        finally:
            wall = time.perf_counter() - t0
            with self._stats_lock:
                self.busy_since = 0.0
                if wall > self.plane.interval_s:
                    # episode count for /debug/vars; the statsd series
                    # (flush.stragglers_total, old in-lock deadline
                    # semantics: one count per interval while a sink is
                    # still running) is emitted by the server's
                    # interval accounting from busy_for_s — which also
                    # catches a delivery that never returns at all
                    self.stragglers += 1
            if span is not None:
                span.finish()
                self.plane.record_span(span)

    def _lane_span(self, span, part: str, start_ns: int, end_ns: int):
        """One measured part of a traced metric job (flush.seg.lane.wait
        / .filter / .sink, and under .sink its own .records / .splice /
        .put) as a child of `span` — its flush.sink.<name> span, or the
        part above it — on the interval's own trace, with real
        timestamps.  With several metric sinks the names repeat per
        trace; the `sink` tag tells them apart.  trace/assembly sums
        only the root's direct children, so none of these moves the
        critical-path table."""
        child = span.child(f"flush.seg.lane.{part}",
                           tags={"sink": self.name})
        child.start_ns = start_ns
        child.end_ns = end_ns
        child.client = None      # ring fast path, like the flush segments
        child.finish()
        self.plane.record_span(child)
        return child

    def _sink_part_spans(self, sink_span, filtered) -> None:
        """The sink call in parts, from the instants the batch's last
        materialize() stamped (samplers.MetricBatch.stamps): building
        the records, the collector's splice, and from there to the
        sink's return (queue.put, the result).  A sink that never
        materialised the batch left no stamp inside the call and gets
        no children; a retried call keeps the last attempt's."""
        stamps = _materialized_within(filtered, sink_span.start_ns,
                                      sink_span.end_ns)
        if stamps is None:
            return
        start, built, spliced = stamps
        self._lane_span(sink_span, "sink.records", start, built)
        self._lane_span(sink_span, "sink.splice", built, spliced)
        self._lane_span(sink_span, "sink.put", spliced, sink_span.end_ns)

    def _account_sink_call(self, job: EgressJob, filtered, start_ns: int,
                           done_ns: int, cpu_ns: int,
                           gc_before: list) -> None:
        """What one sink.flush call (all attempts) cost beyond its wall
        time, onto the interval's timeline row and into stats(): this
        thread's CPU time over the call (wall less this is waiting, for
        the interpreter lock or the scheduler), the collections of any
        generation / of the oldest that started in the process
        meanwhile, the records the call built from the batch's segments
        and how many of them in native code (0 and 0 for a sink that
        did not materialise the batch; native 0 beside records > 0: the
        interpreter built them, samplers.MetricSegment.extend_records)
        and — for a flush serve() scheduled — the time from its tick to
        the sink's return."""
        passes = [after["collections"] - before["collections"]
                  for before, after in zip(gc_before, gc.get_stats())]
        native, interpreted = (
            filtered.built
            if _materialized_within(filtered, start_ns, done_ns)
            else (0, 0))
        call = {"lane_sink_cpu_ms": round(cpu_ns / 1e6, 3),
                "lane_gc_passes": sum(passes),
                "lane_gc_full_passes": passes[-1],
                "lane_records": native + interpreted,
                "lane_records_native": native}
        if job.tick_ns:
            call["tick_to_sink_ms"] = round(
                (done_ns - job.tick_ns) / 1e6, 3)
        with self._stats_lock:
            self.last_sink_call = dict(call, interval=job.interval)
        self.plane.amend_row(job.interval, call)

    def _deliver_metric(self, job: EgressJob, statsd, span) -> None:
        t_filter = time.time_ns()
        filtered, counts = sink_mod.filter_metrics_for_sink(
            self.spec, self.plane.routing_enabled, job.metrics,
            excluded_tags=self.plane.excluded_tags_for(self.name))
        if span is not None:
            self._lane_span(span, "filter", t_filter, time.time_ns())
        start = time.perf_counter()
        try:
            # status counts are emitted whether or not delivery lands
            # (a raising sink must not hide what filtering decided)
            for status in ("skipped", "max_name_length", "max_tags",
                           "max_tag_length", "flushed"):
                statsd.count("flushed_metrics", counts.get(status, 0),
                             tags=self.sink_tags + [f"status:{status}"])
            try:
                self.sink.flush_other_samples(job.events)
            except Exception as e:
                self._count("errors")
                statsd.count("flush.sink_errors_total", 1,
                             tags=self.sink_tags)
                logger.error("sink %s flush_other_samples failed: %s",
                             self.name, e)
            gc_before = gc.get_stats()
            cpu0 = time.thread_time_ns()
            t_sink = time.time_ns()
            try:
                self._attempt_flush(filtered, job, statsd, span)
            finally:
                t_done = time.time_ns()
                self._account_sink_call(
                    job, filtered, t_sink, t_done,
                    time.thread_time_ns() - cpu0, gc_before)
                if span is not None:
                    # the sink.flush call, all attempts and backoffs
                    self._sink_part_spans(
                        self._lane_span(span, "sink", t_sink, t_done),
                        filtered)
        finally:
            statsd.timing("sink.metric_flush_total_duration_ms",
                          (time.perf_counter() - start) * 1e3,
                          tags=self.sink_tags)
            emit_http_phases(self.sink, self.sink_tags, statsd)

    def _attempt_flush(self, filtered, job: EgressJob, statsd,
                       span) -> None:
        """Bounded-retry delivery under the breaker; exhaustion (or an
        open breaker) spills to the durable spool."""
        retry_idx = 0
        while True:
            if not self.breaker.admit():
                self._spill_or_drop(filtered, job, statsd,
                                    "breaker_open", span)
                return
            aspan = (span.child("egress.attempt",
                                tags={"attempt": str(retry_idx + 1),
                                      "points": str(len(filtered))})
                     if span is not None else None)
            try:
                failpoints.inject("egress.sink")
                result = (self.sink.flush(filtered)
                          or sink_mod.MetricFlushResult())
                self._record_delivered(result, statsd)
                return
            except Exception as e:
                self._count("errors")
                if aspan is not None:
                    aspan.error = True
                    aspan.tags["cause"] = type(e).__name__
                    fp = getattr(e, "failpoint", None)
                    if fp:
                        aspan.tags["failpoint"] = str(fp)
                    # stamp the failure NOW — the finally also finishes
                    # (idempotently) but only after the backoff sleep
                    aspan.finish()
                tripped = self.breaker.record_failure()
                if tripped:
                    self._breaker_event("egress.breaker.open", e)
                if (tripped or self.breaker.state() != "closed"
                        or retry_idx >= self.plane.retry.attempts - 1):
                    statsd.count("flush.sink_errors_total", 1,
                                 tags=self.sink_tags)
                    logger.error("sink %s flush failed after %d "
                                 "attempt(s): %s", self.name,
                                 retry_idx + 1, e)
                    self._spill_or_drop(filtered, job, statsd,
                                        "retries_exhausted", span)
                    return
                self._count("retried")
                statsd.count("egress.retries_total", 1,
                             tags=self.sink_tags)
                delay = self.plane.retry.delay_s(retry_idx, self._rng)
                logger.info("sink %s flush attempt %d failed (%s); "
                            "retrying in %.0f ms", self.name,
                            retry_idx + 1, e, delay * 1e3)
                time.sleep(delay)
                retry_idx += 1
            finally:
                if aspan is not None:
                    aspan.finish()
                    self.plane.record_span(aspan)

    def _record_delivered(self, result, statsd) -> None:
        statsd.count(sink_mod.METRICS_FLUSHED_TOTAL, result.flushed,
                     tags=self.sink_tags)
        statsd.count(sink_mod.METRICS_DROPPED_TOTAL, result.dropped,
                     tags=self.sink_tags)
        self._count("delivered")
        self._count("flushed_points", result.flushed)
        if self.breaker.record_success():
            self._breaker_event("egress.breaker.close", None)
            logger.info("sink %s circuit CLOSED (delivery succeeded)",
                        self.name)

    def _breaker_event(self, name: str, cause) -> None:
        snap = self.breaker.snapshot()
        tags = {"sink": self.name, "failures": snap["failures"],
                "trips": snap["trips"],
                "retry_in_s": snap["retry_in_s"]}
        if cause is not None:
            tags["cause"] = type(cause).__name__
            logger.warning(
                "sink %s circuit OPEN (%s consecutive failures, trip "
                "#%s, retry in %.1fs); spilling to the egress spool",
                self.name, snap["failures"], snap["trips"],
                snap["retry_in_s"])
        trace_rec.event_span(self.plane.recorder, name, tags)

    def _spill_or_drop(self, filtered, job: EgressJob, statsd,
                       cause: str, span) -> None:
        """Exhausted (or breaker-refused) payload: spill to this sink's
        durable spool when one is configured, else drop with
        accounting — never silent."""
        pts = len(filtered)
        if pts == 0:
            return
        if self.spool is not None:
            with self._stats_lock:
                self._spill_seq += 1
                seq = self._spill_seq
            tid = span.trace_id if span is not None else job.trace_id
            sid = span.span_id if span is not None else job.parent_span_id
            body = encode_metrics(list(filtered))
            if self.spool.append((self.name, job.interval, seq), body,
                                 pts, trace_id=tid, span_id=sid):
                statsd.count("egress.spilled_total", pts,
                             tags=self.sink_tags + [f"cause:{cause}"])
                logger.info(
                    "egress %s: spilled %d points of interval %d to "
                    "the spool (%s); background replay will "
                    "re-deliver", self.label, pts, job.interval, cause)
                return
        self._count("dropped_points", pts)
        statsd.count("egress.dropped_total", pts,
                     tags=self.sink_tags + [f"cause:{cause}"])
        logger.warning("egress %s: dropping %d points of interval %d "
                       "(%s, no spool)", self.label, pts,
                       job.interval, cause)

    def _replay_deliver(self, rec, body: bytes) -> None:
        """Spool replay: decode the recorded payload and re-flush it
        under the breaker's half-open discipline.  A sink failure
        keeps the record for the next tick (RetryableReplayError);
        records leave the spool only via delivery or visible expiry —
        except an undecodable payload, which propagates plainly so the
        spool drops it with accounting instead of wedging the queue
        head until expiry."""
        # decode BEFORE the breaker admit: a decode failure must not
        # strand the half-open probe flag
        metrics = decode_metrics(body)
        if not self.breaker.admit():
            raise RetryableReplayError(
                f"egress sink {self.name}: breaker open")
        span = None
        if rec.trace_id:
            span = trace_rec.continue_span(
                "egress.replay", rec.trace_id, rec.span_id,
                tags={"sink": self.name,
                      "interval": str(rec.ident[1]),
                      "points": str(rec.n_metrics)})
        try:
            failpoints.inject("egress.sink")
            result = (self.sink.flush(metrics)
                      or sink_mod.MetricFlushResult())
        except Exception as e:
            if span is not None:
                span.error = True
            self._count("errors")
            if self.breaker.record_failure():
                self._breaker_event("egress.breaker.open", e)
            raise RetryableReplayError(str(e)) from e
        finally:
            if span is not None:
                span.finish()
                self.plane.record_span(span)
        self._count("flushed_points", result.flushed)
        # the reference-compatible per-sink delivery series must count
        # replayed deliveries too, or an outage leaves a permanent
        # hole in sink.metrics_flushed_total that never backfills
        statsd = self.plane.statsd()
        statsd.count(sink_mod.METRICS_FLUSHED_TOTAL, result.flushed,
                     tags=self.sink_tags)
        statsd.count(sink_mod.METRICS_DROPPED_TOTAL, result.dropped,
                     tags=self.sink_tags)
        if self.breaker.record_success():
            self._breaker_event("egress.breaker.close", None)
            logger.info("sink %s circuit CLOSED (replay delivered)",
                        self.name)

    def _deliver_span_flush(self, statsd, span) -> None:
        """One span sink's periodic flush (SpanWorker.Flush,
        worker.go:657-678) — async like metric egress, but span sinks
        buffer internally, so there is no payload to retry or spool."""
        start = time.perf_counter()
        try:
            self.sink.flush()
            self._count("delivered")
        except Exception as e:
            self._count("errors")
            statsd.count("flush.sink_errors_total", 1,
                         tags=self.sink_tags)
            logger.error("span sink %s flush failed: %s", self.name, e)
        finally:
            statsd.timing("worker.span.flush_duration_ns",
                          (time.perf_counter() - start) * 1e9,
                          tags=[f"sink:{self.name}"])
            emit_http_phases(self.sink, self.sink_tags, statsd)

    def stats(self) -> dict:
        with self._stats_lock:
            out = {
                "kind": self.kind,
                "queued": self.queue.qsize(),
                "enqueued": self.enqueued,
                "delivered": self.delivered,
                "flushed_points": self.flushed_points,
                "retried": self.retried,
                "errors": self.errors,
                "queue_dropped_points": self.queue_dropped_points,
                "dropped_points": self.dropped_points,
                "stragglers": self.stragglers,
                # wall seconds the CURRENT delivery has been running
                # (0 = idle): a hung sink.flush shows up here — and in
                # flush.stragglers_total via the server's interval
                # accounting — even though it never completes
                "busy_for_s": round(
                    (time.perf_counter() - self.busy_since)
                    if self.busy_since else 0.0, 3),
            }
            if self.last_sink_call:
                # the lane's own last sink.flush call: tick to sink,
                # its CPU time, the collector's passes during it
                out["last_sink_call"] = dict(self.last_sink_call)
        out["breaker"] = self.breaker.snapshot()
        if self.spool is not None:
            out["spool"] = self.spool.stats()
        return out

    def close(self, drain: bool) -> None:
        if self.spool is not None:
            self.spool.close(drain=drain)


class EgressPlane:
    """All of a server's sink lanes plus the shared handoff contract.

    `submit_interval` is the only flush-path entry point: it enqueues
    one job per lane and returns — no filtering, serialization or I/O
    happens under the caller's lock.  `settle` waits for the pending
    job count to hit zero (tests and the graceful-shutdown drain);
    `stats` is the `/debug/vars -> egress` payload, whose spool ledger
    closes exactly (`spilled == replayed + expired + dropped +
    pending`)."""

    def __init__(self, interval_s: float = 10.0, queue_depth: int = 128,
                 retry: Optional[RetryPolicy] = None,
                 breaker_threshold: int = 3,
                 breaker_reset_s: float = 5.0,
                 spool_dir: str = "",
                 spool_max_bytes: int = 64 << 20,
                 spool_max_age_s: float = 600.0,
                 spool_fsync: str = "rotate",
                 spool_replay_interval_s: float = 0.5,
                 routing_enabled: bool = False,
                 excluded_tags_for: Optional[Callable] = None,
                 recorder=None,
                 statsd_fn: Optional[Callable] = None,
                 timeline=None):
        self.interval_s = float(interval_s)
        self.queue_depth = max(1, int(queue_depth))
        self.retry = retry or RetryPolicy()
        self.breaker_threshold = breaker_threshold
        self.breaker_reset_s = breaker_reset_s
        self.spool_dir = spool_dir
        self.spool_max_bytes = spool_max_bytes
        self.spool_max_age_s = spool_max_age_s
        self.spool_fsync = spool_fsync
        self.spool_replay_interval_s = spool_replay_interval_s
        self.routing_enabled = routing_enabled
        self.excluded_tags_for = excluded_tags_for or (lambda name: None)
        self.recorder = recorder
        # the flush timeline (profiling/timeline.py) whose rows the
        # metric lanes amend with what they learn of a flush
        self.timeline = timeline
        # self-metrics client for deliveries with no flush-path job to
        # carry one (spool replays); defaults to a no-op client
        self._statsd_fn = statsd_fn
        self.lanes: list[SinkLane] = []
        self.stopping = threading.Event()
        self._start_lock = threading.Lock()
        self._started = False
        # open jobs across every lane (incremented on submit, closed by
        # settle_job / a queue-full bounce); settle() waits on it
        self._pending = 0
        self._pending_lock = threading.Lock()
        self._pending_zero = threading.Condition(self._pending_lock)

    def statsd(self):
        from veneur_tpu import scopedstatsd
        if self._statsd_fn is not None:
            return scopedstatsd.ensure(self._statsd_fn())
        return scopedstatsd.ensure(None)

    # -- registration ------------------------------------------------------

    def add_metric_sink(self, spec, sink) -> SinkLane:
        spool = None
        if self.spool_dir:
            # keyed by registration ORDER as well as name: two sinks
            # with a colliding name (e.g. two datadog sinks to
            # different endpoints) must never interleave appends into
            # one segment dir or cross-replay each other's payloads.
            # Registration order is config order, so a revived server
            # with the same config maps each lane back to its dir.
            idx = sum(1 for l in self.lanes if l.kind == "metric")
            spool = ForwardSpool(
                os.path.join(self.spool_dir,
                             f"{idx}-{_safe_dirname(sink.name())}"),
                max_bytes=self.spool_max_bytes,
                max_age_s=self.spool_max_age_s,
                fsync=self.spool_fsync,
                replay_interval_s=self.spool_replay_interval_s)
        lane = SinkLane(self, "metric", spec, sink, spool=spool)
        self.lanes.append(lane)
        return lane

    def add_span_sink(self, sink) -> SinkLane:
        lane = SinkLane(self, "span", None, sink)
        self.lanes.append(lane)
        return lane

    # -- lifecycle ---------------------------------------------------------

    def start(self, replayers: bool = True) -> None:
        """Start the lane workers.  `replayers=False` is the lazy
        pre-`Server.start()` form: queued jobs drain, but recovered
        spool records wait for the full start (sinks may not be
        start()ed yet); the full start arms the replayers even when
        the workers were lazily started."""
        with self._start_lock:
            if self._started and not replayers:
                return
            self._started = True
            for lane in self.lanes:
                lane.start(replayers=replayers)

    def job_opened(self) -> None:
        with self._pending_lock:
            self._pending += 1

    def job_closed(self) -> None:
        with self._pending_zero:
            self._pending -= 1
            if self._pending <= 0:
                self._pending_zero.notify_all()

    def record_span(self, span) -> None:
        if self.recorder is not None:
            self.recorder.record_span(span)

    def amend_row(self, interval: int, fields: dict) -> None:
        if self.timeline is not None:
            self.timeline.amend(interval, **fields)

    # -- the flush path's handoff ------------------------------------------

    def submit_interval(self, metrics, events, statsd, interval: int,
                        trace_id: int = 0, parent_span_id: int = 0,
                        traced: bool = False, tick_ns: int = 0) -> None:
        """Enqueue one job per lane and return immediately.  Lanes are
        lazily started so a pre-`start()` flush (tests, tooling) still
        delivers — asynchronously, like every other flush."""
        if not self.lanes:
            return
        if not self._started:
            self.start(replayers=False)
        for lane in self.lanes:
            lane.submit(EgressJob(
                metrics if lane.kind == "metric" else None,
                events, statsd, interval,
                trace_id=trace_id, parent_span_id=parent_span_id,
                traced=traced, tick_ns=tick_ns))

    # -- quiescence / teardown ---------------------------------------------

    def settle(self, timeout_s: float = 10.0) -> bool:
        """Wait until every submitted job has been settled (delivered,
        spilled or dropped-with-accounting).  Does NOT wait for spool
        replay — a blackholed backend's pending records drain on their
        own clock.  Returns False on timeout."""
        deadline = time.monotonic() + timeout_s
        with self._pending_zero:
            while self._pending > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._pending_zero.wait(remaining)
        return True

    def close(self, drain: bool = True, timeout_s: float = 10.0) -> None:
        """Stop the lanes.  `drain` settles queued jobs first and
        fsyncs the spool tails (graceful shutdown); a simulated crash
        passes False — queued jobs die with the process and the spools
        keep their on-disk pending records for the revived instance."""
        if drain:
            self.settle(timeout_s=timeout_s)
        self.stopping.set()
        for lane in self.lanes:
            t = lane._thread
            if t is not None:
                t.join(timeout=1.0)
        for lane in self.lanes:
            lane.close(drain=drain)

    # -- observability -----------------------------------------------------

    def stats(self) -> dict:
        """The `/debug/vars -> egress` payload: per-sink lanes plus the
        aggregated ledger.  The spool ledger closure — in metric
        POINTS, `spilled + recovered == replayed + expired +
        spool_dropped + pending_points` — holds by construction (each
        lane's ForwardSpool maintains it; `pending` counts records,
        `pending_points` the points inside them)."""
        per_sink = {}
        agg = {"flushed": 0, "retried": 0, "errors": 0,
               "queue_dropped": 0, "dropped": 0, "stragglers": 0,
               "spilled": 0, "recovered": 0, "replayed": 0,
               "expired": 0, "spool_dropped": 0, "pending": 0,
               "pending_points": 0}
        breakers = {}
        ledger_closed = True
        for lane in self.lanes:
            st = lane.stats()
            per_sink[lane.label] = st
            agg["flushed"] += st["flushed_points"]
            agg["retried"] += st["retried"]
            agg["errors"] += st["errors"]
            agg["queue_dropped"] += st["queue_dropped_points"]
            agg["dropped"] += st["dropped_points"]
            agg["stragglers"] += st["stragglers"]
            if lane.kind == "metric":
                breakers[lane.name] = st["breaker"]
            sp = st.get("spool")
            if sp is not None:
                agg["spilled"] += sp["spilled_points"]
                agg["recovered"] += sp["recovered_points"]
                agg["replayed"] += sp["replayed_points"]
                agg["expired"] += sp["expired_points"]
                agg["spool_dropped"] += sp["dropped_points"]
                agg["pending"] += sp["pending_records"]
                agg["pending_points"] += sp["pending_points"]
                # per-lane closure over ONE consistent spool snapshot;
                # records a reopen recovered from a previous process's
                # spill are part of the inflow side
                ledger_closed = ledger_closed and (
                    sp["spilled_points"] + sp["recovered_points"]
                    == sp["replayed_points"] + sp["expired_points"]
                    + sp["dropped_points"] + sp["pending_points"])
        agg["ledger_closed"] = ledger_closed
        agg["breakers"] = breakers
        agg["per_sink"] = per_sink
        return agg
