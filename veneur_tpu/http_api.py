"""Operator HTTP API.

Mirrors `http.go:15-67`: /healthcheck, /version, /builddate, optional
/config/json + /config/yaml (secret-redacted, util/config/config.go:65-96),
optional /quitquitquit, the live query plane, and the debug suite
(server.go:1366-1383 / SURVEY §5.1):

  /query                 windowed quantiles served between flushes
                         (?name=&window_s=|slots=&q=0.5,0.99&tags=
                         [&type=histogram|timer]): fuses the window
                         ring's per-interval sub-sketches on read and
                         answers quantiles + a self-describing
                         mergeable payload (veneur_tpu/query/; gated
                         by query_window_slots > 0)
  /debug/vars            runtime stats + native data-plane stage counters
  /debug/threads         stack dump of every live thread
  /debug/profile         JAX device trace (the TPU-side profile)
  /debug/pprof/          index of the host-side profile suite
  /debug/pprof/profile   sampling HOST CPU profile -> folded stacks
                         (?seconds=N&hz=M; py-spy when available, else
                         the in-process sampler — veneur_tpu/profiling)
  /debug/flush_timeline  ring of structured per-flush records (?last=N)
  /debug/trace           flight-recorder span ring: every flush interval
                         is a distributed trace (?trace_id=HEX | ?last=N)
"""

from __future__ import annotations

import gc
import http.server
import json
import logging
import os
import sys
import tempfile
import threading
import time
import traceback
import urllib.parse
from typing import Optional

import yaml

from veneur_tpu import __version__
from veneur_tpu import config as config_mod

BUILD_DATE = "dev"
VERSION = __version__


# -- helpers shared with the proxy's HTTP surface -------------------------

def reply(handler, code: int, body: bytes,
          ctype: str = "text/plain") -> None:
    handler.send_response(code)
    handler.send_header("Content-Type", ctype)
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)


def config_json_body(cfg_dict: dict) -> bytes:
    """util/config/config.go:65-77 shape: indented JSON."""
    return json.dumps(cfg_dict, default=str, indent=2).encode()


def config_yaml_body(cfg_dict: dict) -> bytes:
    """util/config/config.go:78-96 shape: YAML via a JSON round-trip so
    non-scalar config values serialize the same way in both dumps."""
    return yaml.safe_dump(
        json.loads(json.dumps(cfg_dict, default=str))).encode()


def thread_dump() -> bytes:
    """/debug/threads payload: a stack for every live thread."""
    out = []
    for tid, frame in sys._current_frames().items():
        out.append(f"--- thread {tid} ---")
        out.extend(traceback.format_stack(frame))
    return "\n".join(out).encode()


def debug_vars(server) -> dict:
    """The `/debug/vars` payload for one core.Server — the single
    source of the server-tier debug-vars key space.  The handler below
    serves it over HTTP and the telemetry witness
    (analysis/telemetry.py) snapshots it directly, so the statically
    extracted schema and the runtime observation read the same dict.
    """
    stats = {
        "flush_count": server.flush_count,
        "last_flush_unix": server.last_flush_unix,
        "is_local": server.is_local,
        "processed": server.aggregator.processed,
        "imported": server.aggregator.imported,
        "imported_total": getattr(
            server.grpc_import, "imported_count", 0)
        if getattr(server, "grpc_import", None) else 0,
        # import-edge failures: metrics that ARRIVED but failed to
        # import (visible loss; also import.errors_total)
        "import_errors_total": getattr(
            server.grpc_import, "import_errors", 0)
        if getattr(server, "grpc_import", None) else 0,
        # host-path loss counters (python parse / ssf parse / direct
        # span-sink ingest): the silent-loss lint's server-side ledger
        "parse_errors_python": getattr(server, "parse_errors", 0),
        "parse_errors_ssf": getattr(server, "ssf_parse_errors", 0),
        "span_ingest_errors": getattr(server, "span_ingest_errors", 0),
        "metric_sinks": [s.name() for _, s in
                         server.metric_sinks],
        "threads": threading.active_count(),
        # metrics dropped because every forward slot was
        # stalled (bounded-buffering loss, core/server.py)
        "forward_slots_dropped": server.forward_dropped,
    }
    # the cyclic collector, read now: per generation the collections
    # made and the objects they freed or could not, the allocation
    # counts toward the next pass of each, and the objects frozen out
    # of its sight.  MetricBatch.materialize() splices a flush's
    # records into the oldest generation, which zeroes the counts every
    # flush: a quiet long-lived server whose oldest generation is never
    # collected shows here
    stats["gc"] = {
        "enabled": gc.isenabled(),
        "generations": gc.get_stats(),
        "count": gc.get_count(),
        "frozen": gc.get_freeze_count(),
    }
    egress = getattr(server, "egress", None)
    if egress is not None:
        # the egress data plane's ledger: per-sink lanes
        # (queue depth, breaker state, spool) plus the
        # aggregated closure — spilled + recovered == replayed +
        # expired + dropped + pending, so sink-delivery
        # loss is reconcilable from here
        stats["egress"] = egress.stats()
    workers = getattr(server, "span_workers", None)
    if workers:
        # per-span-sink ingest accounting: a full queue or
        # a sink ingest error is visible loss, not a log
        # line (the _SpanSinkWorker drop-counter satellite);
        # sinks with internal loss tallies (ssfmetrics invalid
        # samples, newrelic POST drops) merge theirs in
        stats["span_sinks"] = {
            w.sink.name(): {
                "ingested": w.ingested,
                "dropped": w.dropped,
                "errors": w.errors,
                **(w.sink.loss_stats()
                   if hasattr(w.sink, "loss_stats") else {}),
            } for w in workers}
    fw = getattr(server, "forwarder", None)
    if fw is not None and hasattr(fw, "stats"):
        # the forward client's retry-policy accounting:
        # sent / retries / dropped / spilled metric totals
        stats["forward"] = fw.stats()
    if fw is not None and hasattr(fw, "spool_stats"):
        sp = fw.spool_stats()
        if sp is not None:
            # the durable spool's ledger: pending depth plus
            # spilled/replayed/expired records AND points —
            # spilled == replayed + expired + dropped once
            # drained, so loss is reconcilable from here
            stats["spool"] = sp
    ckpt = getattr(server, "checkpoint_stats", None)
    if ckpt is not None and ckpt.get("enabled"):
        stats["checkpoint"] = dict(ckpt)
    gi = getattr(server, "grpc_import", None)
    if gi is not None and hasattr(gi, "stream_stats"):
        # the V2 stream import since boot (sources/proxy.py): streams,
        # messages, the chunks they were imported in, the handlers'
        # wall time in the request iterator and framing, streams open
        # now (the flush timeline's rows: import_stream_* per interval)
        stats["import_stream"] = gi.stream_stats()
    dedup = getattr(server, "dedup", None)
    if dedup is not None:
        # exactly-once ledger: recorded chunk identities and
        # duplicates skipped (replays of delivered chunks)
        stats["dedup"] = dedup.stats()
    agg = server.aggregator
    if getattr(agg, "moments", None) is not None:
        # sketch-family dispatch: live key counts per histogram
        # family + the moments solver's last worst residual
        stats["sketch_families"] = {
            "dispatch": bool(getattr(agg, "family_dispatch", False)),
            "tdigest_keys": len(agg.digests.kdict),
            "moments_keys": len(agg.moments.kdict),
            "moments_k": agg.moments.k,
            "moments_solver_resid": float(
                getattr(agg, "last_moments_resid", 0.0)),
        }
    # what the last flush's cut did with the staged points (also on
    # the flush timeline's rows): points handed to the flush, bytes of
    # them copied under the aggregator lock (0 = nothing joined at the
    # tick), buffer doublings over the interval
    from veneur_tpu.core.aggregator import (HOT_LEDGER_KEYS,
                                            INTERN_LEDGER_KEYS,
                                            KEY_LEDGER_KEYS,
                                            SET_LEDGER_KEYS,
                                            STAGED_LEDGER_KEYS)
    segs = agg.last_flush_segments
    stats["staged_accumulator"] = {
        key: segs.get(key, 0) for key in STAGED_LEDGER_KEYS}
    # what the set arena's lanes did in the last interval and its flush
    # (also on the flush timeline's rows): register bytes resident on
    # the device, rows estimated there, bytes uploaded and read back,
    # triples scattered / launches / dense rows merged, the lane syncs'
    # lock hold, and the forwarded sketches staged by wire form
    stats["set_lanes"] = {
        key: segs.get(key, 0)
        for key in (*SET_LEDGER_KEYS, "set_import_sparse",
                    "set_import_dense")}
    # what the hot-key lane did in the last interval and how its flush
    # built the digest operand (also on the flush timeline's rows): rows
    # pre-reduced, points into and out of the compress, launches, the
    # passes' hold of the aggregator lock, one launch's bytes, the
    # operand's tiers and padded elements
    stats["hot_lane"] = {
        key: segs.get(key, 0) for key in HOT_LEDGER_KEYS}
    # a row's life over the last interval (also on the flush timeline's
    # rows): keys born and rows freed, arena doublings, live keys and
    # rows ever handed out, the drain's lock hold on keys it did not
    # know — and, if that interval cleared the native intern table, the
    # clears, their drain calls and the identities registered again
    stats["key_lifecycle"] = {
        key: segs.get(key, 0)
        for key in (*KEY_LEDGER_KEYS, "key_birth_held_s")}
    stats["key_lifecycle"].update(
        (key, segs[key]) for key in INTERN_LEDGER_KEYS if key in segs)
    guard = getattr(server.aggregator, "cardinality", None)
    if guard is not None:
        # per-tenant key-budget ledger: exact keys, evicted
        # cardinality, rollup point totals
        stats["cardinality"] = guard.snapshot()
    cubes = getattr(server.aggregator, "cubes", None)
    if cubes is not None:
        # group-by cube ledger: live groups / rollup points /
        # accounted overflow per dimension (conservation:
        # rollup_points == exact-group points + overflowed)
        stats["cube"] = cubes.snapshot()
    # staged-vs-resident assembly probe (parallel/serving.py): the
    # one-shot measured link decision, inspectable without forcing
    # a probe run
    from veneur_tpu.parallel import serving as _serving
    stats["resident_link_probe"] = _serving.link_probe_stats()
    native = getattr(server, "native", None)
    if native is not None:
        ni = native.stats()  # None while tearing down
        if ni is not None:
            stats["native_ingest"] = ni
        st = native.stage_stats()
        if st is not None:
            # monotonic per-stage packet/ns counters
            # (recvmmsg/parse/intern/stage/drain), per reader
            # thread + totals — the live view the ceiling
            # harness (scripts/ingest_ceiling.py) tabulates
            stats["ingest_stages"] = st
        # which queue overflowed, as of the last flush: kernel drops at
        # the UDP sockets' receive buffers, reader publishes that found
        # their ring full, peak ring occupancy (also on the flush
        # timeline's rows, per interval)
        stats["ingest_overflow"] = dict(
            getattr(server, "ingest_overflow", None) or {})
    prewarm = getattr(server, "prewarm_stats", None)
    if prewarm is not None and (server.config.prewarm_flush_shapes
                                or prewarm["programs"]):
        # the boot-time compile of the configured flush shapes: programs
        # compiled so far and their wall seconds
        stats["prewarm_programs"] = prewarm["programs"]
        stats["prewarm_seconds"] = round(prewarm["seconds"], 3)
    timeline = getattr(server, "flush_timeline", None)
    if timeline is not None:
        stats["flush_timeline_recorded"] = \
            timeline.total_recorded
    recorder = getattr(server, "flight_recorder", None)
    if recorder is not None:
        stats["trace_recorded"] = recorder.total_recorded
    query = getattr(server, "query", None)
    if query is not None:
        # live query plane: served/error counts, recent latency
        # percentiles, and per-family ring occupancy (slots held,
        # total cuts, evictions, staged points retained)
        stats["query"] = query.stats()
    retention = getattr(server.aggregator, "retention", None)
    if retention is not None:
        # multi-resolution retention: per-tier bucket occupancy,
        # on-disk bytes, and the spill/expiry ledger (the telemetry
        # witness asserts spilled + recovered == expired + dropped +
        # pending directly over this block)
        stats["retention"] = retention.stats()
    return stats


def make_handler(server) -> type:
    cfg = server.config

    class Handler(http.server.BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

        def _reply(self, code: int, body: bytes,
                   ctype: str = "text/plain") -> None:
            reply(self, code, body, ctype)

        def do_POST(self):
            if self.path == "/quitquitquit" and cfg.http_quit:
                self._reply(200, b"terminating\n")
                threading.Thread(target=server.shutdown, daemon=True).start()
                return
            if self.path == "/flush" and cfg.http_flush_endpoint:
                # the process-separated testbed's interval driver: one
                # synchronous flush, so a supervising harness controls
                # interval boundaries across real process boundaries
                # exactly like the in-process cluster calls
                # server.flush().  Gated: an unauthenticated flush
                # trigger is a DoS lever in production.
                try:
                    server.flush()
                except Exception as e:
                    self._reply(500, f"flush failed: {e}\n".encode())
                    return
                self._reply(200, json.dumps(
                    {"flush_count": server.flush_count}).encode(),
                    "application/json")
                return
            if self.path == "/checkpoint" and cfg.http_flush_endpoint:
                # crash-arm plumbing: force a checkpoint cut NOW (the
                # cross-process analog of Cluster.checkpoint_global)
                try:
                    ok = server.checkpoint_now()
                except Exception as e:
                    self._reply(500,
                                f"checkpoint failed: {e}\n".encode())
                    return
                self._reply(200 if ok else 500, json.dumps(
                    {"ok": bool(ok),
                     "writes": server.checkpoint_stats["writes"]}
                ).encode(), "application/json")
                return
            self._reply(404, b"not found\n")

        def do_GET(self):
            if self.path == "/healthcheck":
                self._reply(200, b"ok\n")
            elif self.path == "/version":
                self._reply(200, VERSION.encode())
            elif self.path == "/builddate":
                self._reply(200, BUILD_DATE.encode())
            elif self.path == "/config/json" and cfg.http_config_endpoint:
                self._reply(200,
                            config_json_body(config_mod.redacted_dict(cfg)),
                            "application/json")
            elif self.path == "/config/yaml" and cfg.http_config_endpoint:
                self._reply(200,
                            config_yaml_body(config_mod.redacted_dict(cfg)),
                            "application/x-yaml")
            elif self.path.startswith("/query"):
                # the live query plane: windowed quantiles between
                # flushes (veneur_tpu/query/).  The engine owns the
                # whole contract — parsing, fusion, telemetry, the
                # flight-recorder query span — and returns the HTTP
                # status with the JSON body
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query)
                code, body = server.query.serve(q)
                self._reply(code, json.dumps(body, indent=2).encode(),
                            "application/json")
            elif self.path == "/debug/vars":
                self._reply(200,
                            json.dumps(debug_vars(server),
                                       indent=2).encode(),
                            "application/json")
            elif self.path.rstrip("/") == "/debug/pprof":
                self._reply(200, _pprof_index(cfg))
            elif self.path.startswith("/debug/pprof/profile"):
                if not cfg.enable_profiling:
                    self._reply(403, b"profiling disabled "
                                b"(set enable_profiling)\n")
                    return
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query)
                try:
                    seconds = float(q.get("seconds", ["2"])[0])
                    hz = int(q.get("hz", [cfg.profiling_cpu_hz])[0])
                except ValueError:
                    self._reply(400, b"bad seconds/hz\n")
                    return
                # positive-check BEFORE the cap: nan fails every
                # comparison, so `not (seconds > 0)` rejects it — while
                # `min(nan, cap) <= 0` would let it through into a
                # sampler that never reaches its deadline
                if not (seconds > 0 and hz > 0):
                    self._reply(400, b"bad seconds/hz\n")
                    return
                seconds = min(seconds,
                              float(cfg.profiling_cpu_max_seconds))
                from veneur_tpu.profiling import cpu as cpu_prof
                folded, backend = cpu_prof.profile_cpu(
                    seconds, hz=hz, use_pyspy=cfg.profiling_use_pyspy)
                self.send_response(200)
                body = folded.encode()
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.send_header("X-Profile-Backend", backend)
                self.end_headers()
                self.wfile.write(body)
            elif self.path.startswith("/debug/flush_timeline"):
                timeline = getattr(server, "flush_timeline", None)
                if timeline is None:
                    self._reply(404, b"no flush timeline\n")
                    return
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query)
                try:
                    last = (int(q["last"][0]) if "last" in q else None)
                except ValueError:
                    self._reply(400, b"bad last\n")
                    return
                out = {"capacity": timeline.capacity,
                       "recorded_total": timeline.total_recorded,
                       "records": timeline.snapshot(last)}
                self._reply(200, json.dumps(out, indent=2).encode(),
                            "application/json")
            elif self.path.startswith("/debug/spans"):
                # raw ring records for the cross-process trace
                # assembler; ?drain=1 takes them atomically so repeated
                # scrapes return disjoint batches (testbed/proccluster)
                from veneur_tpu.trace import recorder as trace_rec
                recorder = getattr(server, "flight_recorder", None)
                if recorder is None:
                    self._reply(404, b"no flight recorder\n")
                    return
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query)
                try:
                    out = trace_rec.debug_spans_body(recorder, q)
                except ValueError:
                    self._reply(400, b"bad drain\n")
                    return
                self._reply(200, json.dumps(out, indent=2).encode(),
                            "application/json")
            elif self.path.startswith("/debug/trace"):
                # the self-tracing flight recorder: always on, like the
                # ring it serves — a black box is most needed when
                # nothing else was enabled ahead of the incident
                from veneur_tpu.trace import recorder as trace_rec
                recorder = getattr(server, "flight_recorder", None)
                if recorder is None:
                    self._reply(404, b"no flight recorder\n")
                    return
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query)
                try:
                    out = trace_rec.debug_trace_body(recorder, q)
                except ValueError:
                    self._reply(400, b"bad trace_id/last\n")
                    return
                self._reply(200, json.dumps(out, indent=2).encode(),
                            "application/json")
            elif self.path.startswith("/debug/profile"):
                if not cfg.enable_profiling:
                    self._reply(403, b"profiling disabled "
                                b"(set enable_profiling)\n")
                    return
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query)
                try:
                    seconds = min(float(q.get("seconds", ["2"])[0]), 60.0)
                except ValueError:
                    self._reply(400, b"bad seconds\n")
                    return
                out = _jax_profile(server, seconds)
                self._reply(200, json.dumps(out, indent=2).encode(),
                            "application/json")
            elif self.path == "/debug/threads":
                self._reply(200, thread_dump())
            else:
                self._reply(404, b"not found\n")

    return Handler


def _pprof_index(cfg) -> bytes:
    """/debug/pprof/ index — parity with the reference's pprof suite
    (net/http/pprof's index page, registered when enable_profiling is on,
    server.go:1366-1383): one line per profile with where to get it."""
    gate = ("" if cfg.enable_profiling
            else "  [disabled: set enable_profiling]")
    qgate = ("" if cfg.query_window_slots > 0
             else "  [disabled: set query_window_slots]")
    lines = [
        "veneur_tpu /debug/pprof/",
        "",
        f"query           /query?name=&window_s=|slots=&q=0.5,0.99"
        f"&tags={qgate}",
        "                windowed quantiles between flushes (the live "
        "query plane)",
        f"profile         /debug/pprof/profile?seconds=N&hz=M{gate}",
        "                host CPU, folded stacks (flamegraph.pl ready)",
        "threads         /debug/threads",
        "                stack dump of every live thread (goroutine "
        "analog)",
        "vars            /debug/vars",
        "                runtime stats + per-stage data-plane counters",
        "flush_timeline  /debug/flush_timeline?last=N",
        "                structured per-flush segment records",
        "trace           /debug/trace?trace_id=HEX | ?last=N",
        "                flight-recorder span ring (per-flush "
        "distributed traces)",
        f"device          /debug/profile?seconds=N{gate}",
        "                JAX device trace (tensorboard-loadable)",
        "",
    ]
    return "\n".join(lines).encode()


# one profile at a time; concurrent requests queue here
_profile_lock = threading.Lock()


def _jax_profile(server, seconds: float) -> dict:
    """Capture a JAX profiler trace while the serving flush path runs.

    Writes a TensorBoard-loadable trace directory and, to guarantee the
    window contains the device program (flush may be seconds away on a
    long interval), drives one flush during the capture.  Returns the
    trace path for `tensorboard --logdir` / `xprof`.
    """
    import jax

    with _profile_lock:
        # Profiler defaults serialize an HLO proto for EVERY module
        # the process ever compiled plus a python-call trace of every
        # live thread — in a long-lived process the export alone can
        # take a minute.  A serving endpoint needs bounded cost: keep
        # the device/TraceMe timeline, drop the unbounded extras.
        # (_profile_lock also guards the one-active-session limit.)
        session = None
        try:
            from jax._src.lib import xla_client

            opts = xla_client.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            session = xla_client.profiler.ProfilerSession(opts)
        except Exception:   # older/newer jaxlib: default profiler
            session = None
        trace_dir = tempfile.mkdtemp(prefix="veneur-jax-trace-")
        t0 = time.perf_counter()

        def _window():
            try:
                # the flush IS the capture payload: the trace window
                # must contain one full device program
                server.flush()
            except Exception:
                logging.getLogger("veneur_tpu.http").exception(
                    "flush under profiler failed")
            remaining = seconds - (time.perf_counter() - t0)
            if remaining > 0:
                # the sleep IS the requested profiler capture window
                time.sleep(remaining)

        if session is not None:
            try:
                _window()
            finally:
                session.stop_and_export(trace_dir)
        else:
            with jax.profiler.trace(trace_dir):
                _window()
        files = sum(len(fs) for _, _, fs in os.walk(trace_dir))
        return {"trace_dir": trace_dir,
                "seconds": round(time.perf_counter() - t0, 3),
                "files": files,
                "hint": f"tensorboard --logdir {trace_dir}"}


class HttpApi:
    def __init__(self, server, address: str):
        from veneur_tpu.util import netaddr

        host, port = netaddr.split_hostport(address)

        class _Server(http.server.ThreadingHTTPServer):
            address_family = netaddr.family(host)

        self.httpd = _Server((host, port), make_handler(server))
        self.httpd.daemon_threads = True
        self.address = self.httpd.server_address
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True, name="http-api")
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
