"""Self-tracing flight recorder (ISSUE 9): deterministic sampler, ring
eviction bounds, trace-context metadata, cross-tier assembly (retry
attempts dedup to one delivered edge), /debug/trace, timeline
cross-links, and context survival across V1 chunk retries and V2 stream
resets without duplicate delivered spans.
"""

import concurrent.futures
import os
import sys
import time
import urllib.error
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import grpc  # noqa: E402
from google.protobuf import empty_pb2  # noqa: E402

from veneur_tpu import config as config_mod  # noqa: E402
from veneur_tpu import failpoints  # noqa: E402
from veneur_tpu import trace as trace_mod  # noqa: E402
from veneur_tpu.forward.client import ForwardClient, RetryPolicy  # noqa: E402
from veneur_tpu.protocol import metric_pb2  # noqa: E402
from veneur_tpu.trace import assembly  # noqa: E402
from veneur_tpu.trace import recorder as trace_rec  # noqa: E402


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------

def test_sampler_deterministic_across_instances():
    a = trace_rec.DeterministicSampler(0.3, seed=7)
    b = trace_rec.DeterministicSampler(0.3, seed=7)
    decisions = [a.sample(i) for i in range(2000)]
    assert decisions == [b.sample(i) for i in range(2000)]
    frac = sum(decisions) / len(decisions)
    assert 0.2 < frac < 0.4, frac
    # a different seed samples a different interval set
    c = trace_rec.DeterministicSampler(0.3, seed=8)
    assert decisions != [c.sample(i) for i in range(2000)]


def test_sampler_edge_rates():
    assert all(trace_rec.DeterministicSampler(1.0).sample(i)
               for i in range(100))
    assert not any(trace_rec.DeterministicSampler(0.0).sample(i)
                   for i in range(100))
    # out-of-range rates clamp instead of misbehaving
    assert trace_rec.DeterministicSampler(7.5).sample(3)
    assert not trace_rec.DeterministicSampler(-1.0).sample(3)


# ---------------------------------------------------------------------------
# ring
# ---------------------------------------------------------------------------

def _mk_span(name="s", trace_id=1, span_id=1, parent_id=0, tags=None):
    sp = trace_mod.Span(name, service="veneur_tpu",
                        tags={k: str(v) for k, v in (tags or {}).items()})
    sp.trace_id = trace_id
    sp.span_id = span_id
    sp.parent_id = parent_id
    sp.end_ns = sp.start_ns + 1_000_000
    return sp.to_proto()


def test_ring_eviction_bounds():
    rec = trace_rec.FlightRecorder(capacity=8)
    for i in range(1, 21):
        rec.ingest(_mk_span(trace_id=i, span_id=i))
    assert len(rec) == 8
    assert rec.total_recorded == 20
    ids = [r["span_id"] for r in rec.snapshot()]
    assert ids == list(range(13, 21))     # oldest evicted, newest last
    assert [r["span_id"] for r in rec.snapshot(last=3)] == [18, 19, 20]
    assert rec.trace(15)[0]["span_id"] == 15
    assert rec.trace(3) == []             # evicted


def test_ring_skips_metrics_only_spans():
    rec = trace_rec.FlightRecorder()
    import veneur_tpu.ssf as ssf_mod
    carrier = ssf_mod.SSFSpan()           # trace_id 0: report() wrapper
    rec.ingest(carrier)
    assert len(rec) == 0


# ---------------------------------------------------------------------------
# metadata propagation
# ---------------------------------------------------------------------------

def test_metadata_roundtrip_and_garbage():
    meta = trace_rec.ctx_metadata(0xabc123, 0x42)
    assert trace_rec.extract_contexts(meta) == [(0xabc123, 0x42)]
    multi = trace_rec.ctxs_metadata([(1, 2), (3, 4)])
    assert trace_rec.extract_contexts(multi) == [(1, 2), (3, 4)]
    assert trace_rec.ctxs_metadata([]) is None
    # foreign keys, malformed values, zero ids: ignored, never raised
    garbage = (("content-type", "application/grpc"),
               (trace_rec.TRACE_CTX_KEY, "nothex:zz"),
               (trace_rec.TRACE_CTX_KEY, "deadbeef"),
               (trace_rec.TRACE_CTX_KEY, "0:0"),
               (trace_rec.TRACE_CTX_KEY, "ff:ee"))
    assert trace_rec.extract_contexts(garbage) == [(0xff, 0xee)]
    assert trace_rec.extract_contexts(None) == []


def test_parse_trace_id_forms():
    assert trace_rec.parse_trace_id("123") == 123
    assert trace_rec.parse_trace_id("0xff") == 255
    assert trace_rec.parse_trace_id("deadbeef") == 0xdeadbeef
    with pytest.raises(ValueError):
        trace_rec.parse_trace_id("not-an-id")


# ---------------------------------------------------------------------------
# assembly
# ---------------------------------------------------------------------------

def _rec(name, tid, sid, parent, tier, tags=None, start_ns=0,
         dur_ms=1.0):
    return {"trace_id": tid, "span_id": sid, "parent_id": parent,
            "name": name, "service": "veneur_tpu", "start_ns": start_ns,
            "duration_ms": dur_ms, "error": False, "tier": tier,
            "tags": {k: str(v) for k, v in (tags or {}).items()}}


def _complete_trace(tid=10):
    root = _rec("flush", tid, 1, 0, "local-0",
                {"tier": "local", "interval": 1, "forward_metrics": 5,
                 "sampled": "true"}, dur_ms=10.0)
    return [
        root,
        _rec("flush.seg.snapshot", tid, 2, 1, "local-0", dur_ms=2.0),
        _rec("flush.seg.device", tid, 3, 1, "local-0", dur_ms=6.0),
        _rec("flush.forward", tid, 4, 1, "local-0", dur_ms=3.0),
        _rec("forward.attempt", tid, 5, 4, "local-0",
             {"attempt": 1}, dur_ms=2.0),
        _rec("proxy.route", tid, 6, 5, "proxy", dur_ms=1.0),
        _rec("global.import", tid, 7, 6, "global-0", dur_ms=1.0),
    ]


def test_assembly_complete_trace():
    rep = assembly.flush_report(_complete_trace())
    assert rep["complete"] and rep["orphans"] == 0
    assert rep["intervals"] == 1
    row = rep["critical_path_ms"][0]
    assert row["complete"] and row["edges"] == {"proxy": 1, "global": 1}
    assert row["segments_ms"] == {"snapshot": 2.0, "device": 6.0}
    assert row["sum_segments_ms"] == 8.0
    assert row["wall_ms"] == 10.0


def test_assembly_detects_orphans_and_missing_edges():
    spans = _complete_trace()
    spans[5]["parent_id"] = 999           # proxy span's parent missing
    rep = assembly.flush_report(spans)
    assert not rep["complete"]
    assert rep["orphans"] >= 1
    # missing import edge entirely
    spans2 = _complete_trace()[:-1]
    rep2 = assembly.flush_report(spans2)
    assert not rep2["complete"]
    assert rep2["critical_path_ms"][0]["edges"]["global"] == 0


def test_assembly_retry_attempts_dedup_to_one_delivered_edge():
    """A failed attempt stays a leaf; the delivered edge counts once
    however many attempt spans exist."""
    spans = _complete_trace()
    failed = _rec("forward.attempt", 10, 8, 4, "local-0",
                  {"attempt": 1, "failpoint": "forward.send"})
    failed["error"] = True
    spans.append(failed)
    rep = assembly.flush_report(spans)
    assert rep["complete"] and rep["orphans"] == 0
    assert rep["critical_path_ms"][0]["edges"] == {"proxy": 1,
                                                  "global": 1}


def test_assembly_unsampled_and_idle_intervals_pass():
    idle = _rec("flush", 11, 1, 0, "local-0",
                {"tier": "local", "interval": 2, "forward_metrics": 0,
                 "sampled": "true"})
    unsampled = _rec("flush", 12, 1, 0, "local-0",
                     {"tier": "local", "interval": 3,
                      "forward_metrics": 4, "sampled": "false"})
    rep = assembly.flush_report([idle, unsampled])
    assert rep["complete"] and rep["orphans"] == 0


def test_assembly_global_flush_joins_via_tag():
    spans = _complete_trace(tid=0x77)
    gflush = _rec("flush", 0x1234, 1, 0, "global-0",
                  {"tier": "global", "interval": 1,
                   "imported_traces": "77", "sampled": "true"},
                  start_ns=50_000_000, dur_ms=4.0)
    rep = assembly.flush_report(spans + [gflush])
    assert rep["intervals"] == 1          # global roots are not rows
    row = rep["critical_path_ms"][0]
    # joined global flush extends the distributed critical path
    assert row["critical_path_ms"] >= 54.0


# ---------------------------------------------------------------------------
# server: flush trace + timeline cross-link + /debug/trace
# ---------------------------------------------------------------------------

def _wait(pred, timeout_s=5.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


@pytest.fixture
def traced_server():
    servers = []

    def boot(**kw):
        cfg = config_mod.Config(interval=10.0, percentiles=[0.5],
                                hostname="trace-test", **kw)
        srv = __import__("veneur_tpu.core.server",
                         fromlist=["Server"]).Server(cfg)
        srv.start()
        servers.append(srv)
        return srv

    yield boot
    for srv in servers:
        srv.shutdown()


def test_flush_trace_recorded_and_timeline_linked(traced_server):
    srv = traced_server()
    srv.process_packet_buffer(b"t.count:3|c\nt.h:12|h")
    srv.flush()
    rec = srv.flight_recorder
    assert _wait(lambda: any(r["name"] == "flush"
                             for r in rec.snapshot()))
    srv.egress.settle(timeout_s=5.0)    # the lanes' spans are in too
    spans = rec.snapshot()
    roots = [r for r in spans if r["name"] == "flush"]
    assert len(roots) == 1
    root = roots[0]
    assert root["tags"]["tier"] == "local" if srv.is_local else "global"
    assert root["tags"]["sampled"] == "true"
    assert root["tags"]["interval"] == "1"
    segs = [r for r in spans if r["name"].startswith("flush.seg.")]
    assert segs, spans
    # segment children hang off the root; their parts (the snapshot's,
    # the egress lane's under flush.sink.<name>) one level below it, and
    # the parts of two of those (the snapshot's columns, the lane's sink
    # call) one level further down, named after their parent
    by_id = {r["span_id"]: r for r in spans}
    direct = [s for s in segs if s["parent_id"] == root["span_id"]]
    deep = ("flush.seg.snapshot.columns.", "flush.seg.lane.sink.")
    for s in segs:
        if s not in direct:
            parent = by_id[s["parent_id"]]
            assert s["name"].startswith(
                ("flush.seg.snapshot.", "flush.seg.lane.")), s
            if s["name"].startswith(deep):
                assert s["name"].rsplit(".", 1)[0] == parent["name"], s
                parent = by_id[parent["parent_id"]]
            assert parent["parent_id"] == root["span_id"], s
    assert [s for s in segs if s["name"].startswith(deep[0])]
    assert {"snapshot", "emit", "fanout"} <= {
        s["name"].split(".")[-1] for s in direct}
    # the timeline row cross-links to the exact trace/span
    row = srv.flush_timeline.snapshot()[-1]
    assert row["trace_id"] == f"{root['trace_id']:x}"
    assert row["span_id"] == f"{root['span_id']:x}"


def test_unsampled_interval_has_root_but_no_children(traced_server):
    srv = traced_server(trace_flush_sample_rate=0.0)
    srv.process_packet_buffer(b"t.count:3|c")
    srv.flush()
    rec = srv.flight_recorder
    assert _wait(lambda: any(r["name"] == "flush"
                             for r in rec.snapshot()))
    spans = rec.snapshot()
    root = [r for r in spans if r["name"] == "flush"][0]
    assert root["tags"]["sampled"] == "false"
    assert not [r for r in spans if r["name"].startswith("flush.seg.")]


def test_tracing_disabled_still_records_root(traced_server):
    srv = traced_server(trace_flush_enabled=False)
    srv.flush()
    rec = srv.flight_recorder
    assert _wait(lambda: any(r["name"] == "flush"
                             for r in rec.snapshot()))
    root = [r for r in rec.snapshot() if r["name"] == "flush"][0]
    assert root["tags"]["sampled"] == "false"


def test_debug_trace_endpoint(traced_server):
    import json

    from veneur_tpu import http_api

    srv = traced_server()
    srv.process_packet_buffer(b"t.count:1|c")
    srv.flush()
    assert _wait(lambda: any(r["name"] == "flush"
                             for r in srv.flight_recorder.snapshot()))
    api = http_api.HttpApi(srv, "127.0.0.1:0")
    api.start()
    host, port = api.address
    base = f"http://{host}:{port}"
    try:
        body = json.loads(urllib.request.urlopen(
            base + "/debug/trace").read())
        assert body["capacity"] == srv.config.trace_ring_capacity
        assert body["recorded_total"] >= 1
        names = {s["name"] for s in body["spans"]}
        assert "flush" in names
        root = [s for s in body["spans"] if s["name"] == "flush"][0]
        one = json.loads(urllib.request.urlopen(
            base + f"/debug/trace?trace_id={root['trace_id']:x}").read())
        assert all(s["trace_id"] == root["trace_id"]
                   for s in one["spans"])
        assert one["spans"]
        last = json.loads(urllib.request.urlopen(
            base + "/debug/trace?last=1").read())
        assert len(last["spans"]) == 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/debug/trace?last=bogus")
        assert ei.value.code == 400
        # /debug/vars carries the ring's monotonic counter
        dbg = json.loads(urllib.request.urlopen(
            base + "/debug/vars").read())
        assert dbg["trace_recorded"] >= 1
    finally:
        api.stop()


# ---------------------------------------------------------------------------
# forward client: context survives retries / stream resets
# ---------------------------------------------------------------------------

class _StubGlobal:
    """Minimal Forward service capturing per-RPC metadata; V1 optional
    (UNIMPLEMENTED when off — the reference-global shape that forces
    the client onto V2 streams)."""

    def __init__(self, v1=True):
        self.v1 = v1
        self.v1_calls = []      # (n_metrics, ctxs)
        self.v2_calls = []

        def send_metrics(request, context):
            if not self.v1:
                context.abort(grpc.StatusCode.UNIMPLEMENTED, "no V1")
            from veneur_tpu.protocol import forward_pb2
            ml = forward_pb2.MetricList.FromString(bytes(request))
            self.v1_calls.append((len(ml.metrics),
                                  trace_rec.extract_contexts(
                                      context.invocation_metadata())))
            return empty_pb2.Empty()

        def send_metrics_v2(request_iterator, context):
            n = sum(1 for _ in request_iterator)
            self.v2_calls.append((n, trace_rec.extract_contexts(
                context.invocation_metadata())))
            return empty_pb2.Empty()

        handler = grpc.method_handlers_generic_handler(
            "forwardrpc.Forward", {
                "SendMetrics": grpc.unary_unary_rpc_method_handler(
                    send_metrics,
                    request_deserializer=lambda b: b,
                    response_serializer=(
                        empty_pb2.Empty.SerializeToString)),
                "SendMetricsV2": grpc.stream_unary_rpc_method_handler(
                    send_metrics_v2,
                    request_deserializer=metric_pb2.Metric.FromString,
                    response_serializer=(
                        empty_pb2.Empty.SerializeToString)),
            })
        self.server = grpc.server(
            concurrent.futures.ThreadPoolExecutor(max_workers=4))
        self.server.add_generic_rpc_handlers([handler])
        self.port = self.server.add_insecure_port("127.0.0.1:0")
        self.server.start()

    def stop(self):
        self.server.stop(grace=0.2)


def _attempt_spans(rec):
    return [r for r in rec.snapshot() if r["name"] == "forward.attempt"]


def test_v1_chunk_retry_context_survives():
    """A dropped first attempt retries under a NEW attempt span; the
    single delivered RPC carries the delivering attempt's context —
    no duplicate delivery, no stale context."""
    stub = _StubGlobal(v1=True)
    rec = trace_rec.FlightRecorder()
    fwd = ForwardClient(f"127.0.0.1:{stub.port}",
                        retry=RetryPolicy(attempts=3,
                                          backoff_base_s=0.01))
    try:
        parent = trace_mod.Span("flush.forward", client=rec)
        pbs = [metric_pb2.Metric(name=f"m{i}") for i in range(5)]
        with failpoints.active("forward.send", "drop", times=1):
            fwd.send_pbs(pbs, trace_parent=parent)
        parent.finish()
        assert len(stub.v1_calls) == 1          # delivered exactly once
        n, ctxs = stub.v1_calls[0]
        assert n == 5 and len(ctxs) == 1
        attempts = _attempt_spans(rec)
        assert len(attempts) == 2
        failed = [a for a in attempts if a["error"]]
        ok = [a for a in attempts if not a["error"]]
        assert len(failed) == 1 and len(ok) == 1
        assert failed[0]["tags"]["failpoint"] == "forward.send"
        # the delivered RPC's context is the SUCCESSFUL attempt's span
        assert ctxs[0] == (parent.trace_id, ok[0]["span_id"])
        assert fwd.stats()["retries"] == 1
    finally:
        fwd.close()
        stub.stop()


def test_v2_stream_reset_context_survives_no_duplicates():
    stub = _StubGlobal(v1=False)
    rec = trace_rec.FlightRecorder()
    fwd = ForwardClient(f"127.0.0.1:{stub.port}",
                        retry=RetryPolicy(attempts=3,
                                          backoff_base_s=0.01))
    try:
        parent = trace_mod.Span("flush.forward", client=rec)
        pbs = [metric_pb2.Metric(name=f"m{i}") for i in range(6)]
        with failpoints.active("forward.v2_stream", "stream-reset",
                               times=1):
            fwd.send_pbs(pbs, trace_parent=parent)
        parent.finish()
        assert len(stub.v2_calls) == 1          # delivered exactly once
        n, ctxs = stub.v2_calls[0]
        assert n == 6 and len(ctxs) == 1
        attempts = _attempt_spans(rec)
        ok = [a for a in attempts if not a["error"]]
        assert len(attempts) == 2 and len(ok) == 1
        assert ctxs[0] == (parent.trace_id, ok[0]["span_id"])
    finally:
        fwd.close()
        stub.stop()


# ---------------------------------------------------------------------------
# end-to-end: traced chaos cell (forward retry across the real 3 tiers)
# ---------------------------------------------------------------------------

def test_traced_forward_retry_chaos_cell():
    """The acceptance contract's fast cell: a forward-drop arm with
    retries must still assemble one complete 3-tier trace per interval
    — duplicate attempts dedup to one delivered edge, zero orphans."""
    from veneur_tpu.testbed.chaos import arm_by_name, run_chaos_arm

    row = run_chaos_arm(arm_by_name("forward-drop"), seed=0, trace=True)
    assert row["ok"], row
    assert row["fired"] > 0 and row["forward_retries"] > 0
    assert row["trace_complete"] and row["trace_orphans"] == 0
    assert row["trace_intervals"] >= 2


def test_direct_local_to_global_forward_trace():
    """Proxyless topology (locals forward straight to a global): the
    attempt context rides the forward RPC itself, so the global's
    import span parents directly to the delivering attempt — driven
    over REAL loopback gRPC with real UDP ingest on the local."""
    import socket

    from veneur_tpu.core.server import Server

    glob = Server(config_mod.Config(grpc_address="127.0.0.1:0",
                                    interval=10.0, percentiles=[0.5],
                                    hostname="g0"))
    glob.start()
    loc = Server(config_mod.Config(
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        forward_address=f"127.0.0.1:{glob.grpc_import.port}",
        interval=10.0, percentiles=[0.5], hostname="l0"))
    loc.start()
    try:
        _, addr = loc.statsd_addrs[0]
        tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        tx.sendto(b"d.lat:12|h\nd.lat:30|h", addr)
        tx.close()
        assert _wait(lambda: (loc._drain_native() or True)
                     and loc.aggregator.digests.staged_count() >= 2
                     or loc.aggregator.processed >= 2)
        loc.flush()
        # the forward is async (flush pool) and both rings fill through
        # their span pipelines: wait for the import span on the GLOBAL
        # and the root flush span on the LOCAL
        assert _wait(lambda: any(
            r["name"] == "global.import"
            for r in glob.flight_recorder.snapshot())), \
            glob.flight_recorder.snapshot()
        assert _wait(lambda: any(
            r["name"] == "flush" and r["tags"].get("forward_metrics",
                                                   "0") != "0"
            for r in loc.flight_recorder.snapshot())), \
            loc.flight_recorder.snapshot()
        spans = ([dict(r, tier="local-0")
                  for r in loc.flight_recorder.snapshot()]
                 + [dict(r, tier="global-0")
                    for r in glob.flight_recorder.snapshot()])
        imp = [s for s in spans if s["name"] == "global.import"][0]
        attempts = [s for s in spans if s["name"] == "forward.attempt"]
        assert imp["parent_id"] in {a["span_id"] for a in attempts}
        rep = assembly.flush_report(spans)
        row = [r for r in rep["critical_path_ms"]
               if r["forwarded"] > 0][0]
        # delivered straight to the global: the import edge registers
        # even without a proxy hop (3-tier completeness still demands
        # one, correctly reported absent here)
        assert row["edges"]["global"] == 1
        assert row["edges"]["proxy"] == 0
        assert row["orphans"] == 0
    finally:
        loc.shutdown()
        glob.shutdown()
