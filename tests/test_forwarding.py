"""Local -> global forwarding over real loopback gRPC, porting the
reference's distributed fixture tests (`server_test.go:312-414`
TestLocalServerMixedMetrics, `flusher_test.go:100-299` TestServerFlushGRPC
family) without a real cluster."""

import queue
import socket
import time

import grpc
import numpy as np
import pytest
from google.protobuf import empty_pb2

from veneur_tpu import config as config_mod
from veneur_tpu.core.server import Server
from veneur_tpu.forward import convert
from veneur_tpu.forward.client import ForwardClient
from veneur_tpu.protocol import forward_pb2, metric_pb2, tdigest_pb2
from veneur_tpu.samplers import samplers as sm
from veneur_tpu.samplers.metric_key import MetricScope
from veneur_tpu.sinks import simple as simple_sinks
# protobuf wire encoders the cortex sink has: one length-delimited
# field, one varint field
from veneur_tpu.sinks.cortex import _tag_field as _ld
from veneur_tpu.sinks.cortex import _varint_field


def boot_global(**kw):
    cfg = config_mod.Config(
        grpc_address="127.0.0.1:0", interval=0.05,
        percentiles=[0.5, 0.9], aggregates=["min", "max", "count"],
        hostname="global", **kw)
    sink = simple_sinks.ChannelMetricSink()
    srv = Server(cfg, extra_metric_sinks=[sink])
    srv.start()
    return srv, sink


def boot_local(forward_addr: str, **kw):
    cfg = config_mod.Config(
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        forward_address=forward_addr, interval=0.05,
        percentiles=[0.5, 0.9], aggregates=["min", "max", "count"],
        hostname="local", **kw)
    sink = simple_sinks.ChannelMetricSink()
    srv = Server(cfg, extra_metric_sinks=[sink])
    srv.start()
    return srv, sink


def flush_and_collect(srv, sink, pred, tries=150):
    for _ in range(tries):
        srv.flush()
        got = []
        while not sink.queue.empty():
            got.extend(sink.queue.get())
        if pred(got):
            return got
        time.sleep(0.05)
    raise AssertionError("timed out waiting for flushed metrics")


def test_local_server_mixed_metrics():
    """Feed histogram samples to a local instance over UDP; assert the
    digest received by the global (via real gRPC) reproduces
    min/max/count/quantiles (server_test.go:312-414)."""
    glob, gsink = boot_global()
    local, lsink = boot_local(f"127.0.0.1:{glob.grpc_import.port}")
    try:
        rng = np.random.default_rng(4)
        data = rng.normal(100, 20, 5000)
        _, addr = local.statsd_addrs[0]
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for chunk in data.reshape(100, 50):
            lines = "\n".join(f"lat:{v:.4f}|h|#svc:x" for v in chunk)
            s.sendto(lines.encode(), addr)
        s.close()
        deadline = time.time() + 5
        while (local.aggregator.processed < 5000
               and time.time() < deadline):
            time.sleep(0.05)
        assert local.aggregator.processed == 5000

        local.flush()  # forwards the digest over gRPC
        got = flush_and_collect(
            glob, gsink, lambda g: any("percentile" in m.name for m in g))
        by = {m.name: m for m in got}
        assert by["lat.50percentile"].value == pytest.approx(
            np.quantile(data, 0.5), rel=0.02)
        assert by["lat.90percentile"].value == pytest.approx(
            np.quantile(data, 0.9), rel=0.02)
        assert by["lat.50percentile"].tags == ["svc:x"]

        # local side emitted aggregates, no percentiles (egress is
        # async: settle the local's lanes before reading its sink)
        local.egress.settle(timeout_s=10.0)
        lgot = []
        while not lsink.queue.empty():
            lgot.extend(lsink.queue.get())
        lby = {m.name: m for m in lgot}
        assert lby["lat.count"].value == 5000
        assert lby["lat.min"].value == pytest.approx(data.min(), rel=1e-3)
        assert lby["lat.max"].value == pytest.approx(data.max(), rel=1e-3)
        assert not any("percentile" in n for n in lby)
    finally:
        local.shutdown()
        glob.shutdown()


def test_global_counters_gauges_sets_over_grpc():
    glob, gsink = boot_global()
    locals_ = []
    try:
        for i in range(3):
            local, _ = boot_local(f"127.0.0.1:{glob.grpc_import.port}")
            locals_.append(local)
            _, addr = local.statsd_addrs[0]
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.sendto(b"reqs:10|c|#veneurglobalonly", addr)
            s.sendto(f"users:u{i}|s".encode(), addr)
            s.sendto(b"users:ushared|s", addr)
            s.close()
        deadline = time.time() + 5
        while any(l.aggregator.processed < 3 for l in locals_) \
                and time.time() < deadline:
            time.sleep(0.05)
        for l in locals_:
            l.flush()
        # flush() no longer waits for its forward future (the old
        # fan-out wait covered it); block until every local's forward
        # slot is released so the global sees all three imports
        deadline = time.time() + 10
        while time.time() < deadline and any(
                l._forward_slots._value < l.FORWARD_MAX_IN_FLIGHT
                for l in locals_):
            time.sleep(0.02)
        got = flush_and_collect(
            glob, gsink,
            lambda g: any(m.name == "reqs" for m in g)
            and any(m.name == "users" for m in g))
        by = {m.name: m for m in got}
        assert by["reqs"].value == 30.0  # 3 x 10, merged by addition
        assert by["users"].value == 4.0  # u0,u1,u2,ushared
    finally:
        for l in locals_:
            l.shutdown()
        glob.shutdown()


def test_v1_send_metrics_batch_import():
    """V1 MetricList is the fleet-internal batch fast path: our global
    imports it (python-grpc V2 streams cap at ~20k msgs/s); the
    reference leaves V1 unimplemented, and the client/proxy probe +
    fall back to V2 against such globals (see
    test_forward_client_v2_fallback_on_unimplemented)."""
    glob, sink = boot_global()
    try:
        client = ForwardClient(f"127.0.0.1:{glob.grpc_import.port}")
        client.send_v1([sm.ForwardMetric(
            name="x", tags=[], kind="counter",
            scope=MetricScope.GLOBAL_ONLY, counter_value=7)])
        got = flush_and_collect(
            glob, sink, lambda ms: any(m.name == "x" for m in ms))
        assert {m.name: m.value for m in got}["x"] == 7.0
        client.close()
    finally:
        glob.shutdown()


def test_forward_client_v2_fallback_on_unimplemented():
    """Against a reference-shaped global (V1 UNIMPLEMENTED), send()
    probes once, falls back to the V2 stream, and delivers every
    metric; later sends skip the probe."""
    from concurrent import futures as cf

    from google.protobuf import empty_pb2
    from veneur_tpu.protocol import forward_pb2, metric_pb2

    got = []

    def v1(request, context):
        context.abort(grpc.StatusCode.UNIMPLEMENTED, "no V1 here")

    def v2(request_iterator, context):
        for pb in request_iterator:
            got.append(pb.name)
        return empty_pb2.Empty()

    handlers = grpc.method_handlers_generic_handler(
        "forwardrpc.Forward", {
            "SendMetrics": grpc.unary_unary_rpc_method_handler(
                v1, request_deserializer=forward_pb2.MetricList.FromString,
                response_serializer=empty_pb2.Empty.SerializeToString),
            "SendMetricsV2": grpc.stream_unary_rpc_method_handler(
                v2, request_deserializer=metric_pb2.Metric.FromString,
                response_serializer=empty_pb2.Empty.SerializeToString)})
    server = grpc.server(cf.ThreadPoolExecutor(max_workers=4))
    server.add_generic_rpc_handlers((handlers,))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    try:
        client = ForwardClient(f"127.0.0.1:{port}")
        fms = [sm.ForwardMetric(name=f"f{i}", tags=[], kind="counter",
                                scope=MetricScope.GLOBAL_ONLY,
                                counter_value=1) for i in range(10)]
        client.send(fms)
        assert client._use_v1 is False
        assert sorted(got) == sorted(f"f{i}" for i in range(10))
        client.send(fms)           # second send: straight to V2
        assert len(got) == 20
        client.close()
    finally:
        server.stop(0)


def test_forward_client_mixed_lb_later_chunk_unimplemented():
    """A mixed-version load balancer can route the first V1 chunk to one
    of our globals and a later chunk to a reference backend
    (UNIMPLEMENTED).  The failed chunks — and only those — must be
    re-sent over V2 in the same flush, and the client must stop using V1
    afterwards (ADVICE r4, forward/client.py)."""
    from concurrent import futures as cf

    from veneur_tpu.forward import client as client_mod

    import threading

    v1_batches = []
    v2_names = []
    v1_calls = [0]
    v1_lock = threading.Lock()   # handlers run on concurrent threads

    def v1(request, context):
        with v1_lock:
            v1_calls[0] += 1
            mine = v1_calls[0]
        if mine > 1:
            context.abort(grpc.StatusCode.UNIMPLEMENTED,
                          "reference backend: no V1")
        v1_batches.append([m.name for m in request.metrics])
        return empty_pb2.Empty()

    def v2(request_iterator, context):
        for pb in request_iterator:
            v2_names.append(pb.name)
        return empty_pb2.Empty()

    handlers = grpc.method_handlers_generic_handler(
        "forwardrpc.Forward", {
            "SendMetrics": grpc.unary_unary_rpc_method_handler(
                v1, request_deserializer=forward_pb2.MetricList.FromString,
                response_serializer=empty_pb2.Empty.SerializeToString),
            "SendMetricsV2": grpc.stream_unary_rpc_method_handler(
                v2, request_deserializer=metric_pb2.Metric.FromString,
                response_serializer=empty_pb2.Empty.SerializeToString)})
    server = grpc.server(cf.ThreadPoolExecutor(max_workers=4))
    server.add_generic_rpc_handlers((handlers,))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    try:
        n = client_mod.BATCH_MAX * 2 + 10   # 3 chunks
        client = ForwardClient(f"127.0.0.1:{port}")
        fms = [sm.ForwardMetric(name=f"f{i}", tags=[], kind="counter",
                                scope=MetricScope.GLOBAL_ONLY,
                                counter_value=1) for i in range(n)]
        client.send(fms)
        # chunk 0 landed over V1; chunks 1-2 were re-sent over V2, each
        # metric delivered exactly once
        assert len(v1_batches) == 1
        delivered = sorted(v1_batches[0] + v2_names)
        assert delivered == sorted(f"f{i}" for i in range(n))
        # the mixed path is now avoided entirely
        assert client._use_v1 is False
        client.send(fms[:5])
        assert v1_calls[0] == 3   # the two aborted probes, nothing new
        client.close()
    finally:
        server.stop(0)


def test_import_bad_metric_does_not_kill_stream():
    """A nil-valued metric mid-stream is logged and skipped; the rest of
    the stream is still imported (worker.go:451-456 error handling)."""
    glob, gsink = boot_global()
    try:
        client = ForwardClient(f"127.0.0.1:{glob.grpc_import.port}")
        good = convert.to_pb(sm.ForwardMetric(
            name="ok", tags=[], kind="counter",
            scope=MetricScope.GLOBAL_ONLY, counter_value=5))
        bad = metric_pb2.Metric(name="nil", type=metric_pb2.Counter)
        client._v2(iter([bad, good]), timeout=5)
        got = flush_and_collect(
            glob, gsink, lambda g: any(m.name == "ok" for m in g))
        assert {m.name for m in got} == {"ok"}
        client.close()
    finally:
        glob.shutdown()


def test_wire_compat_fixture():
    """Serialized metricpb.Metric bytes use the reference's field layout:
    craft a digest metric, round-trip via raw bytes, and check the known
    field numbers survive re-parse with a minimal hand-rolled decoder."""
    fm = sm.ForwardMetric(
        name="h", tags=["a:b"], kind="histogram",
        scope=MetricScope.MIXED,
        digest_means=[1.0, 2.0], digest_weights=[3.0, 4.0],
        digest_min=1.0, digest_max=2.0, digest_rsum=1.5,
        digest_compression=100.0)
    data = convert.to_pb(fm).SerializeToString()
    m = metric_pb2.Metric.FromString(data)
    back = convert.from_pb(m)
    assert back.digest_means == [1.0, 2.0]
    assert back.digest_weights == [3.0, 4.0]
    assert back.digest_rsum == 1.5
    assert back.kind == "histogram"
    # field 1 is the name, wire type 2 (length-delimited): tag byte 0x0A
    assert data[0] == 0x0A


def test_forward_survives_global_restart():
    """Elasticity (§5.3): the local's persistent forward channel rides out
    a global-tier restart — failed interval is dropped with accounting
    (UDP-heritage loss model), then forwarding resumes on the same
    address without restarting the local."""
    import queue
    import socket as socket_mod
    import time

    from veneur_tpu import config as config_mod
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks import simple as simple_sinks

    def boot_global(port=0):
        sink = simple_sinks.ChannelMetricSink()
        srv = Server(config_mod.Config(
            grpc_address=f"127.0.0.1:{port}", interval=0.05,
            percentiles=[0.5], hostname="g"),
            extra_metric_sinks=[sink])
        srv.start()
        return srv, sink

    g1, s1 = boot_global()
    port = g1.grpc_import.port
    local = Server(config_mod.Config(
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        forward_address=f"127.0.0.1:{port}", interval=0.05,
        forward_timeout=2.0, hostname="l"))
    local.start()
    try:
        _, addr = local.statsd_addrs[0]
        tx = socket_mod.socket(socket_mod.AF_INET, socket_mod.SOCK_DGRAM)

        def send_and_flush(name):
            tx.sendto(b"%s:1|c|#veneurglobalonly" % name, addr)
            deadline = time.time() + 5
            base = local.aggregator.processed
            while time.time() < deadline:
                local._drain_native()
                if local.aggregator.processed > base:
                    break
                time.sleep(0.02)
            local.flush()

        def wait_for(srv, sink, name, timeout=10):
            deadline = time.time() + timeout
            while time.time() < deadline:
                srv.flush()
                try:
                    for m in sink.queue.get(timeout=0.2):
                        if m.name == name.decode():
                            return True
                except queue.Empty:
                    pass
            return False

        send_and_flush(b"fw.phase1")
        assert wait_for(g1, s1, b"fw.phase1")

        g1.shutdown()
        send_and_flush(b"fw.lost")    # global down: dropped, not fatal
        time.sleep(1.0)               # let the in-flight forward fail

        g2, s2 = boot_global(port)    # same address, fresh global
        try:
            # the local's channel reconnects; retry a few intervals (gRPC
            # backoff may delay the first successful stream)
            ok = False
            for i in range(15):
                send_and_flush(b"fw.phase2")
                if wait_for(g2, s2, b"fw.phase2", timeout=2):
                    ok = True
                    break
            assert ok, "forwarding did not recover after global restart"
        finally:
            g2.shutdown()
        tx.close()
    finally:
        local.shutdown()


def test_native_import_scan_matches_pb_path():
    """aggregator.import_payload (native wire scan) must produce the
    same aggregate state as import_pb_batch (protobuf path) across all
    four families, and must count nil-valued metrics as failures."""
    import numpy as np

    import veneur_tpu.ingest as ingest_mod
    from veneur_tpu.core.aggregator import MetricAggregator
    from veneur_tpu.protocol import tdigest_pb2
    from veneur_tpu.sketches import hll as hll_mod

    ingest_mod.load_library()   # loud if the engine can't build

    def mk_metrics():
        out = []
        for i in range(40):
            out.append(metric_pb2.Metric(
                name=f"c{i % 7}", type=metric_pb2.Counter,
                tags=[f"env:prod", f"i:{i % 3}"],
                counter=metric_pb2.CounterValue(value=i + 1)))
            out.append(metric_pb2.Metric(
                name=f"g{i % 5}", type=metric_pb2.Gauge,
                tags=["zone:a"],
                gauge=metric_pb2.GaugeValue(value=float(i))))
        sk = hll_mod.HLLSketch()
        for i in range(100):
            sk.insert(b"m%d" % i)
        out.append(metric_pb2.Metric(
            name="users", type=metric_pb2.Set, tags=[],
            set=metric_pb2.SetValue(hyper_log_log=sk.marshal())))
        td = tdigest_pb2.MergingDigestData(
            main_centroids=[
                tdigest_pb2.Centroid(mean=float(v), weight=1.0)
                for v in range(32)],
            compression=100.0, min=0.0, max=31.0, reciprocalSum=1.0)
        out.append(metric_pb2.Metric(
            name="lat", type=metric_pb2.Histogram,
            scope=metric_pb2.Global, tags=["svc:x"],
            histogram=metric_pb2.HistogramValue(t_digest=td)))
        out.append(metric_pb2.Metric(name="nil",
                                     type=metric_pb2.Counter))
        return out

    results = []
    for use_native in (True, False):
        agg = MetricAggregator(percentiles=[0.5, 0.9])
        ms = mk_metrics()
        payload = forward_pb2.MetricList(
            metrics=ms).SerializeToString()
        if use_native:
            ok, failed = agg.import_payload(payload)
        else:
            ok, failed = agg.import_pb_batch(ms)
        assert ok == len(ms) - 1 and failed == 1, (use_native, ok,
                                                   failed)
        res = agg.flush(is_local=False)
        results.append(sorted(
            (m.name, tuple(m.tags), round(m.value, 6))
            for m in res.metrics))
    assert results[0] == results[1]


def test_import_rejects_type_value_oneof_mismatch():
    """A wire-legal Metric whose `type` field contradicts its value
    oneof (e.g. type=Timer carrying a CounterValue) must be REJECTED —
    counted in `failed`, landed in NO family — identically on the
    protobuf batch path and the native wire-scan path.  The legacy
    per-metric convert.from_pb path trusted `type` and would have
    mis-filed the payload (a counter value merged into a digest row);
    the batch paths make the mismatch loud and contractual
    (aggregator._ONEOF_LEGAL_TYPES)."""
    import veneur_tpu.ingest as ingest_mod
    from veneur_tpu.core.aggregator import MetricAggregator
    from veneur_tpu.protocol import tdigest_pb2

    ingest_mod.load_library()   # loud if the engine can't build

    def td():
        return tdigest_pb2.MergingDigestData(
            main_centroids=[tdigest_pb2.Centroid(mean=1.0, weight=1.0)],
            compression=100.0, min=1.0, max=1.0, reciprocalSum=1.0)

    def mk():
        good = [
            metric_pb2.Metric(name="okc", type=metric_pb2.Counter,
                              counter=metric_pb2.CounterValue(value=5)),
            metric_pb2.Metric(name="okg", type=metric_pb2.Gauge,
                              gauge=metric_pb2.GaugeValue(value=2.5)),
            # Timer carrying a HistogramValue is LEGAL (both digest
            # kinds share the oneof field)
            metric_pb2.Metric(name="okt", type=metric_pb2.Timer,
                              histogram=metric_pb2.HistogramValue(
                                  t_digest=td())),
        ]
        bad = [
            # counter payload claiming to be a timer
            metric_pb2.Metric(name="t.as.c", type=metric_pb2.Timer,
                              counter=metric_pb2.CounterValue(value=9)),
            # gauge payload claiming to be a set
            metric_pb2.Metric(name="s.as.g", type=metric_pb2.Set,
                              gauge=metric_pb2.GaugeValue(value=7.0)),
            # histogram payload claiming to be a counter
            metric_pb2.Metric(name="c.as.h", type=metric_pb2.Counter,
                              histogram=metric_pb2.HistogramValue(
                                  t_digest=td())),
        ]
        return good, bad

    for use_native in (True, False):
        agg = MetricAggregator(percentiles=[0.5])
        good, bad = mk()
        ms = good + bad
        if use_native:
            payload = forward_pb2.MetricList(
                metrics=ms).SerializeToString()
            ok, failed = agg.import_payload(payload)
        else:
            ok, failed = agg.import_pb_batch(ms)
        assert (ok, failed) == (len(good), len(bad)), (use_native, ok,
                                                       failed)
        res = agg.flush(is_local=False)
        names = {m.name for m in res.metrics}
        for want in ("okc", "okg", "okt"):
            assert any(n.startswith(want) for n in names), (use_native,
                                                            want, names)
        for reject in ("t.as.c", "s.as.g", "c.as.h"):
            assert not any(n.startswith(reject) for n in names), (
                use_native, reject, names)


def test_import_row_cache_survives_flush_and_gc_cycles():
    """The V1 import identity->row cache must never serve a stale row:
    it outlives a flush that recycled no row (PR 41), the cut at which
    end_interval's GC frees rows clears it, and re-imports after GC
    re-register cleanly with correct totals."""
    from veneur_tpu.core import arena as arena_mod
    from veneur_tpu.core.aggregator import MetricAggregator

    agg = MetricAggregator(percentiles=[0.5])
    pbs_a = [metric_pb2.Metric(
        name="a", type=metric_pb2.Counter, tags=["t:1"],
        counter=metric_pb2.CounterValue(value=2)) for _ in range(5)]
    pbs_b = [metric_pb2.Metric(
        name="b", type=metric_pb2.Counter, tags=["t:2"],
        counter=metric_pb2.CounterValue(value=3)) for _ in range(4)]

    def flush_values():
        res = agg.flush(is_local=False)
        return {m.name: m.value for m in res.metrics}

    pay = forward_pb2.MetricList(
        metrics=pbs_a + pbs_b).SerializeToString()
    agg.import_payload(pay)
    assert agg._import_row_cache          # populated
    by = flush_values()
    assert by["a"] == 10.0 and by["b"] == 12.0
    assert len(agg._import_row_cache) == 2    # no row recycled: kept

    # idle 'a' and 'b' long enough for the arena GC to free their rows,
    # interleaving other keys so rows get recycled
    for i in range(arena_mod.IDLE_GC_INTERVALS + 1):
        filler = forward_pb2.MetricList(metrics=[metric_pb2.Metric(
            name=f"f{i}", type=metric_pb2.Counter,
            counter=metric_pb2.CounterValue(value=1))]
        ).SerializeToString()
        agg.import_payload(filler)
        flush_values()
    # 'a' and 'b' (and the first filler) were freed, and every cut that
    # freed a row cleared the cache: what it holds was resolved since
    assert agg.counters.recycled >= 3
    assert len(agg._import_row_cache) <= 1

    # re-import the original identities: fresh rows, exact totals
    agg.import_payload(pay)
    agg.import_payload(pay)
    by = flush_values()
    assert by["a"] == 20.0 and by["b"] == 24.0


# ---------------------------------------------------------------------------
# columnar digest import: import_payload (the native scan decodes every
# plain t-digest and the payload stages as arrays) against
# import_pb_batch (protobuf objects, merge_digest per record), which is
# the plain reference
# ---------------------------------------------------------------------------

def _wire_list(records) -> bytes:
    """A MetricList from Metric messages or raw Metric bytes."""
    return b"".join(
        _ld(1, r if isinstance(r, bytes) else r.SerializeToString())
        for r in records)


def _wire_records(payload: bytes) -> list:
    """The Metric submessages of a MetricList, as bytes."""
    out, p = [], 0
    while p < len(payload):
        assert payload[p] == 0x0A
        p += 1
        n = shift = 0
        while True:
            b = payload[p]
            p += 1
            n |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        out.append(payload[p:p + n])
        p += n
    return out


def _td(means, weights=None, compression=100.0, **scalars):
    weights = [1.0] * len(means) if weights is None else weights
    return tdigest_pb2.MergingDigestData(
        main_centroids=[tdigest_pb2.Centroid(mean=float(m), weight=float(w))
                        for m, w in zip(means, weights)],
        compression=compression, **scalars)


def _histo(name, td, type=metric_pb2.Histogram, scope=metric_pb2.Mixed,
           tags=("svc:x", "az:b")):
    return metric_pb2.Metric(
        name=name, type=type, scope=scope, tags=list(tags),
        histogram=metric_pb2.HistogramValue(t_digest=td))


def _seeded_digests(seed, n_keys=12, weighted=True, cents=8):
    rng = np.random.default_rng(seed)
    out = []
    for k in range(n_keys):
        means = np.sort(rng.gamma(2.0, 10.0, cents))
        weights = (rng.integers(1, 9, cents).astype(float) if weighted
                   else np.ones(cents))
        out.append(_histo(
            f"lat.{k}", _td(means, weights, min=float(means[0]) - 0.5,
                            max=float(means[-1]) + 0.5,
                            reciprocalSum=float((weights / means).sum())),
            tags=[f"k:{k % 5}", "env:prod"]))
    return out


def _case_singletons():
    return [_wire_list(_seeded_digests(1, weighted=False))]


def _case_weighted():
    # a NaN min off the wire must not stick, as in merge_digest
    extra = _histo("lat.0", _td([3.0, 4.0], [2.0, 2.0], min=float("nan"),
                                max=9.0, reciprocalSum=1.25),
                   tags=["k:0", "env:prod"])
    return [_wire_list(_seeded_digests(2) + [extra])]


def _case_zero_centroids():
    return [_wire_list([
        _histo("empty", _td([], min=0.0, max=0.0)),
        _histo("some", _td([1.0, 2.0], [3.0, 1.0], min=0.5, max=2.5,
                           reciprocalSum=3.5)),
        # no t_digest at all, and no HistogramValue body
        metric_pb2.Metric(name="bare", type=metric_pb2.Timer,
                          histogram=metric_pb2.HistogramValue()),
        _histo("empty", _td([], min=-1.0, max=4.0, reciprocalSum=2.0))])]


def _case_histogram_and_timer():
    td = _td([1.0, 5.0, 9.0], [2.0, 1.0, 1.0], min=1.0, max=9.0,
             reciprocalSum=2.3)
    return [_wire_list([_histo("dur", td), _histo("dur", td,
                                                  type=metric_pb2.Timer),
                        _histo("dur", td, type=metric_pb2.Timer),
                        _histo("dur", td)])]


def _case_global_and_mixed_scope():
    td = _td([2.0, 4.0], [1.0, 1.0], min=2.0, max=4.0, reciprocalSum=0.75)
    return [_wire_list([_histo("sc", td, scope=metric_pb2.Global),
                        _histo("sc", td, scope=metric_pb2.Mixed),
                        _histo("sc", td, scope=metric_pb2.Global),
                        # an enum value the schema does not name reads
                        # as mixed scope, as in _import_slow_pb
                        _histo("sc", td, scope=7)])]


def _case_refused_records():
    td = _td([2.0, 4.0], [1.0, 3.0], min=2.0, max=4.0, reciprocalSum=1.25)
    good = _histo("rf", td)
    bad = [_histo("rf", td, scope=metric_pb2.Local),
           _histo("rf", td, type=metric_pb2.Counter),
           _histo("rf", td, type=metric_pb2.Set),
           _histo("rf", td, type=300),        # past one byte
           _histo("rf", td, scope=257)]       # past one byte: mixed
    # refused before the key is cached, and after
    return [_wire_list(bad[:3] + [good] + bad + [good])]


def _case_family_markers():
    from veneur_tpu.sketches import compactor as cs
    from veneur_tpu.sketches import moments as mo

    rng = np.random.default_rng(5)
    ms = mo.MomentsSketch()
    ms.add_batch(rng.gamma(2.0, 10.0, 500))
    ck = cs.CompactorSketch()
    ck.add_batch(rng.gamma(2.0, 10.0, 500))
    recs = _seeded_digests(3, n_keys=4)
    for name, fm in (("mk.m", sm.ForwardMetric(
            name="mk.m", tags=["a:b"], kind="histogram",
            scope=int(MetricScope.MIXED), moments=ms.vec.tolist())),
            ("mk.c", sm.ForwardMetric(
                name="mk.c", tags=["a:b"], kind="timer",
                scope=int(MetricScope.MIXED),
                compactor=ck.to_vector().tolist()))):
        recs.insert(len(recs) // 2, convert.to_pb(fm))
    # a marker's vector of the wrong length fails its record alone
    recs.insert(1, _histo("mk.bad", _td([1.0, 2.0], compression=-8.0)))
    return [_wire_list(recs + _seeded_digests(3, n_keys=4))]


def _case_samples_and_unknown_fields():
    unknown = _varint_field(15, 7)
    cents = [tdigest_pb2.Centroid(mean=1.5, weight=2.0,
                                  samples=[1.0, 2.0]).SerializeToString()
             + unknown,
             # samples unpacked, then mean and weight
             bytes([3 << 3 | 1]) + np.float64(9.0).tobytes()
             + tdigest_pb2.Centroid(mean=4.5,
                                    weight=1.0).SerializeToString(),
             # mean under the wrong wire type is an unknown field:
             # the centroid keeps mean 0
             _varint_field(1, 3)
             + tdigest_pb2.Centroid(weight=5.0).SerializeToString(),
             # a scalar twice: the last one stands
             tdigest_pb2.Centroid(mean=7.0).SerializeToString()
             + tdigest_pb2.Centroid(mean=8.0,
                                    weight=1.0).SerializeToString()]
    scalars = tdigest_pb2.MergingDigestData(
        compression=100.0, min=1.0, max=8.0,
        reciprocalSum=0.9).SerializeToString()
    digest = (_ld(1, cents[0]) + _ld(1, cents[1]) + scalars + unknown
              + _ld(1, cents[2]) + _ld(9, b"opaque") + _ld(1, cents[3]))
    head = metric_pb2.Metric(name="wire", tags=["a:b"],
                             type=metric_pb2.Histogram).SerializeToString()
    one = head + _ld(7, _ld(1, digest) + unknown) + _ld(12, b"later")
    # the digest split over two t_digest fields, and over two
    # HistogramValue fields: protobuf parses them onto one message
    half_a = _ld(1, cents[0]) + tdigest_pb2.MergingDigestData(
        min=-5.0, max=1.0).SerializeToString()
    half_b = _ld(1, cents[1]) + scalars
    two = head + _ld(7, _ld(1, half_a) + _ld(1, half_b))
    three = head + _ld(7, _ld(1, half_a)) + _ld(7, _ld(1, half_b))
    return [_wire_list([one, two, three])]


def _case_oneof_switches():
    td = _td([1.0, 3.0], [2.0, 2.0], min=1.0, max=3.0, reciprocalSum=2.6)
    hv = _ld(7, metric_pb2.HistogramValue(t_digest=td).SerializeToString())
    cv = _ld(5, metric_pb2.CounterValue(value=4).SerializeToString())
    h = metric_pb2.Metric(name="sw", type=metric_pb2.Histogram
                          ).SerializeToString()
    c = metric_pb2.Metric(name="sw").SerializeToString()  # type Counter
    return [_wire_list([
        h + cv + hv,          # ends a histogram: the counter is gone
        c + hv + cv,          # ends a counter: no digest is staged
        h + hv + cv,          # type Histogram carrying a counter: refused
        h + hv + cv + hv,     # histogram, cleared, histogram afresh
        _histo("sw", td)])]


def _case_interleaved_families():
    from veneur_tpu.sketches import hll as hll_mod

    sk = hll_mod.HLLSketch()
    for i in range(50):
        sk.insert(b"u%d" % i)
    digests = _seeded_digests(4, n_keys=6)
    recs = []
    for i, d in enumerate(digests + digests):
        recs.append(metric_pb2.Metric(
            name=f"c{i % 3}", type=metric_pb2.Counter, tags=["t:1"],
            counter=metric_pb2.CounterValue(value=i + 1)))
        recs.append(d)
        recs.append(metric_pb2.Metric(
            name=f"g{i % 2}", type=metric_pb2.Gauge,
            gauge=metric_pb2.GaugeValue(value=i / 4)))
        if i % 4 == 0:
            recs.append(metric_pb2.Metric(
                name="users", type=metric_pb2.Set,
                set=metric_pb2.SetValue(hyper_log_log=sk.marshal())))
    recs.append(metric_pb2.Metric(name="nil", type=metric_pb2.Gauge))
    return [_wire_list(recs)]


def _case_twice_in_one_interval():
    pay = _wire_list(_seeded_digests(6))
    return [pay, pay]


def _case_across_a_flush():
    pay = _wire_list(_seeded_digests(7))
    other = _wire_list(_seeded_digests(8, n_keys=5))
    return [pay, "flush", other, pay, "flush", pay]


def _case_invalid_utf8_first_sighting():
    td = _td([1.0, 2.0], [1.0, 4.0], min=1.0, max=2.0, reciprocalSum=3.0)
    bad = _histo("u8.BAD", td).SerializeToString().replace(
        b"u8.BAD", b"u8.\xff\xfe\xfd")
    return [_wire_list([_histo("u8.a", td), bad, _histo("u8.b", td),
                        bad, _histo("u8.a", td)])]


# case -> (steps, plain digests that were a key's first sighting
# since the row cache was last cleared, plain digests whose row came
# from the cache)
_COLUMNAR_CASES = {
    "singleton_centroids": (_case_singletons, 12, 0),
    "weighted_centroids": (_case_weighted, 12, 1),
    "zero_centroids": (_case_zero_centroids, 3, 1),
    "histogram_and_timer": (_case_histogram_and_timer, 2, 2),
    "global_and_mixed_scope": (_case_global_and_mixed_scope, 3, 1),
    "local_scope_and_type_mismatch": (_case_refused_records, 2, 1),
    "moments_and_compactor_markers": (_case_family_markers, 4, 4),
    "samples_and_unknown_fields": (_case_samples_and_unknown_fields, 1, 2),
    "oneof_switches": (_case_oneof_switches, 2, 1),
    "counters_gauges_sets_interleaved": (_case_interleaved_families, 6, 6),
    "same_payload_twice": (_case_twice_in_one_interval, 12, 12),
    # 12 keys, resolved once: the cache outlives both flushes (PR 41)
    "across_a_flush": (_case_across_a_flush, 12, 5 + 12 + 12),
    "invalid_utf8_first_sighting": (_case_invalid_utf8_first_sighting,
                                    2, 1),
}


def _arena_state(arena) -> dict:
    """What an import left in a digest-family arena: the consolidated
    COO in a stable order by row, the exact scalars, the uniform flag."""
    arena.sync()
    uniform = arena.staged_uniform
    rows, vals, wts = arena.take_staged()
    order = np.argsort(rows, kind="stable")
    out = {"rows": rows[order], "vals": vals[order], "wts": wts[order],
           "uniform": uniform, "touched": arena.touched.copy()}
    for name in ("d_min", "d_max", "d_rsum", "d_weight", "d_sum",
                 "l_weight"):
        out[name] = getattr(arena, name).copy()
    return out


@pytest.mark.parametrize("case", sorted(_COLUMNAR_CASES))
def test_columnar_digest_import_matches_pb_path(case):
    """The same seeded payloads through import_payload and through
    import_pb_batch leave identical state: consolidated COO, scalars,
    uniform flag, (ok, failed), imported — and identical flushes."""
    import veneur_tpu.ingest as ingest_mod
    from veneur_tpu.core.aggregator import MetricAggregator

    ingest_mod.load_library()   # loud if the engine can't build
    build, want_misses, want_hits = _COLUMNAR_CASES[case]

    def reference_import(agg, payload):
        pbs = []
        for rec in _wire_records(payload):
            try:
                pbs.append(metric_pb2.Metric.FromString(rec))
            except Exception:
                pbs.append(metric_pb2.Metric())    # fails as a nil value
        return agg.import_pb_batch(pbs)

    runs = []
    for native in (True, False):
        agg = MetricAggregator(percentiles=[0.5, 0.9])
        seen = {"counts": [], "flushes": [], "hits": 0, "misses": 0}

        def bank_ledger():
            seen["hits"] += agg._ledger["import_digest_hits"]
            seen["misses"] += agg._ledger["import_digest_misses"]

        for step in build():
            if step == "flush":
                bank_ledger()
                res = agg.flush(is_local=False)
                seen["flushes"].append((res.imported, sorted(
                    (m.name, tuple(m.tags), m.value)
                    for m in res.metrics)))
                # no row was recycled: the cache outlives the flush
                # (the reference's pb path caches no digest)
                assert bool(agg._import_row_cache) == native
            elif native:
                seen["counts"].append(agg.import_payload(step))
            else:
                seen["counts"].append(reference_import(agg, step))
        bank_ledger()
        seen["imported"] = agg.imported
        seen["arenas"] = {fam: _arena_state(getattr(agg, fam))
                          for fam in ("digests", "moments", "compactors")}
        runs.append(seen)

    got, ref = runs
    assert got["counts"] == ref["counts"]
    assert got["imported"] == ref["imported"]
    assert got["flushes"] == ref["flushes"]
    for fam, state in got["arenas"].items():
        for name, value in state.items():
            want = ref["arenas"][fam][name]
            if isinstance(value, np.ndarray):
                assert value.dtype == want.dtype, (fam, name)
                assert np.array_equal(
                    value, want,
                    equal_nan=value.dtype.kind == "f"), (fam, name)
            else:
                assert value == want, (fam, name)
    # the reference never takes the columnar path; the scan path's
    # counter says how often it engaged, and with what
    assert (ref["misses"], ref["hits"]) == (0, 0)
    assert (got["misses"], got["hits"]) == (want_misses, want_hits)
    staged = sum(len(s["rows"]) for s in got["arenas"].values())
    assert staged > 0
