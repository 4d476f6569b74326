"""Server integration tests over real sockets, porting the reference's
fixture pattern (`server_test.go:78-238`): port-0 listeners, 50ms flush
interval, channel sink delivering each flush to the test."""

import os
import queue
import socket
import ssl
import subprocess
import time
import urllib.request

import pytest

from veneur_tpu import config as config_mod
from veneur_tpu import http_api
from veneur_tpu.core.server import Server
from veneur_tpu.sinks import simple as simple_sinks


def make_config(**kw) -> config_mod.Config:
    cfg = config_mod.Config(
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        interval=0.05,
        percentiles=[0.5],
        aggregates=["min", "max", "count"],
        hostname="testbox",
        num_readers=2,
    )
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


@pytest.fixture
def fixture_server():
    servers = []

    def boot(**kw):
        cfg = make_config(**kw)
        sink = simple_sinks.ChannelMetricSink()
        srv = Server(cfg, extra_metric_sinks=[sink])
        srv.start()
        servers.append(srv)
        return srv, sink

    yield boot
    for srv in servers:
        srv.shutdown()


def drain_until(sink, pred, timeout=5.0):
    """Collect flushed metric batches until pred(all) or timeout."""
    all_metrics = []
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            batch = sink.queue.get(timeout=0.1)
        except queue.Empty:
            continue
        all_metrics.extend(batch)
        if pred(all_metrics):
            return all_metrics
    raise AssertionError(f"timed out; got {[m.name for m in all_metrics]}")


def test_udp_end_to_end(fixture_server):
    srv, sink = fixture_server()
    kind, addr = srv.statsd_addrs[0]
    assert kind == "udp"
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.sendto(b"a.b.c:42|c|#x:y\ntemp:70|g", addr)
    s.close()
    srv.flush_count = 0
    # flush manually (no ticker thread in tests)
    time.sleep(0.1)
    srv.flush()
    ms = drain_until(sink, lambda all_m: len(all_m) >= 2)
    by = {m.name: m for m in ms}
    assert by["a.b.c"].value == 42.0
    assert by["a.b.c"].tags == ["x:y"]
    assert by["temp"].value == 70.0


def test_udp_multiple_readers_shared_port(fixture_server):
    srv, sink = fixture_server(num_readers=4)
    _, addr = srv.statsd_addrs[0]
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    for i in range(100):
        s.sendto(f"hits:1|c".encode(), addr)
    s.close()
    time.sleep(0.3)
    srv.flush()
    ms = drain_until(sink, lambda all_m: any(m.name == "hits" for m in all_m))
    hits = [m for m in ms if m.name == "hits"]
    assert sum(m.value for m in hits) == 100.0


def test_tcp_end_to_end(fixture_server):
    srv, sink = fixture_server(
        statsd_listen_addresses=["tcp://127.0.0.1:0"])
    _, addr = srv.statsd_addrs[0]
    c = socket.create_connection(addr)
    c.sendall(b"tcp.metric:7|c\n")
    c.close()
    time.sleep(0.2)
    srv.flush()
    ms = drain_until(sink, lambda a: any(m.name == "tcp.metric" for m in a))
    assert [m for m in ms if m.name == "tcp.metric"][0].value == 7.0


def test_unixgram_end_to_end(fixture_server, tmp_path):
    path = str(tmp_path / "statsd.sock")
    srv, sink = fixture_server(
        statsd_listen_addresses=[f"unixgram://{path}"])
    c = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)
    c.sendto(b"ux:3|c", path)
    c.close()
    time.sleep(0.2)
    srv.flush()
    ms = drain_until(sink, lambda a: any(m.name == "ux" for m in a))
    assert [m for m in ms if m.name == "ux"][0].value == 3.0


def test_unix_stream_end_to_end(fixture_server, tmp_path):
    path = str(tmp_path / "statsd-stream.sock")
    srv, sink = fixture_server(statsd_listen_addresses=[f"unix://{path}"])
    c = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    c.connect(path)
    c.sendall(b"uxs:9|g\n")
    c.close()
    time.sleep(0.2)
    srv.flush()
    ms = drain_until(sink, lambda a: any(m.name == "uxs" for m in a))
    assert [m for m in ms if m.name == "uxs"][0].value == 9.0


def _make_certs(tmp_path):
    """Self-signed CA + server + client certs via openssl CLI."""
    ca_key = tmp_path / "ca.key"
    ca_crt = tmp_path / "ca.crt"
    def run(*args):
        subprocess.run(args, check=True, capture_output=True)
    run("openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
        "-keyout", str(ca_key), "-out", str(ca_crt), "-days", "1",
        "-subj", "/CN=test-ca")
    ext = tmp_path / "san.cnf"
    ext.write_text("subjectAltName=IP:127.0.0.1,DNS:localhost\n")
    certs = {}
    for who in ("server", "client"):
        key = tmp_path / f"{who}.key"
        csr = tmp_path / f"{who}.csr"
        crt = tmp_path / f"{who}.crt"
        run("openssl", "req", "-newkey", "rsa:2048", "-nodes",
            "-keyout", str(key), "-out", str(csr),
            "-subj", f"/CN=127.0.0.1")
        # SANs required: gRPC's TLS stack ignores CN-only certs
        run("openssl", "x509", "-req", "-in", str(csr), "-CA", str(ca_crt),
            "-CAkey", str(ca_key), "-CAcreateserial", "-days", "1",
            "-extfile", str(ext), "-out", str(crt))
        certs[who] = (str(key), str(crt))
    return str(ca_crt), certs


@pytest.mark.skipif(
    subprocess.run(["which", "openssl"], capture_output=True).returncode != 0,
    reason="openssl unavailable")
def test_tls_client_cert_required(fixture_server, tmp_path):
    ca, certs = _make_certs(tmp_path)
    skey, scrt = certs["server"]
    ckey, ccrt = certs["client"]
    srv, sink = fixture_server(
        statsd_listen_addresses=["tcp://127.0.0.1:0"],
        tls_key=skey, tls_certificate=scrt, tls_authority_certificate=ca)
    _, addr = srv.statsd_addrs[0]

    # correct client cert works
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    ctx.check_hostname = False
    ctx.verify_mode = ssl.CERT_NONE
    ctx.load_cert_chain(ccrt, ckey)
    raw = socket.create_connection(addr)
    tls = ctx.wrap_socket(raw)
    tls.sendall(b"tls.metric:5|c\n")
    tls.close()
    time.sleep(0.3)
    srv.flush()
    ms = drain_until(sink, lambda a: any(m.name == "tls.metric" for m in a))
    assert [m for m in ms if m.name == "tls.metric"][0].value == 5.0

    # no client cert is rejected
    ctx2 = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    ctx2.check_hostname = False
    ctx2.verify_mode = ssl.CERT_NONE
    raw2 = socket.create_connection(addr)
    with pytest.raises(ssl.SSLError):
        tls2 = ctx2.wrap_socket(raw2)
        tls2.sendall(b"evil:1|c\n")
        tls2.recv(1)  # force handshake completion
    time.sleep(0.2)
    srv.flush()
    srv.egress.settle(timeout_s=5.0)   # fan-out is async now
    while not sink.queue.empty():
        batch = sink.queue.get()
        assert not any(m.name == "evil" for m in batch)


def test_events_reach_sink_other_samples(fixture_server):
    srv, sink = fixture_server()
    _, addr = srv.statsd_addrs[0]
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.sendto(b"_e{5,5}:hello|world|t:info", addr)
    s.close()
    # the event reaches the sink with the flush that follows its drain,
    # and under a loaded machine the drain can be late: flush until it
    # did, not once after a fixed sleep
    deadline = time.time() + 20
    while time.time() < deadline and not sink.other_samples:
        time.sleep(0.1)
        srv.flush()
    assert sink.other_samples
    assert sink.other_samples[0].name == "hello"


def test_ticker_flushes(fixture_server):
    import threading
    srv, sink = fixture_server()
    _, addr = srv.statsd_addrs[0]
    t = threading.Thread(target=srv.serve, daemon=True)
    t.start()
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.sendto(b"tick:1|c", addr)
    s.close()
    ms = drain_until(sink, lambda a: any(m.name == "tick" for m in a))
    assert ms
    srv.shutdown()


def test_ticker_drops_the_ticks_a_slow_flush_missed(fixture_server):
    """A flush that outlasts several intervals is followed by ONE flush
    at once (the tick the reference's ticker holds) and then by flushes
    on the grid again, not by a burst replaying every missed tick."""
    import threading
    srv, _sink = fixture_server(interval=0.2)
    interval = srv.config.interval
    starts = []
    slow = threading.Event()

    def flush(tick=None):
        starts.append(time.time())
        if len(starts) == 2:
            time.sleep(4.5 * interval)      # misses four ticks
            slow.set()

    srv.flush = flush
    t = threading.Thread(target=srv.serve, daemon=True)
    t.start()
    assert slow.wait(10 + 8 * interval)
    deadline = time.time() + 10 + 8 * interval
    while len(starts) < 6 and time.time() < deadline:
        time.sleep(interval / 10)
    srv.stop_serving()
    t.join(5)
    assert len(starts) >= 6
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    # the slow flush, then the one held tick at once, then the grid
    assert gaps[1] >= 4.5 * interval
    assert gaps[2] < interval
    for gap in gaps[3:5]:
        assert 0.5 * interval < gap < 1.5 * interval, gaps
    # ... and the grid is the old one: whole intervals from the start
    phase = (starts[4] - starts[0]) / interval
    assert abs(phase - round(phase)) < 0.35, (phase, gaps)


def test_watchdog_fires():
    cfg = make_config(flush_watchdog_missed_flushes=2, interval=0.05)
    srv = Server(cfg)
    fired = []
    srv.shutdown_hook = lambda: fired.append(True)
    srv.last_flush_unix = time.time() - 10  # long overdue
    srv.start()
    deadline = time.time() + 2
    while time.time() < deadline and not fired:
        time.sleep(0.02)
    srv.shutdown()
    assert fired


def test_http_api(fixture_server):
    srv, _ = fixture_server(http_config_endpoint=True)
    api = http_api.HttpApi(srv, "127.0.0.1:0")
    api.start()
    host, port = api.address
    base = f"http://{host}:{port}"
    assert urllib.request.urlopen(base + "/healthcheck").read() == b"ok\n"
    assert urllib.request.urlopen(base + "/version").read()
    cfg_json = urllib.request.urlopen(base + "/config/json").read()
    assert b"interval" in cfg_json
    assert b"REDACTED" not in cfg_json  # no secrets set
    dbg = urllib.request.urlopen(base + "/debug/vars").read()
    assert b"flush_count" in dbg
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(base + "/nope")
    api.stop()


def test_config_yaml_roundtrip(tmp_path, monkeypatch):
    p = tmp_path / "veneur.yaml"
    p.write_text("""
interval: "5s"
percentiles: [0.5, 0.99]
aggregates: ["max", "count"]
statsd_listen_addresses:
  - udp://127.0.0.1:8126
forward_address: "$FORWARD_TARGET"
metric_sinks:
  - kind: blackhole
    name: bh
""")
    env = {"FORWARD_TARGET": "globalbox:3000",
           "VENEUR_HOSTNAME": "overridden"}
    cfg = config_mod.read_config(str(p), environ=env)
    assert cfg.interval == 5.0
    assert cfg.percentiles == [0.5, 0.99]
    assert cfg.forward_address == "globalbox:3000"
    assert cfg.is_local
    assert cfg.hostname == "overridden"
    assert cfg.metric_sinks[0].kind == "blackhole"


def test_config_strict_rejects_unknown(tmp_path):
    p = tmp_path / "bad.yaml"
    p.write_text("no_such_field: 1\n")
    with pytest.raises(ValueError):
        config_mod.read_config(str(p), strict=True, environ={})
    cfg = config_mod.read_config(str(p), strict=False, environ={})
    assert cfg.interval == 10.0


def test_ingest_knob_validation(tmp_path):
    """The ingest_* data-plane knobs strict-parse from YAML, clamp
    negative counts to 0 (engine default), and reject unknown dispatch
    enum values loudly."""
    p = tmp_path / "ingest.yaml"
    p.write_text("""
ingest_reader_shards: 4
ingest_reader_pinning: true
ingest_reader_batch: 128
ingest_simd: sse2
ingest_backend: recvmmsg
ingest_ring_slots: 2048
""")
    cfg = config_mod.read_config(str(p), strict=True, environ={})
    assert cfg.ingest_reader_shards == 4
    assert cfg.ingest_reader_pinning is True
    assert cfg.ingest_reader_batch == 128
    assert cfg.ingest_simd == "sse2"
    assert cfg.ingest_backend == "recvmmsg"
    assert cfg.ingest_ring_slots == 2048

    neg = config_mod.Config(ingest_reader_shards=-3, ingest_reader_batch=-1,
                            ingest_ring_slots=-8)
    neg.apply_defaults()
    assert (neg.ingest_reader_shards, neg.ingest_reader_batch,
            neg.ingest_ring_slots) == (0, 0, 0)

    for knob, val in (("ingest_simd", "neon"),
                      ("ingest_backend", "epoll")):
        bad = config_mod.Config(**{knob: val})
        with pytest.raises(ValueError, match=knob):
            bad.apply_defaults()


def test_sink_filtering():
    from veneur_tpu import sinks as sink_mod
    from veneur_tpu.samplers.samplers import InterMetric
    from veneur_tpu.util.matcher import TagMatcher
    spec = sink_mod.SinkSpec(
        kind="x", name="x", max_name_length=10, max_tags=2,
        strip_tags=[TagMatcher(kind="prefix", value="secret")],
        add_tags={"env": "prod"})
    ms = [
        InterMetric("ok", 0, 1, ["a:1", "secret:x"], "counter"),
        InterMetric("waytoolongname", 0, 1, [], "counter"),
        InterMetric("manytags", 0, 1, ["a:1", "b:2", "c:3"], "counter"),
    ]
    out, counts = sink_mod.filter_metrics_for_sink(spec, False, ms)
    assert [m.name for m in out] == ["ok"]
    assert out[0].tags == ["a:1", "env:prod"]
    assert counts["max_name_length"] == 1
    assert counts["max_tags"] == 1
    # original untouched (sinks must not mutate shared metrics)
    assert ms[0].tags == ["a:1", "secret:x"]


def test_matcher_semantics():
    from veneur_tpu.util import matcher as mm
    cfgs = [mm.Matcher(
        name=mm.NameMatcher(kind="prefix", value="api."),
        tags=[mm.TagMatcher(kind="exact", value="env:prod"),
              mm.TagMatcher(kind="prefix", value="canary", unset=True)])]
    assert mm.match(cfgs, "api.hits", ["env:prod"])
    assert not mm.match(cfgs, "web.hits", ["env:prod"])
    assert not mm.match(cfgs, "api.hits", ["env:dev"])
    assert not mm.match(cfgs, "api.hits", ["env:prod", "canary:true"])


def test_http_debug_profile(fixture_server):
    """JAX profiler trace endpoint (SURVEY §5.1 analog of pprof)."""
    import json as json_mod

    srv, _ = fixture_server(enable_profiling=True)
    api = http_api.HttpApi(srv, "127.0.0.1:0")
    api.start()
    host, port = api.address
    base = f"http://{host}:{port}"
    body = urllib.request.urlopen(
        base + "/debug/profile?seconds=0.2", timeout=30).read()
    out = json_mod.loads(body)
    assert out["files"] > 0 and "veneur-jax-trace-" in out["trace_dir"]
    api.stop()


def test_http_debug_profile_disabled(fixture_server):
    srv, _ = fixture_server()  # enable_profiling defaults off
    api = http_api.HttpApi(srv, "127.0.0.1:0")
    api.start()
    host, port = api.address
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(
            f"http://{host}:{port}/debug/profile", timeout=10)
    assert exc.value.code == 403
    api.stop()


def test_tags_exclude_per_sink(fixture_server):
    """tags_exclude: bare keys strip everywhere; "key|sinkname" strips for
    that sink only (setSinkExcludedTags, server.go:660,1456-1463)."""
    srv, sink = fixture_server(tags_exclude=["nonce", "region|channel"])
    # the fixture's channel sink is named "channel"
    _, addr = srv.statsd_addrs[0]
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.sendto(b"te.m:1|c|#nonce:abc,region:us,keep:yes", addr)
    s.close()
    deadline = time.time() + 5
    while time.time() < deadline and srv.aggregator.processed < 1:
        time.sleep(0.05)
        srv._drain_native()
    srv.flush()
    ms = drain_until(sink, lambda a: any(m.name == "te.m" for m in a))
    m = [x for x in ms if x.name == "te.m"][0]
    assert m.tags == ["keep:yes"], m.tags


def test_grpc_listen_addresses_edge_ingest(fixture_server):
    """grpc_listen_addresses hosts SSF SendSpan + dogstatsd SendPacket on
    any instance (StartGRPC, networking.go:326-391) WITHOUT the Forward
    import service (that is grpc_address's global-tier job)."""
    import grpc as grpc_mod

    from veneur_tpu.core.server import _SpanSinkWorker
    from veneur_tpu.protocol import (dogstatsd_grpc_pb2, metric_pb2,
                                     ssf_pb2)
    from veneur_tpu.sinks.simple import ChannelSpanSink

    span_sink = ChannelSpanSink()
    srv, sink = fixture_server(
        grpc_listen_addresses=["tcp://127.0.0.1:0"])
    srv.span_sinks.append(span_sink)
    srv.span_workers.append(
        _SpanSinkWorker(span_sink, 100, 1, srv._shutdown))
    port = srv.grpc_ingest_listeners[0].port
    channel = grpc_mod.insecure_channel(f"127.0.0.1:{port}")

    # dogstatsd bytes over gRPC
    send_packet = channel.unary_unary(
        "/dogstatsd.DogstatsdGRPC/SendPacket",
        request_serializer=(
            dogstatsd_grpc_pb2.DogstatsdPacket.SerializeToString),
        response_deserializer=dogstatsd_grpc_pb2.Empty.FromString)
    send_packet(dogstatsd_grpc_pb2.DogstatsdPacket(
        packetBytes=b"grpc.edge:11|c"), timeout=5)

    # SSF span over gRPC
    send_span = channel.unary_unary(
        "/ssf.SSFGRPC/SendSpan",
        request_serializer=ssf_pb2.SSFSpan.SerializeToString,
        response_deserializer=lambda b: b)
    send_span(ssf_pb2.SSFSpan(version=0, trace_id=5, id=6, name="eop",
                              service="svc", start_timestamp=1,
                              end_timestamp=2), timeout=5)

    # the Forward service must NOT be served on this listener
    v2 = channel.stream_unary(
        "/forwardrpc.Forward/SendMetricsV2",
        request_serializer=metric_pb2.Metric.SerializeToString,
        response_deserializer=lambda b: b)
    with pytest.raises(grpc_mod.RpcError) as exc:
        v2(iter([metric_pb2.Metric(name="x")]), timeout=5)
    assert exc.value.code() == grpc_mod.StatusCode.UNIMPLEMENTED

    # grpc.health.v1 probe (networking.go:377-384 analog)
    health = channel.unary_unary(
        "/grpc.health.v1.Health/Check",
        request_serializer=lambda b: b,
        response_deserializer=lambda b: b)
    assert health(b"", timeout=5) == b"\x08\x01"  # status=SERVING

    # received-per-protocol accounting for both gRPC ingest kinds
    assert srv.proto_received["dogstatsd-grpc"] == 1
    assert srv.proto_received["ssf-grpc"] == 1

    srv.flush()
    ms = drain_until(sink, lambda a: any(m.name == "grpc.edge" for m in a))
    assert [m for m in ms if m.name == "grpc.edge"][0].value == 11.0
    got_span = span_sink.queue.get(timeout=5)
    assert got_span.name == "eop"
    channel.close()


@pytest.mark.skipif(
    subprocess.run(["which", "openssl"], capture_output=True).returncode != 0,
    reason="openssl unavailable")
def test_grpc_ingest_listener_honors_tls(fixture_server, tmp_path):
    """With server TLS configured, the edge gRPC listener serves mTLS
    (networking.go:363-374) — plaintext clients are rejected."""
    import grpc as grpc_mod

    from veneur_tpu.protocol import dogstatsd_grpc_pb2

    ca, certs = _make_certs(tmp_path)
    skey, scrt = certs["server"]
    ckey, ccrt = certs["client"]
    srv, sink = fixture_server(
        grpc_listen_addresses=["tcp://127.0.0.1:0"],
        tls_key=skey, tls_certificate=scrt,
        tls_authority_certificate=ca)
    port = srv.grpc_ingest_listeners[0].port

    def send(channel):
        rpc = channel.unary_unary(
            "/dogstatsd.DogstatsdGRPC/SendPacket",
            request_serializer=(
                dogstatsd_grpc_pb2.DogstatsdPacket.SerializeToString),
            response_deserializer=dogstatsd_grpc_pb2.Empty.FromString)
        rpc(dogstatsd_grpc_pb2.DogstatsdPacket(
            packetBytes=b"grpc.tls:3|c"), timeout=5)

    # plaintext must fail
    with pytest.raises(grpc_mod.RpcError):
        ch = grpc_mod.insecure_channel(f"127.0.0.1:{port}")
        send(ch)
    # mTLS client works
    with open(ca, "rb") as f:
        ca_b = f.read()
    with open(ckey, "rb") as f:
        key_b = f.read()
    with open(ccrt, "rb") as f:
        crt_b = f.read()
    creds = grpc_mod.ssl_channel_credentials(
        root_certificates=ca_b, private_key=key_b, certificate_chain=crt_b)
    ch = grpc_mod.secure_channel(f"127.0.0.1:{port}", creds)
    send(ch)
    ch.close()
    srv.flush()
    ms = drain_until(sink, lambda a: any(m.name == "grpc.tls" for m in a))
    assert [m for m in ms if m.name == "grpc.tls"][0].value == 3.0


def test_grpc_health_unknown_service_not_found(fixture_server):
    import grpc as grpc_mod

    srv, _ = fixture_server(grpc_listen_addresses=["tcp://127.0.0.1:0"])
    port = srv.grpc_ingest_listeners[0].port
    ch = grpc_mod.insecure_channel(f"127.0.0.1:{port}")
    health = ch.unary_unary("/grpc.health.v1.Health/Check",
                            request_serializer=lambda b: b,
                            response_deserializer=lambda b: b)
    assert health(b"", timeout=5) == b"\x08\x01"
    # service name "veneur" (field 1, len 6): SERVING
    assert health(b"\x0a\x06veneur", timeout=5) == b"\x08\x01"
    with pytest.raises(grpc_mod.RpcError) as exc:
        health(b"\x0a\x04nope", timeout=5)
    assert exc.value.code() == grpc_mod.StatusCode.NOT_FOUND
    ch.close()


def test_grpc_ingest_half_tls_config_fails_loud(tmp_path):
    cfg = make_config(grpc_listen_addresses=["tcp://127.0.0.1:0"],
                      tls_key=str(tmp_path / "only.key"))
    srv = Server(cfg)
    with pytest.raises(ValueError, match="both"):
        srv.start()
    srv.shutdown()


def test_ipv6_udp_listener(fixture_server):
    """udp://[::1]:0 binds an AF_INET6 listener and ingests normally
    (the reference resolves either address family)."""
    srv, sink = fixture_server(
        statsd_listen_addresses=["udp://[::1]:0"])
    kind, addr = srv.statsd_addrs[0]
    s = socket.socket(socket.AF_INET6, socket.SOCK_DGRAM)
    s.sendto(b"v6.c:6|c", (addr[0], addr[1]))
    s.close()
    deadline = time.time() + 5
    while time.time() < deadline and srv.aggregator.processed < 1:
        time.sleep(0.05)
        srv._drain_native()
    srv.flush()
    ms = drain_until(sink, lambda a: any(m.name == "v6.c" for m in a))
    assert [m for m in ms if m.name == "v6.c"][0].value == 6.0
