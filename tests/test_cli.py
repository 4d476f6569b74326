"""CLI entry-point tests (cmd/veneur, veneur-emit, veneur-prometheus)."""

import os
import socket
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

from veneur_tpu.cli import veneur as cli_veneur
from veneur_tpu.cli import veneur_emit as cli_emit
from veneur_tpu.cli import veneur_prometheus as cli_prom


def _udp_receiver():
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    sock.settimeout(3.0)
    return sock, sock.getsockname()[1]


def test_veneur_validate_config(tmp_path, capsys):
    cfgfile = tmp_path / "v.yaml"
    cfgfile.write_text(
        "interval: 5s\npercentiles: [0.5, 0.99]\n"
        "statsd_listen_addresses: ['udp://127.0.0.1:0']\n")
    rc = cli_veneur.main(["-f", str(cfgfile), "-validate-config"])
    assert rc == 0
    assert "config valid" in capsys.readouterr().out


def test_veneur_bad_config_rejected(tmp_path):
    cfgfile = tmp_path / "bad.yaml"
    cfgfile.write_text("interval: [not, a, duration]\n")
    assert cli_veneur.main(["-f", str(cfgfile), "-validate-config"]) == 1


def test_veneur_requires_config_flag():
    assert cli_veneur.main([]) == 1


def test_veneur_version(capsys):
    assert cli_veneur.main(["-version"]) == 0
    assert "veneur-tpu" in capsys.readouterr().out


def test_emit_statsd_metrics_and_tags():
    sock, port = _udp_receiver()
    rc = cli_emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                        "-name", "x.y", "-count", "3", "-tag", "a:b"])
    assert rc == 0
    data, _ = sock.recvfrom(65536)
    sock.close()
    assert data == b"x.y:3|c|#a:b"


def test_emit_event_and_service_check():
    sock, port = _udp_receiver()
    rc = cli_emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                        "-event_title", "deploy", "-event_text", "done",
                        "-sc_name", "db.up", "-sc_status", "1"])
    assert rc == 0
    data, _ = sock.recvfrom(65536)
    sock.close()
    lines = data.split(b"\n")
    assert lines[0].startswith(b"_e{6,4}:deploy|done")
    assert lines[1].startswith(b"_sc|db.up|1")


def test_emit_command_mode_times_subprocess():
    sock, port = _udp_receiver()
    rc = cli_emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                        "-command", "true"])
    assert rc == 0
    data, _ = sock.recvfrom(65536)
    sock.close()
    assert data.startswith(b"veneur-emit.command.duration_ms:")
    assert b"|ms" in data


def test_emit_command_nonzero_exit_propagates():
    sock, port = _udp_receiver()
    rc = cli_emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                        "-command", "false"])
    sock.close()
    assert rc == 1


def test_emit_ssf_span():
    from veneur_tpu import ssf as ssf_mod
    sock, port = _udp_receiver()
    rc = cli_emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                        "-name", "op", "-gauge", "1.5", "-ssf"])
    assert rc == 0
    data, _ = sock.recvfrom(65536)
    sock.close()
    span = ssf_mod.SSFSpan.FromString(data)
    assert span.name == "op" and span.service == "veneur-emit"
    assert span.metrics[0].name == "op"
    assert abs(span.metrics[0].value - 1.5) < 1e-6


def test_veneur_prometheus_once():
    class H(BaseHTTPRequestHandler):
        def do_GET(self):
            body = b"# TYPE up gauge\nup 1\n"
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    httpd = HTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    sock, port = _udp_receiver()
    try:
        rc = cli_prom.main([
            "-m", f"http://127.0.0.1:{httpd.server_address[1]}/metrics",
            "-s", f"127.0.0.1:{port}", "-p", "prom.", "-once"])
        assert rc == 0
        data, _ = sock.recvfrom(65536)
        assert data == b"prom.up:1|g"
        data, _ = sock.recvfrom(65536)   # self-stat follows
        assert data.startswith(b"prom.veneur.prometheus.metrics_flushed")
    finally:
        sock.close()
        httpd.shutdown()
        httpd.server_close()


def test_server_wires_statsd_and_diagnostics():
    from veneur_tpu.config import Config
    from veneur_tpu.core.server import Server
    sock, port = _udp_receiver()
    cfg = Config(interval=60.0, stats_address=f"127.0.0.1:{port}",
                 diagnostics_metrics_enabled=True,
                 veneur_metrics_additional_tags=["self:1"])
    srv = Server(cfg)
    srv.start()
    try:
        assert srv.statsd is not None and srv.diagnostics is not None
        srv.diagnostics.report_once()
        data, _ = sock.recvfrom(65536)
        assert data.startswith(b"veneur.")
        assert b"|#self:1" in data
    finally:
        srv.shutdown()
        sock.close()


def test_scopedstatsd_scope_tags():
    from veneur_tpu import scopedstatsd
    sock, port = _udp_receiver()
    client = scopedstatsd.ScopedClient(
        f"127.0.0.1:{port}",
        scopes=scopedstatsd.MetricScopes(counter="global", gauge="local"),
        tags=["base:1"])
    client.count("c", 2, tags=["k:v"])
    data, _ = sock.recvfrom(65536)
    # self-metrics carry the reference's "veneur." namespace
    # (cmd/veneur/main.go:92)
    assert data == b"veneur.c:2|c|#base:1,k:v,veneurglobalonly"
    client.gauge("g", 1.5)
    data, _ = sock.recvfrom(65536)
    assert data == b"veneur.g:1.5|g|#base:1,veneurlocalonly"
    client.close()
    sock.close()
    # nil-safety
    noop = scopedstatsd.ensure(None)
    noop.count("x", 1)


def test_diagnostics_collect_and_report():
    from veneur_tpu import diagnostics

    class Rec:
        def __init__(self):
            self.gauges = {}

        def gauge(self, name, value, tags=None, rate=1.0):
            self.gauges[name] = value

    rec = Rec()
    diag = diagnostics.Diagnostics(statsd=rec, interval_s=60.0)
    stats = diag.report_once()
    assert stats["uptime_ms"] >= 0
    assert stats["threads"] >= 1
    assert "mem.rss_bytes" in stats
    # bare names: the "veneur." namespace is the statsd CLIENT's job
    # (ScopedClient), never double-prefixed here
    assert rec.gauges["threads"] == stats["threads"]


def test_example_configs_load():
    """The annotated example configs must stay valid against the real
    loaders (the reference ships example.yaml/example_host.yaml/
    example_proxy.yaml; these are their capability twins)."""
    import os

    import yaml

    from veneur_tpu import config as config_mod
    from veneur_tpu.proxy.proxy import proxy_config_from_dict

    root = os.path.join(os.path.dirname(__file__), os.pardir)
    env = {"DATADOG_API_KEY": "k", "SPLUNK_HEC_TOKEN": "t"}

    cfg = config_mod.read_config(os.path.join(root, "example.yaml"),
                                 strict=True, environ=env)
    assert cfg.grpc_address and not cfg.is_local
    assert cfg.interval == 10.0
    assert cfg.mesh_devices == 4
    assert {s.kind for s in cfg.metric_sinks} >= {"datadog", "s3", "cortex"}
    assert cfg.metric_sinks[0].config["api_key"] == "k"  # $ENV expanded
    assert cfg.metric_sink_routing[0].matched == [
        "s3-archive", "datadog", "cortex"]
    assert cfg.sources[0].kind == "openmetrics"

    host = config_mod.read_config(os.path.join(root, "example_host.yaml"),
                                  strict=True, environ={})
    assert host.is_local and host.forward_timeout == 10.0

    with open(os.path.join(root, "example_proxy.yaml")) as f:
        pdata = yaml.safe_load(f)
    # the REAL loader the proxy CLI uses (durations included)
    pcfg = proxy_config_from_dict(pdata)
    assert pcfg.static_destinations
    assert pcfg.discovery_interval == 10.0
    assert pcfg.grpc_tls_address and pcfg.ignore_tags


def test_netaddr_parsing():
    import pytest as _pytest

    from veneur_tpu.util import netaddr

    assert netaddr.split_hostport("127.0.0.1:8126") == ("127.0.0.1", 8126)
    assert netaddr.split_hostport("[::1]:8126") == ("::1", 8126)
    assert netaddr.split_hostport(":8126") == ("127.0.0.1", 8126)
    assert netaddr.split_hostport("host", default_port=9) == ("host", 9)
    with _pytest.raises(ValueError, match="bracketed"):
        netaddr.split_hostport("::1")          # unbracketed v6: loud
    with _pytest.raises(ValueError, match="bracketed"):
        netaddr.split_hostport("2001:db8::1:8126")  # ambiguous: loud
    with _pytest.raises(ValueError, match="missing port"):
        netaddr.split_hostport("host")
    # bracketed v6 with no port takes the default (ADVICE r2)
    assert netaddr.split_hostport("[::1]", default_port=9) == ("::1", 9)
    # negative and out-of-range ports are loud, not int("-1")
    with _pytest.raises(ValueError, match="invalid port"):
        netaddr.split_hostport("host:-1")
    with _pytest.raises(ValueError, match="invalid port"):
        netaddr.split_hostport("host:65536")
    import socket as s
    assert netaddr.family("::1") == s.AF_INET6
    assert netaddr.family("10.0.0.1") == s.AF_INET


def test_emit_ipv6_destination():
    sock = socket.socket(socket.AF_INET6, socket.SOCK_DGRAM)
    sock.bind(("::1", 0))
    sock.settimeout(3.0)
    port = sock.getsockname()[1]
    rc = cli_emit.main(["-hostport", f"udp://[::1]:{port}",
                        "-name", "v6.e", "-count", "1"])
    assert rc == 0
    data, _ = sock.recvfrom(65536)
    sock.close()
    assert data == b"v6.e:1|c"

def test_veneur_prometheus_translation_semantics():
    """cmd/veneur-prometheus translate.go parity: histogram bucket ->
    `.le%f` count deltas, summary quantiles -> percentile gauges, label
    ignore/rename/add, ignored metric families, counter delta cache."""
    from veneur_tpu.cli.veneur_prometheus import Translator

    tr = Translator(ignored_labels="^secret", renamed={"env": "stage"},
                    added={"team": "infra"}, ignored_metrics="^skip_me")
    scrape1 = """
# TYPE reqs counter
reqs{env="prod",secret_id="x"} 10
# TYPE temp gauge
temp 21.5
# TYPE skip_me counter
skip_me 5
# TYPE lat histogram
lat_bucket{le="0.5"} 3
lat_bucket{le="+Inf"} 7
lat_sum 9.5
lat_count 7
# TYPE rt summary
rt{quantile="0.5"} 0.2
rt{quantile="0.99"} NaN
rt_sum 12.5
rt_count 30
"""
    first = tr.translate(scrape1)
    by = {(n, tuple(t)): (v, mt) for n, v, mt, t in first}
    # first sweep: the cache has no basis, so counters emit a ZERO delta
    # (stats.go:78-83 returns 0); gauges and quantiles emit immediately
    assert by[("temp", ("team:infra",))] == (21.5, "g")
    assert by[("lat.sum", ("team:infra",))] == (9.5, "g")
    assert by[("rt.sum", ("team:infra",))] == (12.5, "g")
    assert by[("rt.50percentile", ("team:infra",))] == (0.2, "g")
    assert by[("reqs", ("stage:prod", "team:infra"))] == (0.0, "c")
    assert by[("lat.count", ("team:infra",))] == (0.0, "c")
    assert not any(n.startswith("skip_me") for n, *_ in first)

    scrape2 = scrape1.replace('reqs{env="prod",secret_id="x"} 10',
                              'reqs{env="prod",secret_id="x"} 14') \
        .replace('lat_bucket{le="0.5"} 3', 'lat_bucket{le="0.5"} 5') \
        .replace('lat_bucket{le="+Inf"} 7', 'lat_bucket{le="+Inf"} 10') \
        .replace('lat_count 7', 'lat_count 10') \
        .replace('rt_count 30', 'rt_count 33')
    second = tr.translate(scrape2)
    by2 = {(n, tuple(t)): (v, mt) for n, v, mt, t in second}
    # counter delta with ignored label dropped, env renamed, team added
    assert by2[("reqs", ("stage:prod", "team:infra"))] == (4, "c")
    # histogram buckets: reference %f naming, cumulative deltas, le tag
    # stripped
    assert by2[("lat.le0.500000", ("team:infra",))] == (2, "c")
    # +Inf bucket keeps Go's %f rendering (translate.go:176)
    assert by2[("lat.le+Inf", ("team:infra",))] == (3, "c")
    assert by2[("lat.count", ("team:infra",))] == (3, "c")
    assert by2[("rt.count", ("team:infra",))] == (3, "c")
    # NaN quantile never emits
    assert not any(n == "rt.99percentile" for n, *_ in second)

    # a series first appearing mid-stream counts its FULL value
    # (stats.go:85-88: the cache has a basis, the series is new); an
    # unchanged counter emits a zero delta rather than being suppressed
    scrape3 = scrape2 + '# TYPE newcomer counter\nnewcomer 7\n'
    third = tr.translate(scrape3)
    by3 = {(n, tuple(t)): (v, mt) for n, v, mt, t in third}
    assert by3[("newcomer", ("team:infra",))] == (7, "c")
    assert by3[("reqs", ("stage:prod", "team:infra"))] == (0.0, "c")


def test_emit_grpc_mode_statsd_and_ssf():
    """-grpc routes the same payloads over the server's gRPC ingest edge
    (cmd/veneur-emit/main.go:240-258 dogstatsd packets, 318-341 SSF
    spans) instead of UDP."""
    import time

    from veneur_tpu import config as config_mod
    from veneur_tpu.core.server import Server, _SpanSinkWorker
    from veneur_tpu.sinks import simple as simple_sinks
    from veneur_tpu.sinks.simple import ChannelSpanSink

    sink = simple_sinks.ChannelMetricSink()
    span_sink = ChannelSpanSink()
    srv = Server(config_mod.Config(
        grpc_listen_addresses=["tcp://127.0.0.1:0"], interval=0.05,
        percentiles=[0.5], hostname="h"), extra_metric_sinks=[sink])
    srv.span_sinks.append(span_sink)
    srv.span_workers.append(
        _SpanSinkWorker(span_sink, 100, 1, srv._shutdown))
    srv.start()
    try:
        port = srv.grpc_ingest_listeners[0].port

        # statsd counter over DogstatsdGRPC/SendPacket
        rc = cli_emit.main(["-hostport", f"127.0.0.1:{port}",
                            "-name", "grpc.emit", "-count", "7",
                            "-tag", "a:b", "-grpc"])
        assert rc == 0
        deadline = time.time() + 5
        got = []
        while time.time() < deadline:
            srv._drain_native()
            srv.flush()
            while not sink.queue.empty():
                got.extend(sink.queue.get())
            if any(m.name == "grpc.emit" for m in got):
                break
            time.sleep(0.05)
        by = {m.name: m for m in got}
        assert by["grpc.emit"].value == 7.0
        assert by["grpc.emit"].tags == ["a:b"]

        # SSF span over SSFGRPC/SendSpan
        rc = cli_emit.main(["-hostport", f"127.0.0.1:{port}",
                            "-name", "op.grpc", "-gauge", "1.5",
                            "-ssf", "-grpc"])
        assert rc == 0
        deadline = time.time() + 5
        span = None
        while time.time() < deadline and span is None:
            try:
                s = span_sink.queue.get(timeout=0.2)
            except Exception:
                continue
            if s.name == "op.grpc":   # skip flush self-trace spans
                span = s
        assert span is not None and span.service == "veneur-emit"
        assert span.metrics[0].value == 1.5
    finally:
        srv.shutdown()


def _run_chip_smoke(*args):
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=600, cwd=repo, env=env)


def test_chip_smoke_rehearsal_runs_the_whole_control_flow():
    """`chip_smoke.py --rehearse`: the chip run's control flow on the CPU
    at a tiny size — global in-process, 2 CLI locals, sender child, every
    check — so a later PR cannot break the script unnoticed.  Its last
    line says ok: false: a rehearsal can never pass as a chip run."""
    import json

    run = _run_chip_smoke("--rehearse")
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-3000:]
    lines = [json.loads(ln) for ln in run.stdout.splitlines()]
    assert {"rehearsal": True} in lines
    checks = [ln for ln in lines if "check" in ln]
    assert checks and all(c["ok"] for c in checks)
    names = {c["check"] for c in checks}
    assert {"global_native_engine", "local_boot", "dense_flush_shape",
            "direct_interval", "percentiles_interval_0", "local_no_loss",
            "global_no_loss", "no_compile_after_first_interval"} <= names
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}


def test_chip_smoke_fails_without_an_accelerator():
    """No TPU -> non-zero exit before any traffic, and no result line."""
    run = _run_chip_smoke()
    assert run.returncode != 0
    assert '"ok"' not in run.stdout.splitlines()[-1]
    assert "sender" not in run.stdout
