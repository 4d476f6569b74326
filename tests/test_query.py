"""Live query plane (veneur_tpu/query/): window rings, the fusion
engine, the /query HTTP surface, the proxy scatter-gather codec, and
the testbed oracle cell."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from veneur_tpu.core.aggregator import MetricAggregator
from veneur_tpu.query.engine import (QueryEngine, QueryError,
                                     merge_responses,
                                     weighted_quantiles_np)
from veneur_tpu.query.rings import WindowRing
from veneur_tpu.samplers import samplers as sm
from veneur_tpu.samplers.metric_key import MetricScope, UDPMetric


def _part(n_keys: int = 0, n_points: int = 0,
          name: str = "k") -> dict:
    """A minimal digest-family snapshot part."""
    rows = np.arange(n_keys, dtype=np.int64)
    names = np.asarray([f"{name}{i}" for i in range(n_keys)], object)
    tags = np.empty(n_keys, object)
    for i in range(n_keys):
        tags[i] = []
    return {
        "rows": rows,
        "names": names,
        "name_hashes": np.asarray([hash(f"{name}{i}")
                                   for i in range(n_keys)], np.int64)
        if n_keys else np.zeros(0, np.int64),
        "tags": tags,
        "kinds": np.asarray(["histogram"] * n_keys, object),
        "scopes": np.zeros(n_keys, np.int8),
        "staged": (np.zeros(n_points, np.int64),
                   np.arange(n_points, dtype=np.float64),
                   np.ones(n_points, np.float64)),
        "d_min": np.zeros(n_keys), "d_max": np.ones(n_keys),
        "d_weight": np.ones(n_keys), "d_sum": np.ones(n_keys),
        "d_rsum": np.ones(n_keys),
    }


def _agg(slots: int = 4, rules=(), **kw) -> MetricAggregator:
    return MetricAggregator(
        percentiles=[0.5, 0.99], query_window_slots=slots,
        query_slot_seconds=0.05,
        sketch_family_rules=list(rules), **kw)


def _ingest_histo(agg, name: str, vals) -> None:
    with agg.lock:
        for v in vals:
            agg._process_locked(UDPMetric(
                name=name, type=sm.TYPE_HISTOGRAM, value=float(v),
                scope=MetricScope.MIXED))


MOMENTS_RULE = {"match": "mh*", "family": "moments"}


# -- ring mechanics ---------------------------------------------------------

def test_ring_rotation_and_eviction_bounds():
    ring = WindowRing(3, 1.0)
    for i in range(7):
        ring.rotate(_part(), float(i + 1))
    st = ring.stats()
    assert st["slots"] == 3            # bounded at capacity
    assert st["cuts"] == 7
    assert st["evicted"] == 4
    assert st["last_cut_unix"] == 7.0
    take, info = ring.covering(slots=2, now=7.0)
    assert [s.t_end for s in take] == [7.0, 6.0]   # newest first
    assert info["fresh"] and not info["partial"]


def test_ring_covering_window_and_partial_semantics():
    ring = WindowRing(4, 1.0)
    # empty ring: nothing to fuse, partial, not fresh
    take, info = ring.covering(slots=1, now=1.0)
    assert take == [] and info["partial"] and not info["fresh"]
    for i in range(4):
        ring.rotate(_part(), float(i + 1))
    # a window covering the last ~2 slots
    take, info = ring.covering(window_s=1.5, now=4.2)
    assert [s.t_end for s in take] == [4.0, 3.0]
    assert not info["partial"] and info["fresh"]
    # a sub-slot window still answers from the newest completed cut
    take, info = ring.covering(window_s=0.01, now=4.2)
    assert [s.t_end for s in take] == [4.0]
    # more slots than the ring holds = partial coverage
    take, info = ring.covering(slots=9, now=4.2)
    assert len(take) == 4 and info["partial"]
    # a window reaching past the ring's memory = partial (cuts were
    # evicted: the first slot here is seq 0, so grow past it first)
    for i in range(4, 7):
        ring.rotate(_part(), float(i + 1))
    take, info = ring.covering(window_s=100.0, now=7.2)
    assert len(take) == 4 and info["partial"]


def test_slot_lookup_by_name_tags_and_kind():
    ring = WindowRing(2, 1.0)
    part = _part(n_keys=8)
    part["kinds"][3] = "timer"
    ring.rotate(part, 1.0)
    slot = ring.covering(slots=1, now=1.0)[0][0]
    assert slot.positions("k3", "") == (3,)
    assert slot.positions("k3", "", kind="timer") == (3,)
    assert slot.positions("k3", "", kind="histogram") == ()
    assert slot.positions("k3", "a:b") == ()      # tag mismatch
    assert slot.positions("nope", "") == ()


# -- the numpy eval twin ----------------------------------------------------

def test_weighted_quantiles_np_matches_jax_twin():
    import jax.numpy as jnp

    from veneur_tpu.sketches import tdigest as td
    rng = np.random.default_rng(3)
    vals = rng.gamma(2.0, 10.0, 257)
    wts = rng.integers(1, 5, 257).astype(np.float64)
    qs = [0.1, 0.5, 0.9, 0.99]
    got = weighted_quantiles_np(vals, wts, float(vals.min()),
                                float(vals.max()), qs)
    pad = 512
    dv = np.zeros((1, pad), np.float32)
    dw = np.zeros((1, pad), np.float32)
    dv[0, :257] = vals
    dw[0, :257] = wts
    ref = np.asarray(td.weighted_eval(
        jnp.asarray(dv), jnp.asarray(dw),
        jnp.asarray([vals.min()], jnp.float32),
        jnp.asarray([vals.max()], jnp.float32),
        jnp.asarray(qs, jnp.float32)))[0, :4]
    np.testing.assert_allclose(got, ref, rtol=2e-5)
    # empty cloud -> None
    assert weighted_quantiles_np(np.zeros(0), np.zeros(0), 0, 1,
                                 qs) is None


# -- engine fusion ----------------------------------------------------------

def test_engine_windowed_answer_matches_exact_quantiles():
    agg = _agg()
    eng = QueryEngine(agg)
    rng = np.random.default_rng(0)
    per_iv = []
    for _ in range(5):
        vals = rng.gamma(2.0, 10.0, 300)
        _ingest_histo(agg, "api.latency", vals)
        per_iv.append(vals)
        agg.flush(is_local=False)
    out = eng.query("api.latency", qs=[0.5, 0.99], slots=3)
    ref = np.concatenate(per_iv[-3:])
    assert out["count"] == len(ref)            # exact fused count
    assert out["slots_fused"] == 3 and out["fresh"]
    assert out["family"] == "tdigest"
    # raw staged points fuse exactly: the answer is the twin's
    # evaluation of the true window point cloud
    for q in (0.5, 0.99):
        exact = float(np.quantile(ref, q, method="hazen"))
        span = float(ref.max() - ref.min())
        assert abs(out["quantiles"][repr(q)] - exact) / span < 0.01
    # the payload is self-describing and mergeable
    p = out["payload"]
    assert p["family"] == "tdigest" and p["count"] == len(ref)


def test_engine_moments_window_fusion_is_vector_add():
    agg = _agg(rules=[MOMENTS_RULE])
    eng = QueryEngine(agg)
    rng = np.random.default_rng(1)
    per_iv = []
    for _ in range(4):
        vals = rng.gamma(2.0, 10.0, 200)
        _ingest_histo(agg, "mh.lat", vals)
        per_iv.append(vals)
        agg.flush(is_local=False)
    out = eng.query("mh.lat", qs=[0.5], slots=2)
    ref = np.concatenate(per_iv[-2:])
    assert out["family"] == "moments"
    assert out["count"] == len(ref)            # exact vector-add count
    assert out["payload"]["family"] == "moments"
    exact = float(np.quantile(ref, 0.5))
    span = float(ref.max() - ref.min())
    assert abs(out["quantiles"][repr(0.5)] - exact) / span < 0.05


def test_engine_mixed_family_window_flags_and_follows_mass():
    """One key living in BOTH families across a window (the documented
    cross-tier rules-mismatch degradation): the answer follows the
    larger-mass family and flags mixed_families."""
    agg = _agg(rules=[MOMENTS_RULE])
    eng = QueryEngine(agg)
    _ingest_histo(agg, "mh.mixed", np.full(30, 5.0))
    # force the SAME identity into the digest arena (what a
    # payload-routed import from a rules-mismatched tier does)
    with agg.lock:
        row = agg.digests.row_for(
            __import__("veneur_tpu.samplers.metric_key",
                       fromlist=["MetricKey"]).MetricKey(
                "mh.mixed", sm.TYPE_HISTOGRAM, ""),
            MetricScope.MIXED, [])
        agg.digests.sample(row, 7.0, 1.0)
        agg.digests.sample(row, 9.0, 1.0)
    agg.flush(is_local=False)
    out = eng.query("mh.mixed", qs=[0.5], slots=1)
    assert out["mixed_families"]
    assert out["family"] == "moments"          # 30 points beat 2
    assert out["count"] == 30.0


def test_engine_absent_key_and_disabled_plane():
    agg = _agg()
    eng = QueryEngine(agg)
    agg.flush(is_local=False)
    out = eng.query("never.seen", slots=1)
    assert out["count"] == 0.0 and out["family"] == "none"
    assert out["quantiles"] == {} and out["payload"] is None
    assert out["fresh"]          # the window itself is fresh; just empty
    off = MetricAggregator(percentiles=[0.5])
    assert off.query_rings is None
    with pytest.raises(QueryError) as ei:
        QueryEngine(off).query("x", slots=1)
    assert ei.value.code == 404


def test_engine_serve_contract_and_param_validation():
    agg = _agg()
    eng = QueryEngine(agg, tier="global")
    _ingest_histo(agg, "h", [1.0, 2.0, 3.0])
    agg.flush(is_local=False)
    code, body = eng.serve({"name": ["h"], "q": ["0.5,0.99"],
                            "slots": ["1"]})
    assert code == 200 and body["count"] == 3.0
    assert body["staleness_ms"] is not None
    assert eng.stats()["served"] == 1
    for bad in ({"q": ["0.5"]},                      # no name
                {"name": ["h"], "q": ["1.5"]},       # q out of range
                {"name": ["h"], "q": ["x"]},
                {"name": ["h"], "slots": ["0"]},
                {"name": ["h"], "window_s": ["-1"]},
                {"name": ["h"], "type": ["gauge"]}):
        code, body = eng.serve(bad)
        assert code == 400 and "error" in body
    assert eng.stats()["errors"] == 6


# -- cold-ring-on-restore contract -----------------------------------------

def test_checkpoint_restore_cold_starts_the_ring():
    """Rings are NOT checkpointed (the documented contract): a restore
    reproduces the arenas bit-exactly but the window ring starts cold —
    the first post-boot query answers partial until cuts refill it."""
    agg = _agg()
    _ingest_histo(agg, "h", [1.0, 2.0, 3.0])
    agg.flush(is_local=False)
    assert agg.query_rings["tdigest"].stats()["cuts"] == 1
    meta, arrays = agg.checkpoint_state()
    fresh = _agg()
    fresh.restore_state(meta, arrays)
    assert fresh.query_rings["tdigest"].stats()["cuts"] == 0
    out = QueryEngine(fresh).query("h", slots=1)
    assert out["slots_fused"] == 0 and out["partial"]
    assert not out["fresh"] and out["count"] == 0.0
    # one post-restore interval makes the plane serve again
    _ingest_histo(fresh, "h", [4.0, 5.0])
    fresh.flush(is_local=False)
    out = QueryEngine(fresh).query("h", slots=1)
    assert out["count"] == 2.0 and out["fresh"]


# -- the proxy merge codec --------------------------------------------------

def test_merge_responses_fuses_payloads_per_family():
    agg = _agg(rules=[MOMENTS_RULE])
    eng = QueryEngine(agg)
    _ingest_histo(agg, "h", [1.0, 2.0, 3.0, 4.0])
    _ingest_histo(agg, "mh0", [10.0, 20.0])
    agg.flush(is_local=False)
    r_td = eng.query("h", qs=[0.5], slots=1)
    merged = merge_responses([r_td, r_td], [0.5])
    assert merged["family"] == "tdigest"
    assert merged["count"] == 8.0              # point clouds concat
    # a doubled cloud keeps the same median
    assert merged["quantiles"][repr(0.5)] == \
        r_td["quantiles"][repr(0.5)]
    r_mo = eng.query("mh0", qs=[0.5], slots=1)
    merged = merge_responses([r_mo, r_mo], [0.5])
    assert merged["family"] == "moments" and merged["count"] == 4.0
    # mixed upstream families: larger mass wins, flagged
    merged = merge_responses([r_td, r_mo], [0.5])
    assert merged["mixed_families"] and merged["family"] == "tdigest"
    # no payloads at all
    merged = merge_responses([], [0.5])
    assert merged["family"] == "none" and merged["count"] == 0.0


def test_proxy_untyped_query_fans_out_to_both_kind_owners():
    """The wire routing key embeds the metric KIND, so 'x' as a
    histogram and 'x' as a timer can live on different globals.  A
    /query that does not pin type= must reach BOTH kind-routed owners
    (deduped when they coincide) — the histogram-only default silently
    answered count=0 for timer keys."""
    import http.server
    import threading

    from veneur_tpu.proxy.proxy import Proxy, ProxyConfig
    from veneur_tpu.sources.proxy import GrpcImportServer

    hits: dict = {}

    def stub(label: str):
        class H(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                hits.setdefault(label, []).append(self.path)
                body = json.dumps({
                    "name": "x", "tags": [], "count": 0.0,
                    "sum": 0.0, "min": None, "max": None,
                    "family": "none", "quantiles": {},
                    "payload": None, "mixed_families": False,
                    "slots_fused": 1, "partial": False,
                    "fresh": True, "staleness_ms": 1.0}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=srv.serve_forever,
                         daemon=True).start()
        return srv, f"127.0.0.1:{srv.server_address[1]}"

    g1 = GrpcImportServer("127.0.0.1:0", import_metric=lambda m: None)
    g2 = GrpcImportServer("127.0.0.1:0", import_metric=lambda m: None)
    g1.start()
    g2.start()
    h1, h1_addr = stub("A")
    h2, h2_addr = stub("B")
    a1, a2 = f"127.0.0.1:{g1.port}", f"127.0.0.1:{g2.port}"
    proxy = Proxy(ProxyConfig(
        grpc_address="127.0.0.1:0", http_address="127.0.0.1:0",
        static_destinations=[a1, a2],
        query_destinations={a1: h1_addr, a2: h2_addr}))
    try:
        proxy.handle_discovery()
        # find a name whose histogram and timer keys route to
        # DIFFERENT members (exists with overwhelming probability)
        name = None
        for i in range(200):
            cand = f"split{i}"
            dh = proxy.destinations.get(
                proxy._query_routing_key(cand, [], "histogram"))
            dt = proxy.destinations.get(
                proxy._query_routing_key(cand, [], "timer"))
            if dh is not dt:
                name = cand
                break
        assert name is not None
        code, body = proxy.handle_query({"name": [name]})
        assert code == 200
        assert len(body["upstreams"]) == 2       # both kind owners
        assert set(hits) == {"A", "B"}
        hits.clear()
        code, body = proxy.handle_query({"name": [name],
                                         "type": ["timer"]})
        assert code == 200
        assert len(body["upstreams"]) == 1       # pinned kind: one hop
        assert len(hits) == 1
        # mesh_fanout: every member holds the FULL replicated data, so
        # exactly ONE member answers (merging replicas double-counts)
        mesh = Proxy(ProxyConfig(
            grpc_address="127.0.0.1:0", http_address="127.0.0.1:0",
            mesh_fanout=True, static_destinations=[a1, a2],
            query_destinations={a1: h1_addr, a2: h2_addr}))
        try:
            mesh.handle_discovery()
            hits.clear()
            code, body = mesh.handle_query({"name": [name]})
            assert code == 200
            assert len(body["upstreams"]) == 1
            assert len(hits) == 1
        finally:
            mesh.stop()
    finally:
        proxy.stop()
        h1.shutdown()
        h2.shutdown()
        g1.stop()
        g2.stop()


def test_proxy_query_routing_key_sorts_tags():
    """Wire tags are parse-canonicalized (sorted), so the owning
    global was chosen from the sorted join — a query's tag ORDER must
    not change the ring member it routes to."""
    from veneur_tpu.proxy.proxy import Proxy, ProxyConfig
    proxy = Proxy(ProxyConfig(grpc_address="127.0.0.1:0",
                              http_address="127.0.0.1:0"))
    try:
        k1 = proxy._query_routing_key("x", ["b:1", "a:1"], "histogram")
        k2 = proxy._query_routing_key("x", ["a:1", "b:1"], "histogram")
        assert k1 == k2 == "xhistograma:1,b:1"
    finally:
        proxy.stop()


# -- the HTTP surface -------------------------------------------------------

def test_http_query_endpoint_and_debug_vars(tmp_path):
    from veneur_tpu import config as config_mod
    from veneur_tpu.core.server import Server
    from veneur_tpu.http_api import HttpApi
    srv = Server(config_mod.Config(interval=10.0,
                                   percentiles=[0.5, 0.99],
                                   query_window_slots=4,
                                   hostname="q-test"))
    srv.start()
    api = HttpApi(srv, "127.0.0.1:0")
    api.start()
    try:
        _ingest_histo(srv.aggregator, "tb.q", [1.0, 2.0, 3.0])
        srv.flush()
        base = f"http://127.0.0.1:{api.address[1]}"
        with urllib.request.urlopen(
                f"{base}/query?name=tb.q&slots=1&q=0.5") as resp:
            body = json.loads(resp.read())
        # no forward_address => a global-tier server
        assert body["count"] == 3.0 and body["tier"] == "global"
        assert body["quantiles"][repr(0.5)] == 2.0
        # malformed -> 400 with an error body
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/query?q=0.5")
        assert ei.value.code == 400
        # telemetry lands at /debug/vars -> query
        with urllib.request.urlopen(f"{base}/debug/vars") as resp:
            dv = json.loads(resp.read())
        assert dv["query"]["served"] == 1
        assert dv["query"]["errors"] == 1
        assert dv["query"]["rings"]["tdigest"]["cuts"] >= 1
        # the query span reached the flight recorder
        names = [r["name"] for r in srv.flight_recorder.snapshot()]
        assert "query" in names
    finally:
        api.stop()
        srv.shutdown()


def test_http_query_404_when_disabled():
    from veneur_tpu import config as config_mod
    from veneur_tpu.core.server import Server
    from veneur_tpu.http_api import HttpApi
    srv = Server(config_mod.Config(interval=10.0,
                                   query_window_slots=0,
                                   hostname="q-off"))
    srv.start()
    api = HttpApi(srv, "127.0.0.1:0")
    api.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{api.address[1]}/query?name=x")
        assert ei.value.code == 404
    finally:
        api.stop()
        srv.shutdown()


# -- the testbed oracle cell ------------------------------------------------

def test_testbed_query_oracle_cell():
    """The fast tier-1 cell: windowed /query answers on all three
    tiers gated on the exact CPU oracle — exact fused counts,
    per-family committed envelopes, the staleness contract, and the
    one-global-per-key invariant read back through the query plane."""
    from veneur_tpu.testbed.dryrun import run_dryrun
    # histo_samples stays at the dossier's committed small-n shape
    # (n=200): the moments maxent envelope is evidence-backed down to
    # 200 samples, and a windowed fuse of fewer has no committed bar
    report = run_dryrun(n_locals=1, n_globals=1, intervals=2,
                        histo_keys=1, moments_histo_keys=1,
                        counter_keys=2, set_keys=1, histo_samples=200,
                        query=True)
    assert report["ok"], report
    qr = report["query"]
    assert qr is not None and qr["ok"], qr
    assert qr["served"] > 0 and qr["errors"] == 0
    assert qr["envelope_ok"] and qr["staleness_ok"]
    assert qr["counts_exact"]
    assert qr["p99_ms"] is not None and qr["staleness_ms"] is not None


@pytest.mark.slow
def test_testbed_query_oracle_full_sweep():
    """The full sweep: multiple locals and ring-routed globals, more
    intervals than the probe window (so windows genuinely slide), both
    sketch families."""
    from veneur_tpu.testbed.dryrun import run_dryrun
    report = run_dryrun(n_locals=2, n_globals=2, intervals=4,
                        histo_keys=3, moments_histo_keys=2,
                        histo_samples=200, query=True)
    assert report["ok"], report
    qr = report["query"]
    assert qr["ok"] and qr["served"] >= 40 and qr["errors"] == 0


# -- the ?since=&step= range form (multi-resolution retention) --------------

def test_range_form_param_validation_400s():
    """Every malformed range request answers 400, never a crash or a
    silent full-window fallback: future since=, step<=0, non-finite
    values, a lone since= or step=, until= at or before since=,
    mixing the range form with slots=/window_s=, and a bin count
    past MAX_RANGE_BINS."""
    import time as _time

    agg = _agg()
    eng = QueryEngine(agg)
    _ingest_histo(agg, "h", [1.0])
    agg.flush(is_local=False)
    now = _time.time()
    bad = [
        {"name": ["h"], "since": [repr(now + 60)], "step": ["1"]},
        {"name": ["h"], "since": [repr(now - 60)], "step": ["0"]},
        {"name": ["h"], "since": [repr(now - 60)], "step": ["-1"]},
        {"name": ["h"], "since": [repr(now - 60)], "step": ["nan"]},
        {"name": ["h"], "since": ["inf"], "step": ["1"]},
        {"name": ["h"], "since": ["x"], "step": ["1"]},
        {"name": ["h"], "since": [repr(now - 60)]},       # no step
        {"name": ["h"], "step": ["1"]},                   # no since
        {"name": ["h"], "since": [repr(now - 60)], "step": ["1"],
         "until": [repr(now - 60)]},                      # until<=since
        {"name": ["h"], "since": [repr(now - 60)], "step": ["1"],
         "slots": ["1"]},
        {"name": ["h"], "since": [repr(now - 60)], "step": ["1"],
         "window_s": ["5"]},
        {"name": ["h"], "since": [repr(now - 7 * 86400)],
         "step": ["0.001"]},                              # bins cap
    ]
    for q in bad:
        code, body = eng.serve(q)
        assert code == 400 and "error" in body, q
    assert eng.stats()["errors"] == len(bad)
    # the window forms stay hardened too
    for q in ({"name": ["h"], "window_s": ["0"]},
              {"name": ["h"], "window_s": ["nan"]},
              {"name": ["h"], "window_s": ["inf"]},
              {"name": ["h"], "window_s": ["-0.5"]}):
        code, body = eng.serve(q)
        assert code == 400 and "error" in body, q


def test_range_form_serves_bins_over_the_ring():
    """Without retention tiers the range form still answers from the
    window ring's slots, with coverage metadata per bin."""
    import time as _time

    agg = _agg()
    eng = QueryEngine(agg)
    # the first-ever cut's slot is zero-width (no prior cut anchors
    # its window start), so warm the ring before the measured flush
    agg.flush(is_local=False)
    # ...and give the measured slot a width: two back-to-back cuts can
    # lie under the planner's overlap slack (1e-4 of the step = 0.5 ms),
    # and a slot that narrow covers no bin (it failed by the clock)
    _time.sleep(0.01)
    _ingest_histo(agg, "h", [1.0, 2.0, 3.0, 4.0])
    agg.flush(is_local=False)
    since = _time.time() - 5.0
    code, body = eng.serve({"name": ["h"], "q": ["0.5"],
                            "since": [repr(since)], "step": ["5"]})
    assert code == 200 and body["range"]
    assert body["bins"] == len(body["series"]) >= 1
    assert "ring" in body["sources"]
    assert sum(e["count"] for e in body["series"]) == 4.0
    covered = [e for e in body["series"] if e["count"] > 0]
    assert covered and covered[0]["family"] == "tdigest"
    assert covered[0]["coverage_s"] > 0
    assert covered[0]["quantiles"][repr(0.5)] == 2.5


def test_range_form_404_when_query_plane_disabled():
    import time as _time

    agg = MetricAggregator(percentiles=[0.5], query_window_slots=0)
    eng = QueryEngine(agg)
    code, body = eng.serve({"name": ["h"],
                            "since": [repr(_time.time() - 10)],
                            "step": ["10"]})
    assert code == 404


def test_http_range_query_endpoint(tmp_path):
    """?since=&step= over HTTP end to end, against a server whose
    retention ladder is live: response carries bins/series/sources
    and the /debug/vars retention block grows its served counter."""
    import time as _time

    from veneur_tpu import config as config_mod
    from veneur_tpu.core.server import Server
    from veneur_tpu.http_api import HttpApi
    srv = Server(config_mod.Config(
        interval=10.0, percentiles=[0.5],
        query_window_slots=4, hostname="r-test",
        retention_tiers=[{"seconds": 0.25, "buckets": 4},
                         {"seconds": 0.5, "buckets": 4}],
        retention_dir=str(tmp_path / "tiers")))
    srv.start()
    api = HttpApi(srv, "127.0.0.1:0")
    api.start()
    try:
        t0 = _time.time()
        _ingest_histo(srv.aggregator, "tb.r", [1.0, 2.0, 3.0])
        srv.flush()
        assert srv.aggregator.retention.drain(timeout=10.0)
        base = f"http://127.0.0.1:{api.address[1]}"
        url = (f"{base}/query?name=tb.r&q=0.5"
               f"&since={t0 - 1.0}&step=10")
        with urllib.request.urlopen(url) as resp:
            body = json.loads(resp.read())
        assert body["range"] and body["bins"] >= 1
        assert sum(e["count"] for e in body["series"]) == 3.0
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"{base}/query?name=tb.r&since=1&step=0")
        assert ei.value.code == 400
        with urllib.request.urlopen(f"{base}/debug/vars") as resp:
            dv = json.loads(resp.read())
        assert dv["retention"]["compactions"] >= 1
        assert dv["retention"]["buckets"] >= 1
        assert dv["query"]["served"] >= 1
    finally:
        api.stop()
        srv.shutdown()
