"""The V1 import's identity->row cache outlives the flush (PR 41, half 2).

A cached row is good for as long as it holds its key; only a recycled row
(the idle GC in `end_interval`, `release_keys`) can break that, so the cut
clears the whole cache when the arenas' `recycled` totals moved and leaves
it alone otherwise.  What must hold whatever the cache does: every
interval's flush is what a fresh aggregator — whose cache is cold, the
parent's behaviour — flushes for the same payloads."""

import importlib.util
import os
import types

import numpy as np
import pytest

from veneur_tpu import config as config_mod
from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.aggregator import MetricAggregator
from veneur_tpu.core.server import Server
from veneur_tpu.forward.client import ForwardClient
from veneur_tpu.protocol import forward_pb2, metric_pb2, tdigest_pb2
from veneur_tpu.sinks.simple import ChannelMetricSink
from veneur_tpu.sketches import hll as hll_mod

GC = arena_mod.IDLE_GC_INTERVALS


# -- one record of each family, by (name, value seed) ------------------------

def _counter(name, v, tags=("t:1", "env:p")):
    return metric_pb2.Metric(name=name, type=metric_pb2.Counter,
                             tags=list(tags),
                             counter=metric_pb2.CounterValue(value=int(v)))


def _gauge(name, v, tags=("t:1", "env:p")):
    return metric_pb2.Metric(name=name, type=metric_pb2.Gauge,
                             tags=list(tags),
                             gauge=metric_pb2.GaugeValue(value=float(v)))


def _set(name, v, tags=("t:1", "env:p")):
    sk = hll_mod.HLLSketch()
    for i in range(20 + 7 * int(v)):
        sk.insert(b"%s/%d" % (name.encode(), i))
    return metric_pb2.Metric(name=name, type=metric_pb2.Set,
                             tags=list(tags),
                             set=metric_pb2.SetValue(
                                 hyper_log_log=sk.marshal()))


def _digest(name, v, tags=("t:1", "env:p")):
    means = np.sort(np.random.default_rng([len(name), int(v)]).gamma(
        2.0, 10.0, 8)) + float(v)
    td = tdigest_pb2.MergingDigestData(
        main_centroids=[tdigest_pb2.Centroid(mean=float(m), weight=2.0)
                        for m in means],
        compression=100.0, min=float(means[0]) - 0.5,
        max=float(means[-1]) + 0.5,
        reciprocalSum=float((2.0 / means).sum()))
    return metric_pb2.Metric(
        name=name, type=metric_pb2.Timer, scope=metric_pb2.Mixed,
        tags=list(tags), histogram=metric_pb2.HistogramValue(t_digest=td))


_RECORD = {"counters": _counter, "gauges": _gauge, "sets": _set,
           "digests": _digest}
# (path, family) pairs whose records ask the cache for their row: the
# scanned payload for all four families, import_pb_batch for counters
# and gauges (its sets and histograms keep _import_slow_pb: row_for
# every time, in neither count)
_CACHED = [("pb", "counters"), ("pb", "gauges"), ("scan", "counters"),
           ("scan", "gauges"), ("scan", "digests"), ("scan", "sets")]
_ALL = _CACHED + [("pb", "digests"), ("pb", "sets")]


def _importer(path):
    if path == "scan":
        import veneur_tpu.ingest as ingest_mod
        try:
            ingest_mod.load_library()
        except Exception as e:      # noqa: BLE001
            pytest.skip(f"no native engine here: {e}")

        def run(agg, pbs):
            assert agg._native_import is not False
            return agg.import_payload(forward_pb2.MetricList(
                metrics=pbs).SerializeToString())
        return run
    return lambda agg, pbs: agg.import_pb_batch(pbs)


def _flushed(agg) -> list:
    res = agg.flush(is_local=False)
    return sorted((m.name, tuple(m.tags), m.value) for m in res.metrics)


def _cold(imp, pbs) -> list:
    """What an aggregator that has never seen a key flushes for `pbs`."""
    agg = MetricAggregator(percentiles=[0.5, 0.99])
    assert imp(agg, pbs) == (len(pbs), 0)
    return _flushed(agg)


def _row_counts(agg) -> tuple:
    seg = agg.last_flush_segments
    return (seg["import_row_misses"], seg["import_row_hits"],
            seg["import_row_cache_clears"])


@pytest.mark.parametrize("path,family", _ALL)
def test_a_key_of_every_interval_is_resolved_once(path, family):
    imp, rec = _importer(path), _RECORD[family]
    agg = MetricAggregator(percentiles=[0.5, 0.99])
    cached = (path, family) in _CACHED
    for interval in (1, 2, 3):
        pbs = [rec("k.a", interval), rec("k.b", 10 + interval)]
        assert imp(agg, pbs) == (2, 0)
        got = _flushed(agg)
        assert got and got == _cold(imp, pbs), interval
        want = ((2, 0) if interval == 1 else (0, 2)) if cached else (0, 0)
        assert _row_counts(agg) == (*want, 0), interval
    if family == "counters":
        assert ("k.a", ("t:1", "env:p"), 3.0) in got
    assert len(agg._import_row_cache) == (2 if cached else 0)


@pytest.mark.parametrize("path,family", _CACHED)
def test_a_recycled_row_never_gives_a_new_key_the_old_keys_data(path,
                                                               family):
    imp, rec = _importer(path), _RECORD[family]
    agg = MetricAggregator(percentiles=[0.5, 0.99])
    ar = getattr(agg, family)
    # interval 1: OLD and STAY; from then on STAY alone
    first = [rec("k.old", 1), rec("k.stay", 2)]
    assert imp(agg, first) == (2, 0)
    assert _flushed(agg) == _cold(imp, first)
    old_row = next(r for (k, _), r in ar.kdict.items() if k.name == "k.old")
    clears = []
    for interval in range(2, GC + 2):
        pbs = [rec("k.stay", interval)]
        assert imp(agg, pbs) == (1, 0)
        assert _flushed(agg) == _cold(imp, pbs)
        misses, hits, cleared = _row_counts(agg)
        assert (misses, hits) == (0, 1), interval   # never cleared so far
        clears.append(cleared)
    # OLD sat out IDLE_GC_INTERVALS cuts: the last one freed its row and
    # cleared the cache, for that reason
    assert clears == [0] * (GC - 1) + [1]
    assert agg.last_flush_segments["columns_by_family"]["cache"].keys() \
        == {"recycled"}
    assert not agg._import_row_cache and ar.recycled == 1
    assert all(k.name != "k.old" for k, _ in ar.kdict)
    # a DIFFERENT key takes the recycled row, and OLD comes back beside
    # it in the same payload: each flushes its own data
    pbs = [rec("k.new", 5), rec("k.stay", 6), rec("k.old", 7),
           rec("k.new", 8)]
    assert imp(agg, pbs) == (4, 0)
    rows = {k.name: r for (k, _), r in ar.kdict.items()}
    assert rows["k.new"] == old_row != rows["k.old"]
    assert _flushed(agg) == _cold(imp, pbs)
    assert _row_counts(agg) == (3, 1, 0)
    # and the interval after, all of them from the cache
    pbs = [rec("k.old", 9), rec("k.new", 10), rec("k.stay", 11)]
    assert imp(agg, pbs) == (3, 0)
    assert _flushed(agg) == _cold(imp, pbs)
    assert _row_counts(agg) == (0, 3, 0)


@pytest.mark.parametrize("path,family", _CACHED)
def test_an_eviction_by_release_keys_clears_the_cache(path, family):
    imp, rec = _importer(path), _RECORD[family]
    agg = MetricAggregator(percentiles=[0.5, 0.99])
    ar = getattr(agg, family)
    pbs = [rec("k.evicted", 1), rec("k.kept", 2)]
    assert imp(agg, pbs) == (2, 0)
    assert _flushed(agg) == _cold(imp, pbs)
    assert _row_counts(agg) == (2, 0, 0) and len(agg._import_row_cache) == 2
    # the eager form of the GC (the cardinality guard's and the cubes'
    # end of interval call it under the lock, after the cut)
    dk = next(dk for dk in ar.kdict if dk[0].name == "k.evicted")
    with agg.lock:
        assert ar.release_keys([dk]) == 1
    pbs = [rec("k.kept", 3)]
    assert imp(agg, pbs) == (1, 0)          # the cache still answers...
    assert _flushed(agg) == _cold(imp, pbs)
    assert _row_counts(agg) == (0, 1, 1)    # ...until this cut
    assert agg.last_flush_segments["columns_by_family"]["cache"].keys() \
        == {"recycled"}
    assert not agg._import_row_cache
    # another key takes the evicted row; the evicted key's return does
    # not land on it
    pbs = [rec("k.other", 4), rec("k.evicted", 5), rec("k.kept", 6)]
    assert imp(agg, pbs) == (3, 0)
    assert _flushed(agg) == _cold(imp, pbs)
    assert _row_counts(agg) == (3, 0, 0)


@pytest.mark.parametrize("family", ["counters", "gauges"])
def test_a_sender_that_permutes_its_tags_trips_the_size_bound(family):
    """import_pb_batch keys on the tags in wire order: one row, many
    keys.  Past twice the keys of the arenas it serves the cut clears
    the cache (reason `size`); the values are right throughout."""
    rec = _RECORD[family]
    imp = _importer("pb")
    agg = MetricAggregator(percentiles=[0.5])
    orders = [("a:1", "b:2", "c:3"), ("b:2", "a:1", "c:3"),
              ("c:3", "b:2", "a:1"), ("c:3", "a:1", "b:2")]
    pbs = [rec("k.perm", 1, tags=orders[0]), rec("k.perm", 2, tags=orders[1])]
    assert imp(agg, pbs) == (2, 0)
    got = _flushed(agg)
    assert [(n, v) for n, _, v in got] == [
        ("k.perm", 3.0 if family == "counters" else 2.0)]
    # two keys for one row: at the bound, not past it
    assert _row_counts(agg) == (2, 0, 0) and len(agg._import_row_cache) == 2
    pbs = [rec("k.perm", i + 1, tags=o) for i, o in enumerate(orders)]
    assert imp(agg, pbs) == (4, 0)
    got = _flushed(agg)
    assert [(n, v) for n, _, v in got] == [
        ("k.perm", 10.0 if family == "counters" else 4.0)]
    assert _row_counts(agg) == (2, 2, 1)
    assert agg.last_flush_segments["columns_by_family"]["cache"].keys() \
        == {"size"}
    assert not agg._import_row_cache
    assert len(getattr(agg, family).kdict) == 1


@pytest.mark.parametrize("path", ["pb", "scan"])
def test_a_guard_armed_import_caches_nothing(path):
    imp = _importer(path)
    agg = MetricAggregator(percentiles=[0.5], cardinality_key_budget=100)
    assert agg.cardinality is not None
    for interval in (1, 2):
        pbs = [_RECORD[f](f"g.{f}", interval) for f in sorted(_RECORD)]
        assert imp(agg, pbs) == (4, 0)
        assert not agg._import_row_cache
        assert len(_flushed(agg)) >= 4
        assert _row_counts(agg) == (0, 0, 0)


def test_restore_state_starts_with_an_empty_cache():
    imp = _importer("pb")
    agg = MetricAggregator(percentiles=[0.5])
    assert imp(agg, [_counter("r.c", 1)]) == (1, 0)
    meta, arrays = agg.checkpoint_state()
    fresh = MetricAggregator(percentiles=[0.5])
    fresh._import_row_cache[("stale", (), 0)] = 7
    fresh.restore_state(meta, arrays)
    assert not fresh._import_row_cache
    assert fresh.counters.hw == 1 and fresh._import_recycled_seen == 0
    assert imp(fresh, [_counter("r.c", 2)]) == (1, 0)
    assert ("r.c", ("t:1", "env:p"), 3.0) in _flushed(fresh)


# -- the served path ---------------------------------------------------------

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _bench_module(rel: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + rel.replace("/", "_")[:-3], os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_forwarder_that_stops_and_returns_is_answered_as_the_reference():
    """A global and two ForwardClients over 13 short intervals.  Fleet A
    forwards every interval; fleet B's keys stop after interval 2, are
    freed at the cut of interval 12 and return at 13 onto recycled rows.
    Every interval's emitted percentiles are `benchmark/reference/
    forward.py`'s answers for who forwarded in it."""
    gen = _bench_module("loadgen/forward.py")
    ref = _bench_module("reference/forward.py")
    p = {"locals": 1, "keys_per_local": 24, "samples_per_digest": 64,
         "samples_per_centroid": 8, "variants": 2, "sampled_keys": 24}
    cfg = {"server": {"percentiles": [0.5, 0.9, 0.99]},
           # the rule's limit is the benchmark's own (configs/
           # global-fleet8.json) and is the sharp one: a point of another
           # key or interval moves an answer by far more.  One local's 8
           # centroids are too coarse a sketch of its 64 samples for the
           # cell's envelope against the samples themselves: anywhere
           # inside the key's span passes
           "limits": {"percentile_span_err": 1e-5,
                      "vs_samples_span_err": {"0.5": 1.0, "0.9": 1.0,
                                              "0.99": 1.0}}}
    fleets = {"bench": (4100001, range(1, 14)),
              "benchb": (4100002, (1, 2, 13))}
    payloads = {}
    for prefix, (seed, _) in fleets.items():
        per_variant = []
        for v in range(p["variants"]):
            pbs = gen._build_pbs(gen.model(seed, p, v), 0, p)
            for pb in pbs:
                pb.name = prefix + pb.name[len(gen.PREFIX):]
            per_variant.append(pbs)
        payloads[prefix] = per_variant

    sink = ChannelMetricSink()
    glob = Server(config_mod.Config(
        grpc_address="127.0.0.1:0", interval=600.0,
        percentiles=cfg["server"]["percentiles"], hostname="g41"),
        extra_metric_sinks=[sink])
    glob.start()
    clients = {prefix: ForwardClient(
        f"127.0.0.1:{glob.grpc_import.port}", timeout_s=10.0,
        source=f"fleet-{prefix}") for prefix in fleets}
    seen = {prefix: [] for prefix in fleets}
    rows = []
    try:
        for interval in range(1, 14):
            for prefix, (_, when) in fleets.items():
                if interval in when:
                    clients[prefix].send_pbs(
                        payloads[prefix][interval % p["variants"]],
                        epoch=interval)
            glob.flush()
            assert glob.egress.settle(timeout_s=20.0)
            batch = []
            while not sink.queue.empty():
                batch += sink.queue.get_nowait()
            rows.append(glob.flush_timeline.snapshot()[-1])
            for prefix, (_, when) in fleets.items():
                got = {m.name: m.value for m in batch
                       if m.name.startswith(prefix + ".h.")
                       and m.name.endswith("percentile")}
                if interval in when:
                    seen[prefix].append({"interval": interval, "got": got,
                                         "percentile_metrics": len(got)})
                else:
                    assert not got, (interval, prefix)
    finally:
        for c in clients.values():
            c.close()
        glob.shutdown()

    for prefix, (seed, when) in fleets.items():
        assert [iv["interval"] for iv in seen[prefix]] == list(when)
        fleet = types.SimpleNamespace(PREFIX=prefix, model=gen.model)
        pl = ref.plan(fleet, seed, p, cfg)
        for c in ref.compare(fleet, seed, p, cfg, pl, seen[prefix]):
            assert c["value"] <= c["limit"], (prefix, c)
    misses = [r["import_row_misses"] for r in rows]
    hits = [r["import_row_hits"] for r in rows]
    clears = [r["import_row_cache_clears"] for r in rows]
    assert [m + h for m, h in zip(misses, hits)] == (
        [48, 48] + [24] * 10 + [48])
    assert misses[0] == 48 and misses[1] == 0
    # fleet B's rows went back on the free list at the cut of interval
    # 12, the cache with them, and interval 13 resolved everything anew
    assert clears[11] == 1 and misses[12] == 48
    assert glob.aggregator.digests.recycled >= 24
    # in between a clear happens only where the server's own telemetry
    # let a key die, and nothing is resolved twice without one
    for i in range(2, 12):
        assert misses[i] == (24 if clears[i - 1] else 0), (i, misses, clears)
