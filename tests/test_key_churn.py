"""A node whose key space turns over (the benchmark's `node1-churn`): a
rolling deploy renames a twentieth of the timer and counter keys every
interval, at a small size on the CPU.

A served node (Server through the UDP listener, the native engine, the
drain, `srv.flush()`, the egress lane) is sent `IDLE_GC_INTERVALS` + 4
intervals of the benchmark's own `udp_churn` model, with an
`intern_gc_threshold` that one interval passes, and every interval's sink
batch is held to the benchmark's plain reference
(`benchmark/reference/udp_churn.py`, which imports nothing of the
program).  The rest reads the same run: a key's first interval under a
new name starts clean (shallow and past the 512-sample cap), a retired
name is silent, every line is counted across the intern clear, a row the
idle GC freed is the one a later birth receives, and the timeline row's
`key_births` / `key_deaths` / `arena_grows` / `intern_*` say what
happened.
"""

import functools
import importlib.util
import json
import os
import socket
import time

import numpy as np
import pytest

from veneur_tpu import config as config_mod
from veneur_tpu import http_api
from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.server import Server
from veneur_tpu.sinks.simple import ChannelMetricSink

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark")


def _load(folder, name):
    spec = importlib.util.spec_from_file_location(
        f"key_churn_{folder}_{name}", os.path.join(BENCH, folder,
                                                   f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gen = _load("loadgen", "udp_churn")
ref = _load("reference", "udp_churn")

with open(os.path.join(BENCH, "configs", "node1-churn.json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(BENCH, "configs", "node1-zipf.json")) as _f:
    ZIPF_CFG = json.load(_f)

INTERVALS = arena_mod.IDLE_GC_INTERVALS + 4
# the cell's mix at a test's size: 800 timer slots of which 40 are renamed
# an interval (their idle generations outgrow the arena's 1,024 rows), the
# three hottest ranks of a variant past the 512-sample cap
MIX = {
    "kind": "udp_churn", "interval_s": 2.0, "timer_keys": 800,
    "timer_lines": 12000, "zipf_constant": 0.99, "counter_lines": 400,
    "counter_keys": 40, "gauge_lines": 40, "gauge_keys": 10,
    "set_lines": 200, "set_keys": 10, "set_members": 1000,
    "max_datagram_bytes": 1400, "burst": 16, "due_share": 0.25,
    "pace_share": 0.5, "variants": 2, "sampled_keys": 800,
    "sampled_hot_ranks": 8, "sampled_renamed": 40, "churn_share": 0.05,
    "churn_period": 20, "aged_intervals": 0, "plan_intervals": INTERVALS,
}
# seeds under which a slot past the cap is renamed in an interval that
# finds it hot, after the idle GC has begun to free rows
# (test_the_seeds_rename_a_hot_key holds them to it)
SEEDS = (45, 54)
FAMILIES = {"timer": ("t", "digests"), "counter": ("c", "counters")}


def _failed(comparisons):
    return [(c["name"], c["value"], c["limit"]) for c in comparisons
            if not c["value"] <= c["limit"]]


@functools.lru_cache(maxsize=None)
def _names_by_interval(seed):
    """Per interval and family: the names that are sent a line."""
    out = []
    for n in range(INTERVALS):
        m = gen.model(seed, MIX, n)
        t = {gen.timer_name(k, int(m["t_gen"][k]))
             for k in np.nonzero(m["key_count"])[0].tolist()}
        c = {gen.counter_name(k, int(m["c_gen"][k]))
             for k in np.unique(m["c_key"]).tolist()}
        out.append({"timer": t, "counter": c})
    return out


def _intern_threshold(seed):
    """Identities the engine has interned once interval 8 is in: the drain
    tick that sees one more clears the table, in interval 9, and what
    grows again afterwards stays under it to the end of the run."""
    seen = set()
    for n, names in enumerate(_names_by_interval(seed)):
        seen |= names["timer"] | names["counter"]
        if n == 8:
            break
    return len(seen) + MIX["gauge_keys"] + MIX["set_keys"]


def _drive(seed):
    """The served run: per interval the sink batch reduced as the
    benchmark's collector reduces it, the timeline row, and the arenas'
    free lists and name -> row maps after the cut."""
    sink = ChannelMetricSink()
    threshold = _intern_threshold(seed)
    srv = Server(config_mod.Config(
        statsd_listen_addresses=["udp://127.0.0.1:0"], interval=600.0,
        percentiles=CFG["server"]["percentiles"], aggregates=["min", "max",
                                                              "count"],
        hostname="key-churn-test", native_ingest=True,
        ingest_drain_interval=0.02, intern_gc_threshold=threshold,
        ), extra_metric_sinks=[sink])
    out = {"seed": seed, "threshold": threshold, "intervals": []}
    try:
        srv.start()
        agg, native = srv.aggregator, srv.native
        sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        addr = tuple(srv.statsd_addrs[0][1])
        sent = 0
        out["capacity0"] = {f: getattr(agg, a).capacity
                            for f, (_, a) in FAMILIES.items()}
        for n in range(INTERVALS):
            m = gen.model(seed, MIX, n)
            dgs = gen.udp.pack(gen.format_lines(m, MIX),
                               MIX["max_datagram_bytes"])
            for i, d in enumerate(dgs):
                sock.sendto(d, addr)
                if i % 16 == 15:
                    time.sleep(0.004)   # loopback: do not outrun the reader
            sent += gen.ledger(MIX)["lines"]
            give_up = time.time() + 30
            while time.time() < give_up:
                # the drainer's own call (it runs beside this one)
                srv._count_drained(native.drain_or_gc(threshold))
                if native.engine.totals()[0] >= sent:
                    break
                time.sleep(0.02)
            live_before = {f: set(getattr(agg, a).kdict.values())
                           for f, (_, a) in FAMILIES.items()}
            events = agg.compile_events
            srv.flush()
            assert srv.egress.settle(timeout_s=30.0)
            batch = sink.queue.get(timeout=10)
            got, count_sum, pm = {}, 0.0, 0
            for mt in batch:
                if not mt.name.startswith("bench."):
                    continue
                got[mt.name] = mt.value
                if mt.name.startswith("bench.t."):
                    pm += mt.name.endswith("percentile")
                    if mt.name.endswith(".count"):
                        count_sum += mt.value
            rows = {}
            for f, (_, a) in FAMILIES.items():
                rows[f] = {k.name: r
                           for (k, _s), r in getattr(agg, a).kdict.items()
                           if k.name.startswith("bench.")}
            out["intervals"].append({
                "interval": n, "got": got, "count_sum": count_sum,
                "percentile_metrics": pm,
                "row": srv.flush_timeline.snapshot()[-1],
                "vars": http_api.debug_vars(srv)["key_lifecycle"],
                "compiled_before_flush": events,
                "compile_events": agg.compile_events,
                "rows": rows,
                "freed": {f: live_before[f] - set(
                    getattr(agg, a).kdict.values())
                    for f, (_, a) in FAMILIES.items()},
                "capacity": {f: getattr(agg, a).capacity
                             for f, (_, a) in FAMILIES.items()},
                "engine_lines": native.engine.totals()[0], "sent": sent})
        out["totals"] = native.engine.totals()
        out["intern_clears"] = native.intern_clears
        end = [s for s in srv.flight_recorder.snapshot()
               if s["name"] == "flush.seg.snapshot.columns.end"]
        out["end_span_tags"] = [s["tags"] for s in end]
        sock.close()
    finally:
        srv.shutdown()
    return out


_RUNS = {}


@pytest.fixture(scope="module", params=SEEDS)
def run(request):
    seed = request.param
    if seed not in _RUNS:
        _RUNS[seed] = _drive(seed)
    return _RUNS[seed]


@pytest.fixture(scope="module")
def plans():
    return {seed: ref.plan(gen, seed, MIX, CFG) for seed in SEEDS}


def _hot_renamed(seed):
    """(interval, slot) pairs: a slot renamed in an interval in which it
    receives more than the cap's 512 samples."""
    out = []
    for n in range(INTERVALS):
        m = gen.model(seed, MIX, n)
        out += [(n, k) for k in m["t_renamed"].tolist()
                if m["key_count"][k] > CFG["limits"]["hot_key_samples"]]
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_the_seeds_rename_a_hot_key(seed):
    assert [n for n, _ in _hot_renamed(seed)
            if n > arena_mod.IDLE_GC_INTERVALS], "pick another seed"
    # and the clear is planned where a later regrowth cannot reach it
    names = _names_by_interval(seed)
    after = set()
    for n in range(9, INTERVALS):
        after |= names[n]["timer"] | names[n]["counter"]
    assert len(after) + MIX["gauge_keys"] + MIX["set_keys"] \
        <= _intern_threshold(seed)


# -- held to the plain reference, every interval ------------------------------

def test_every_interval_matches_the_reference(run, plans):
    pl = plans[run["seed"]]
    for iv in run["intervals"]:
        comps = ref.compare(gen, run["seed"], MIX, CFG, pl, [iv])
        assert not _failed(comps), (iv["interval"], _failed(comps))
    by = {c["name"]: c for c in ref.compare(
        gen, run["seed"], MIX, CFG, pl, run["intervals"])}
    assert by["hot_p50_rank_err"]["value"] > 0        # hot keys were there
    assert by["intervals_without_renamed_keys"]["value"] == 0


@pytest.mark.parametrize("depth", ["shallow", "past_the_cap"])
def test_renamed_key_starts_clean(run, plans, depth):
    """The configuration's `renamed_key_starts_clean`: the name's first
    interval answers from that interval's samples alone."""
    seed, mdl = run["seed"], plans[run["seed"]]["model"]
    pcts = CFG["server"]["percentiles"]
    checked = 0
    for n in range(1, INTERVALS):
        m = gen.model(seed, MIX, n)
        got = run["intervals"][n]["got"]
        for k in m["t_renamed"].tolist():
            e = mdl.stats(n % MIX["variants"], k)
            if e is None or ("one_stage" in e) != (depth == "past_the_cap"):
                continue
            base = gen.timer_name(k, int(m["t_gen"][k]))
            assert got[f"{base}.count"] == e["n"]
            assert got[f"{base}.min"] == pytest.approx(e["min"], abs=1e-3)
            assert got[f"{base}.max"] == pytest.approx(e["max"], abs=1e-3)
            for q, want in zip(pcts, e.get("hazen", e.get("one_stage"))):
                have = got[f"{base}.{int(q * 100)}percentile"]
                if depth == "shallow":
                    assert abs(have - want) <= 1e-5 * e["span"] \
                        + 2.0 ** -22 * abs(want)
                else:
                    assert abs(ref.tc.rank_of(e["curve"], have) - q) \
                        <= 2.0 * ref.tc.cluster_width(q, 100.0)
            # the name it retired said nothing
            old = gen.timer_name(k, int(m["t_gen"][k]) - 1)
            assert not [x for x in got if x.startswith(old + ".")]
            checked += 1
    assert checked >= (1 if depth == "past_the_cap" else 200)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_retired_name_is_silent(run, family):
    """The configuration's `retired_key_is_silent`: a name emits in
    exactly the intervals that sent it a line."""
    names = _names_by_interval(run["seed"])
    letter = FAMILIES[family][0]
    for n, iv in enumerate(run["intervals"]):
        emitted = {x if family == "counter" else x.rsplit(".", 1)[0]
                   for x in iv["got"] if x.startswith(f"bench.{letter}.")}
        assert emitted == names[n][family], n
    if family == "timer":
        for n, iv in enumerate(run["intervals"]):
            assert iv["percentile_metrics"] == 3 * len(names[n]["timer"])


def test_no_line_lost_across_the_intern_clear(run):
    """The configuration's `no_line_lost`, with the engine's table cleared
    in the middle of the run: one clear, on one interval's row, every
    identity that was still live registered again, and every line of
    every interval counted."""
    lines, malformed, _packets, too_long = run["totals"]
    assert lines == INTERVALS * gen.ledger(MIX)["lines"]
    assert malformed == 0 and too_long == 0
    for iv in run["intervals"]:
        assert iv["engine_lines"] == iv["sent"]
        assert iv["count_sum"] == MIX["timer_lines"]
    assert run["intern_clears"] == 1
    cleared = [iv for iv in run["intervals"] if "intern_clears" in iv["row"]]
    assert [iv["interval"] for iv in cleared] == [9]
    row = cleared[0]["row"]
    assert row["intern_clears"] == 1 and row["intern_clear_ms"] > 0
    # between the clear and the cut the interval's remaining lines found
    # their names' rows again
    assert 0 < row["intern_reregistered"] <= row["arena_rows_live"]
    assert cleared[0]["vars"]["intern_clears"] == 1
    for iv in run["intervals"]:
        if iv is not cleared[0]:
            assert "intern_clear_ms" not in iv["row"]
            assert "intern_reregistered" not in iv["vars"]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_recycled_row_is_the_one_a_later_birth_receives(run, family):
    """Rows the idle GC frees at a cut are handed out again, last freed
    first, to the names born in the next interval — which start clean
    (test_every_interval_matches_the_reference)."""
    names = _names_by_interval(run["seed"])
    gc = arena_mod.IDLE_GC_INTERVALS
    recycled = 0
    for n in range(gc, INTERVALS - 1):
        freed = run["intervals"][n]["freed"][family]
        # the names that died are those last sent a line gc cuts before
        last_sent = names[n - gc][family] - set().union(
            *(names[j][family] for j in range(n - gc + 1, n + 1)))
        assert len(freed) == len(last_sent) > 0, n
        born = names[n + 1][family] - set().union(
            *(names[j][family] for j in range(n + 1)))
        rows_of_born = {run["intervals"][n + 1]["rows"][family][x]
                        for x in born}
        assert len(rows_of_born & freed) == min(len(born), len(freed)) > 0
        recycled += len(rows_of_born & freed)
    assert recycled > 0


def test_births_less_deaths_is_the_change_in_live_rows(run):
    live = 0
    gc = arena_mod.IDLE_GC_INTERVALS
    for n, iv in enumerate(run["intervals"]):
        row = iv["row"]
        assert row["key_births"] - row["key_deaths"] \
            == row["arena_rows_live"] - live, n
        live = row["arena_rows_live"]
        assert row["arena_rows_hw"] >= row["arena_rows_live"]
        assert row["key_births"] > 0 and row["key_birth_held_ms"] > 0
        assert (row["key_deaths"] > 0) == (n >= gc), n
        assert iv["vars"]["key_births"] == row["key_births"]
        assert iv["vars"]["key_deaths"] == row["key_deaths"]
        assert iv["vars"]["arena_rows_live"] == row["arena_rows_live"]
    # from the first death on a flush frees about what it registers
    names = _names_by_interval(run["seed"])
    for n in range(gc + 1, INTERVALS):
        born = sum(len(names[n][f] - set().union(
            *(names[j][f] for j in range(n)))) for f in FAMILIES)
        assert run["intervals"][n]["row"]["key_births"] == born
    # the deaths by family ride the columns.end span
    tags = [t for t in run["end_span_tags"] if any(
        k.startswith("deaths.") for k in t)]
    assert len(tags) == INTERVALS - gc
    assert all({"deaths.digests", "deaths.counters"} <= set(t) for t in tags)


def test_arena_grows_counts_each_doubling_once(run):
    doublings = sum(
        int(np.log2(run["intervals"][-1]["capacity"][f]
                    / run["capacity0"][f])) for f in FAMILIES)
    # 800 timer slots x (1 + 0.05 x 10 idle generations) outgrow the
    # arena's 1,024 rows
    assert run["intervals"][-1]["capacity"]["timer"] == 2048
    assert sum(iv["row"]["arena_grows"] for iv in run["intervals"]) \
        == doublings == 1
    grew = [iv["interval"] for iv in run["intervals"]
            if iv["row"]["arena_grows"]]
    assert len(grew) == 1
    n = grew[0]
    assert run["intervals"][n]["capacity"]["timer"] == 2048
    assert run["intervals"][n - 1]["capacity"]["timer"] == 1024


def test_no_compile_with_the_arenas_at_their_grown_capacity(run):
    """The configuration's `no_compile_in_window`: once the arenas have
    grown and rows die and are born every interval, a flush launches only
    programs an earlier one launched."""
    grew = max(iv["interval"] for iv in run["intervals"]
               if iv["row"]["arena_grows"])
    steady = [iv for iv in run["intervals"]
              if iv["interval"] > max(grew, arena_mod.IDLE_GC_INTERVALS)]
    assert len(steady) >= 2
    assert len({iv["compile_events"] for iv in steady}
               | {steady[0]["compiled_before_flush"]}) == 1


# -- the configuration file ----------------------------------------------------

def test_the_configuration_keeps_node1_zipfs_shapes_and_adds_its_guarantees():
    server = dict(CFG["server"])
    assert server.pop("intern_gc_threshold") == 96800
    assert server.pop("hostname") != ZIPF_CFG["server"]["hostname"]
    zipf_server = dict(ZIPF_CFG["server"])
    zipf_server.pop("hostname")
    assert server == zipf_server
    assert CFG["limits"] == ZIPF_CFG["limits"]
    assert CFG["guarantees_numbers"] == ZIPF_CFG["guarantees_numbers"]
    assert set(CFG["guarantees"]) == set(ZIPF_CFG["guarantees"]) | {
        "renamed_key_starts_clean", "retired_key_is_silent"}
    assert CFG["reduced"] == ["interval", "rate", "intern_gc_threshold"]
    assert set(CFG["reduced"]) <= set(CFG["reduced_why"])
    with open(os.path.join(BENCH, "traffic", "udp-churn.json")) as f:
        churn = json.load(f)
    with open(os.path.join(BENCH, "traffic", "udp-zipf.json")) as f:
        zipf = json.load(f)
    for key, value in zipf.items():
        if key not in ("kind", "why", "rehearse"):
            assert churn[key] == value, key
    assert churn["churn_share"] * churn["churn_period"] == 1.0
    assert churn["aged_intervals"] > arena_mod.IDLE_GC_INTERVALS


def test_the_reference_imports_nothing_of_the_program():
    for name in ("udp_churn",):
        with open(os.path.join(BENCH, "reference", f"{name}.py")) as f:
            src = f.read()
        assert "veneur_tpu" not in src.replace(
            "imports nothing of the program", "")
        assert "import jax" not in src
