"""Generator kind `udp_zipf` and its reference: the generator's ledger is
its model, the reference passes on the model's own answers, and it fails
the two controls the cell's limits were set between — the program's bf16
staging (the shallow keys' 1e-5) and a pre-reduce that drops a tick's tile
(the hot keys' rank limit, with `.count` still exact)."""

import json
import os

import numpy as np
import pytest

from conftest import BENCH, load, run_rehearsal

gen = load("loadgen", "udp_zipf.py")
ref = load("reference", "udp_zipf.py")
tc = load("reference", "tdigest_compress.py")

CELL = "zipf.hotset"
# the rehearsal's boot launches at the arena's pre-size: keep it a test's
SMALL = ("--server-override", "arena_initial_capacity=1024")


def _mix(rehearse=True):
    with open(os.path.join(BENCH, "traffic", "udp-zipf.json")) as f:
        p = json.load(f)
    if rehearse:
        p.update(p["rehearse"])
    p.pop("rehearse")
    return p


def _cfg():
    with open(os.path.join(BENCH, "configs", "node1-zipf.json")) as f:
        return json.load(f)


def test_ledger_is_the_model():
    p = _mix()
    led = gen.ledger(p)
    for v in range(p["variants"]):
        m = gen.model(11, p, v)
        lines = gen.format_lines(m, p)
        assert len(lines) == led["lines"] == (
            p["timer_lines"] + p["counter_lines"] + p["gauge_lines"]
            + p["set_lines"])
        timers = [ln for ln in lines if b"|ms|" in ln]
        assert len(timers) == led["counted_lines"] == p["timer_lines"]
        per_key = np.bincount(
            [int(ln.split(b":")[0].split(b".")[2]) for ln in timers],
            minlength=p["timer_keys"])
        np.testing.assert_array_equal(per_key, m["key_count"])
        np.testing.assert_array_equal(
            m["key_count"], np.bincount(m["t_key"],
                                        minlength=p["timer_keys"]))
        # the text carries the model's values, in its order
        assert timers[0] == b"bench.t.%d:%.3f|ms|#%s" % (
            m["t_key"][0], m["t_val"][0],
            gen.udp.key_tags(int(m["t_key"][0])).encode())
        # Zipf: the hottest rank takes ~1 / H of the lines, ranks descend
        by_rank = m["key_count"][m["rank_key"]]
        share = gen.rank_shares(p)
        assert abs(by_rank[0] / p["timer_lines"] - share[0]) < 0.03
        assert by_rank[:4].sum() > by_rank[4:8].sum() > by_rank[8:12].sum()
        # a hot key's samples are spread over the whole send
        where = np.nonzero(m["t_key"] == m["rank_key"][0])[0]
        assert where.min() < 0.05 * p["timer_lines"] \
            and where.max() > 0.95 * p["timer_lines"]
        datagrams = gen.udp.pack(lines, p["max_datagram_bytes"])
        assert max(map(len, datagrams)) <= p["max_datagram_bytes"]
        assert sum(d.count(b"\n") + 1 for d in datagrams) == led["lines"]


def test_the_hot_set_moves_between_variants():
    p = _mix()
    a, b = gen.model(11, p, 0), gen.model(11, p, 1)
    assert not np.array_equal(a["rank_key"], b["rank_key"])
    assert len(set(a["rank_key"][:8]) & set(b["rank_key"][:8])) <= 2
    assert not np.array_equal(a["t_val"], b["t_val"])
    # the same seed gives the same interval
    again = gen.model(11, p, 0)
    np.testing.assert_array_equal(a["t_key"], again["t_key"])
    np.testing.assert_array_equal(a["t_val"], again["t_val"])
    assert not np.array_equal(a["t_key"], gen.model(12, p, 0)["t_key"])


def test_full_size_depth_profile_is_the_issues():
    """At the cell's size the skew is what the configuration says:
    H ~ 12, the hottest key ~16.7k of 200,000 lines, ~33 keys past 512,
    ~275 past 64, ~30k keys touched."""
    p = _mix(rehearse=False)
    m = gen.model(5, p, 0)
    c = m["key_count"]
    assert 1 / gen.rank_shares(p)[0] == pytest.approx(12.0, abs=0.1)
    assert 15_500 < c.max() < 17_900
    assert 28 <= (c > 512).sum() <= 38
    assert 255 <= (c > 64).sum() <= 300
    assert 28_500 < (c > 0).sum() < 31_500
    assert c.sum() == p["timer_lines"]


def _own_answers(seed, p, cfg, pl, n_iv=4):
    """Intervals answered by the reference's own expectation: hazen for
    the shallow keys, the one-stage float64 compress for the hot ones."""
    pcts = cfg["server"]["percentiles"]
    out = []
    for iv in range(n_iv):
        v = iv % p["variants"]
        w = ref.expected(gen, seed, p, cfg, pl["keys"][v], v)
        got = {}
        for k, e in w["keys"].items():
            ans = e["hazen"] if "hazen" in e else e["one_stage"]
            for q, x in zip(pcts, ans):
                got[f"bench.t.{k}.{int(q * 100)}percentile"] = float(x)
            got[f"bench.t.{k}.min"] = e["min"]
            got[f"bench.t.{k}.max"] = e["max"]
            got[f"bench.t.{k}.count"] = float(e["n"])
        got.update({f"bench.c.{k}": val for k, val in w["counters"].items()})
        got.update({f"bench.g.{k}": val for k, val in w["gauges"].items()})
        got.update({f"bench.s.{k}": float(n) for k, n in w["sets"].items()})
        out.append({"interval": iv, "got": got,
                    "count_sum": float(p["timer_lines"]),
                    "percentile_metrics": w["touched"] * len(pcts)})
    return out


def _failed(comparisons):
    return [c["name"] for c in comparisons if not c["value"] <= c["limit"]]


def test_reference_passes_on_the_models_own_answers():
    p, cfg = _mix(), _cfg()
    pl = ref.plan(gen, 21, p, cfg)
    assert all(len(ks) == p["sampled_keys"] for ks in pl["keys"].values())
    # the hottest ranks of each variant are among its compared keys
    for v, ks in pl["keys"].items():
        assert set(gen.model(21, p, v)["rank_key"][:p["sampled_hot_ranks"]]
                   ) <= set(ks.tolist())
    ivs = _own_answers(21, p, cfg, pl)
    comps = ref.compare(gen, 21, p, cfg, pl, ivs)
    assert not _failed(comps), _failed(comps)
    by = {c["name"]: c for c in comps}
    # two cluster widths (configs/node1-zipf.json `hot_rank_widths`)
    assert by["hot_p50_rank_err"]["limit"] == pytest.approx(0.0209, abs=1e-4)
    assert by["hot_p99_rank_err"]["limit"] == pytest.approx(0.0042, abs=1e-4)
    assert 0 < by["hot_p50_rank_err"]["value"]     # hot keys were compared
    assert by["hot_p50_rank_dist_vs_one_stage"]["value"] == 0.0

    # a hot key three clusters off fails its rank limit and nothing else; a
    # lost sample fails the exact count; a shallow key 1e-4 of its span
    # off fails the hazen limit
    hot = next(k for k, e in ref.expected(
        gen, 21, p, cfg, pl["keys"][0], 0)["keys"].items()
        if "one_stage" in e)
    e = ref.expected(gen, 21, p, cfg, pl["keys"][0], 0)["keys"][hot]
    bad = _own_answers(21, p, cfg, pl)
    bad[0]["got"][f"bench.t.{hot}.50percentile"] = float(
        np.percentile(e["sorted"], 53.5, method="hazen"))
    assert _failed(ref.compare(gen, 21, p, cfg, pl, bad)) \
        == ["hot_p50_rank_err"]
    bad = _own_answers(21, p, cfg, pl)
    bad[2]["got"][f"bench.t.{hot}.count"] -= 1.0
    bad[2]["count_sum"] -= 1.0
    failed = _failed(ref.compare(gen, 21, p, cfg, pl, bad))
    assert failed == ["timer_samples_not_counted"] or failed == [
        "sampled_counts_not_exact", "timer_samples_not_counted"]
    bad = _own_answers(21, p, cfg, pl)
    cold = next(k for k, e2 in ref.expected(
        gen, 21, p, cfg, pl["keys"][0], 0)["keys"].items()
        if "hazen" in e2 and e2["n"] > 3)
    e2 = ref.expected(gen, 21, p, cfg, pl["keys"][0], 0)["keys"][cold]
    bad[0]["got"][f"bench.t.{cold}.90percentile"] += 1e-4 * e2["span"]
    assert _failed(ref.compare(gen, 21, p, cfg, pl, bad)) \
        == ["p90_span_err_vs_hazen"]


def _verdict(lines):
    return [ln for ln in lines if ln.get("info") == "verdict"][0]


def _failed_lines(lines):
    return [ln["compared"] for ln in lines if ln.get("ok") is False]


def test_rehearse_end_to_end_and_traced(bench_json):
    rc, lines, err = run_rehearsal(CELL, *SMALL, trace=1, seconds=6)
    assert rc == 0, err[-2000:]
    bad = [ln for ln in lines if ln.get("ok") is False or "problem" in ln]
    assert _verdict(lines)["comparisons_ok"], bad
    last = lines[-1]
    assert last["correct"] is False and last["failed"] == 0
    listed = {m["name"] for m in bench_json["per_layer"]
              if CELL in (m.get("workloads") or [CELL])}
    # what only a device trace can give is left out on the CPU
    on_cpu = {n for n in listed
              if "kernel" not in n and not n.startswith("hot_compress_ms")
              and "hbm_share" not in n}
    assert on_cpu <= set(last["metrics"]) <= listed
    assert last["metrics"]["hot_compress_held_ms"]["value"] > 0
    assert 0 < last["metrics"]["flush_dense_fill"]["value"] <= 100
    by = {ln["compared"]: ln for ln in lines if "compared" in ln}
    assert by["hot_p50_rank_err"]["value"] > 0       # hot keys were there
    assert by["intervals_without_hot_keys"]["value"] == 0


def test_bf16_staging_fails_the_shallow_keys():
    rc, lines, err = run_rehearsal(
        CELL, *SMALL, "--server-override", "digest_bf16_staging=true",
        seconds=6)
    assert rc == 0, err[-2000:]
    assert not _verdict(lines)["comparisons_ok"]
    assert any("span_err_vs_hazen" in n for n in _failed_lines(lines)), \
        _failed_lines(lines)
    assert not [ln for ln in lines if "problem" in ln]


def test_a_dropped_tile_fails_the_hot_keys_and_keeps_the_counts():
    rc, lines, err = run_rehearsal(CELL, *SMALL, seconds=6,
                                   script="tests/broken_hot_lane.py")
    assert rc == 0, err[-2000:]
    assert not _verdict(lines)["comparisons_ok"]
    failed = _failed_lines(lines)
    assert any(n.startswith("hot_p") and n.endswith("rank_err")
               for n in failed), failed
    # the host's scalars never knew: every count exact, nothing lost, and
    # the shallow keys untouched
    by = {ln["compared"]: ln for ln in lines if "compared" in ln}
    for name in ("sampled_counts_not_exact", "timer_samples_not_counted",
                 "minmax_span_err", "p50_span_err_vs_hazen",
                 "p99_span_err_vs_hazen", "lines_lost_at_engine"):
        assert by[name]["ok"], by[name]
    assert lines[-1]["correct"] is False
