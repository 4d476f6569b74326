"""Generator kind `udp_churn` and its reference: the model is a pure
function of (seed, mix, the deploy's interval), every slot is renamed
exactly once in a period, the lines carry the generation in name and tag,
the reference passes on the model's own answers and counts a dirty renamed
key, a retired name that answered and an interval beyond its plan; the
rehearsal walks the cell's control flow, and the two controls fail where
the cell's limits say they must."""

import json
import os

import numpy as np
import pytest

from conftest import BENCH, load, run_rehearsal

gen = load("loadgen", "udp_churn.py")
ref = load("reference", "udp_churn.py")

CELL = "zipf.churn"
# the rehearsal's boot launches at the arena's pre-size: keep it a test's
SMALL = ("--server-override", "arena_initial_capacity=1024")


def _mix(rehearse=True):
    with open(os.path.join(BENCH, "traffic", "udp-churn.json")) as f:
        p = json.load(f)
    if rehearse:
        p.update(p["rehearse"])
    p.pop("rehearse")
    return p


def _cfg():
    with open(os.path.join(BENCH, "configs", "node1-churn.json")) as f:
        return json.load(f)


def test_the_mix_is_udp_zipfs_letter_for_letter():
    with open(os.path.join(BENCH, "traffic", "udp-zipf.json")) as f:
        zipf = json.load(f)
    with open(os.path.join(BENCH, "traffic", "udp-churn.json")) as f:
        churn = json.load(f)
    for key, value in zipf.items():
        if key == "rehearse":
            for k2, v2 in value.items():
                assert churn["rehearse"][k2] == v2, k2
        elif key not in ("kind", "why"):
            assert churn[key] == value, key
    assert churn["kind"] == "udp_churn"
    assert churn["churn_share"] == 0.05 and churn["churn_period"] == 20
    assert churn["aged_intervals"] == 11


def test_the_model_is_a_pure_function_of_seed_mix_and_interval():
    p = _mix()
    for n in (0, 7, 31):
        a, b = gen.model(11, p, n), gen.model(11, p, n)
        assert sorted(a) == sorted(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
        assert gen.format_lines(a, p) == gen.format_lines(b, p)
        # udp_zipf's model of the interval's variant, slot for key
        z = gen.zipf.model(11, p, n % p["variants"])
        for k in z:
            np.testing.assert_array_equal(a[k], z[k])
    assert not np.array_equal(gen.model(11, p, 7)["t_gen"],
                              gen.model(12, p, 7)["t_gen"])
    assert not np.array_equal(gen.model(11, p, 7)["t_gen"],
                              gen.model(11, p, 8)["t_gen"])
    assert gen.deploy_interval(p, 3) == 3 + p["aged_intervals"]


def test_the_senders_payloads_are_the_models_lines():
    """The child keeps a variant's lines and writes anew only those of the
    slots renamed since: every payload is still `format_lines` of the
    interval's model, packed."""
    p = _mix()
    state = gen.prepare({"seed": 11, "traffic": p, "interval_s": 2.0})
    try:
        assert sorted(state["payloads"]) == [0, 1]
        for n in range(25):
            want = gen.udp.pack(gen.format_lines(gen.model(11, p, n), p),
                                p["max_datagram_bytes"])
            got = state["payloads"].pop(n) if n < 2 else gen._build(state, n)
            assert got == want, n
    finally:
        gen.close(state)


@pytest.mark.parametrize("family", sorted(gen.FAMILIES))
def test_each_slot_is_renamed_exactly_once_in_a_period(family):
    p = _mix(rehearse=False)
    n_slots = p[gen.FAMILIES[family][0]]
    block = gen.slot_block(5, p, family)
    period = p["churn_period"]
    times = np.zeros(n_slots, np.int64)
    for n in range(40, 40 + period):
        slots = gen.renamed(block, p, n)
        assert len(slots) == round(p["churn_share"] * n_slots)
        times[slots] += 1
        # the generation moves for exactly the renamed slots
        moved = gen.generation(block, p, n) - gen.generation(block, p, n - 1)
        np.testing.assert_array_equal(np.nonzero(moved)[0], np.sort(slots))
        assert set(moved.tolist()) == {0, 1}
    assert (times == 1).all()
    assert (gen.generation(block, p, 0) == (block == 0)).all()


def test_the_lines_carry_the_generation_in_name_and_tag():
    p = _mix()
    led = gen.ledger(p)
    m = gen.model(11, p, 25)
    lines = gen.format_lines(m, p)
    assert len(lines) == led["lines"]
    timers = [ln for ln in lines if b"|ms|" in ln]
    assert len(timers) == led["counted_lines"] == p["timer_lines"]
    k, v = int(m["t_key"][0]), m["t_val"][0]
    g = int(m["t_gen"][k])
    assert timers[0] == b"bench.t.%d.v%d:%.3f|ms|#%s,version:%d" % (
        k, g, v, gen.udp.key_tags(k).encode(), g)
    per_slot = np.bincount(
        [int(ln.split(b".")[2]) for ln in timers], minlength=p["timer_keys"])
    np.testing.assert_array_equal(per_slot, m["key_count"])
    counters = [ln for ln in lines if b"|c|" in ln]
    k = int(m["c_key"][0])
    assert counters[0] == b"bench.c.%d.v%d:%d|c|#version:%d" % (
        k, m["c_gen"][k], m["c_val"][0], m["c_gen"][k])
    # gauges and sets keep udp_zipf's names
    z = gen.zipf.format_lines(m, p)
    assert lines[-(p["gauge_lines"] + p["set_lines"]):] \
        == z[-(p["gauge_lines"] + p["set_lines"]):]
    # a renamed slot's old name is gone from the interval that renames it
    before = b"\n".join(gen.format_lines(gen.model(11, p, 24), p))
    after = b"\n".join(lines)
    for k in m["t_renamed"].tolist():
        old = b"bench.t.%d.v%d:" % (k, m["t_gen"][k] - 1)
        assert old not in after
        if gen.model(11, p, 24)["key_count"][k]:
            assert old in before


def test_full_size_turnover_is_the_issues():
    """2,500 timer and 500 counter slots an interval, ~2,550 of them
    touched; the names the engine has seen when the window's tenth flush
    comes are about the configuration's intern_gc_threshold."""
    p = _mix(rehearse=False)
    m = gen.model(5, p, 14)
    assert len(m["t_renamed"]) == 2500 and len(m["c_renamed"]) == 500
    touched = int((m["key_count"][m["t_renamed"]] > 0).sum()) + len(
        np.intersect1d(m["c_renamed"], m["c_key"]))
    assert 1700 < touched < 2300


def _own_answers(seed, p, cfg, pl, intervals):
    """Intervals answered by the reference's own expectation."""
    pcts = cfg["server"]["percentiles"]
    mdl = pl["model"]
    out = []
    for iv in intervals:
        n = mdl.deploy(iv)
        v = n % p["variants"]
        m = mdl.base[v]
        t_gen, c_gen = mdl.gens("t", n), mdl.gens("c", n)
        got = {}
        for slots in mdl.sample(n).values():
            for k in slots.tolist():
                e = mdl.stats(v, k)
                if e is None:
                    continue
                base = gen.timer_name(k, int(t_gen[k]))
                ans = e["hazen"] if "hazen" in e else e["one_stage"]
                for q, x in zip(pcts, ans):
                    got[f"{base}.{int(q * 100)}percentile"] = float(x)
                got[f"{base}.min"] = e["min"]
                got[f"{base}.max"] = e["max"]
                got[f"{base}.count"] = float(e["n"])
        c_want = np.bincount(m["c_key"], weights=m["c_val"],
                             minlength=p["counter_keys"])
        got.update({gen.counter_name(k, int(c_gen[k])): c_want[k]
                    for k in np.nonzero(c_want)[0].tolist()})
        got.update({f"bench.g.{k}": val for k, val in zip(
            m["g_key"].tolist(), m["g_val"].tolist())})
        got.update({f"bench.s.{k}": float(len(np.unique(
            m["s_mem"][m["s_key"] == k])))
            for k in np.unique(m["s_key"]).tolist()})
        out.append({"interval": iv, "got": got,
                    "count_sum": float(p["timer_lines"]),
                    "percentile_metrics":
                        int((m["key_count"] > 0).sum()) * len(pcts)})
    return out


def _failed(comparisons):
    return [c["name"] for c in comparisons if not c["value"] <= c["limit"]]


def test_reference_passes_on_the_models_own_answers_and_counts_the_faults():
    p, cfg = _mix(), _cfg()
    pl = ref.plan(gen, 21, p, cfg)
    mdl = pl["model"]
    ivs = _own_answers(21, p, cfg, pl, range(3, 9))
    assert all(set(iv["got"]) <= pl["wanted"] for iv in ivs)
    comps = ref.compare(gen, 21, p, cfg, pl, ivs)
    assert not _failed(comps), _failed(comps)
    by = {c["name"]: c for c in comps}
    assert by["hot_p50_rank_err"]["value"] > 0
    # every measured interval compared the hottest ranks, slots renamed in
    # it and slots renamed the interval before
    for iv in range(3, 9):
        n = mdl.deploy(iv)
        s = mdl.sample(n)
        assert set(s["hot"].tolist()) == set(
            mdl.base[n % 2]["rank_key"][:p["sampled_hot_ranks"]].tolist())
        assert len(s["renamed"]) == p["sampled_renamed"]
        assert set(s["renamed"].tolist()) <= set(
            mdl.renamed("t", n).tolist())
        assert set(s["renamed_before"].tolist()) <= set(
            mdl.renamed("t", n - 1).tolist())
        assert sum(map(len, s.values())) == p["sampled_keys"]

    # a renamed key that carried a count over: not clean, and not exact
    n = mdl.deploy(4)
    k = next(k for k in mdl.sample(n)["renamed"].tolist()
             if mdl.stats(n % 2, k) is not None)
    name = gen.timer_name(k, int(mdl.gens("t", n)[k]))
    bad = _own_answers(21, p, cfg, pl, range(3, 9))
    bad[1]["got"][f"{name}.count"] += 3.0
    assert _failed(ref.compare(gen, 21, p, cfg, pl, bad)) == [
        "sampled_counts_not_exact", "renamed_keys_not_clean"]
    # ... or a maximum
    bad = _own_answers(21, p, cfg, pl, range(3, 9))
    bad[1]["got"][f"{name}.max"] += 5.0
    assert _failed(ref.compare(gen, 21, p, cfg, pl, bad)) == [
        "minmax_span_err", "renamed_keys_not_clean"]
    # the same fault on a key renamed the interval before is not this
    # interval's rename
    k2 = next(k for k in mdl.sample(n)["renamed_before"].tolist()
              if mdl.stats(n % 2, k) is not None)
    bad = _own_answers(21, p, cfg, pl, range(3, 9))
    bad[1]["got"][gen.timer_name(k2, int(mdl.gens("t", n)[k2]))
                  + ".count"] += 3.0
    assert _failed(ref.compare(gen, 21, p, cfg, pl, bad)) == [
        "sampled_counts_not_exact"]
    # the retired name answers: a timer's, a counter's
    bad = _own_answers(21, p, cfg, pl, range(3, 9))
    old = gen.timer_name(k, int(mdl.gens("t", n)[k]) - 1)
    assert f"{old}.count" in pl["wanted"]
    bad[1]["got"][f"{old}.count"] = 2.0
    assert _failed(ref.compare(gen, 21, p, cfg, pl, bad)) == [
        "retired_names_emitted"]
    bad = _own_answers(21, p, cfg, pl, range(3, 9))
    kc = int(mdl.renamed("c", n)[0])
    old = gen.counter_name(kc, int(mdl.gens("c", n)[kc]) - 1)
    assert old in pl["wanted"]
    bad[1]["got"][old] = 1.0
    assert _failed(ref.compare(gen, 21, p, cfg, pl, bad)) == [
        "retired_names_emitted"]
    # an interval past the plan is said, not guessed
    late = _own_answers(21, p, cfg, pl, [p["plan_intervals"]])
    assert "intervals_beyond_plan" in _failed(
        ref.compare(gen, 21, p, cfg, pl, late))


def _verdict(lines):
    return [ln for ln in lines if ln.get("info") == "verdict"][0]


def _failed_lines(lines):
    return [ln["compared"] for ln in lines if ln.get("ok") is False]


def test_rehearse_end_to_end_and_traced(bench_json):
    rc, lines, err = run_rehearsal(CELL, *SMALL, trace=1, seconds=8)
    assert rc == 0, err[-2000:]
    bad = [ln for ln in lines if ln.get("ok") is False or "problem" in ln]
    assert _verdict(lines)["comparisons_ok"], bad
    last = lines[-1]
    assert last["correct"] is False and last["failed"] == 0
    listed = {m["name"] for m in bench_json["per_layer"]
              if CELL in (m.get("workloads") or [CELL])}
    # what only a device trace can give is left out on the CPU, and the
    # rehearsal's 8 s hold no intern clear
    on_cpu = {n for n in listed
              if "kernel" not in n and not n.startswith("hot_compress_ms")
              and "hbm_share" not in n and n != "intern_clear_ms"}
    assert on_cpu <= set(last["metrics"]) <= listed
    m = last["metrics"]
    assert m["key_births"]["value"] > 0 and m["key_deaths"]["value"] > 0
    assert m["key_birth_held_ms"]["value"] > 0
    assert m["arena_grows"]["value"] == 0
    # the aged intervals were sent before the run's first, and reported
    reports = [ln for ln in lines if ln.get("info") == "loadgen_interval"]
    aged = [r for r in reports if r["interval"] < 0]
    assert [r["deploy_interval"] for r in aged] == list(range(11))
    assert [r["deploy_interval"] - r["interval"] for r in reports] \
        == [11] * len(reports)
    by = {ln["compared"]: ln for ln in lines if "compared" in ln}
    for name in ("renamed_keys_not_clean", "retired_names_emitted",
                 "intervals_without_renamed_keys", "intervals_beyond_plan",
                 "lines_lost_at_engine"):
        assert by[name]["value"] == 0, by[name]


def test_an_intern_clear_in_the_window_loses_no_line():
    # the rehearsal's engine has interned 867 identities when deploy
    # interval 14 (the run's third) is in and 894 an interval later
    rc, lines, err = run_rehearsal(
        CELL, *SMALL, "--server-override", "intern_gc_threshold=880",
        trace=1, seconds=8)
    assert rc == 0, err[-2000:]
    assert _verdict(lines)["comparisons_ok"], [
        ln for ln in lines if ln.get("ok") is False or "problem" in ln]
    assert lines[-1]["metrics"]["intern_clear_ms"]["value"] > 0


def test_bf16_staging_fails_a_percentile_limit():
    rc, lines, err = run_rehearsal(
        CELL, *SMALL, "--server-override", "digest_bf16_staging=true",
        seconds=6)
    assert rc == 0, err[-2000:]
    assert not _verdict(lines)["comparisons_ok"]
    assert any("span_err_vs_hazen" in n for n in _failed_lines(lines)), \
        _failed_lines(lines)
    assert not [ln for ln in lines if "problem" in ln]


def test_a_dirty_recycled_row_fails_the_renamed_keys():
    rc, lines, err = run_rehearsal(CELL, *SMALL, seconds=6,
                                   script="tests/broken_churn.py")
    assert rc == 0, err[-2000:]
    assert not _verdict(lines)["comparisons_ok"]
    failed = _failed_lines(lines)
    assert "renamed_keys_not_clean" in failed, failed
    # the engine never knew: no line lost, nothing malformed, and the
    # names that kept their rows (counters, gauges, sets) are exact
    by = {ln["compared"]: ln for ln in lines if "compared" in ln}
    for name in ("lines_lost_at_engine", "malformed_or_too_long",
                 "counters_not_exact", "gauges_not_last_write",
                 "retired_names_emitted", "compile_events_in_window"):
        assert by[name]["ok"], by[name]
    assert lines[-1]["correct"] is False
