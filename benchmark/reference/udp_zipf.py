"""Plain reference for generator kind `udp_zipf`: what a node must emit
for Zipfian timer lines.  numpy only; imports nothing of the program
(`tdigest_rule.py` and `tdigest_compress.py`, beside this file, are the
benchmark's own).

`plan` says which metric names the collector has to keep from each sink
batch; `compare` holds every measured interval's answers against the
samples SENT (the generator's model), for `sampled_keys` timer keys per
variant — the `sampled_hot_ranks` hottest ranks of the variant and the
rest drawn, seeded, from the other keys:

  * a key with at most `limits.hot_key_samples` (512) samples in the
    interval never left the staged depth, every sample is a singleton
    centroid and the documented rule IS `numpy.percentile(method="hazen")`:
    span-normalised error against `limits.percentile_span_err`, as the
    `udp` kind — after one float32 step (2^-23) of the answer's own
    magnitude is taken off: under Zipf most compared keys have two or
    three samples, some of them 0.01 apart, and 1e-5 of such a span is
    below what float32 can tell apart at 50;
  * a key past it was compressed (the configuration's guarantee: "the
    t-digest's at compression 100"), and is held in RANK space against
    the true samples: |F_n(answer) - q| <= `limits.hot_rank_widths` x
    dq(q), dq(q) = pi sqrt(q (1 - q)) / (1.5 delta) the width of the
    cluster that holds q (tdigest_compress.py); the configuration says
    how many widths and why.  A second number beside it: the rank
    distance to what ONE float64 compress of all the key's samples
    answers (two answers, each within its own limit, may stand twice
    that apart);
  * `min` / `max` within the span limit and `.count` exact, hot or not;
  * counters exact, gauges last-write, sets within the HLL bound, every
    timer sample counted (sum of `.count` = timer lines sent), as `udp`.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


F32_STEP = 2.0 ** -23

tc = _beside("tdigest_compress")
rule = _beside("tdigest_rule")


def sampled_keys(gen, seed: int, p: dict, variant: int) -> np.ndarray:
    """The variant's compared keys: its hottest ranks, then a seeded draw
    from the rest of the key space."""
    m = gen.model(seed, p, variant)
    n = min(p["sampled_keys"], p["timer_keys"])
    hot = m["rank_key"][:min(p["sampled_hot_ranks"], n)]
    rest = m["rank_key"][len(hot):]
    drawn = np.random.default_rng([int(seed), 13, int(variant)]).choice(
        rest, n - len(hot), replace=False)
    return np.concatenate([hot, drawn])


def plan(gen, seed: int, p: dict, cfg: dict) -> dict:
    pcts = cfg["server"]["percentiles"]
    keys = {v: sampled_keys(gen, seed, p, v) for v in range(p["variants"])}
    wanted = set()
    for ks in keys.values():
        for k in ks.tolist():
            for q in pcts:
                wanted.add(f"{gen.PREFIX}.t.{k}.{int(q * 100)}percentile")
            for suffix in ("min", "max", "count"):
                wanted.add(f"{gen.PREFIX}.t.{k}.{suffix}")
    wanted.update(f"{gen.PREFIX}.c.{k}" for k in range(p["counter_keys"]))
    wanted.update(f"{gen.PREFIX}.g.{k}" for k in range(p["gauge_keys"]))
    wanted.update(f"{gen.PREFIX}.s.{k}" for k in range(p["set_keys"]))
    return {"wanted": wanted, "keys": keys,
            "count_suffix": ".count", "count_prefix": f"{gen.PREFIX}.t."}


def expected(gen, seed: int, p: dict, cfg: dict, keys: np.ndarray,
             variant: int) -> dict:
    """What one variant's interval must answer, from the samples sent."""
    pcts = cfg["server"]["percentiles"]
    delta = float(cfg["guarantees_numbers"]["digest_compression"])
    m = gen.model(seed, p, variant)
    order = np.argsort(m["t_key"], kind="stable")
    vals = m["t_val"][order]
    starts = np.concatenate([[0], np.cumsum(m["key_count"])])
    per_key = {}
    for k in keys.tolist():
        s = np.sort(vals[starts[k]:starts[k + 1]])
        if not len(s):
            continue            # not touched this interval: emits nothing
        e = {"sorted": s, "n": len(s), "min": s[0], "max": s[-1],
             "span": (s[-1] - s[0]) or 1.0}
        if len(s) <= cfg["limits"]["hot_key_samples"]:
            e["hazen"] = np.percentile(s, np.asarray(pcts) * 100.0,
                                       method="hazen")
        else:
            e["curve"] = tc.rank_curve(s)
            e["one_stage"] = tc.one_stage_quantiles(
                s, pcts, delta, rule.weighted_quantiles)
            e["one_stage_rank"] = [tc.rank_of(e["curve"], x)
                                   for x in e["one_stage"]]
        per_key[k] = e
    c_want = np.bincount(m["c_key"], weights=m["c_val"],
                         minlength=p["counter_keys"])
    return {
        "keys": per_key,
        "touched": int((m["key_count"] > 0).sum()),
        "counters": {k: c_want[k] for k in np.nonzero(c_want)[0].tolist()},
        "gauges": dict(zip(m["g_key"].tolist(), m["g_val"].tolist())),
        "sets": {k: len(np.unique(m["s_mem"][m["s_key"] == k]))
                 for k in np.unique(m["s_key"]).tolist()},
    }


def compare(gen, seed: int, p: dict, cfg: dict, pl: dict,
            intervals: list[dict]) -> list[dict]:
    """`intervals`: one dict per measured interval: `interval` (its
    number, which picks the payload variant), `got` (name -> value for the
    wanted names), `count_sum`, `percentile_metrics`.  Returns the numbers
    compared, each beside its limit."""
    pcts = cfg["server"]["percentiles"]
    lim = float(cfg["limits"]["percentile_span_err"])
    widths = float(cfg["limits"]["hot_rank_widths"])
    delta = float(cfg["guarantees_numbers"]["digest_compression"])
    precision = int(cfg["server"].get("set_precision", 14))
    # 3 standard errors at the configured precision, no tighter than 3%,
    # with an absolute floor where cardinalities are a few dozen
    hll_rel = max(3.0 * 1.04 / np.sqrt(2.0 ** precision), 0.03)
    want = {}
    shallow = {q: 0.0 for q in pcts}
    hot = {q: 0.0 for q in pcts}
    hot_vs_one = {q: 0.0 for q in pcts}
    worst_minmax = worst_set = 0.0
    hot_keys = shallow_keys = 0
    missing = counts_wrong = counters_wrong = gauges_wrong = 0
    samples_lost = pm_missing = 0
    pre = gen.PREFIX
    for iv in intervals:
        v = iv["interval"] % p["variants"]
        if v not in want:
            want[v] = expected(gen, seed, p, cfg, pl["keys"][v], v)
        w, got = want[v], iv["got"]
        for k, e in w["keys"].items():
            for suffix in ("min", "max"):
                have = got.get(f"{pre}.t.{k}.{suffix}")
                if have is None:
                    missing += 1
                else:
                    worst_minmax = max(worst_minmax,
                                       abs(have - e[suffix]) / e["span"])
            have = got.get(f"{pre}.t.{k}.count")
            counts_wrong += have != e["n"]
            is_hot = "one_stage" in e
            hot_keys += is_hot
            shallow_keys += not is_hot
            for j, q in enumerate(pcts):
                have = got.get(f"{pre}.t.{k}.{int(q * 100)}percentile")
                if have is None:
                    missing += 1
                elif is_hot:
                    rank = tc.rank_of(e["curve"], have)
                    hot[q] = max(hot[q], abs(rank - q))
                    hot_vs_one[q] = max(hot_vs_one[q],
                                        abs(rank - e["one_stage_rank"][j]))
                else:
                    # float32's own grain first: no float32 answer can be
                    # nearer than one step (2^-23) of its magnitude, and a
                    # key whose few samples lie 0.01 apart has a span of
                    # which 1e-5 is less than that step
                    off = abs(have - e["hazen"][j]) - F32_STEP * abs(
                        e["hazen"][j])
                    shallow[q] = max(shallow[q], off / e["span"])
        counters_wrong += sum(1 for k, val in w["counters"].items()
                              if got.get(f"{pre}.c.{k}") != val)
        gauges_wrong += sum(
            1 for k, val in w["gauges"].items()
            if not abs(got.get(f"{pre}.g.{k}", np.nan) - val) <= 1e-3)
        for k, true in w["sets"].items():
            have = got.get(f"{pre}.s.{k}", np.nan)
            r = abs(have - true) / max(5.0, hll_rel * true)
            worst_set = max(worst_set, float("inf") if np.isnan(r) else r)
        samples_lost += abs(p["timer_lines"] - int(round(iv["count_sum"])))
        pm_missing += abs(w["touched"] * len(pcts)
                          - iv["percentile_metrics"])
    out = [{"name": f"p{int(q * 100)}_span_err_vs_hazen",
            "value": shallow[q], "limit": lim} for q in pcts]
    out += [{"name": f"hot_p{int(q * 100)}_rank_err",
             "value": hot[q],
             "limit": widths * tc.cluster_width(q, delta)} for q in pcts]
    out += [{"name": f"hot_p{int(q * 100)}_rank_dist_vs_one_stage",
             "value": hot_vs_one[q],
             "limit": 2.0 * widths * tc.cluster_width(q, delta)}
            for q in pcts]
    out += [
        {"name": "minmax_span_err", "value": worst_minmax, "limit": lim},
        {"name": "sampled_metrics_missing", "value": missing, "limit": 0},
        {"name": "sampled_counts_not_exact", "value": int(counts_wrong),
         "limit": 0},
        # the comparison must have had both kinds of key to look at
        {"name": "intervals_without_hot_keys",
         "value": int(hot_keys == 0) + int(shallow_keys == 0), "limit": 0},
        {"name": "counters_not_exact", "value": counters_wrong, "limit": 0},
        {"name": "gauges_not_last_write", "value": gauges_wrong, "limit": 0},
        {"name": "set_err_over_hll_bound", "value": worst_set, "limit": 1.0},
        {"name": "timer_samples_not_counted", "value": samples_lost,
         "limit": 0},
        {"name": "percentile_metrics_missing", "value": pm_missing,
         "limit": 0},
    ]
    return out
