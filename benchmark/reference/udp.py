"""Plain reference for generator kind `udp`: what a node must emit for the
lines it was sent.  numpy only; imports nothing of the program.

`plan` says which metric names the collector has to keep from each sink
batch; `compare` holds every measured interval's answers against the
samples SENT (the generator's model):

  * percentiles of `sampled_keys` seeded timer keys against
    `numpy.percentile(..., method="hazen")`, span-normalised.  While a
    key's samples per interval fit the staged depth every sample is a
    singleton centroid and the documented rule (reference/tdigest_rule.py)
    IS the hazen percentile, so the limit is a rounding limit, not a
    sketch-accuracy one: see the configuration's `limits`;
  * counters exact, gauges last-write, sets within the HLL bound;
  * every timer sample counted (sum of `.count` = samples sent).
"""

from __future__ import annotations

import numpy as np


def _sample_keys(seed: int, p: dict) -> np.ndarray:
    n = min(p["sampled_keys"], p["timer_keys"])
    return np.sort(np.random.default_rng([int(seed), 3]).choice(
        p["timer_keys"], n, replace=False))


def plan(gen, seed: int, p: dict, cfg: dict) -> dict:
    keys = _sample_keys(seed, p)
    pcts = cfg["server"]["percentiles"]
    wanted = set()
    for k in keys.tolist():
        for q in pcts:
            wanted.add(f"{gen.PREFIX}.t.{k}.{int(q * 100)}percentile")
        wanted.add(f"{gen.PREFIX}.t.{k}.min")
        wanted.add(f"{gen.PREFIX}.t.{k}.max")
    wanted.update(f"{gen.PREFIX}.c.{k}" for k in range(p["counter_keys"]))
    wanted.update(f"{gen.PREFIX}.g.{k}" for k in range(p["gauge_keys"]))
    wanted.update(f"{gen.PREFIX}.s.{k}" for k in range(p["set_keys"]))
    return {"wanted": wanted, "keys": keys,
            "count_suffix": ".count", "count_prefix": f"{gen.PREFIX}.t.",
            "percentile_metrics": p["timer_keys"] * len(pcts)}


def compare(gen, seed: int, p: dict, cfg: dict, pl: dict,
            intervals: list[dict]) -> list[dict]:
    """`intervals`: one dict per measured interval: `interval` (its
    number, which picks the payload variant), `got` (name -> value for the
    wanted names), `count_sum`, `percentile_metrics`.  Returns the numbers
    compared, each beside its limit."""
    pcts = cfg["server"]["percentiles"]
    lim = float(cfg["limits"]["percentile_span_err"])
    precision = int(cfg["server"].get("set_precision", 14))
    # 3 standard errors at the configured precision, no tighter than 3%,
    # with an absolute floor where cardinalities are a few dozen (the
    # smoke's rule)
    hll_rel = max(3.0 * 1.04 / np.sqrt(2.0 ** precision), 0.03)
    keys = pl["keys"]
    models = {}
    worst_pct = {q: 0.0 for q in pcts}
    worst_minmax = 0.0
    worst_set = 0.0
    counters_wrong = gauges_wrong = missing = 0
    samples_lost = 0
    pm_missing = 0
    for iv in intervals:
        v = iv["interval"] % p["variants"]
        if v not in models:
            models[v] = gen.model(seed, p, v)
        m, got = models[v], iv["got"]
        vals = m["t_val"][keys]
        span = vals.max(axis=1) - vals.min(axis=1)
        span = np.where(span > 0, span, 1.0)
        for q in pcts:
            want = np.percentile(vals, q * 100.0, axis=1, method="hazen")
            have = np.array([got.get(
                f"{gen.PREFIX}.t.{k}.{int(q * 100)}percentile", np.nan)
                for k in keys.tolist()])
            missing += int(np.isnan(have).sum())
            err = np.nanmax(np.abs(have - want) / span) if len(keys) else 0.0
            worst_pct[q] = max(worst_pct[q], float(err))
        for suffix, want in (("min", vals.min(axis=1)),
                             ("max", vals.max(axis=1))):
            have = np.array([got.get(f"{gen.PREFIX}.t.{k}.{suffix}", np.nan)
                             for k in keys.tolist()])
            missing += int(np.isnan(have).sum())
            worst_minmax = max(worst_minmax, float(
                np.nanmax(np.abs(have - want) / span)))
        c_want = np.bincount(m["c_key"], weights=m["c_val"],
                             minlength=p["counter_keys"])
        counters_wrong += sum(
            1 for k in np.nonzero(c_want)[0].tolist()
            if got.get(f"{gen.PREFIX}.c.{k}") != c_want[k])
        g_last = dict(zip(m["g_key"].tolist(), m["g_val"].tolist()))
        gauges_wrong += sum(
            1 for k, val in g_last.items()
            if not abs(got.get(f"{gen.PREFIX}.g.{k}", np.nan) - val) <= 1e-3)
        for k in np.unique(m["s_key"]).tolist():
            true = len(np.unique(m["s_mem"][m["s_key"] == k]))
            have = got.get(f"{gen.PREFIX}.s.{k}", np.nan)
            bound = max(5.0, hll_rel * true)
            r = abs(have - true) / bound
            worst_set = max(worst_set, float("inf") if np.isnan(r) else r)
        samples_lost += abs(int(m["t_val"].size) - int(round(iv["count_sum"])))
        pm_missing += abs(pl["percentile_metrics"] - iv["percentile_metrics"])
    out = [{"name": f"p{int(q * 100)}_span_err_vs_hazen",
            "value": worst_pct[q], "limit": lim} for q in pcts]
    out += [
        {"name": "minmax_span_err", "value": worst_minmax, "limit": lim},
        {"name": "sampled_metrics_missing", "value": missing, "limit": 0},
        {"name": "counters_not_exact", "value": counters_wrong, "limit": 0},
        {"name": "gauges_not_last_write", "value": gauges_wrong, "limit": 0},
        {"name": "set_err_over_hll_bound", "value": worst_set, "limit": 1.0},
        {"name": "timer_samples_not_counted", "value": samples_lost,
         "limit": 0},
        {"name": "percentile_metrics_missing", "value": pm_missing,
         "limit": 0},
    ]
    return out
