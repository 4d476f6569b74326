"""Plain reference for generator kind `forward`: what a global must emit
for the digests its locals forwarded.  numpy only; imports nothing of the
program.

Per key the global holds locals x centroids weighted points (8 x 32 =
256).  Two comparisons per percentile, over every measured interval:

  * against the documented quantile rule evaluated over those merged
    centroids in float64 (`reference/tdigest_rule.py`), span-normalised:
    a rounding limit (the configuration's `limits`), the one a lower
    staging precision must fail;
  * against the true hazen percentile of the locals x samples_per_digest
    samples behind the centroids: a sketch-accuracy number that a lower
    precision hardly moves.  It is there to catch a merge that loses or
    doubles a local, and a reference rule that is wrong the way the
    program is; its limits (the configuration's
    `limits.vs_samples_span_err`) are 3x the largest the rule itself
    gives on this payload over 12 seeds (PERF.md section 2).
"""

from __future__ import annotations

import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _rule():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_tdigest_rule", os.path.join(HERE, "tdigest_rule.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _sample_keys(seed: int, p: dict) -> np.ndarray:
    n = min(p["sampled_keys"], p["keys_per_local"])
    return np.sort(np.random.default_rng([int(seed), 3]).choice(
        p["keys_per_local"], n, replace=False))


def plan(gen, seed: int, p: dict, cfg: dict) -> dict:
    keys = _sample_keys(seed, p)
    pcts = cfg["server"]["percentiles"]
    wanted = {f"{gen.PREFIX}.h.{k}.{int(q * 100)}percentile"
              for k in keys.tolist() for q in pcts}
    return {"wanted": wanted, "keys": keys,
            "count_suffix": None, "count_prefix": f"{gen.PREFIX}.h.",
            "percentile_metrics": p["keys_per_local"] * len(pcts)}


def compare(gen, seed: int, p: dict, cfg: dict, pl: dict,
            intervals: list[dict]) -> list[dict]:
    pcts = cfg["server"]["percentiles"]
    lim = float(cfg["limits"]["percentile_span_err"])
    envelope = cfg["limits"]["vs_samples_span_err"]
    rule = _rule()
    keys = pl["keys"]
    expected = {}
    worst_rule = {q: 0.0 for q in pcts}
    worst_true = {q: 0.0 for q in pcts}
    missing = pm_missing = 0
    for iv in intervals:
        v = iv["interval"] % p["variants"]
        if v not in expected:
            m = gen.model(seed, p, v)
            # [keys, locals * centroids] and [keys, locals * samples]
            means = np.concatenate(list(m["means"][:, keys]), axis=1)
            weights = np.concatenate(list(m["weights"][:, keys]), axis=1)
            samples = np.concatenate(list(m["samples"][:, keys]), axis=1)
            lo, hi = samples.min(axis=1), samples.max(axis=1)
            expected[v] = (
                rule.weighted_quantiles(means, weights, lo, hi, pcts),
                np.percentile(samples, [q * 100.0 for q in pcts], axis=1,
                              method="hazen").T,
                np.where(hi > lo, hi - lo, 1.0))
        by_rule, true, span = expected[v]
        got = iv["got"]
        for j, q in enumerate(pcts):
            have = np.array([got.get(
                f"{gen.PREFIX}.h.{k}.{int(q * 100)}percentile", np.nan)
                for k in keys.tolist()])
            missing += int(np.isnan(have).sum())
            worst_rule[q] = max(worst_rule[q], float(
                np.nanmax(np.abs(have - by_rule[:, j]) / span)))
            worst_true[q] = max(worst_true[q], float(
                np.nanmax(np.abs(have - true[:, j]) / span)))
        pm_missing += abs(pl["percentile_metrics"] - iv["percentile_metrics"])
    out = [{"name": f"p{int(q * 100)}_span_err_vs_rule",
            "value": worst_rule[q], "limit": lim} for q in pcts]
    out += [{"name": f"p{int(q * 100)}_span_err_vs_samples",
             "value": worst_true[q], "limit": float(envelope[str(q)])}
            for q in pcts]
    out += [
        {"name": "sampled_metrics_missing", "value": missing, "limit": 0},
        {"name": "percentile_metrics_missing", "value": pm_missing,
         "limit": 0},
    ]
    return out
