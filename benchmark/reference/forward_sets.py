"""Plain reference for generator kind `forward_sets`: what a global must
emit for the set sketches its locals forwarded.  numpy only; imports
nothing of the program.

Per sampled key the expected union is the register-wise maximum of the
locals' registers AS THEY WENT ON THE WIRE: a local's sketch is sent
dense from `is_dense_on_wire` up, and the dense form (axiomhq's 4-bit
registers) clamps ranks to 15; sparse sketches keep theirs.  Compared,
over every measured interval:

  (a) `estimate_excess_vs_reference`: the emitted estimate against the
      LogLog-Beta estimate of that union in float64, written from the
      published formula (Qin, Kim, Tung 2016: est = alpha m (m - z) /
      (beta(z) + sum 2^-r), beta a degree-7 polynomial in ln(z + 1)).
      The program rounds its estimate to a whole count (floor(est +
      0.5)), so what is compared is the distance beyond that half
      count, relative: max(|emitted - est| - 0.5, 0) / est.  A rounding
      limit (the configuration's `limits.estimate_excess`): float32
      register sums pass it, bfloat16 ones (`estimate(...,
      bf16=True)`, the control) do not; a merge that drops a local
      reads about 1/3 low, one that adds the locals' estimates 1/3
      high;
  (b) against the TRUE distinct count, by the configuration's rule
      max(5, 3 standard errors at p, 3 %), as `reference/udp.py` has
      it.  That rule is per estimate, and this kind compares some 8,000
      of them a run: a sound sketch exceeds it now and then (a
      263-member set that drew 10 register collisions where 2 are
      expected reads 3.04 % low).  So two numbers: the worst
      `set_err_over_hll_bound`, which may not pass
      `limits.hll_bound_worst` times the rule, and
      `sets_beyond_hll_bound`, the estimates beyond the rule itself,
      at most `limits.hll_bound_beyond_share` of those compared;
  (c) exact: sets that did not reach the sink, sampled keys missing.
"""

from __future__ import annotations

import numpy as np

# LogLog-Beta's bias polynomial at p = 14 (the paper's table; axiomhq/
# hyperloglog beta14), lowest power first: beta(z) = c0 z + c1 l + ... +
# c7 l^7 with l = ln(z + 1)
BETA14 = (-0.370393911, 0.070471823, 0.17393686, 0.16339839,
          -0.09237745, 0.03738027, -0.005384159, 0.00042419)
DENSE_RANK_MAX = 15


def _to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), as
    float32."""
    u = np.asarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _tree_sum(x: np.ndarray, bf16: bool) -> np.ndarray:
    """Sum along the last axis (a power of two long): float64, or a
    pairwise tree whose every partial sum is rounded to bfloat16."""
    if not bf16:
        return x.astype(np.float64).sum(axis=-1)
    x = _to_bf16(x)
    while x.shape[-1] > 1:
        x = _to_bf16(x[..., 0::2] + x[..., 1::2])
    return x[..., 0].astype(np.float64)


def estimate(regs: np.ndarray, bf16: bool = False) -> np.ndarray:
    """LogLog-Beta estimates of `[n, 2^14]` registers, not rounded to a
    count.  `bf16`: accumulate the two register sums in bfloat16 (the
    lower-precision control)."""
    m = regs.shape[-1]
    if m != 1 << 14:
        raise ValueError("the reference has LogLog-Beta's constants "
                         "for p = 14 only")
    z = _tree_sum((regs == 0).astype(np.float32), bf16)
    s = _tree_sum(np.exp2(-regs.astype(np.float32)), bf16)
    ln = np.log(z + 1.0)
    beta = BETA14[0] * z
    power = np.ones_like(ln)
    for c in BETA14[1:]:
        power = power * ln
        beta = beta + c * power
    alpha = 0.7213 / (1.0 + 1.079 / m)
    return alpha * m * (m - z) / (beta + s)


def union_on_wire(model: dict) -> np.ndarray:
    """[keys, m]: register-wise maximum over the locals of what each
    sent (dense sketches clamped to 15)."""
    regs = np.where(model["dense"][:, :, None],
                    np.minimum(model["regs"], DENSE_RANK_MAX),
                    model["regs"])
    return regs.max(axis=0)


def excess(emitted: np.ndarray, est: np.ndarray) -> np.ndarray:
    """Distance beyond the half count the program's rounding may put
    between its estimate and `est`, relative to `est`."""
    return np.maximum(np.abs(emitted - est) - 0.5, 0.0) \
        / np.maximum(est, 1.0)


def _sample_keys(gen, seed: int, p: dict, variant: int) -> np.ndarray:
    """`sampled_keys` keys of the variant, always with its `edge_ranks`
    largest and smallest sets."""
    n, e = p["set_keys"], min(p["edge_ranks"], p["set_keys"] // 2)
    by_rank = gen.key_of_rank(seed, p, variant)
    edge = np.concatenate([by_rank[:e], by_rank[n - e:]])
    rest = np.random.default_rng([int(seed), 3, int(variant)]).choice(
        by_rank[e:n - e], max(0, min(p["sampled_keys"], n) - 2 * e),
        replace=False)
    return np.sort(np.concatenate([edge, rest]))


def plan(gen, seed: int, p: dict, cfg: dict) -> dict:
    if int(cfg["server"].get("set_precision", 14)) != int(p["precision"]):
        raise ValueError("the mix's precision is not the server's")
    # every set's name is kept: (c) counts the sets that reached the sink
    return {"wanted": {f"{gen.PREFIX}.s.{k}"
                       for k in range(p["set_keys"])},
            "keys": {v: _sample_keys(gen, seed, p, v)
                     for v in range(p["variants"])},
            "count_suffix": None, "count_prefix": f"{gen.PREFIX}.s.",
            "percentile_metrics": 0}


def compare(gen, seed: int, p: dict, cfg: dict, pl: dict,
            intervals: list[dict], bf16: bool = False) -> list[dict]:
    limits = cfg["limits"]
    # the configuration's rule, as reference/udp.py has it
    hll_rel = max(3.0 * 1.04 / np.sqrt(2.0 ** int(p["precision"])), 0.03)
    expected = {}
    worst_ref = worst_true = 0.0
    missing = not_emitted = beyond = compared = 0
    for iv in intervals:
        v = iv["interval"] % p["variants"]
        keys = pl["keys"][v]
        if v not in expected:
            m = gen.model(seed, p, v, keys)
            expected[v] = (estimate(union_on_wire(m), bf16=bf16),
                           m["sizes"].astype(np.float64))
        est, true = expected[v]
        got = iv["got"]
        have = np.array([got.get(f"{gen.PREFIX}.s.{k}", np.nan)
                         for k in keys.tolist()])
        missing += int(np.isnan(have).sum())
        not_emitted += abs(p["set_keys"] - len(got))
        if np.isnan(have).all():
            worst_ref = worst_true = float("inf")
            continue
        worst_ref = max(worst_ref, float(np.nanmax(excess(have, est))))
        over = np.abs(have - true) / np.maximum(5.0, hll_rel * true)
        worst_true = max(worst_true, float(np.nanmax(over)))
        beyond += int(np.nansum(over > 1.0))
        compared += int(np.sum(~np.isnan(have)))
    return [
        {"name": "estimate_excess_vs_reference", "value": worst_ref,
         "limit": float(limits["estimate_excess"])},
        {"name": "set_err_over_hll_bound", "value": worst_true,
         "limit": float(limits["hll_bound_worst"])},
        {"name": "sets_beyond_hll_bound", "value": beyond,
         "limit": int(compared * limits["hll_bound_beyond_share"])},
        {"name": "sets_not_emitted", "value": not_emitted, "limit": 0},
        {"name": "sampled_metrics_missing", "value": missing, "limit": 0},
    ]
