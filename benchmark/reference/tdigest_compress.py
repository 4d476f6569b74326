"""The t-digest compress the hot-key lane documents, in plain numpy
(float64), and the answer a hot key should give through it.

Written from the equations in `veneur_tpu/sketches/tdigest.py::compress`
(the program's batched form of upstream's `mergeAllTemps`,
`tdigest/merging_digest.go:140-255`, with the arcsine scale function of
`merging_digest.go:258-262`), kept here so that no later PR to the
program can change what the benchmark compares against.  It imports
nothing of the program.

For one key, `compress(mean, weight, delta, cap)`:

  1. drop empty points (weight 0), sort the rest by mean (stable);
  2. left quantile edge of point i: q_i = (cum_i - w_i) / total;
  3. cluster of point i: floor(k(q_i)) clipped to [0, cap - 1], on the
     scale k(q) = 1.5 * delta * (asin(2q - 1) / pi + 1/2) — the program's
     scale function refined by OVERSAMPLE = 1.5, so a cluster spans at
     most 1/1.5 of a unit of upstream's k plus its last member;
  4. a cluster's centroid: the weighted mean of its points, its weight
     their sum; empty clusters are left out.

A cluster at quantile q is about dq(q) = pi * sqrt(q (1 - q)) / (1.5 delta)
wide in rank (the inverse of k's slope), which is what bounds a
quantile's rank error (`cluster_width`).
"""

from __future__ import annotations

import numpy as np

OVERSAMPLE = 1.5


def scale_k(q: np.ndarray, delta: float) -> np.ndarray:
    q = np.clip(np.asarray(q, np.float64), 0.0, 1.0)
    return OVERSAMPLE * delta * (np.arcsin(2.0 * q - 1.0) / np.pi + 0.5)


def cluster_width(q: float, delta: float) -> float:
    """Rank width of the cluster that holds quantile q."""
    return float(np.pi * np.sqrt(q * (1.0 - q)) / (OVERSAMPLE * delta))


def compress(mean, weight, delta: float, cap: int):
    """One key's weighted points -> its centroids (means, weights),
    sorted by mean, at most `cap` of them."""
    mean = np.asarray(mean, np.float64)
    weight = np.asarray(weight, np.float64)
    real = weight > 0
    order = np.argsort(mean[real], kind="stable")
    m, w = mean[real][order], weight[real][order]
    if not len(m):
        return m, w
    cum = np.cumsum(w)
    q_left = (cum - w) / cum[-1]
    cluster = np.clip(np.floor(scale_k(q_left, delta)).astype(np.int64),
                      0, cap - 1)
    w_out = np.bincount(cluster, weights=w, minlength=cap)
    wm_out = np.bincount(cluster, weights=w * m, minlength=cap)
    occ = w_out > 0
    m_out, w_out = wm_out[occ] / w_out[occ], w_out[occ]
    back = np.argsort(m_out, kind="stable")
    return m_out[back], w_out[back]


def centroid_capacity(delta: float) -> int:
    """Centroid slots per key: floor(1.5 delta) + 1 clusters, rounded up
    to a multiple of 8."""
    need = int(np.floor(OVERSAMPLE * delta)) + 1
    return ((need + 7) // 8) * 8


def one_stage_quantiles(samples, qs, delta: float, rule) -> np.ndarray:
    """What a key answers when ALL its samples are compressed once and
    the centroids read by the documented quantile rule (`rule` =
    tdigest_rule.weighted_quantiles): the second number beside a hot
    key's rank error."""
    samples = np.asarray(samples, np.float64)
    m, w = compress(samples, np.ones(len(samples)), delta,
                    centroid_capacity(delta))
    return rule(m[None, :], w[None, :], samples.min()[None],
                samples.max()[None], qs)[0]


def rank_curve(samples_sorted: np.ndarray):
    """F_n of the samples sent as a curve (xs, ranks): the empirical
    distribution in the quantile rule's own (midpoint) convention, made
    continuous — sample i of n (sorted, from 0) stands at (i + 1/2) / n,
    equal samples at the middle of their run, and F_n is linear in
    between, so the exact `hazen` percentile at q reads q."""
    xs, first, count = np.unique(samples_sorted, return_index=True,
                                 return_counts=True)
    return xs, (first + 0.5 * count) / len(samples_sorted)


def rank_of(curve, x: float) -> float:
    """F_n(x) on a `rank_curve`."""
    return float(np.interp(x, *curve))


def rank_error(samples_sorted: np.ndarray, answer: float, q: float) -> float:
    """|F_n(answer) - q|."""
    return abs(rank_of(rank_curve(samples_sorted), answer) - q)
