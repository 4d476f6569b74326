"""The quantile rule the served flush documents, in plain numpy (float64).

A transcription of `veneur_tpu/sketches/tdigest.py::weighted_eval` (the
XLA twin of `ops/sorted_eval.py`; upstream's rule is
`tdigest/merging_digest.go:266-332`), kept here so that no later PR to
the program can change what the benchmark compares against.  It imports
nothing of the program.

For one key: sort the weighted points by value (stable), place point i at
cumulative position cum_i - w_i / 2, find the two points whose positions
bracket q * total, interpolate linearly between their values, clamp to
the digest's [min, max].  For unit weights this is numpy's "hazen"
percentile.
"""

from __future__ import annotations

import numpy as np


def weighted_quantiles(mean: np.ndarray, weight: np.ndarray,
                       d_min: np.ndarray, d_max: np.ndarray,
                       qs) -> np.ndarray:
    """mean, weight: [K, D] (weight 0 = empty cell); d_min, d_max: [K];
    returns [K, len(qs)]."""
    mean = np.asarray(mean, np.float64)
    weight = np.asarray(weight, np.float64)
    qs = np.asarray(qs, np.float64)
    key = np.where(weight > 0, mean, np.inf)
    order = np.argsort(key, axis=1, kind="stable")
    mean = np.take_along_axis(mean, order, axis=1)
    weight = np.take_along_axis(weight, order, axis=1)
    cum = np.cumsum(weight, axis=1)
    total = cum[:, -1:]
    n_real = (weight > 0).sum(axis=1, keepdims=True)
    cmid = cum - 0.5 * weight
    tq = qs[None, :] * total                                    # [K, P]
    idx = (cmid[:, :, None] < tq[:, None, :]).sum(axis=1)       # [K, P]
    ii = np.clip(idx, 1, np.maximum(n_real - 1, 1))
    m_lo = np.take_along_axis(mean, ii - 1, axis=1)
    m_hi = np.take_along_axis(mean, ii, axis=1)
    c_lo = np.take_along_axis(cmid, ii - 1, axis=1)
    c_hi = np.take_along_axis(cmid, ii, axis=1)
    t = np.where(c_hi > c_lo, (tq - c_lo) / np.maximum(c_hi - c_lo, 1e-300),
                 0.0)
    q = m_lo + (m_hi - m_lo) * np.clip(t, 0.0, 1.0)
    q = np.where(n_real <= 1, mean[:, :1], q)
    q = np.clip(q, np.asarray(d_min, np.float64)[:, None],
                np.asarray(d_max, np.float64)[:, None])
    return np.where(total > 0, q, 0.0)
