"""Sequential CPU merging t-digest — the baseline arm.

A faithful re-implementation of the reference's sequential algorithm
(`tdigest/merging_digest.go:115-262`): buffered Adds, sort temps, single
in-order greedy merge pass with the arcsine scale function, shuffled re-Add
on Merge (`merging_digest.go:374-389`).  Used as the accuracy yardstick
for the parallel TPU kernels.  Pure numpy/python — deliberately the "what a
CPU global node does" algorithm, not a TPU design.
"""

from __future__ import annotations

import math

import numpy as np


class SequentialDigest:
    def __init__(self, compression: float = 100.0):
        self.compression = float(compression)
        self.size_bound = int(math.pi * compression / 2 + 0.5)
        tc = min(925.0, max(20.0, compression))
        self.temp_cap = int(7.5 + 0.37 * tc - 2e-4 * tc * tc)
        self.means = np.zeros(self.size_bound + 1, np.float64)
        self.weights = np.zeros(self.size_bound + 1, np.float64)
        self.n = 0
        self.main_weight = 0.0
        self.temp_v: list[float] = []
        self.temp_w: list[float] = []
        self.temp_weight = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.rsum = 0.0

    def add(self, value: float, weight: float = 1.0) -> None:
        if not math.isfinite(value) or weight <= 0:
            raise ValueError("invalid value added")
        if len(self.temp_v) >= self.temp_cap:
            self._merge_temps()
        self.min = min(self.min, value)
        self.max = max(self.max, value)
        # IEEE semantics like the Go reference: weight/0 -> +Inf, no crash.
        self.rsum += weight / value if value != 0 else math.inf
        self.temp_v.append(value)
        self.temp_w.append(weight)
        self.temp_weight += weight

    def add_batch(self, values, weights=None) -> None:
        values = np.asarray(values, np.float64).ravel()
        weights = (np.ones_like(values) if weights is None
                   else np.asarray(weights, np.float64).ravel())
        for v, w in zip(values, weights):
            self.add(float(v), float(w))

    def _k(self, q: float) -> float:
        return self.compression * (math.asin(2 * q - 1) / math.pi + 0.5)

    def _merge_temps(self) -> None:
        if not self.temp_v:
            return
        tv = np.asarray(self.temp_v, np.float64)
        tw = np.asarray(self.temp_w, np.float64)
        order = np.argsort(tv, kind="stable")
        tv, tw = tv[order], tw[order]
        # merge sorted temp stream with sorted main centroids
        am = np.concatenate([self.means[:self.n], tv])
        aw = np.concatenate([self.weights[:self.n], tw])
        order = np.argsort(am, kind="stable")
        am, aw = am[order], aw[order]

        total = self.main_weight + self.temp_weight
        out_m: list[float] = []
        out_w: list[float] = []
        merged = 0.0
        last_idx = 0.0
        for m, w in zip(am, aw):
            next_idx = self._k(min(1.0, (merged + w) / total))
            if next_idx - last_idx > 1 or not out_m:
                out_m.append(m)
                out_w.append(w)
                last_idx = self._k(merged / total)
            else:
                # Welford update: weight before mean
                out_w[-1] += w
                out_m[-1] += (m - out_m[-1]) * w / out_w[-1]
            merged += w
        self.n = len(out_m)
        self.means[:self.n] = out_m
        self.weights[:self.n] = out_w
        self.main_weight = total
        self.temp_v, self.temp_w = [], []
        self.temp_weight = 0.0

    def merge(self, other: "SequentialDigest",
              rng: np.random.Generator | None = None) -> None:
        other._merge_temps()
        rng = rng or np.random.default_rng()
        old_rsum = self.rsum
        for i in rng.permutation(other.n):
            self.add(float(other.means[i]), float(other.weights[i]))
        self.rsum = old_rsum + other.rsum

    def merge_centroids(self, means, weights, cmin, cmax, crsum,
                        rng: np.random.Generator | None = None) -> None:
        """Merge a serialized centroid list (the ImportMetric path,
        worker.go:402-459)."""
        rng = rng or np.random.default_rng()
        old_rsum = self.rsum
        n = len(means)
        for i in rng.permutation(n):
            self.add(float(means[i]), float(weights[i]))
        self.rsum = old_rsum + crsum
        self.min = min(self.min, cmin)
        self.max = max(self.max, cmax)

    def count(self) -> float:
        return self.main_weight + self.temp_weight

    def sum(self) -> float:
        self._merge_temps()
        return float(np.dot(self.means[:self.n], self.weights[:self.n]))

    def reciprocal_sum(self) -> float:
        return self.rsum

    def _bounds(self):
        m = self.means[:self.n]
        upper = np.empty(self.n)
        upper[:-1] = 0.5 * (m[1:] + m[:-1])
        upper[-1] = self.max
        lower = np.empty(self.n)
        lower[0] = self.min
        lower[1:] = upper[:-1]
        return lower, upper

    def quantile(self, q: float) -> float:
        self._merge_temps()
        if self.n == 0:
            return math.nan
        lower, upper = self._bounds()
        w = self.weights[:self.n]
        cum = np.cumsum(w)
        target = q * self.main_weight
        i = int(np.searchsorted(cum, target, side="left"))
        i = min(i, self.n - 1)
        before = cum[i] - w[i]
        prop = min(1.0, max(0.0, (target - before) / w[i]))
        return float(lower[i] + prop * (upper[i] - lower[i]))

    def cdf(self, x: float) -> float:
        self._merge_temps()
        if self.n == 0:
            return math.nan
        if x <= self.min:
            return 0.0
        if x >= self.max:
            return 1.0
        lower, upper = self._bounds()
        w = self.weights[:self.n]
        span = np.maximum(upper - lower, 0.0)
        frac = np.where(span > 0, np.clip((x - lower) / np.where(span > 0, span, 1), 0, 1),
                        (x >= upper).astype(np.float64))
        return float(np.sum(w * frac) / self.main_weight)

    def centroids(self):
        self._merge_temps()
        return self.means[:self.n].copy(), self.weights[:self.n].copy()
