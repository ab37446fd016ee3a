"""(Copy of veneur_tpu/ops/tdigest_ref.py, kept with the benchmark so that no
later PR can move the yardstick.)

Scalar merging t-digest (Dunning), the host-side reference implementation.

Algorithmic parity with reference tdigest/merging_digest.go:23-483: temp
buffer of raw centroids, amortized sorted merge into a bounded main list
using the arcsine k-scale, quantile/CDF by uniform interpolation over
centroid upper bounds, digest merge by shuffled re-insertion.

This implementation is the statistical ground truth that the batched device
kernel (veneur_tpu.ops.batch_tdigest) is validated against, and the
serialization boundary for the forward plane.
"""

from __future__ import annotations

import math
import random
from typing import List, Optional, Sequence, Tuple


def size_bound(compression: float) -> int:
    """Provable upper bound on the main centroid list length."""
    return int(math.pi * compression / 2 + 0.5)


def temp_buffer_size(compression: float) -> int:
    """Temp-buffer sizing heuristic from Dunning's paper."""
    c = min(925.0, max(20.0, compression))
    return int(7.5 + 0.37 * c - 2e-4 * c * c)


class MergingDigest:
    __slots__ = ("compression", "means", "weights", "main_weight", "_temp",
                 "temp_weight", "min", "max", "reciprocal_sum", "_temp_cap")

    def __init__(self, compression: float = 100.0):
        self.compression = compression
        self.means: List[float] = []
        self.weights: List[float] = []
        self.main_weight = 0.0
        self._temp: List[Tuple[float, float]] = []
        self.temp_weight = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.reciprocal_sum = 0.0
        self._temp_cap = temp_buffer_size(compression)

    # -- ingestion -------------------------------------------------------

    def add(self, value: float, weight: float = 1.0) -> None:
        if math.isnan(value) or math.isinf(value) or weight <= 0:
            raise ValueError("invalid value added")
        if len(self._temp) >= self._temp_cap:
            self._merge_all_temps()
        self.min = min(self.min, value)
        self.max = max(self.max, value)
        # Go float semantics: 1/0 = +Inf, not an error
        self.reciprocal_sum += (
            math.copysign(math.inf, value) if value == 0 else 1.0 / value
        ) * weight
        self._temp.append((value, weight))
        self.temp_weight += weight

    def _index_estimate(self, quantile: float) -> float:
        # arcsine k-scale: index of the centroid containing this quantile
        return self.compression * (
            math.asin(2.0 * quantile - 1.0) / math.pi + 0.5)

    def _merge_all_temps(self) -> None:
        if not self._temp:
            return
        self._temp.sort()
        total = self.main_weight + self.temp_weight
        merged_weight = 0.0
        last_index = 0.0
        new_means: List[float] = []
        new_weights: List[float] = []

        # two-pointer ascending merge of (main, temp), compressing on the fly
        i = j = 0
        n_main, n_temp = len(self.means), len(self._temp)
        while i < n_main or j < n_temp:
            if i < n_main and (j >= n_temp or self.means[i] < self._temp[j][0]):
                mean, weight = self.means[i], self.weights[i]
                i += 1
            else:
                mean, weight = self._temp[j]
                j += 1
            next_index = self._index_estimate((merged_weight + weight) / total)
            if next_index - last_index > 1 or not new_means:
                # too wide to merge into the current centroid: start a new one
                new_means.append(mean)
                new_weights.append(weight)
                last_index = self._index_estimate(merged_weight / total)
            else:
                # Welford update; weight must be updated before mean
                new_weights[-1] += weight
                new_means[-1] += (mean - new_means[-1]) * weight / new_weights[-1]
            merged_weight += weight

        self.means, self.weights = new_means, new_weights
        self.main_weight = total
        self._temp = []
        self.temp_weight = 0.0

    # -- queries ---------------------------------------------------------

    def _upper_bound(self, i: int) -> float:
        # centroids are assumed uniform between midpoints of neighbors
        if i != len(self.means) - 1:
            return (self.means[i + 1] + self.means[i]) / 2.0
        return self.max

    def quantile(self, quantile: float) -> float:
        if quantile < 0 or quantile > 1:
            raise ValueError("quantile out of bounds")
        self._merge_all_temps()
        q = quantile * self.main_weight
        weight_so_far = 0.0
        lower = self.min
        for i, w in enumerate(self.weights):
            upper = self._upper_bound(i)
            if q <= weight_so_far + w:
                proportion = (q - weight_so_far) / w
                return lower + proportion * (upper - lower)
            weight_so_far += w
            lower = upper
        return math.nan

    def cdf(self, value: float) -> float:
        self._merge_all_temps()
        if not self.means:
            return math.nan
        if value <= self.min:
            return 0.0
        if value >= self.max:
            return 1.0
        weight_so_far = 0.0
        lower = self.min
        for i, w in enumerate(self.weights):
            upper = self._upper_bound(i)
            if value < upper:
                weight_so_far += w * (value - lower) / (upper - lower)
                return weight_so_far / self.main_weight
            weight_so_far += w
            lower = upper
        return math.nan

    def count(self) -> float:
        return self.main_weight + self.temp_weight

    def sum(self) -> float:
        self._merge_all_temps()
        return sum(m * w for m, w in zip(self.means, self.weights))

    # -- merge & serialization ------------------------------------------

    def merge(self, other: "MergingDigest", rng: Optional[random.Random] = None) -> None:
        """Merge another digest into this one by shuffled re-insertion
        (reference merging_digest.go:374-389)."""
        old_reciprocal = self.reciprocal_sum
        order = list(range(len(other.means)))
        (rng or random).shuffle(order)
        for i in order:
            self.add(other.means[i], other.weights[i])
        for mean, weight in other._temp:
            self.add(mean, weight)
        self.reciprocal_sum = old_reciprocal + other.reciprocal_sum

    def data(self) -> dict:
        """Serializable snapshot (the proto MergingDigestData shape)."""
        self._merge_all_temps()
        return {
            "main_centroids": [
                {"mean": m, "weight": w}
                for m, w in zip(self.means, self.weights)
            ],
            "compression": self.compression,
            "min": self.min,
            "max": self.max,
            "reciprocal_sum": self.reciprocal_sum,
        }

    @staticmethod
    def from_data(d: dict) -> "MergingDigest":
        td = MergingDigest(d.get("compression", 100.0))
        td.means = [c["mean"] for c in d.get("main_centroids", [])]
        td.weights = [c["weight"] for c in d.get("main_centroids", [])]
        td.main_weight = sum(td.weights)
        td.min = d.get("min", math.inf)
        td.max = d.get("max", -math.inf)
        td.reciprocal_sum = d.get("reciprocal_sum", 0.0)
        return td

    @staticmethod
    def from_centroids(
        means: Sequence[float], weights: Sequence[float],
        vmin: float, vmax: float, reciprocal_sum: float = 0.0,
        compression: float = 100.0,
    ) -> "MergingDigest":
        td = MergingDigest(compression)
        td.means = list(means)
        td.weights = list(weights)
        td.main_weight = sum(td.weights)
        td.min = vmin
        td.max = vmax
        td.reciprocal_sum = reciprocal_sum
        return td
