"""Scalar/numpy Circllhist-style log-linear histogram, the host-side
reference.

Capability parity with the Circllhist data structure (arXiv:2001.06561):
a value is binned by (sign, decimal exponent, two-significant-digit
mantissa bucket) — bin (e, m) covers [m*10^(e-1), (m+1)*10^(e-1)) with
m in 10..99 — so the bin layout is FIXED and merges are exact register
additions (commutative, associative, lossless). Unlike the t-digest
family this makes globally-exact latency distributions possible through
the local -> proxy -> global forward tier: bins forwarded from N locals
and summed on the global are bit-identical to a single node that saw
every sample.

The paper's structure is sparse over the full int8 exponent range; the
device table (veneur_tpu.ops.batch_llhist) is a dense (keys x BINS)
int32 register array, so this module fixes a bounded exponent window
[EXP_MIN, EXP_MAX] (covering 1e-9 .. 1e16 — nanoseconds to ~115 days in
seconds, with headroom for bytes/counts). Magnitudes below the window
collapse into the zero bin, magnitudes above clamp into the top bin of
their sign; both are counted by callers that care (llhist.clamped
self-metric).

Quantiles interpolate linearly inside the located bin, so the error is
bounded by one bin width (<= 10% of the value, the log-linear
guarantee). Sum/mean are approximated from bin midpoints, as in the
reference implementation.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

# decimal exponent window of the dense layout: bin (e, m) covers
# [m*10^(e-1), (m+1)*10^(e-1)), m in 10..99
EXP_MIN = -9
EXP_MAX = 15
NEXP = EXP_MAX - EXP_MIN + 1  # 25 exponents
MANT = 90                     # mantissa buckets 10..99

# bin index layout: 0 = zero bin, then positive bins ordered by
# (exponent, mantissa), then negative bins in the same order
ZERO_BIN = 0
POS_BASE = 1
NEG_BASE = 1 + MANT * NEXP
BINS = 1 + 2 * MANT * NEXP  # 4501

# smallest representable magnitude; |v| below it falls in the zero bin
MIN_MAG = 10.0 ** EXP_MIN
# top-bin lower edge; |v| >= MAX_MAG clamps into the top bin of its sign
MAX_MAG = 10.0 ** (EXP_MAX + 1)

# per-bin geometry, indexed by bin id. For a negative bin the "left"
# edge is the smaller (more negative) end, so [left, left+width) always
# brackets the bin's values and quantile interpolation is sign-agnostic.
_e = np.repeat(np.arange(EXP_MIN, EXP_MAX + 1, dtype=np.float64), MANT)
_m = np.tile(np.arange(10, 100, dtype=np.float64), NEXP)
_pos_width = 10.0 ** (_e - 1)
_pos_left = _m * _pos_width
BIN_WIDTH = np.concatenate([[0.0], _pos_width, _pos_width])
BIN_LEFT = np.concatenate([[0.0], _pos_left, -(_pos_left + _pos_width)])
BIN_MID = np.concatenate(
    [[0.0], _pos_left + _pos_width / 2, -(_pos_left + _pos_width / 2)])
del _e, _m, _pos_width, _pos_left

# value-ascending traversal order of the bin ids (negative bins from
# most negative, the zero bin, then positive bins ascending) — the
# quantile walk and cumulative bucket export both run in this order
ORDER = np.argsort(BIN_MID, kind="stable").astype(np.int32)
LEFT_SORTED = BIN_LEFT[ORDER]
WIDTH_SORTED = BIN_WIDTH[ORDER]
MID_SORTED = BIN_MID[ORDER]
# upper edge of each bin in sorted order (the Prometheus `le` bound)
UPPER_SORTED = LEFT_SORTED + WIDTH_SORTED
# bin id -> its position in that traversal (the inverse of ORDER)
RANK = np.empty(BINS, np.int32)
RANK[ORDER] = np.arange(BINS, dtype=np.int32)


def bin_index(values) -> np.ndarray:
    """Vectorized value -> bin id. NaN/Inf are the caller's problem for
    finite-math purposes (the DogStatsD parser rejects them); +/-Inf
    clamps into the top bin of its sign, NaN lands in the zero bin."""
    v = np.asarray(values, np.float64)
    scalar = v.ndim == 0
    v = np.atleast_1d(v)
    out = np.zeros(v.shape, np.int32)
    a = np.abs(v)
    nz = a >= MIN_MAG
    if nz.any():
        a_nz = a[nz]
        with np.errstate(over="ignore", invalid="ignore"):
            e = np.floor(np.log10(a_nz))
        e = np.where(np.isfinite(e), e, float(EXP_MAX))
        # float-log correction: force 10^e <= a < 10^(e+1) before the
        # mantissa extraction (log10 of exact powers can land a hair off)
        e = np.where(a_nz < 10.0 ** e, e - 1, e)
        e = np.where(a_nz >= 10.0 ** (e + 1), e + 1, e)
        e = np.clip(e, EXP_MIN, EXP_MAX)
        with np.errstate(over="ignore"):
            mant = np.floor(a_nz / 10.0 ** (e - 1))
        mant = np.clip(np.where(np.isfinite(mant), mant, 99.0), 10, 99)
        idx = (POS_BASE + (e - EXP_MIN) * MANT + (mant - 10)).astype(np.int32)
        idx = np.where(v[nz] < 0, idx + MANT * NEXP, idx)
        out[nz] = idx
    return out[0] if scalar else out


def clamped_mask(values) -> np.ndarray:
    """Which samples fell outside the representable window (collapsed to
    the zero bin or clamped into a top bin) — the accuracy-loss signal
    surfaced as the llhist.clamped self-metric."""
    a = np.abs(np.asarray(values, np.float64))
    return ((a > 0) & (a < MIN_MAG)) | (a >= MAX_MAG)


def quantiles(bins: np.ndarray, ps: Sequence[float]) -> np.ndarray:
    """Quantiles from a dense register row (linear interpolation inside
    the located bin; error <= one bin width). An all-zero row reads 0."""
    c = np.asarray(bins, np.float64)[ORDER]
    csum = np.cumsum(c)
    total = csum[-1]
    out = np.zeros(len(ps), np.float64)
    if total <= 0:
        return out
    for i, p in enumerate(ps):
        # rank in (0, total]; the 0.5 floor makes p=0 read the minimum
        # occupied bin (counts are integral)
        rank = max(min(float(p), 1.0) * total, 0.5)
        j = int(np.searchsorted(csum, rank, side="left"))
        j = min(j, csum.shape[0] - 1)
        prev = csum[j - 1] if j > 0 else 0.0
        cnt = c[j]
        frac = (rank - prev) / cnt if cnt > 0 else 0.5
        out[i] = LEFT_SORTED[j] + WIDTH_SORTED[j] * min(max(frac, 0.0), 1.0)
    return out


def nonzero_entries(table) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """A register table `(rows, >= BINS)` as its nonzero entries
    `(row, bin id, count int64)`, row by row and by ascending bin id
    within a row: one scan, whatever the table's integer width."""
    table = np.asarray(table)
    rows, bins = np.nonzero(table)
    return rows, bins, table[rows, bins].astype(np.int64)


def entry_sums(rows, bins, counts, n_rows: int) -> np.ndarray:
    """THE definition of an llhist's `.sum`, for `n_rows` rows at once:
    float64 `count * BIN_MID[bin]` over a row's nonzero registers,
    added one after another in ascending bin id (the order
    `nonzero_entries` yields; `np.bincount` accumulates its weights in
    input order). Every `.sum` the program emits comes through here, so
    it depends on the registers alone: not on a BLAS kernel's blocking,
    the table's width or padding, the row's place in it, or how many
    shards merged into it."""
    return np.bincount(
        rows, weights=np.asarray(counts, np.float64) * BIN_MID[bins],
        minlength=n_rows)


def cumulative_entries(rows, bins, counts, n_rows: int):
    """Every row's cumulative buckets from its nonzero entries, as CSR:
    -> (indptr (n_rows + 1,), rank (nnz,), cum (nnz,) int64, total
    (n_rows,) int64). Row `i` owns `indptr[i]:indptr[i + 1]`: its
    nonzero registers in value-ascending order, `rank` their positions
    in that traversal (an index into UPPER_SORTED) and `cum` the count
    up to and including each; `total[i]` is the row's count, the
    `+Inf` bucket. What `LLHist.cumulative_buckets` gives row by row."""
    rows = np.asarray(rows, np.int64)
    order = np.argsort(rows * BINS + RANK[bins], kind="stable")
    per_row = np.bincount(rows, minlength=n_rows)
    indptr = np.concatenate([[0], np.cumsum(per_row)])
    run = np.concatenate([[0], np.cumsum(counts[order])])
    before = run[indptr[:-1]]  # the running count at each row's start
    return (indptr, RANK[bins[order]], run[1:] - np.repeat(before, per_row),
            run[indptr[1:]] - before)


def approx_sum(bins: np.ndarray) -> float:
    """Midpoint-weighted sum of one register row (the Circllhist sum
    approximation), by `entry_sums`."""
    rows, idx, counts = nonzero_entries(np.asarray(bins)[None, :])
    return float(entry_sums(rows, idx, counts, 1)[0])


def count(bins: np.ndarray) -> float:
    return float(np.asarray(bins, np.int64).sum())


class LLHist:
    """Dense log-linear histogram over BINS int64 registers."""

    __slots__ = ("bins",)

    def __init__(self, bins=None):
        self.bins = (np.zeros(BINS, np.int64) if bins is None
                     else np.asarray(bins, np.int64).copy())

    def insert(self, value: float, count: int = 1) -> None:
        self.bins[int(bin_index(value))] += int(count)

    def insert_many(self, values, counts=None) -> None:
        idx = bin_index(values)
        w = (np.ones(idx.shape, np.int64) if counts is None
             else np.asarray(counts, np.int64))
        np.add.at(self.bins, idx, w)

    def merge(self, other: "LLHist") -> None:
        self.bins += other.bins

    def quantile(self, p: float) -> float:
        return float(quantiles(self.bins, (p,))[0])

    def quantiles(self, ps: Sequence[float]) -> np.ndarray:
        return quantiles(self.bins, ps)

    def sum(self) -> float:
        return approx_sum(self.bins)

    def count(self) -> int:
        return int(self.bins.sum())

    def cumulative_buckets(self) -> Tuple[np.ndarray, np.ndarray]:
        """(upper_bounds, cumulative_counts) over occupied bins in
        value-ascending order — the Prometheus `_bucket`/`le` export
        shape (the +Inf bucket is the total and is the caller's to
        append)."""
        c = self.bins[ORDER]
        csum = np.cumsum(c)
        nz = np.flatnonzero(c)
        return UPPER_SORTED[nz], csum[nz]
