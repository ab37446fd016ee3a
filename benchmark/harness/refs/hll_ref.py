"""(Copy of veneur_tpu/ops/hll_ref.py, kept with the benchmark so that no
later PR can move the yardstick; its one import of the program, the FNV
hash, is inlined.)

Scalar HyperLogLog (dense, precision 14), the host-side reference.

Capability parity with the reference's vendored axiomhq/hyperloglog (p=14,
16384 registers, ~0.8% standard error, LogLog-Beta estimator, register-max
merge). The member hash is fnv1a-64 with a murmur3-style finalizer — our
own deterministic choice (both ends of the forward plane are this
framework), not the reference's metrohash.

The batched device kernel (veneur_tpu.ops.batch_hll) holds registers as a
(keys x 16384) int8 array; this scalar form is used for validation and as
the serialization boundary.
"""

from __future__ import annotations

import math

import numpy as np

_FNV64_OFFSET = 0xCBF29CE484222325
_FNV64_PRIME = 0x100000001B3


def fnv1a_64(data: bytes, h: int = _FNV64_OFFSET) -> int:
    """Copy of veneur_tpu/util/fnv.py's 64-bit FNV-1a: the references
    import nothing of the program."""
    for b in data:
        h = ((h ^ b) * _FNV64_PRIME) & 0xFFFFFFFFFFFFFFFF
    return h

P = 14
M = 1 << P  # 16384 registers
MAX_RHO = 64 - P + 1

_ALPHA = 0.7213 / (1 + 1.079 / M)
_M64 = (1 << 64) - 1

# LogLog-Beta bias-correction polynomial for p=14 (LogLog-Beta paper,
# coefficients as used by the reference's vendored estimator).
_BETA14 = (0.070471823, 0.17393686, 0.16339839, -0.09237745,
           0.03738027, -0.005384159, 0.00042419)
_BETA14_EZ = -0.370393911


def hash_member(member: bytes) -> int:
    """Deterministic 64-bit member hash: fnv1a-64 + avalanche finalizer."""
    h = fnv1a_64(member)
    h ^= h >> 33
    h = (h * 0xFF51AFD7ED558CCD) & _M64
    h ^= h >> 33
    h = (h * 0xC4CEB9FE1A85EC53) & _M64
    h ^= h >> 33
    return h


def pos_val(x: int) -> tuple:
    """Split a 64-bit hash into (register index, rho)."""
    idx = x >> (64 - P)
    w = ((x << P) | (1 << (P - 1))) & _M64
    # rho = leading zeros of w, plus 1
    rho = 65 - w.bit_length()
    return idx, rho


def beta14(ez: float) -> float:
    zl = math.log(ez + 1.0)
    acc = _BETA14_EZ * ez
    zp = 1.0
    for c in _BETA14:
        zp *= zl
        acc += c * zp
    return acc


def estimate_from_registers(regs: np.ndarray) -> float:
    """LogLog-Beta cardinality estimate from a dense register array.
    The reference adds 0.5 inside and truncates on return
    (hyperloglog.go:225-231), yielding whole numbers."""
    regs = np.asarray(regs)
    if not regs.any():
        return 0.0
    ez = float(np.count_nonzero(regs == 0))
    s = float(np.sum(np.exp2(-regs.astype(np.float64))))
    return float(np.floor(_ALPHA * M * (M - ez) / (beta14(ez) + s) + 1.0))


class HLL:
    """Dense HyperLogLog sketch over 16384 int8 registers."""

    __slots__ = ("regs",)

    def __init__(self, regs=None):
        self.regs = (np.zeros(M, dtype=np.int8) if regs is None
                     else np.asarray(regs, dtype=np.int8))

    def insert(self, member: bytes) -> None:
        idx, rho = pos_val(hash_member(member))
        if rho > self.regs[idx]:
            self.regs[idx] = rho

    def insert_hash(self, h: int) -> None:
        idx, rho = pos_val(h)
        if rho > self.regs[idx]:
            self.regs[idx] = rho

    def estimate(self) -> float:
        return estimate_from_registers(self.regs)

    def merge(self, other: "HLL") -> None:
        np.maximum(self.regs, other.regs, out=self.regs)

    # -- serialization (our own wire format: raw registers) --------------

    def to_bytes(self) -> bytes:
        return self.regs.tobytes()

    @staticmethod
    def from_bytes(data: bytes) -> "HLL":
        if len(data) != M:
            raise ValueError(f"HLL register dump must be {M} bytes")
        return HLL(np.frombuffer(data, dtype=np.int8).copy())
