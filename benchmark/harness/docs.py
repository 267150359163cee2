"""YCSB's record and its key popularity, every byte a function of
(seed, ordinal, version).

``Docs`` is a copy of ``chip_smoke.Docs`` without the integer field ``n``
that YCSB does not have: 10 fields x 100 B, keys ``user`` + 20 digits in
hashed order.  ``zipfian`` is YCSB's ZipfianGenerator (Gray et al.,
"Quickly generating billion-record synthetic databases", SIGMOD 1994),
vectorised; rank r is ordinal r, and the key hash scatters the hot
ordinals over the key space as YCSB's scrambled form does."""

from __future__ import annotations

import numpy as np

_MASK = (1 << 64) - 1


def mix64(x: int) -> int:
    """splitmix64 finalizer: a bijection on 64-bit words."""
    x &= _MASK
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK
    return x ^ (x >> 31)


class Docs:
    def __init__(self, seed: int, fields: int = 10,
                 field_bytes: int = 100) -> None:
        self.fields, self.field_bytes = fields, field_bytes
        self.salt = mix64(seed + 0x9E3779B97F4A7C15)
        rng = np.random.default_rng(seed)
        self.pool = (
            rng.integers(97, 123, size=(1 << 20) + 128, dtype=np.uint8)
            .tobytes()
            .decode("ascii")
        )
        self._span = len(self.pool) - field_bytes

    def key(self, i: int) -> str:
        return f"user{mix64(i ^ self.salt):020d}"

    def field(self, i: int, j: int, version: int) -> str:
        off = mix64(((i * 16 + j) << 20 | version) ^ self.salt) % self._span
        return self.pool[off : off + self.field_bytes]

    def doc(self, i: int, version: int) -> dict:
        return {
            f"field{j}": self.field(i, j, version)
            for j in range(self.fields)
        }

    def version_of(self, i: int, doc, lo: int, hi: int):
        """Which version in [lo, hi] the stored record is, by its
        field0; None if none of them (or not a record at all)."""
        if not isinstance(doc, dict):
            return None
        got = doc.get("field0")
        for version in range(hi, lo - 1, -1):
            if got == self.field(i, 0, version):
                return version
        return None


MAX_VERSION = (1 << 20) - 1  # versions have 20 bits of the field hash's word


def zipfian(rng: np.random.Generator, n_items: int, theta: float,
            size: int) -> np.ndarray:
    """``size`` ranks in [0, n_items), rank 0 the most popular, with
    P(rank r) proportional to 1 / (r + 1)^theta."""
    zetan = float((1.0 / np.arange(1, n_items + 1) ** theta).sum())
    zeta2 = 1.0 + 0.5**theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n_items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    ranks = (n_items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ranks[uz < zeta2] = 1
    ranks[uz < 1.0] = 0
    return np.minimum(ranks, n_items - 1)
