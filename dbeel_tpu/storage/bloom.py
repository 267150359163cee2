"""Bloom filter for SSTables.

Role parity with the reference's use of the ``bloomfilter`` crate at 1% FP
(/root/reference/src/storage_engine/lsm_tree.rs:44-50, 1026-1034): one
filter per sufficiently-large SSTable, checked before the index binary
search on reads.

Double hashing (Kirsch–Mitzenmacher): bit_i = (h1 + i*h2) mod m with two
murmur3_32 seeds.  ``add_batch`` vectorizes the build over all keys of an
SSTable with numpy, which is how the device compaction path rebuilds
blooms for merged outputs without a per-key Python loop.
"""

from __future__ import annotations

import math
import struct
from typing import Iterable, Optional, Tuple

import numpy as np

from ..utils.murmur import murmur3_32, murmur3_32_batch

_SEED1 = 0x9747B28C
_SEED2 = 0x85EBCA6B

_HEADER = struct.Struct("<QII")  # num_bits, num_hashes, reserved


class BloomFilter:
    def __init__(
        self, num_bits: int, num_hashes: int, bits: Optional[np.ndarray] = None
    ) -> None:
        """``bits``: the caller's own zeroed uint8 array of
        ``(num_bits + 7) // 8`` bytes, in place of a fresh one."""
        self.num_bits = max(64, int(num_bits))
        self.num_hashes = max(1, int(num_hashes))
        nbytes = (self.num_bits + 7) // 8
        if bits is None:
            bits = np.zeros(nbytes, dtype=np.uint8)
        elif bits.dtype != np.uint8 or bits.shape != (nbytes,):
            raise ValueError(
                f"bloom bits must be {nbytes} uint8, got "
                f"{bits.dtype} {bits.shape}"
            )
        self.bits = bits

    @staticmethod
    def size_for(n_items: int, fp_rate: float = 0.01) -> Tuple[int, int]:
        """(num_bits, num_hashes) of a filter for ``n_items`` keys."""
        n = max(1, n_items)
        m = int(-n * math.log(fp_rate) / (math.log(2) ** 2)) + 1
        k = max(1, round(m / n * math.log(2)))
        return max(64, m), k

    @classmethod
    def with_capacity(
        cls, n_items: int, fp_rate: float = 0.01
    ) -> "BloomFilter":
        return cls(*cls.size_for(n_items, fp_rate))

    def _indices(self, key: bytes) -> np.ndarray:
        h1 = murmur3_32(key, _SEED1)
        h2 = murmur3_32(key, _SEED2) | 1
        i = np.arange(self.num_hashes, dtype=np.uint64)
        return (np.uint64(h1) + i * np.uint64(h2)) % np.uint64(self.num_bits)

    def add(self, key: bytes) -> None:
        idx = self._indices(key)
        np.bitwise_or.at(
            self.bits, (idx >> np.uint64(3)).astype(np.int64),
            np.left_shift(1, (idx & np.uint64(7)).astype(np.int64)).astype(
                np.uint8
            ),
        )

    def add_batch(self, keys: Iterable[bytes]) -> None:
        keys = list(keys)
        if not keys:
            return
        h1 = murmur3_32_batch(keys, _SEED1).astype(np.uint64)
        h2 = (murmur3_32_batch(keys, _SEED2) | 1).astype(np.uint64)
        i = np.arange(self.num_hashes, dtype=np.uint64)[None, :]
        idx = (h1[:, None] + i * h2[:, None]) % np.uint64(self.num_bits)
        idx = idx.ravel()
        np.bitwise_or.at(
            self.bits, (idx >> np.uint64(3)).astype(np.int64),
            np.left_shift(1, (idx & np.uint64(7)).astype(np.int64)).astype(
                np.uint8
            ),
        )

    def check(self, key: bytes) -> bool:
        idx = self._indices(key)
        byte = self.bits[(idx >> np.uint64(3)).astype(np.int64)]
        bit = (byte >> (idx & np.uint64(7)).astype(np.uint8)) & 1
        return bool(bit.all())

    def serialize(self) -> bytes:
        return (
            _HEADER.pack(self.num_bits, self.num_hashes, 0)
            + self.bits.tobytes()
        )

    @classmethod
    def deserialize(cls, buf: bytes) -> Optional["BloomFilter"]:
        if len(buf) < _HEADER.size:
            return None
        num_bits, num_hashes, _ = _HEADER.unpack_from(buf, 0)
        bf = cls(num_bits, num_hashes)
        body = np.frombuffer(buf, dtype=np.uint8, offset=_HEADER.size)
        if body.size != bf.bits.size:
            return None
        bf.bits = body.copy()
        return bf
