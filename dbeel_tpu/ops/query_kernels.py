"""Device lane of the query plane's numeric filter masks (PR 13).

The scan plane's pushdown evaluator (storage/query_vec.py) is numpy on
the host.  This module evaluates the numeric comparison and range
leaves of a staged float64 column under ``jax.jit`` — exactly.  The
accelerator has no float64 (x64 is off, and a float64 array handed to
jit is silently rounded to float32: ``16777217.0 > 16777216.0`` would
answer False), so the column never crosses as floats.  The host maps
each float64 to its order-preserving 64-bit key (sign bit flipped for
non-negatives, all bits flipped for negatives; -0.0 folded onto +0.0),
splits it into two uint32 words, and the kernel compares the word
pairs lexicographically (the ``_lex_gt`` idiom of ops/bitonic.py).
Total order on the keys is IEEE order on the values; NaN rows — which
compare false under every operator but ``!=`` — are carried as their
own mask.  The result is the numpy lane's mask bit for bit.  Sums are
order-sensitive in floating point and stay on the host.

The lane is open where the process holds an accelerator
(``device.held()``: the single-process node after ``acquire()``), or
where ``DBEEL_QUERY_DEVICE=cpu_ok`` forces the jit CPU backend for the
parity tests.  A kernel that fails raises.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional, Tuple

import numpy as np

from .. import device

_OPS = ("==", "!=", "<", "<=", ">", ">=")

# Below this many rows the jit dispatch overhead exceeds the numpy
# kernel outright; the host path serves small stages regardless.
MIN_DEVICE_ROWS = 4096

# Staged columns are padded to a multiple of this many rows so the
# kernels compile for a handful of shapes, not one per stage size.
ROW_BUCKET = 1 << 16

_SIGN = np.uint64(1) << np.uint64(63)


def available() -> bool:
    """True when the jitted mask kernels may serve evaluations: this
    process holds an accelerator, or the tests force the CPU backend.
    Never initialises JAX from the serving path."""
    force = os.environ.get("DBEEL_QUERY_DEVICE", "")
    if force in ("0", "off"):
        return False
    if force in ("1", "cpu_ok"):
        return True
    held = device.held()
    return held is not None and held["platform"] != "cpu"


def serves(rows: int) -> bool:
    """Whether the device lane evaluates a stage of ``rows`` rows: the
    lane is open and the stage is big enough to pay for the dispatch
    (a selection on the data; the numpy lane gives the same mask)."""
    return rows >= MIN_DEVICE_ROWS and available()


def order_key(x: float) -> Tuple[int, int]:
    """(hi, lo) uint32 words of one float64's order-preserving key."""
    hi, lo, _nan = order_words(np.array([x], dtype=np.float64))
    return int(hi[0]), int(lo[0])


def order_words(
    vals: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """float64 column → (hi, lo, isnan): the two uint32 words of each
    value's order-preserving key, and the NaN rows."""
    bits = (vals + 0.0).view(np.uint64)  # -0.0 + 0.0 == +0.0
    key = np.where((bits & _SIGN) != 0, ~bits, bits | _SIGN)
    return (
        (key >> np.uint64(32)).astype(np.uint32),
        key.astype(np.uint32),
        np.isnan(vals),
    )


class StagedColumn:
    """One float64 column staged for the device lane: order words,
    NaN and validity masks, padded to a ROW_BUCKET multiple (padding
    rows are invalid).  Built once per column and cached by the
    caller, so a query pays the conversion pass once per stage."""

    __slots__ = ("n", "hi", "lo", "nan", "valid")

    def __init__(self, vals: np.ndarray, valid: np.ndarray) -> None:
        self.n = int(vals.size)
        padded = -(-self.n // ROW_BUCKET) * ROW_BUCKET
        hi, lo, nan = order_words(vals)
        self.hi = np.zeros(padded, dtype=np.uint32)
        self.lo = np.zeros(padded, dtype=np.uint32)
        self.nan = np.zeros(padded, dtype=bool)
        self.valid = np.zeros(padded, dtype=bool)
        self.hi[: self.n] = hi
        self.lo[: self.n] = lo
        self.nan[: self.n] = nan
        self.valid[: self.n] = valid


def _gt(hi, lo, oh, ol):
    return (hi > oh) | ((hi == oh) & (lo > ol))


def _eq(hi, lo, oh, ol):
    return (hi == oh) & (lo == ol)


def _cmp_body(hi, lo, nan, valid, oh, ol, op):
    gt, eq = _gt(hi, lo, oh, ol), _eq(hi, lo, oh, ol)
    m = {
        "==": eq,
        "!=": ~eq,
        "<": ~(gt | eq),
        "<=": ~gt,
        ">": gt,
        ">=": gt | eq,
    }[op]
    # IEEE: NaN compares false under every operator but "!=".
    m = (m | nan) if op == "!=" else (m & ~nan)
    return m & valid


def _range_body(hi, lo, nan, valid, lh, ll, hh, hl, use_lo, use_hi):
    import jax.numpy as jnp

    ge_lo = _gt(hi, lo, lh, ll) | _eq(hi, lo, lh, ll)
    lt_hi = ~(_gt(hi, lo, hh, hl) | _eq(hi, lo, hh, hl))
    m = jnp.where(use_lo, ge_lo & ~nan, True)
    m = m & jnp.where(use_hi, lt_hi & ~nan, True)
    return m & valid


_jitted = None


def kernels() -> dict:
    """The jitted mask kernels, built once: ``cmp(hi, lo, nan, valid,
    oh, ol, op=)`` and ``range(hi, lo, nan, valid, lh, ll, hh, hl,
    use_lo, use_hi)`` over uint32 word columns."""
    global _jitted
    if _jitted is None:
        import jax

        _jitted = {
            "cmp": partial(jax.jit, static_argnames=("op",))(
                _cmp_body
            ),
            "range": jax.jit(_range_body),
        }
    return _jitted


def eval_cmp(
    col: StagedColumn, operand: float, op: str
) -> np.ndarray:
    """Device mask of ``column <op> operand`` over the valid rows."""
    if op not in _OPS:
        raise ValueError(f"unknown comparison {op!r}")
    if operand != operand:  # NaN operand: decided without a compare
        base = col.valid if op == "!=" else np.zeros_like(col.valid)
        return base[: col.n].copy()
    oh, ol = order_key(operand)
    out = kernels()["cmp"](
        col.hi, col.lo, col.nan, col.valid,
        np.uint32(oh), np.uint32(ol), op=op,
    )
    return np.asarray(out)[: col.n]


def eval_range(
    col: StagedColumn, lo: Optional[float], hi: Optional[float]
) -> np.ndarray:
    """Device mask of ``lo <= column < hi`` (either bound optional)."""
    if (lo is not None and lo != lo) or (hi is not None and hi != hi):
        return np.zeros(col.n, dtype=bool)  # a NaN bound admits nothing
    lh, ll = order_key(0.0 if lo is None else lo)
    hh, hl = order_key(0.0 if hi is None else hi)
    out = kernels()["range"](
        col.hi, col.lo, col.nan, col.valid,
        np.uint32(lh), np.uint32(ll), np.uint32(hh), np.uint32(hl),
        lo is not None, hi is not None,
    )
    return np.asarray(out)[: col.n]
