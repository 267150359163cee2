"""Bitonic merge network — the TPU-native compaction merge kernel.

Why not ``lax.sort``: XLA's TPU sort with a multi-operand comparator is
pathological for this workload (measured on TPU v5e: 8-key sort of 2^18
rows = 202 s compile + 41 ms/run, vs 0.2 ms for 1 key).  Compaction
doesn't need a full sort anyway — its inputs are K *already-sorted* runs
(SSTables are sorted by construction).  A bitonic merge network does the
k-way merge in ``log2(K)`` batched pairwise rounds of ``log2(L)``
elementwise compare-exchange stages: only static reshapes, compares and
selects — tiny HLO, fast compile, HBM-bandwidth-bound execution.  This is
the "batched bitonic merge expressed in jax.jit" the north star names
(BASELINE.json), replacing the reference's per-entry heap loop
(/root/reference/src/storage_engine/lsm_tree.rs:1038-1066).

Row format is the 9-column uint32 entry stack of parallel/dist_merge.py:
  cols 0-3 k0..k3 (16B big-endian key prefix), 4 key_len,
  5-6 ~ts hi/lo, 7 ~src, 8 carried entry index.
Lexicographic comparator over cols 0-7; sentinel rows (all 0xFFFFFFFF)
sort last.  Equal full tuples cannot occur for distinct entries except
keys longer than the 16-byte prefix, which the host fixes up afterwards
(storage/columnar.py).
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..storage import columnar

NUM_COLS = 9
NUM_KEY_COLS = 8
NUM_EQ_COLS = 5  # key identity = prefix words + key_len
SENTINEL = np.uint32(0xFFFFFFFF)


def _lex_gt(a: jnp.ndarray, b: jnp.ndarray, ncmp: int = NUM_KEY_COLS):
    """a > b lexicographically over the first ``ncmp`` columns.
    a, b: (..., C)."""
    gt = jnp.zeros(a.shape[:-1], dtype=bool)
    eq = jnp.ones(a.shape[:-1], dtype=bool)
    for c in range(ncmp):
        ac, bc = a[..., c], b[..., c]
        gt = gt | (eq & (ac > bc))
        eq = eq & (ac == bc)
    return gt


def _bitonic_to_sorted(x: jnp.ndarray, ncmp: int) -> jnp.ndarray:
    """(B, L, C) rows that are bitonic along axis 1 → ascending rows.
    Classic bitonic merge: stages with strides L/2, L/4, …, 1, each a
    static reshape + compare-exchange."""
    b, l, c = x.shape
    s = l // 2
    while s >= 1:
        y = x.reshape(b, l // (2 * s), 2, s, c)
        lo, hi = y[:, :, 0], y[:, :, 1]
        swap = _lex_gt(lo, hi, ncmp)[..., None]
        nlo = jnp.where(swap, hi, lo)
        nhi = jnp.where(swap, lo, hi)
        x = jnp.stack([nlo, nhi], axis=2).reshape(b, l, c)
        s //= 2
    return x


def _merge_level(x: jnp.ndarray, ncmp: int = NUM_KEY_COLS) -> jnp.ndarray:
    """(K, P, C) sorted runs → (K/2, 2P, C) sorted runs: concat each even
    run with its odd neighbour reversed (ascending+descending = bitonic),
    then merge — all K/2 pairs in one batched op."""
    a = x[0::2]
    b_rev = x[1::2][:, ::-1]
    return _bitonic_to_sorted(
        jnp.concatenate([a, b_rev], axis=1), ncmp
    )


def _merged_with_same(stacks: jnp.ndarray):
    x = stacks
    while x.shape[0] > 1:
        x = _merge_level(x, NUM_KEY_COLS)
    out = x[0]
    eq = jnp.ones(out.shape[0] - 1, dtype=bool)
    for c in range(NUM_EQ_COLS):
        eq = eq & (out[1:, c] == out[:-1, c])
    eq = eq & (out[1:, 4] != SENTINEL)
    same = jnp.concatenate([jnp.zeros((1,), bool), eq])
    return out, same


@jax.jit
def merge_runs_kernel(
    stacks: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(K, P, NUM_COLS) sorted (sentinel-padded) runs, K and P powers of
    two → (K*P, NUM_COLS) globally sorted stack + same-key flags."""
    return _merged_with_same(stacks)


@jax.jit
def merge_runs_perm_kernel(
    stacks: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Like merge_runs_kernel but returns only (sorted entry indices,
    same flags) — a ~9x smaller device→host transfer."""
    out, same = _merged_with_same(stacks)
    return out[:, 8], same


def sort_stack_kernel(stack: jnp.ndarray):
    """Full bitonic sort of an unsorted (N, NUM_COLS) stack (N pow2):
    every row is a 1-length run, then the merge tournament."""
    return merge_runs_kernel(stack[:, None, :])


# ----------------------------------------------------------------------
# Prefix kernel — the transfer-minimal device path.
#
# The hot path ships only the 8-byte big-endian key prefix per entry
# (2 uint32 words) and receives a single packed uint32 order index back
# (whether the small transfer still pays on a chip-local host has not
# been measured on the current chip).  Timestamps/sources never leave the
# host: any entries tying on the 8-byte prefix (same key, shared prefix,
# or key longer than 8 bytes with equal head) are re-ordered on the host
# by (full key, ~ts, ~src) — which also subsumes long-key handling, so
# this path is fully general.  Comparator = (k0, k1, idx) where idx is a
# device-built unique iota (sentinel rows get idx=MAX and therefore sort
# strictly last, making a static top-slice safe).
# ----------------------------------------------------------------------


def _prefix_merge_body(
    prefixes: jnp.ndarray, counts: jnp.ndarray, out_rows: int
):
    k, p, _ = prefixes.shape
    iota = (
        jnp.arange(k, dtype=jnp.uint32)[:, None] * jnp.uint32(p)
        + jnp.arange(p, dtype=jnp.uint32)[None, :]
    )
    valid = jnp.arange(p, dtype=jnp.uint32)[None, :] < counts[:, None]
    idx = jnp.where(valid, iota, jnp.uint32(0xFFFFFFFF))
    x = jnp.concatenate([prefixes, idx[:, :, None]], axis=2)
    while x.shape[0] > 1:
        x = _merge_level(x, ncmp=3)
    return x[0, :out_rows, 2]


@functools.partial(jax.jit, static_argnames=("out_rows",))
def merge_runs_prefix_kernel(
    prefixes: jnp.ndarray,  # (K, P, 2) uint32
    counts: jnp.ndarray,  # (K,) uint32 valid rows per run
    out_rows: int,
):
    return _prefix_merge_body(prefixes, counts, out_rows)


# ----------------------------------------------------------------------
# Round-3 transfer-minimal kernels (ops/pipeline.py hot path).
#
# Uplink: the pipeline rebases every partition's 8-byte prefixes to the
# partition minimum and right-shifts so the span fits 32 bits — an
# order-preserving u32 approximation (collisions become host-fixed tie
# blocks, exactly like genuinely equal prefixes).  The operand is ONE
# u32 word per entry instead of two: half the h2d bytes and a cheaper
# comparator.  Wide partitions where the shift would collapse dense
# clusters keep the exact 2-word operand (the host checks cheaply).
#
# Downlink: within one partition each run's survivors appear in
# increasing position order (the comparator is a total order and runs
# are pre-sorted), so run-id alone reconstructs the permutation with
# per-run counters on the host.  The kernel therefore returns only the
# run-id sequence, bit-packed `pack_bits` per entry into u32 words —
# 8x (K<=16) or 4x (K<=256) fewer d2h bytes than the packed u32 index.
# ----------------------------------------------------------------------


def _pack_rids(idx_sorted: jnp.ndarray, logp: int, pack_bits: int):
    """Sorted packed indices (N,) u32 → bit-packed run-ids, pack_bits
    per entry, little-end-first within each u32 word."""
    per = 32 // pack_bits
    n = idx_sorted.shape[0]
    pad = (-n) % per
    if pad:
        idx_sorted = jnp.concatenate(
            [idx_sorted, jnp.full((pad,), SENTINEL, jnp.uint32)]
        )
    rid = (idx_sorted >> jnp.uint32(logp)) & jnp.uint32(
        (1 << pack_bits) - 1
    )
    group = rid.reshape(-1, per)
    shifts = jnp.arange(per, dtype=jnp.uint32) * jnp.uint32(pack_bits)
    # Disjoint bit ranges: sum == bitwise-or.
    return jnp.sum(
        group << shifts[None, :], axis=1, dtype=jnp.uint32
    )


def _prefix32_packed_body(
    vals: jnp.ndarray, counts: jnp.ndarray, pack_bits: int
):
    k, p = vals.shape
    iota = (
        jnp.arange(k, dtype=jnp.uint32)[:, None] * jnp.uint32(p)
        + jnp.arange(p, dtype=jnp.uint32)[None, :]
    )
    valid = jnp.arange(p, dtype=jnp.uint32)[None, :] < counts[:, None]
    idx = jnp.where(valid, iota, SENTINEL)
    x = jnp.stack([vals, idx], axis=2)
    while x.shape[0] > 1:
        x = _merge_level(x, ncmp=2)
    return _pack_rids(x[0, :, 1], p.bit_length() - 1, pack_bits)


def _prefix64_packed_body(
    prefixes: jnp.ndarray, counts: jnp.ndarray, pack_bits: int
):
    k, p, _ = prefixes.shape
    iota = (
        jnp.arange(k, dtype=jnp.uint32)[:, None] * jnp.uint32(p)
        + jnp.arange(p, dtype=jnp.uint32)[None, :]
    )
    valid = jnp.arange(p, dtype=jnp.uint32)[None, :] < counts[:, None]
    idx = jnp.where(valid, iota, SENTINEL)
    x = jnp.concatenate([prefixes, idx[:, :, None]], axis=2)
    while x.shape[0] > 1:
        x = _merge_level(x, ncmp=3)
    return _pack_rids(x[0, :, 2], p.bit_length() - 1, pack_bits)


@functools.partial(jax.jit, static_argnames=("pack_bits",))
def merge_runs_prefix32_packed_batch_kernel(
    vals: jnp.ndarray,  # (J, K, P) u32 — J partitions per launch
    counts: jnp.ndarray,  # (J, K) u32
    pack_bits: int,
):
    """Batched variant: J keyspace partitions merged in ONE device
    program (vmap over the partition axis): batching divides the
    per-launch overhead by J; empty slots (counts=0) pad the final
    batch to keep one compiled shape."""
    return jax.vmap(
        lambda v, c: _prefix32_packed_body(v, c, pack_bits)
    )(vals, counts)


@functools.partial(jax.jit, static_argnames=("pack_bits",))
def merge_runs_prefix64_packed_batch_kernel(
    prefixes: jnp.ndarray,  # (J, K, P, 2) u32
    counts: jnp.ndarray,  # (J, K) u32
    pack_bits: int,
):
    return jax.vmap(
        lambda v, c: _prefix64_packed_body(v, c, pack_bits)
    )(prefixes, counts)


def rid_pack_bits(k2: int) -> int:
    """Smallest packing width in {1,2,4,8,16} holding run-ids < k2."""
    need = max(1, (k2 - 1).bit_length())
    for b in (1, 2, 4, 8, 16):
        if need <= b:
            return b
    raise ValueError(f"too many runs for rid packing: {k2}")


def unpack_rids(
    words: np.ndarray, pack_bits: int, n: int
) -> np.ndarray:
    """Host-side inverse of _pack_rids → (n,) run-ids as uint32."""
    per = 32 // pack_bits
    mask = np.uint32((1 << pack_bits) - 1)
    shifts = (
        np.arange(per, dtype=np.uint32) * np.uint32(pack_bits)
    )
    rids = (words[:, None] >> shifts[None, :]) & mask
    return rids.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("out_rows",))
def merge_runs_prefix_batch_kernel(
    prefixes: jnp.ndarray,  # (J, K, P, 2) — J independent merge jobs
    counts: jnp.ndarray,  # (J, K)
    out_rows: int,
):
    """Coalesced launch: J shards' compaction merges in ONE device
    program via vmap over the job axis (the BASELINE.json north star —
    'coalesce per-shard compaction jobs into one TPU launch')."""
    return jax.vmap(
        lambda p, c: _prefix_merge_body(p, c, out_rows)
    )(prefixes, counts)


def stage_prefixes(
    cols: columnar.MergeColumns,
    run_counts: List[int],
    k: int = 0,
    p: int = 0,
):
    """Host staging for the prefix kernel: sentinel-padded (K, P, 2)
    prefix words, per-run counts, per-run base offsets, and the
    64Ki-bucketed output row count (few jit traces, ~n d2h bytes).
    ``k``/``p`` may be forced larger for coalesced batches that need a
    common shape."""
    n = len(cols)
    k = max(k, _pow2(max(1, len(run_counts))))
    p = max(p, _pow2(max(8, max(run_counts) if run_counts else 8)))
    prefixes = np.full((k, p, 2), SENTINEL, dtype=np.uint32)
    counts = np.zeros(k, dtype=np.uint32)
    bases = np.zeros(k, dtype=np.int64)
    base = 0
    for r, cnt in enumerate(run_counts):
        prefixes[r, :cnt, 0] = cols.key_words[base : base + cnt, 0]
        prefixes[r, :cnt, 1] = cols.key_words[base : base + cnt, 1]
        counts[r] = cnt
        bases[r] = base
        base += cnt
    out_rows = min(k * p, ((n + 65535) >> 16) << 16)
    return prefixes, counts, bases, out_rows


def device_merge_prefix_order(
    cols: columnar.MergeColumns, run_counts: List[int]
) -> np.ndarray:
    """Device order of ``cols`` by 8-byte key prefix (ties by staging
    position — resolve with columnar.fixup_and_dedup_prefix
    afterwards).
    Returns perm as int64 entry indices."""
    n = len(cols)
    if n == 0:
        return np.zeros(0, np.int64)
    prefixes, counts, bases, out_rows = stage_prefixes(cols, run_counts)
    p = prefixes.shape[1]
    packed = merge_runs_prefix_kernel(prefixes, counts, out_rows)
    packed = np.asarray(packed)[:n]
    run = packed >> np.uint32(p.bit_length() - 1)
    pos = packed & np.uint32(p - 1)
    return bases[run.astype(np.int64)] + pos.astype(np.int64)


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def build_run_stacks(
    cols: columnar.MergeColumns, run_counts: List[int]
) -> np.ndarray:
    """Stage merge columns as a (K, P, 9) sentinel-padded uint32 tensor,
    one sorted run per input sstable."""
    k = _pow2(max(1, len(run_counts)))
    p = _pow2(max(8, max(run_counts) if run_counts else 8))
    stacks = np.full((k, p, NUM_COLS), SENTINEL, dtype=np.uint32)
    ts_inv = ~cols.timestamp
    base = 0
    for r, cnt in enumerate(run_counts):
        sl = slice(base, base + cnt)
        stacks[r, :cnt, 0] = cols.key_words[sl, 0]
        stacks[r, :cnt, 1] = cols.key_words[sl, 1]
        stacks[r, :cnt, 2] = cols.key_words[sl, 2]
        stacks[r, :cnt, 3] = cols.key_words[sl, 3]
        stacks[r, :cnt, 4] = cols.key_size[sl]
        stacks[r, :cnt, 5] = (ts_inv[sl] >> np.uint64(32)).astype(np.uint32)
        stacks[r, :cnt, 6] = (
            ts_inv[sl] & np.uint64(0xFFFFFFFF)
        ).astype(np.uint32)
        stacks[r, :cnt, 7] = ~cols.src[sl]
        stacks[r, :cnt, 8] = np.arange(base, base + cnt, dtype=np.uint32)
        base += cnt
    return stacks


@functools.partial(jax.jit, static_argnames=("out_rows",))
def _prefix_kernel_from_runs(prefix_runs, counts, out_rows: int):
    """Pipelined variant: per-run (P, 2) device arrays stacked on-device
    (uploads overlapped with host-side staging of later runs)."""
    return _prefix_merge_body(
        jnp.stack(prefix_runs), counts, out_rows
    )


def device_merge_prefix_order_pipelined(sources):
    """Like device_merge_prefix_order but fed directly from SSTables:
    each run's prefix slice is device_put as soon as its file is read,
    overlapping disk IO with host→device transfer.  Each file is read
    exactly once — the raw pieces
    are returned for columnar.assemble_columns.

    Returns (perm int64, pieces) over the sources' concatenated
    entries."""
    counts_list = [s.entry_count for s in sources]
    n = sum(counts_list)
    pieces = []
    if n == 0:
        return np.zeros(0, np.int64), pieces
    k = _pow2(max(1, len(sources)))
    p = _pow2(max(8, max(counts_list)))
    dev_runs = []
    bases = np.zeros(k, dtype=np.int64)
    base = 0
    sentinel_run = None
    for r in range(k):
        if r >= len(sources):
            if sentinel_run is None:
                sentinel_run = jax.device_put(
                    np.full((p, 2), SENTINEL, dtype=np.uint32)
                )
            dev_runs.append(sentinel_run)
            continue
        cnt = counts_list[r]
        offs, ks, fs = sources[r].read_index_columns()
        raw = sources[r].read_data_bytes()
        pieces.append((raw, offs, ks, fs))
        data = np.frombuffer(raw, dtype=np.uint8)
        words = columnar.prefix_words(
            data, offs.astype(np.uint64), ks
        )
        run = np.full((p, 2), SENTINEL, dtype=np.uint32)
        run[:cnt, 0] = words[:, 0]
        run[:cnt, 1] = words[:, 1]
        bases[r] = base
        base += cnt
        dev_runs.append(jax.device_put(run))  # async upload
    counts = np.zeros(k, dtype=np.uint32)
    counts[: len(sources)] = counts_list
    out_rows = min(k * p, ((n + 65535) >> 16) << 16)
    packed = _prefix_kernel_from_runs(
        tuple(dev_runs), counts, out_rows
    )
    packed = np.asarray(packed)[:n]
    run_ids = packed >> np.uint32(p.bit_length() - 1)
    pos = packed & np.uint32(p - 1)
    perm = bases[run_ids.astype(np.int64)] + pos.astype(np.int64)
    return perm, pieces


def device_merge_sorted_runs(
    cols: columnar.MergeColumns, run_counts: List[int]
) -> Tuple[np.ndarray, np.ndarray]:
    """Host wrapper: returns (perm, same) over ``cols`` like
    ops.merge.device_sort_dedup, via the bitonic merge network."""
    n = len(cols)
    if n == 0:
        return np.zeros(0, np.int64), np.zeros(0, bool)
    stacks = build_run_stacks(cols, run_counts)
    idx, same = merge_runs_perm_kernel(stacks)
    # Slice on the host: a device-side [:n] compiles a dynamic_slice
    # for every distinct row count.
    perm = np.asarray(idx)[:n].astype(np.int64)
    same_np = np.asarray(same)[:n]
    return perm, same_np
