"""Bitonic merge network — the TPU-native compaction merge kernel.

Why not ``lax.sort``: XLA's TPU sort with a multi-operand comparator is
pathological for this workload (measured on TPU v5e: 8-key sort of 2^18
rows = 202 s compile + 41 ms/run, vs 0.2 ms for 1 key).  Compaction
doesn't need a full sort anyway — its inputs are K *already-sorted* runs
(SSTables are sorted by construction).  A bitonic merge network does the
k-way merge in ``log2(K)`` batched pairwise rounds of ``log2(L)``
elementwise compare-exchange stages: only static reshapes, compares and
selects.  This is the "batched bitonic merge
expressed in jax.jit" the north star names (BASELINE.json), replacing the
reference's per-entry heap loop
(/root/reference/src/storage_engine/lsm_tree.rs:1038-1066).

The layout rule.  The network does no arithmetic to speak of; a launch
costs the bytes its temporaries move, and the chip pads every
temporary's two minor dimensions to (8, 128) tiles.  So: a row's
columns travel column axis first, row axis minor (a trailing column
axis of 2, 3 or 9 was padded to 128), and a stage splits only major
axes.  A level of 2^14 rows or more (``_VIEW_ROWS``) takes its columns
apart, one array each, and runs its large strides in a view that keeps
the low index bits (one tile's worth, ``_TILE_BITS``) and the batch in
the two minor dimensions, then its small strides in the transposed view
that keeps the high bits there: one transpose in, one between, one out,
and no stage leaves a minor dimension under 128 elements or a
second-minor under 8.  Shorter levels split the row axis as it lies,
all columns in one array (one select a comparator whatever the width:
the sort kernel and small merges compile as fast as they did; their
strides under 128 are padded).  Two stages share one split and one
stack (``_STAGES_PER_PASS``).  The rule reads static shapes only, and
the sequence of compare-exchanges — same pairs, same direction, swap
iff lo > hi — is the textbook one whatever view a stage runs in, so
the output is too, ties among equal rows included
(tests/test_bitonic_network.py).  The compiler's own count for one
launch of the one-word kernel on a v5e (tests/test_tpu_compile.py holds
it): 19.1 GB accessed and 0.54 GB of temporaries at (4, 64, 2^14),
10.1 GB and 0.27 GB at (4, 8, 2^17); the row-major network this
replaced counted 166.7 and 106.0 GB, 3.2 GB of temporaries, and ran at
those bytes over 819 GB/s (PERF.md §3, §6).  The ideal is 105 stages x
64 MB = 6.7 GB: a kernel that keeps a block's stages in VMEM is the
next step.  Write a stage so that XLA's CPU backend materialises it
(split, select, stack): ``where(swap, flip(x), x)`` is one pass on the
chip and exponential recomputation on the CPU the tests run on.

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


# The chip lays an array's two minor dimensions out in (8, 128) tiles.
_LANES = 128
_LANE_BITS = _LANES.bit_length() - 1
# A level this long fills 128 lanes in both tiled views below.
_VIEW_ROWS = _LANES * _LANES
# log2 of one tile's elements: the index bits a tiled view keeps minor.
_TILE_BITS = 10
# Compare-exchange stages between two materialisations of the columns.
_STAGES_PER_PASS = 2


def _lex_gt(a, b, ncmp: int):
    """a > b lexicographically over the first ``ncmp`` columns.
    a, b: sequences of columns of one shape."""
    gt = a[ncmp - 1] > b[ncmp - 1]
    for c in range(ncmp - 2, -1, -1):
        gt = (a[c] > b[c]) | ((a[c] == b[c]) & gt)
    return gt


def _columns(groups):
    return [g[c] for g in groups for c in range(g.shape[0])]


def _compare_exchange(lo, hi, ncmp: int):
    """One comparator over rows held as groups of columns (each group
    one array, column axis first): swap iff lo > hi."""
    swap = _lex_gt(_columns(lo), _columns(hi), ncmp)
    return (
        tuple(jnp.where(swap, h, l) for l, h in zip(lo, hi)),
        tuple(jnp.where(swap, l, h) for l, h in zip(lo, hi)),
    )


def _stages(groups, count: int, ncmp: int):
    """Groups shaped (columns, batch, rows, *tile): the ``count``
    bitonic-merge stages of strides rows/2, rows/4, … along the row
    axis, in that order.  Only the row axis is ever split, so whatever
    ``tile`` is stays the minor dimensions of every temporary.
    ``_STAGES_PER_PASS`` stages share one split and one stack: the same
    comparators in the same order, fewer passes over the columns."""
    batch, rows = groups[0].shape[1:3]
    tile = groups[0].shape[3:]
    done = 0
    while done < count:
        r = min(_STAGES_PER_PASS, count - done)
        fan = 1 << r
        split = (batch << done, fan, (rows >> done) // fan) + tile
        parts = [
            tuple(g.reshape(g.shape[:1] + split)[:, :, i] for g in groups)
            for i in range(fan)
        ]
        d = fan // 2
        while d:
            for i in range(fan):
                if not i & d:
                    parts[i], parts[i + d] = _compare_exchange(
                        parts[i], parts[i + d], ncmp
                    )
            d //= 2
        groups = tuple(
            jnp.stack(part, axis=2).reshape(g.shape)
            for g, part in zip(groups, zip(*parts))
        )
        done += r
    return groups


def _bitonic_to_sorted(groups, ncmp: int):
    """Groups (columns, B, L), each row bitonic → ascending rows.
    Classic bitonic merge: compare-exchange stages of strides L/2, L/4,
    …, 1.

    The layout rule (module docstring) reads only static shapes: a
    level shorter than ``_VIEW_ROWS`` splits the row axis as it lies,
    its columns in whatever groups they came in; a longer one takes
    its columns apart (they stay apart) and runs its large strides in
    a view whose tiles hold the low index bits, and its small strides
    in the transposed view whose tiles hold the high ones — one
    transpose in, one between, one out."""
    _, b, l = groups[0].shape
    n = l.bit_length() - 1
    if l < _VIEW_ROWS:
        return _stages(groups, n, ncmp)
    m = min(_TILE_BITS, n - _LANE_BITS)
    low, high = 1 << m, l >> m
    # Index bits >= m major, (batch x the rest of a tile, 128) minor.
    cols = tuple(
        c.reshape(b, high, low // _LANES, _LANES)
        .transpose(1, 0, 2, 3)
        .reshape(1, 1, high, b * low // _LANES, _LANES)
        for c in _columns(groups)
    )
    cols = _stages(cols, n - m, ncmp)
    # Index bits < m major, (batch x high / 128, 128) minor.
    cols = tuple(
        c.reshape(high, b, low)
        .transpose(2, 1, 0)
        .reshape(1, 1, low, b * high // _LANES, _LANES)
        for c in cols
    )
    cols = _stages(cols, m, ncmp)
    return tuple(
        c.reshape(low, b, high).transpose(1, 2, 0).reshape(1, b, l)
        for c in cols
    )


def _merge_level(groups, ncmp: int):
    """Groups (columns, K, P) of sorted runs → (columns, K/2, 2P):
    concat each even run with its odd neighbour reversed
    (ascending+descending = bitonic), then merge — all K/2 pairs in one
    batched op."""
    return _bitonic_to_sorted(
        tuple(
            jnp.concatenate(
                [g[:, 0::2], jnp.flip(g[:, 1::2], axis=2)], axis=2
            )
            for g in groups
        ),
        ncmp,
    )


def _merge_runs(cols, ncmp: int):
    """Columns (K, P) of sorted runs → columns (K*P,), sorted."""
    groups = (jnp.stack(cols),)
    while groups[0].shape[1] > 1:
        groups = _merge_level(groups, ncmp)
    return tuple(c[0] for c in _columns(groups))


def _merged_with_same(stacks: jnp.ndarray):
    out = _merge_runs(
        tuple(stacks[:, :, c] for c in range(NUM_COLS)), NUM_KEY_COLS
    )
    eq = out[4][1:] != SENTINEL
    for c in range(NUM_EQ_COLS):
        eq = eq & (out[c][1:] == out[c][:-1])
    same = jnp.concatenate([jnp.zeros((1,), bool), eq])
    return out, same


@jax.jit
def merge_runs_kernel(
    stacks: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(K, P, NUM_COLS) sorted (sentinel-padded) runs, K and P powers of
    two → (K*P, NUM_COLS) globally sorted stack + same-key flags."""
    out, same = _merged_with_same(stacks)
    return jnp.stack(out, axis=1), same


@jax.jit
def merge_runs_perm_kernel(
    stacks: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Like merge_runs_kernel but returns only (sorted entry indices,
    same flags) — a ~9x smaller device→host transfer."""
    out, same = _merged_with_same(stacks)
    return out[8], same


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


def _merged_order(keys, counts: jnp.ndarray):
    """Key columns (K, P) of sorted runs, ``counts`` valid rows a run →
    (K*P,) packed positions (run * P + row) in merged order; rows past a
    run's count carry SENTINEL and sort last."""
    k, p = keys[0].shape
    row = jnp.arange(p, dtype=jnp.uint32)[None, :]
    iota = jnp.arange(k, dtype=jnp.uint32)[:, None] * jnp.uint32(p) + row
    idx = jnp.where(row < counts[:, None], iota, SENTINEL)
    return _merge_runs((*keys, idx), len(keys) + 1)[-1]


def _prefix_merge_body(
    prefixes: jnp.ndarray, counts: jnp.ndarray, out_rows: int
):
    order = _merged_order((prefixes[:, :, 0], prefixes[:, :, 1]), counts)
    return order[:out_rows]


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
    p = vals.shape[1]
    order = _merged_order((vals,), counts)
    return _pack_rids(order, p.bit_length() - 1, pack_bits)


def _prefix64_packed_body(
    prefixes: jnp.ndarray, counts: jnp.ndarray, pack_bits: int
):
    p = prefixes.shape[1]
    order = _merged_order((prefixes[:, :, 0], prefixes[:, :, 1]), counts)
    return _pack_rids(order, p.bit_length() - 1, pack_bits)


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
