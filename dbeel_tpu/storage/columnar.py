"""Columnar staging for batched (vectorized / device) compaction.

This is the host side of the north-star design (BASELINE.md): the
reference's per-entry k-way heap merge (/root/reference/src/storage_engine/
lsm_tree.rs:1038-1066) is re-expressed as bulk array ops —

  1. *columnarize*: one bulk read per SSTable; index files parse straight
     into (offset, key_size, full_size) columns, keys load into a fixed
     16-byte big-endian prefix matrix viewed as 4 uint32 words (numeric
     compare == lexicographic compare);
  2. *sort + dedup kernel*: an ascending lexicographic sort over
     (key words, key_len, ~timestamp, ~source) — so within one key the
     newest timestamp (tie: newest input) comes first — then a
     keep-first-per-key mask.  Runs on numpy (host) or jax (TPU device);
  3. *fixup*: keys longer than the 16-byte prefix can tie; every tied
     prefix block is re-sorted on the host with full-key compares (rare);
  4. *gather*: surviving records are copied out of the source data files
     by vectorized range-gather and streamed to the output SSTable.

Dedup semantics match the reference exactly: keep the newest timestamp
per key, ties broken toward the newer input sstable; tombstones dropped
only when compacting the bottom level (compaction.rs:90-92).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np

from .entry import ENTRY_HEADER_SIZE

KEY_PREFIX_BYTES = 16
KEY_PREFIX_WORDS = KEY_PREFIX_BYTES // 4


@dataclass
class MergeColumns:
    """Concatenated columns over all input sstables, in input order
    (sources must be passed oldest→newest so larger src == newer)."""

    data: np.ndarray  # uint8, all data files concatenated
    start: np.ndarray  # u64, absolute record start in `data`
    key_size: np.ndarray  # u32
    full_size: np.ndarray  # u32
    timestamp: np.ndarray  # u64 bit-view of int64 nanos (always >= 0)
    src: np.ndarray  # u32, index into sources (position, not sstable id)
    key_words: np.ndarray  # (N, 4) u32 big-endian prefix words
    is_tombstone: np.ndarray  # bool

    def __len__(self) -> int:
        return int(self.start.size)


def read_source_pieces(sources: Sequence):
    """One bulk read per sstable → [(raw, offsets, key_sizes,
    full_sizes)] for assemble_columns."""
    return [
        (table.read_data_bytes(), *table.read_index_columns())
        for table in sources
    ]


def load_columns(sources: Sequence) -> MergeColumns:
    """sources: SSTable-likes exposing read_index_columns() and
    read_data_bytes()."""
    return assemble_columns(read_source_pieces(sources))


def assemble_columns(pieces) -> MergeColumns:
    """pieces: [(raw_bytes, offsets u64, key_sizes u32, full_sizes
    u32)] per source, oldest→newest."""
    datas: List[bytes] = []
    starts: List[np.ndarray] = []
    key_sizes: List[np.ndarray] = []
    full_sizes: List[np.ndarray] = []
    srcs: List[np.ndarray] = []
    base = 0
    for i, (raw, offs, ks, fs) in enumerate(pieces):
        datas.append(raw)
        starts.append(offs.astype(np.uint64) + np.uint64(base))
        key_sizes.append(ks)
        full_sizes.append(fs)
        srcs.append(np.full(offs.size, i, dtype=np.uint32))
        base += len(raw)
    data = np.frombuffer(b"".join(datas), dtype=np.uint8)
    start = np.concatenate(starts) if starts else np.zeros(0, np.uint64)
    key_size = (
        np.concatenate(key_sizes) if key_sizes else np.zeros(0, np.uint32)
    )
    full_size = (
        np.concatenate(full_sizes) if full_sizes else np.zeros(0, np.uint32)
    )
    src = np.concatenate(srcs) if srcs else np.zeros(0, np.uint32)
    n = start.size

    uniform = (
        n > 0
        and data.size == n * int(full_size[0])
        and (full_size == full_size[0]).all()
        and (key_size == key_size[0]).all()
        # Record i must actually live at row i (same guard as
        # gather_records) — duck-typed sources could order differently.
        and (
            start
            == np.arange(n, dtype=np.uint64) * np.uint64(full_size[0])
        ).all()
    )
    if uniform:
        # Fixed-size records: the whole data blob is an (N, record)
        # matrix — strided views replace fancy-indexed gathers.
        rec = int(full_size[0])
        ks = int(key_size[0])
        mat = data.reshape(n, rec)
        ts = mat[:, 8:16].reshape(-1).view("<u8").astype(np.uint64)
        kmat = np.zeros((n, KEY_PREFIX_BYTES), dtype=np.uint8)
        kmat[:, : min(ks, KEY_PREFIX_BYTES)] = mat[
            :, ENTRY_HEADER_SIZE : ENTRY_HEADER_SIZE
            + min(ks, KEY_PREFIX_BYTES)
        ]
        key_words = (
            np.ascontiguousarray(kmat)
            .view(np.dtype(">u4"))
            .astype(np.uint32)
            .reshape(n, KEY_PREFIX_WORDS)
        )
    else:
        # Timestamps live at record offset 8 (header: kl, vl, ts).
        ts = np.zeros(n, dtype=np.uint64)
        if n:
            ts_pos = (start + np.uint64(8))[:, None] + np.arange(
                8, dtype=np.uint64
            )
            ts_bytes = data[ts_pos.astype(np.int64)]
            ts = ts_bytes.astype(np.uint64) @ (
                np.uint64(1)
                << (np.arange(8, dtype=np.uint64) * np.uint64(8))
            )
        key_words = prefix_words(data, start, key_size)

    # value_len == 0 <=> tombstone (full == header + key).
    is_tomb = full_size == key_size + np.uint32(ENTRY_HEADER_SIZE)
    return MergeColumns(
        data=data,
        start=start,
        key_size=key_size,
        full_size=full_size,
        timestamp=ts,
        src=src,
        key_words=key_words,
        is_tombstone=is_tomb,
    )


def prefix_words(
    data: np.ndarray, start: np.ndarray, key_size: np.ndarray
) -> np.ndarray:
    """(N, 4) big-endian uint32 words of the zero-padded 16-byte key
    prefix."""
    n = start.size
    if n == 0:
        return np.zeros((0, KEY_PREFIX_WORDS), dtype=np.uint32)
    key_start = start + np.uint64(ENTRY_HEADER_SIZE)
    lanes = np.arange(KEY_PREFIX_BYTES, dtype=np.uint64)
    pos = key_start[:, None] + lanes
    valid = lanes < key_size.astype(np.uint64)[:, None]
    pos = np.minimum(pos, np.uint64(max(0, data.size - 1)))
    mat = np.where(valid, data[pos.astype(np.int64)], 0).astype(np.uint8)
    return (
        np.ascontiguousarray(mat)
        .view(np.dtype(">u4"))
        .astype(np.uint32)
        .reshape(n, KEY_PREFIX_WORDS)
    )


def sort_columns_numpy(cols: MergeColumns) -> np.ndarray:
    """Host (numpy) lexicographic sort: key asc, then newest ts first,
    then newest source first.  Returns the permutation."""
    inv_ts = ~cols.timestamp
    inv_src = ~cols.src
    return np.lexsort(
        (
            inv_src,
            inv_ts,
            cols.key_size,
            cols.key_words[:, 3],
            cols.key_words[:, 2],
            cols.key_words[:, 1],
            cols.key_words[:, 0],
        )
    )


def full_key(cols: MergeColumns, i: int) -> bytes:
    s = int(cols.start[i]) + ENTRY_HEADER_SIZE
    return cols.data[s : s + int(cols.key_size[i])].tobytes()


def _flags_to_runs(flags: np.ndarray) -> List[Tuple[int, int]]:
    """Adjacent-pair flags → [lo, hi) index runs covering flagged pairs."""
    runs: List[Tuple[int, int]] = []
    run_start = None
    run_end = 0
    for b in np.flatnonzero(flags):
        if run_start is None:
            run_start, run_end = b, b + 1
        elif b == run_end:
            run_end = b + 1
        else:
            runs.append((run_start, run_end + 1))
            run_start, run_end = b, b + 1
    if run_start is not None:
        runs.append((run_start, run_end + 1))
    return runs


def tie_positions_and_blocks(flags: np.ndarray, scratch=None):
    """Adjacent-pair tie flags (n-1,) → (positions, block_id): the
    sorted positions participating in any tie block, and a 0-based
    block index per position.  Blocks are maximal chains of flagged
    pairs; a False flag between two flagged pairs separates blocks even
    when the positions are contiguous.  ``scratch``: a bool buffer of at
    least n entries to mark the block members in, in place of a fresh
    one."""
    if flags.size == 0 or not flags.any():
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    if scratch is None:
        in_block = np.zeros(flags.size + 1, dtype=bool)
    else:
        in_block = scratch[: flags.size + 1]
        in_block.fill(False)
    in_block[:-1] |= flags
    in_block[1:] |= flags
    positions = np.flatnonzero(in_block)
    starts = np.ones(positions.size, dtype=bool)
    starts[1:] = ~flags[positions[:-1]]
    block_id = np.cumsum(starts) - 1
    return positions, block_id


def tie_block_sort(
    block_id: np.ndarray,  # (m,) int64, ascending
    key_words: np.ndarray,  # (m, W) native u64 of BE-padded key bytes
    key_len: np.ndarray,  # (m,)
    inv_ts: np.ndarray,  # (m,) u64, ~timestamp
    inv_src: np.ndarray,  # (m,)  ~source (newest-first tiebreak)
):
    """One vectorized lexsort ordering every tie block by the exact
    merge order (full key asc, newest ts, newest src), blocks kept in
    place via the primary block_id key.  Returns (order, dup): the
    permutation over the m tie entries and per-sorted-entry duplicate
    flags (equal full key as predecessor within the same block; the
    first = newest survives)."""
    cols = (
        (inv_src, inv_ts, key_len)
        + tuple(
            key_words[:, w]
            for w in range(key_words.shape[1] - 1, -1, -1)
        )
        + (block_id,)
    )
    order = np.lexsort(cols)
    dup = np.zeros(order.size, dtype=bool)
    if order.size > 1:
        kb = key_words[order]
        dup[1:] = (
            (block_id[order][1:] == block_id[order][:-1])
            & (key_len[order][1:] == key_len[order][:-1])
            & np.all(kb[1:] == kb[:-1], axis=1)
        )
    return order, dup


def padded_key_words(
    data: np.ndarray,
    key_start: np.ndarray,
    key_len: np.ndarray,
    pad_to: int = 0,
) -> np.ndarray:
    """(m, W) native-u64 words of the zero-padded key bytes (big-endian
    within each word, so numeric order == lexicographic byte order;
    equal padded words + equal length <=> equal key).  ``pad_to``
    forces a common byte width across separate calls (multi-buffer
    callers gathering per source)."""
    m = key_start.size
    max_len = int(key_len.max()) if m else 0
    lpad = max(8, pad_to, ((max_len + 7) // 8) * 8)
    if m == 0:
        return np.zeros((0, lpad // 8), dtype=np.uint64)
    lanes = np.arange(lpad, dtype=np.uint64)
    pos = key_start.astype(np.uint64)[:, None] + lanes
    valid = lanes < key_len.astype(np.uint64)[:, None]
    pos = np.minimum(pos, np.uint64(max(0, data.size - 1)))
    mat = np.where(valid, data[pos.astype(np.int64)], 0).astype(
        np.uint8
    )
    return (
        np.ascontiguousarray(mat)
        .view(np.dtype(">u8"))
        .astype(np.uint64)
        .reshape(m, lpad // 8)
    )


def tie_block_widths(
    block_id: np.ndarray, key_len: np.ndarray
) -> np.ndarray:
    """Per-entry padded-key byte width, bounded by the entry's BLOCK
    max key length (pow2-multiples-of-8 buckets): one long-key outlier
    widens only its own bucket's key matrix, not every tie entry's."""
    if block_id.size == 0:
        return np.zeros(0, np.int64)
    nblocks = int(block_id[-1]) + 1
    blk_max = np.zeros(nblocks, dtype=np.int64)
    np.maximum.at(blk_max, block_id, key_len.astype(np.int64))
    widths = np.empty(nblocks, np.int64)
    for b in np.unique(blk_max):
        c = (int(b) + 7) // 8
        p = 1
        while p < max(1, c):
            p <<= 1
        widths[blk_max == b] = 8 * p
    return widths[block_id]


def fixup_and_dedup_prefix(
    cols: MergeColumns, perm: np.ndarray, words: int = KEY_PREFIX_WORDS
):
    """Vectorized tie fixup + dedup: one lexsort per key-width bucket
    over the tie-block entries (full padded key, ~ts, ~src) instead of
    per-entry Python compares.  Returns (perm, keep)."""
    n = perm.size
    keep = np.ones(n, dtype=bool)
    if n <= 1:
        return perm, keep
    kw = cols.key_words[perm]
    flags = np.all(kw[1:, :words] == kw[:-1, :words], axis=1)
    positions, block_id = tie_positions_and_blocks(flags)
    if positions.size == 0:
        return perm, keep
    sel = perm[positions]
    ks = cols.key_size[sel]
    inv_ts = ~cols.timestamp[sel]
    inv_src = ~cols.src[sel]
    ent_w = tie_block_widths(block_id, ks)
    perm = perm.copy()
    for w in np.unique(ent_w):
        bm = ent_w == w
        kwords = padded_key_words(
            cols.data,
            cols.start[sel[bm]] + np.uint64(ENTRY_HEADER_SIZE),
            ks[bm],
            pad_to=int(w),
        )
        order, dup = tie_block_sort(
            block_id[bm], kwords, ks[bm], inv_ts[bm], inv_src[bm]
        )
        sub_pos = positions[bm]
        perm[sub_pos] = sel[bm][order]
        keep[sub_pos] = ~dup
    return perm, keep


def fixup_long_key_ties(cols: MergeColumns, perm: np.ndarray) -> np.ndarray:
    """Re-sort prefix-tie blocks containing keys longer than the prefix.

    After the columnar sort, all entries sharing an exact 16-byte prefix
    are contiguous.  If any of them extends past the prefix, (prefix,
    key_len) no longer determines lexicographic order, so the block is
    re-sorted on the host with full-key compares.  Never triggers when
    keys fit the prefix (e.g. the 16-byte-key benchmark)."""
    if perm.size <= 1:
        return perm
    kw = cols.key_words[perm]
    ks = cols.key_size[perm]
    same_prefix = np.all(kw[1:] == kw[:-1], axis=1)
    long = ks > KEY_PREFIX_BYTES
    tie = same_prefix & (long[1:] | long[:-1])
    if not tie.any():
        return perm
    perm = perm.copy()
    for lo, hi in _flags_to_runs(tie):
        block = perm[lo:hi]
        order = sorted(
            range(block.size),
            key=lambda j: (
                full_key(cols, int(block[j])),
                ~cols.timestamp[block[j]],
                ~cols.src[block[j]],
            ),
        )
        perm[lo:hi] = block[np.array(order)]
    return perm


def dedup_mask(cols: MergeColumns, perm: np.ndarray) -> np.ndarray:
    """keep-first-per-key over the sorted permutation (newest wins)."""
    n = perm.size
    keep = np.ones(n, dtype=bool)
    if n <= 1:
        return keep
    kw = cols.key_words[perm]
    ks = cols.key_size[perm]
    same = np.all(kw[1:] == kw[:-1], axis=1) & (ks[1:] == ks[:-1])
    # Prefix+len equality is only provisional for long keys: confirm with
    # full compares there (runs are already correctly ordered by fixup).
    long = ks > KEY_PREFIX_BYTES
    suspect = np.flatnonzero(same & (long[1:] | long[:-1]))
    if suspect.size:
        for j in suspect:
            if full_key(cols, int(perm[j + 1])) != full_key(
                cols, int(perm[j])
            ):
                same[j] = False
    keep[1:] = ~same
    return keep


def ranges_to_positions(
    starts: np.ndarray, lengths: np.ndarray
) -> np.ndarray:
    """Expand (start, length) ranges into one flat index vector.

    Vectorized multi-range gather: out[k] indexes every byte of every
    range, in range order."""
    lengths = lengths.astype(np.int64)
    starts = starts.astype(np.int64)
    total = int(lengths.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    step = np.ones(total, dtype=np.int64)
    step[0] = starts[0]
    ends = np.cumsum(lengths)[:-1]
    if ends.size:
        step[ends] = starts[1:] - (starts[:-1] + lengths[:-1] - 1)
    return np.cumsum(step)


def gather_records_array(
    cols: MergeColumns, order: np.ndarray
) -> np.ndarray:
    """Raw records selected by ``order`` (post-dedup) as one uint8
    array (no extra bytes copy — write it in chunks)."""
    if order.size == 0:
        return np.zeros(0, dtype=np.uint8)
    fs = cols.full_size
    rec = int(fs[0])
    if cols.data.size == fs.size * rec and (fs == fs[0]).all():
        # Uniform records: row-gather of an (N, rec) view — orders of
        # magnitude faster than the per-byte position expansion.
        if (cols.start == np.arange(fs.size, dtype=np.uint64) * rec).all():
            return cols.data.reshape(-1, rec)[order].reshape(-1)
    pos = ranges_to_positions(
        cols.start[order], cols.full_size[order]
    )
    return cols.data[pos]


def gather_records(cols: MergeColumns, order: np.ndarray) -> bytes:
    return gather_records_array(cols, order).tobytes()
