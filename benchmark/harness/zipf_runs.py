"""Sorted runs of an update-heavy tree: L0 tables whose keys repeat, as
YCSB core workload A's update stream leaves them.

One stream of writes, drawn from ``--seed``: each write's record by
YCSB's zipfian (rank ``i`` of ``recordcount`` with probability
proportional to ``1 / i^constant``; exact inverse-CDF draws, not YCSB's
closed-form approximation), its value length uniform, its timestamp its
place in the stream.  A memtable is bounded by its entry count and keeps
a key's newest write, so a table is the newest write of each of the
stream's next ``entries_per_run`` distinct keys; the write that follows
opens the next table.  A rank's key is a seeded hash of the rank, 16
uniform bytes, which is what YCSB's scrambling does: hot records lie
anywhere in the keyspace.  The records are ``varlen_runs``'s (the same
header, filler and files), and ``varlen_runs.model`` is the model.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.harness.sstable_runs import _INDEX_DTYPE, _key_order


def rank_cdf(recordcount: int, constant: float) -> np.ndarray:
    """P(rank <= i), i = 1..recordcount, of the zipfian."""
    cdf = np.cumsum(np.arange(1, recordcount + 1, dtype=np.float64) ** -constant)
    cdf /= cdf[-1]
    return cdf


def draw_ranks(rng, cdf: np.ndarray, n: int) -> np.ndarray:
    """``n`` ranks, 0-based (0 the hottest), by inverse CDF."""
    ranks = np.searchsorted(cdf, rng.random(n), side="right")
    return np.minimum(ranks, len(cdf) - 1)


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser: a bijection of the 64-bit words."""
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def rank_keys(ranks: np.ndarray, salts) -> np.ndarray:
    """The 16-byte key of each rank: two salted mixes of the rank, so a
    record has one key for the whole run of the benchmark and distinct
    ranks have distinct keys (each half is a bijection)."""
    r = np.asarray(ranks).astype(np.uint64)
    keys = np.empty((len(r), 2), dtype=">u8")
    for half, salt in enumerate(salts):
        keys[:, half] = _mix(r + np.uint64(salt))
    return keys.view(np.uint8).reshape(len(r), 16)


def next_table(rng, cdf: np.ndarray, entries: int, pending: np.ndarray):
    """The writes one memtable takes before it holds ``entries``
    distinct ranks: (ranks in write order, the writes drawn and not yet
    taken).  ``pending``: the stream already drawn."""
    ranks = pending
    while True:
        if len(ranks) >= entries:
            _uniq, first = np.unique(ranks, return_index=True)
            if len(first) >= entries:
                break
        # Usually enough at once (the cell's tables take ~2.45 writes
        # an entry), never too little for long.
        more = max(entries, 3 * entries - len(ranks))
        ranks = np.concatenate([ranks, draw_ranks(rng, cdf, more)])
    # The write that brings the table's last distinct key ends it.
    taken = int(np.partition(first, entries - 1)[entries - 1]) + 1
    return ranks[:taken], ranks[taken:]


def newest_writes(ranks: np.ndarray):
    """(the distinct ranks, ascending; where each one's last write
    stands in ``ranks``)."""
    uniq, from_end = np.unique(ranks[::-1], return_index=True)
    return uniq, len(ranks) - 1 - from_end


def build_runs(dir_path: str, recordcount: int, n_runs: int,
               entries_per_run: int, seed: int, key_bytes: int,
               value_bytes_min: int, value_bytes_max: int,
               zipfian_constant: float):
    """Write ``n_runs`` sorted sstables of ``entries_per_run`` distinct
    keys each.  Returns (the tables' indices; per run its keys as an
    (n, key_bytes) uint8 array, its timestamps and its records' full
    sizes, the columns ``varlen_runs.model`` takes; the writes drawn)."""
    if key_bytes != 16:
        raise ValueError("the run builder sorts 16-byte keys as two words")
    if entries_per_run > recordcount:
        raise ValueError("a table cannot hold more keys than there are records")
    rng = np.random.default_rng(seed)
    cdf = rank_cdf(recordcount, zipfian_constant)
    salts = rng.integers(0, 1 << 63, size=2, dtype=np.uint64)
    pending = np.zeros(0, dtype=np.int64)
    written = 0
    columns = []
    for r in range(n_runs):
        ranks, pending = next_table(rng, cdf, entries_per_run, pending)
        # A value's length is drawn at every write; the table holds the
        # newest write's.
        lengths = rng.integers(
            value_bytes_min, value_bytes_max + 1, size=len(ranks)
        )
        uniq, last = newest_writes(ranks)
        keys = rank_keys(uniq, salts)
        order = _key_order(keys)
        keys, last = keys[order], last[order]
        ts = (written + last).astype("<i8")
        written += len(ranks)
        full = write_run(
            dir_path, r, keys, ts, lengths[last].astype(np.uint32)
        )
        columns.append((keys, ts, full))
    return [r * 2 for r in range(n_runs)], columns, written


def write_run(dir_path: str, r: int, keys: np.ndarray, ts: np.ndarray,
              vlens: np.ndarray) -> np.ndarray:
    """Table ``r``'s data and index files, record for record as
    ``varlen_runs.build_runs`` writes its run ``r``.  Returns the
    records' full sizes."""
    from dbeel_tpu.storage.entry import (
        DATA_FILE_EXT, ENTRY_HEADER_SIZE, INDEX_FILE_EXT, file_name,
    )

    n, key_bytes = keys.shape
    # The header: key_size u32, value_size u32, timestamp i64.
    head_bytes = ENTRY_HEADER_SIZE + key_bytes
    full = vlens + np.uint32(head_bytes)
    offsets = np.zeros(n, dtype=np.int64)
    np.cumsum(full[:-1], out=offsets[1:])
    # Every byte of a record is its value's filler, then the header
    # and the key are laid over the record's head.
    filler = ((np.arange(n) + r) % 251).astype(np.uint8)
    arr = np.repeat(filler, full.astype(np.int64))
    head = np.empty((n, head_bytes), dtype=np.uint8)
    sizes = head[:, :8].view("<u4")
    sizes[:, 0] = key_bytes
    sizes[:, 1] = vlens
    head[:, 8:ENTRY_HEADER_SIZE] = ts.view(np.uint8).reshape(n, 8)
    head[:, ENTRY_HEADER_SIZE:] = keys
    arr[offsets[:, None] + np.arange(head_bytes, dtype=np.int64)] = head
    index = np.zeros(n, dtype=_INDEX_DTYPE)
    index["offset"] = offsets
    index["key_size"] = key_bytes
    index["full_size"] = full
    idx = r * 2  # even, as flushes number their tables
    with open(os.path.join(dir_path, file_name(idx, DATA_FILE_EXT)), "wb") as f:
        f.write(arr.data)
    with open(os.path.join(dir_path, file_name(idx, INDEX_FILE_EXT)), "wb") as f:
        f.write(index.tobytes())
    return full
