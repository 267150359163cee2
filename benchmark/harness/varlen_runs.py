"""Sorted runs of variable-length records for the wide-merge cells, and the
plain model of what a correct merge of them writes.

The builder is ``bench.build_runs(..., variable_values=True)`` with its
per-record Python loop (minutes at 10M keys) replaced by one ``np.repeat``
and one scatter a run, and its structured-dtype argsort by the lexsort of
``sstable_runs``.  It draws from the seed what ``bench.py`` draws, in the
same order (a run's keys, then its value lengths), so it writes the same
bytes; a test holds it to that.  PERF.md lists the original.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.harness.sstable_runs import (
    _INDEX_DTYPE, _key_order, _key_words,
)


def build_runs(dir_path: str, total_keys: int, n_runs: int, seed: int,
               key_bytes: int, value_bytes_min: int, value_bytes_max: int):
    """Write ``n_runs`` sorted sstables of ``total_keys // n_runs``
    uniform random keys each, every run over the whole keyspace, a
    record's value length uniform over [min, max] and its bytes filler
    (a merge never parses them); run ``r``'s timestamps lie above run
    ``r - 1``'s.  Returns (the tables' indices, and per run: its keys as
    an (n, key_bytes) uint8 array, its timestamps, its records' full
    sizes), for the model."""
    from dbeel_tpu.storage.entry import (
        DATA_FILE_EXT, ENTRY_HEADER_SIZE, INDEX_FILE_EXT, file_name,
    )

    if key_bytes != 16:
        raise ValueError("the run builder sorts 16-byte keys as two words")
    rng = np.random.default_rng(seed)
    per_run = total_keys // n_runs
    # The header: key_size u32, value_size u32, timestamp i64.
    head_bytes = ENTRY_HEADER_SIZE + key_bytes
    lanes = np.arange(head_bytes, dtype=np.int64)
    columns = []
    for r in range(n_runs):
        keys = rng.integers(0, 256, size=(per_run, key_bytes), dtype=np.uint8)
        keys = keys[_key_order(keys)]
        ts = (np.int64(r) * total_keys + np.arange(per_run)).astype("<i8")
        vlens = rng.integers(
            value_bytes_min, value_bytes_max + 1, size=per_run
        ).astype(np.uint32)
        full = vlens + np.uint32(head_bytes)
        offsets = np.zeros(per_run, dtype=np.int64)
        np.cumsum(full[:-1], out=offsets[1:])
        # Every byte of a record is its value's filler, then the header
        # and the key are laid over the record's head.
        filler = ((np.arange(per_run) + r) % 251).astype(np.uint8)
        arr = np.repeat(filler, full.astype(np.int64))
        head = np.empty((per_run, head_bytes), dtype=np.uint8)
        sizes = head[:, :8].view("<u4")
        sizes[:, 0] = key_bytes
        sizes[:, 1] = vlens
        head[:, 8:ENTRY_HEADER_SIZE] = ts.view(np.uint8).reshape(per_run, 8)
        head[:, ENTRY_HEADER_SIZE:] = keys
        arr[offsets[:, None] + lanes] = head
        index = np.zeros(per_run, dtype=_INDEX_DTYPE)
        index["offset"] = offsets
        index["key_size"] = key_bytes
        index["full_size"] = full
        idx = r * 2  # even, as flushes number their tables
        with open(
            os.path.join(dir_path, file_name(idx, DATA_FILE_EXT)), "wb"
        ) as f:
            f.write(arr.data)
        with open(
            os.path.join(dir_path, file_name(idx, INDEX_FILE_EXT)), "wb"
        ) as f:
            f.write(index.tobytes())
        columns.append((keys, ts, full))
    return [r * 2 for r in range(n_runs)], columns


def model(keys: np.ndarray, ts: np.ndarray, full_size: np.ndarray,
          tombstone: np.ndarray | None = None):
    """The plain reference, independent of the program: sort every
    run's entries together by key, keep the newest entry of each key,
    drop it if it is a tombstone (a merge that does not keep them).
    Returns (entries a correct merge writes, the byte length of its
    data file: the survivors' full sizes summed).  ``keys``: (n, 16)
    uint8; the others (n,), ``tombstone`` boolean or None for none."""
    if not len(keys):
        return 0, 0
    a, b = _key_words(keys)
    # Last key first: by key, then newest timestamp first.
    order = np.lexsort((-np.asarray(ts, dtype=np.int64), b, a))
    a, b = a[order], b[order]
    newest = np.ones(len(order), dtype=bool)
    newest[1:] = (a[1:] != a[:-1]) | (b[1:] != b[:-1])
    if tombstone is not None:
        newest &= ~np.asarray(tombstone, dtype=bool)[order]
    sizes = np.asarray(full_size)[order][newest]
    return int(newest.sum()), int(sizes.astype(np.int64).sum())
