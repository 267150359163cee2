"""Sorted runs for the merge cells: a copy of ``bench.build_runs`` (its
uniform-value branch), the generator PR 22's smoke also used, with its
structured-dtype argsort (5.6 s a run) replaced by a lexsort of the two
key words (0.5 s a run; the same order, so the same bytes).  Every byte is
a function of the seed.  PERF.md lists the original for a later PR to
delete."""

from __future__ import annotations

import hashlib
import os

import numpy as np

_INDEX_DTYPE = np.dtype(
    [("offset", "<u8"), ("key_size", "<u4"), ("full_size", "<u4")]
)


def build_runs(dir_path: str, total_keys: int, n_runs: int, seed: int,
               key_bytes: int, value_bytes: int):
    """Write ``n_runs`` sorted sstables of ``total_keys // n_runs``
    uniform random keys each.  Returns (their indices, every run's keys
    as an (n, key_bytes) uint8 array, for the model)."""
    from dbeel_tpu.storage.entry import (
        DATA_FILE_EXT, INDEX_FILE_EXT, file_name,
    )

    if key_bytes != 16:
        raise ValueError("the run builder sorts 16-byte keys as two words")
    record = 16 + key_bytes + value_bytes
    rng = np.random.default_rng(seed)
    per_run = total_keys // n_runs
    all_keys = []
    for r in range(n_runs):
        keys = rng.integers(0, 256, size=(per_run, key_bytes), dtype=np.uint8)
        keys = keys[_key_order(keys)]
        ts = (np.int64(r) * total_keys + np.arange(per_run)).astype("<i8")
        arr = np.zeros((per_run, record), dtype=np.uint8)
        hdr = arr[:, :16].view("<u4")
        hdr[:, 0] = key_bytes
        hdr[:, 1] = value_bytes
        arr[:, 8:16] = ts.view(np.uint8).reshape(per_run, 8)
        arr[:, 16 : 16 + key_bytes] = keys
        val = (keys[:, :8].astype(np.uint16).sum(axis=1) % 251).astype(
            np.uint8
        )
        arr[:, 16 + key_bytes :] = val[:, None]
        index = np.zeros(per_run, dtype=_INDEX_DTYPE)
        index["offset"] = np.arange(per_run, dtype=np.uint64) * record
        index["key_size"] = key_bytes
        index["full_size"] = record
        idx = r * 2  # even, as flushes number their tables
        with open(
            os.path.join(dir_path, file_name(idx, DATA_FILE_EXT)), "wb"
        ) as f:
            f.write(arr.tobytes())
        with open(
            os.path.join(dir_path, file_name(idx, INDEX_FILE_EXT)), "wb"
        ) as f:
            f.write(index.tobytes())
        all_keys.append(keys)
    return [r * 2 for r in range(n_runs)], all_keys


def _key_words(keys: np.ndarray):
    """16-byte keys as two big-endian words, so that comparing (a, b)
    compares the keys' bytes."""
    words = np.ascontiguousarray(keys).view(">u8").reshape(len(keys), 2)
    return words[:, 0].astype(np.uint64), words[:, 1].astype(np.uint64)


def _key_order(keys: np.ndarray) -> np.ndarray:
    a, b = _key_words(keys)
    return np.lexsort((b, a))


def model_entry_count(all_keys) -> int:
    """The plain reference: sort every run's keys together and keep one
    entry per distinct key (newest wins) — how many entries a correct
    merge writes."""
    keys = np.concatenate(all_keys)
    a, b = _key_words(keys)
    order = np.lexsort((b, a))
    a, b = a[order], b[order]
    distinct = (a[1:] != a[:-1]) | (b[1:] != b[:-1])
    return int(distinct.sum()) + 1 if len(keys) else 0


OUTPUT_EXTS = ("compact_data", "compact_index", "compact_bloom")


def hash_and_remove_output(dir_path: str, out_index: int, want_hash: bool):
    """SHA-256 of one merge's output triplet (data, index, bloom), or
    None; the files are removed either way."""
    from dbeel_tpu.storage.entry import file_name

    digest = hashlib.sha256() if want_hash else None
    for ext in OUTPUT_EXTS:
        path = os.path.join(dir_path, file_name(out_index, ext))
        if digest is not None:
            with open(path, "rb") as f:
                while True:
                    block = f.read(1 << 24)
                    if not block:
                        break
                    digest.update(block)
        os.unlink(path)
    sums = os.path.join(dir_path, file_name(out_index, "compact_sums"))
    if os.path.exists(sums):
        os.unlink(sums)
    return digest.hexdigest() if digest is not None else None
