"""Sorted runs of a tree that deletes: TPC-C's NEW-ORDER table as a
document store's L0 tables hold it, and the plain model of what a
correct bottom merge of them writes.

One stream of writes, drawn from ``--seed``.  First the load (TPC-C rev
5.11, 4.3.3.1): for every warehouse and each of its 10 districts, 900
rows, orders 2,101-3,000.  Then the transaction mix as this table sees
it (5.2.3): each next transaction is a New-Order with probability 45/49
- one insert, warehouse and district uniform, the district's next order
number (2.4.2.2) - or a Delivery with probability 4/49 - warehouse
uniform, and for each of its 10 districts one delete of the lowest
undelivered order, skipped where the district has none (2.7.4.2).  A
write's timestamp is its place in the stream.  A row's key is the
msgpack array ``[NO_W_ID, NO_D_ID, NO_O_ID]`` (``93 ww dd cd hh ll``),
its value the msgpack map of the three columns (30 bytes): a 52-byte
record; a delete is the store's tombstone, the key under an empty
value: 22 bytes.  A table is one flushed memtable as ``zipf_runs``
defines it: the newest write of each of the stream's next
``entries_per_run`` distinct keys.

The stream is built whole, in numpy: the transactions drawn, expanded to
writes, each district's queue followed by a cumulative sum in the
district's own order of events (a delete that would find the queue empty
is the reflected walk's dropped step), the k-th delivered order of a
district being 2,101 + k.  A test replays it with a dict of Python
queues.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.harness.sstable_runs import _INDEX_DTYPE
from benchmark.harness.zipf_runs import newest_writes

DISTRICTS = 10  # a warehouse's (TPC-C 1.2.1)
LOADED = 900  # NEW-ORDER rows a district is loaded with (4.3.3.1) ...
FIRST_ORDER = 2101  # ... orders 2,101-3,000
NEW_ORDER, DELIVERY = 45, 4  # the mix's shares of the two (5.2.3)
KEY_BYTES = 6
ROW_BYTES = 52  # header 16, key 6, value 30
TOMBSTONE_BYTES = 22


def key_ids(w, d, o) -> np.ndarray:
    """One integer a key, ordered as the keys' bytes are: warehouse
    (1-based), district (1-based), order."""
    return (
        (np.asarray(w, np.int64) << 32)
        | (np.asarray(d, np.int64) << 16)
        | np.asarray(o, np.int64)
    )


def split_ids(ids: np.ndarray):
    return ids >> 32, (ids >> 16) & 0xFFFF, ids & 0xFFFF


def load(warehouses: int) -> np.ndarray:
    """The load's key ids in load order: warehouse by warehouse,
    district by district, orders ascending."""
    w = np.arange(1, warehouses + 1, dtype=np.int64)[:, None, None]
    d = np.arange(1, DISTRICTS + 1, dtype=np.int64)[None, :, None]
    o = np.arange(FIRST_ORDER, FIRST_ORDER + LOADED, dtype=np.int64)
    return key_ids(w, d, o[None, None, :]).ravel()


def transactions(rng, warehouses: int, n: int):
    """``n`` transactions of the mix after the load, as writes in
    stream order: (key ids, which are deletes)."""
    delivery = rng.random(n) < DELIVERY / (NEW_ORDER + DELIVERY)
    txn_w = rng.integers(1, warehouses + 1, size=n)
    txn_d = rng.integers(1, DISTRICTS + 1, size=n)
    # A New-Order is one write, a Delivery one a district.
    count = np.where(delivery, DISTRICTS, 1)
    first = np.cumsum(count) - count
    txn = np.repeat(np.arange(n), count)
    delete = delivery[txn]
    w = txn_w[txn]
    d = np.where(delete, np.arange(len(txn)) - first[txn] + 1, txn_d[txn])
    # Each district's events in its own order, side by side.
    district = (w - 1) * DISTRICTS + (d - 1)
    order = np.argsort(district, kind="stable")
    group = district[order]
    opens = np.flatnonzero(np.r_[True, group[1:] != group[:-1]])
    sizes = np.diff(np.r_[opens, len(group)])

    def in_group(flags):
        """Cumulative count of ``flags`` inside each district."""
        flags = flags.astype(np.int64)
        total = np.cumsum(flags)
        return total - np.repeat(total[opens] - flags[opens], sizes)

    deletes = in_group(delete[order])
    inserts = in_group(~delete[order])
    # The queue's length after each event had every delete found a
    # row; a delete that finds none is skipped, which is the walk
    # reflected at zero: the steps dropped so far are how far the
    # unreflected walk has been below zero.  (The offset a district
    # makes the running minimum start anew in each.)
    level = LOADED + inserts - deletes
    far = 2 * (LOADED + len(group)) + 1
    gid = np.repeat(np.arange(len(opens), dtype=np.int64), sizes)
    lowest = np.minimum.accumulate(level - gid * far) + gid * far
    dropped = np.maximum(0, -lowest)
    skipped = np.zeros(len(group), dtype=bool)
    skipped[1:] = dropped[1:] > dropped[:-1]
    skipped[opens] = dropped[opens] > 0
    # FIFO: a district's k-th delivered order is its k-th ever.
    o_sorted = np.where(
        delete[order],
        FIRST_ORDER + deletes - dropped - 1,
        FIRST_ORDER + LOADED + inserts - 1,
    )
    o = np.empty(len(txn), dtype=np.int64)
    o[order] = o_sorted
    written = np.ones(len(txn), dtype=bool)
    written[order] = ~skipped
    return key_ids(w, d, o)[written], delete[written]


def stream(seed: int, warehouses: int, writes: int):
    """The first ``writes`` or more writes of the stream: (key ids,
    which are deletes); a write's place is its timestamp."""
    loaded = load(warehouses)
    per_txn = (NEW_ORDER + DELIVERY * DISTRICTS) / (NEW_ORDER + DELIVERY)
    n = int(max(0, writes - len(loaded)) / per_txn * 1.05) + 2_000
    while True:
        ids, delete = transactions(np.random.default_rng(seed), warehouses, n)
        if len(loaded) + len(ids) >= writes:
            return (
                np.concatenate([loaded, ids]),
                np.concatenate([np.zeros(len(loaded), dtype=bool), delete]),
            )
        n *= 2


def cut_tables(ids: np.ndarray, n_runs: int, entries: int):
    """Where each memtable's writes start and end in the stream: it is
    flushed by the write that brings its ``entries``-th distinct key.
    ``n_runs + 1`` places, or None if the stream ends first."""
    places = [0]
    for _ in range(n_runs):
        lo = places[-1]
        hi = lo + entries
        while True:
            if hi > len(ids):
                return None
            distinct = len(np.unique(ids[lo:hi]))
            if distinct == entries:
                break
            hi += entries - distinct
        places.append(hi)
    return places


def encode_keys(ids: np.ndarray) -> np.ndarray:
    """msgpack ``[w, d, o]``: fixarray 3, two positive fixints, one
    uint 16.  (n, 6) uint8."""
    w, d, o = split_ids(ids)
    if len(ids) and (w.max() > 127 or d.max() > 127 or o.max() > 0xFFFF):
        raise ValueError("a key's column outgrew its msgpack encoding")
    keys = np.empty((len(ids), KEY_BYTES), dtype=np.uint8)
    keys[:, 0] = 0x93
    keys[:, 1] = w
    keys[:, 2] = d
    keys[:, 3] = 0xCD
    keys[:, 4] = o >> 8
    keys[:, 5] = o & 0xFF
    return keys


def encode_values(ids: np.ndarray) -> np.ndarray:
    """msgpack ``{"no_o_id": o, "no_d_id": d, "no_w_id": w}``: fixmap
    3, fixstr 7 names.  (n, 30) uint8."""
    w, d, o = split_ids(ids)
    values = np.empty((len(ids), ROW_BYTES - TOMBSTONE_BYTES), dtype=np.uint8)
    values[:, 0] = 0x83
    for at, name in ((1, b"no_o_id"), (12, b"no_d_id"), (21, b"no_w_id")):
        values[:, at] = 0xA7
        values[:, at + 1:at + 8] = np.frombuffer(name, dtype=np.uint8)
    values[:, 9] = 0xCD
    values[:, 10] = o >> 8
    values[:, 11] = o & 0xFF
    values[:, 20] = d
    values[:, 29] = w
    return values


def write_run(dir_path: str, r: int, ids: np.ndarray, ts: np.ndarray,
              tombstone: np.ndarray) -> np.ndarray:
    """Table ``r``'s data and index files: a row 52 bytes, a tombstone
    22, keys ascending.  Returns the records' full sizes."""
    from dbeel_tpu.storage.entry import (
        DATA_FILE_EXT, ENTRY_HEADER_SIZE, INDEX_FILE_EXT, file_name,
    )

    n = len(ids)
    full = np.where(tombstone, TOMBSTONE_BYTES, ROW_BYTES).astype(np.uint32)
    offsets = np.zeros(n, dtype=np.int64)
    np.cumsum(full[:-1], out=offsets[1:])
    arr = np.empty(int(full.sum(dtype=np.int64)), dtype=np.uint8)
    # The header (key_size u32, value_size u32, timestamp i64), the key.
    head = np.empty((n, TOMBSTONE_BYTES), dtype=np.uint8)
    sizes = head[:, :8].view("<u4")
    sizes[:, 0] = KEY_BYTES
    sizes[:, 1] = full - TOMBSTONE_BYTES
    head[:, 8:ENTRY_HEADER_SIZE] = (
        ts.astype("<i8").view(np.uint8).reshape(n, 8)
    )
    head[:, ENTRY_HEADER_SIZE:] = encode_keys(ids)
    arr[offsets[:, None] + np.arange(TOMBSTONE_BYTES)] = head
    rows = ~tombstone
    arr[
        offsets[rows][:, None]
        + np.arange(TOMBSTONE_BYTES, ROW_BYTES)
    ] = encode_values(ids[rows])
    index = np.zeros(n, dtype=_INDEX_DTYPE)
    index["offset"] = offsets
    index["key_size"] = KEY_BYTES
    index["full_size"] = full
    idx = r * 2  # even, as flushes number their tables
    with open(os.path.join(dir_path, file_name(idx, DATA_FILE_EXT)), "wb") as f:
        f.write(arr.data)
    with open(os.path.join(dir_path, file_name(idx, INDEX_FILE_EXT)), "wb") as f:
        f.write(index.tobytes())
    return full


def build_runs(dir_path: str, warehouses: int, n_runs: int,
               entries_per_run: int, seed: int, grace_runs: int):
    """Write ``n_runs`` sorted sstables of ``entries_per_run`` distinct
    keys each.  Returns (the tables' indices; per run its key ids, its
    timestamps and which entries are tombstones, the columns ``model``
    takes; the writes the tables took; the gc-grace cutoff: the
    timestamp of the first write of the oldest of the newest
    ``grace_runs`` tables)."""
    need = n_runs * entries_per_run
    while True:
        ids, delete = stream(seed, warehouses, need)
        places = cut_tables(ids, n_runs, entries_per_run)
        if places is not None:
            break
        need += entries_per_run
    columns = []
    for r, (lo, hi) in enumerate(zip(places, places[1:])):
        uniq, last = newest_writes(ids[lo:hi])
        ts = lo + last
        tombstone = delete[ts]
        write_run(dir_path, r, uniq, ts, tombstone)
        columns.append((uniq, ts, tombstone))
    cutoff = places[n_runs - grace_runs]
    return [r * 2 for r in range(n_runs)], columns, places[-1], cutoff


def model(ids: np.ndarray, ts: np.ndarray, tombstone: np.ndarray,
          cutoff: int) -> dict:
    """The plain reference, from the stream's columns and no merge's
    output: of every key its newest entry, dropped if that is a
    tombstone whose timestamp lies below ``cutoff`` (one at or above it
    is written).  What a correct merge reads, writes and keeps."""
    order = np.lexsort((-np.asarray(ts, dtype=np.int64), ids))
    sorted_ids = ids[order]
    newest = np.ones(len(order), dtype=bool)
    newest[1:] = sorted_ids[1:] != sorted_ids[:-1]
    tomb = np.asarray(tombstone, dtype=bool)[order]
    held = newest & tomb & (np.asarray(ts)[order] >= cutoff)
    rows = newest & ~tomb
    kept, live = int(held.sum()), int(rows.sum())
    return {
        "entries_in": len(order),
        "tombstones_in": int(tomb.sum()),
        "entries_out": live + kept,
        "rows_out": live,
        "tombstones_kept": kept,
        "bytes_out": live * ROW_BYTES + kept * TOMBSTONE_BYTES,
    }
