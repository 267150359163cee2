"""The pipeline's tombstone branch (ISSUE 37): tables that hold deletes,
as a collection used as a queue leaves them, merged by ``pipeline_merge``
and by the heap oracle under the same gc-grace cutoff, byte for byte, and
against a dict that replays the rule: of every key its newest version,
dropped if that is a tombstone whose timestamp lies below the cutoff (no
cutoff: every tombstone).  The decode's C pass
(``dbeel_pipe_drop_tombstones``) alone is held to
``compaction.drop_tombstones_mask`` on random columns.
"""

import ctypes
import random
import struct

import numpy as np
import pytest

from dbeel_tpu.ops import pipeline as pipeline_mod
from dbeel_tpu.ops.device_compaction import DeviceMergeStrategy
from dbeel_tpu.storage import native
from dbeel_tpu.storage.compaction import (
    PIPELINE_SHAPE, compaction_stats, drop_tombstones_mask,
)
from dbeel_tpu.storage.native import native_available

from test_pipeline_dedup import _vs_heap, _write_tables

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native library unavailable"
)


@pytest.fixture(autouse=True)
def _through_the_pipeline(monkeypatch):
    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)


def _queue_tables(seed, nruns, per_run, share):
    """A stream of writes cut into ``nruns`` memtables of ``per_run``
    distinct keys: a write is a delete with probability ``share`` — of
    the oldest row still live where there is one (a queue), else of a
    key nobody wrote — and a new row otherwise.  Timestamps are places
    in the stream, from 1.  key -> (value, ts) a table."""
    rng = random.Random(seed)
    live, tables, place = [], [], 0
    for _ in range(nruns):
        table = {}
        while len(table) < per_run:
            place += 1
            if rng.random() < share:
                key = live.pop(0) if live else rng.randbytes(12)
                table[key] = (b"", place)
            else:
                key = rng.randbytes(12)
                live.append(key)
                table[key] = (b"row-%d" % place, place)
        tables.append(table)
    return tables, place


def _expected(tables, keep_tomb, cutoff):
    """What a correct merge writes, by a dict."""
    newest = {}
    for table in tables:
        for key, (value, ts) in table.items():
            if key not in newest or ts > newest[key][1]:
                newest[key] = (value, ts)
    out = []
    for key in sorted(newest):
        value, ts = newest[key]
        if value == b"" and not keep_tomb and (not cutoff or ts < cutoff):
            continue
        out.append((key, value, ts))
    return out


def _cutoff(name, tables, writes):
    stamps = sorted(
        ts for table in tables for value, ts in table.values() if not value
    )
    return {
        "none": None,
        "zero": 0,
        "below-all": 1,
        "above-all": writes + 1,
        "middle": writes // 2,
        # Equal to a tombstone's timestamp: that tombstone is kept.
        "a-tombstones-own": stamps[len(stamps) // 2] if stamps else writes // 3,
    }[name]


@pytest.mark.parametrize(
    "cutoff_name",
    ["none", "zero", "below-all", "above-all", "middle", "a-tombstones-own"],
)
@pytest.mark.parametrize("share", [0.0, 0.45, 1.0],
                         ids=["no-deletes", "queue", "all-deletes"])
@pytest.mark.parametrize("nruns,per_run", [(8, 300), (64, 40)],
                         ids=["8-tables", "64-tables"])
def test_tombstones_and_the_cutoff_against_the_heap_merge(
    tmp_dir, nruns, per_run, share, cutoff_name
):
    tables, writes = _queue_tables(3701 + nruns, nruns, per_run, share)
    cutoff = _cutoff(cutoff_name, tables, writes)
    before = compaction_stats.stats()["shape"]
    records, decoded = _vs_heap(
        tmp_dir, _write_tables(tmp_dir, tables), False, cutoff
    )
    after = compaction_stats.stats()["shape"]
    want = _expected(tables, False, cutoff)
    assert records == want
    tombstones = sum(
        not value for table in tables for value, _ts in table.values()
    )
    kept = sum(not value for _k, value, _ts in want)
    assert after["tombstones_in"] - before["tombstones_in"] == tombstones
    assert after["tombstones_kept"] - before["tombstones_kept"] == kept
    if cutoff_name in ("none", "zero", "above-all"):
        assert kept == 0
    if cutoff_name == "below-all" and share:
        assert kept > 0
    if cutoff_name == "a-tombstones-own" and share:
        assert any(ts == cutoff and not v for _k, v, ts in want)


K = b"order-00042!"


@pytest.mark.parametrize(
    "tables,cutoff,want",
    [
        # The tombstone older than the row it ties with: the row wins,
        # whatever the cutoff.
        ([{K: (b"row", 20)}, {K: (b"", 10)}], None, [(K, b"row", 20)]),
        ([{K: (b"", 10)}, {K: (b"row", 20)}], 5, [(K, b"row", 20)]),
        # Newer: the tombstone wins, then the cutoff decides it.
        ([{K: (b"row", 10)}, {K: (b"", 20)}], None, []),
        ([{K: (b"row", 10)}, {K: (b"", 20)}], 21, []),
        ([{K: (b"row", 10)}, {K: (b"", 20)}], 20, [(K, b"", 20)]),
        ([{K: (b"", 20)}, {K: (b"row", 10)}], 15, [(K, b"", 20)]),
        # Of two tombstones the newer is the one the cutoff is asked of.
        ([{K: (b"", 10)}, {K: (b"", 30)}], 20, [(K, b"", 30)]),
        ([{K: (b"", 30)}, {K: (b"", 10)}], 31, []),
        # A tombstone without a partner.
        ([{K: (b"", 20)}, {b"other-key": (b"v", 1)}], None,
         [(b"other-key", b"v", 1)]),
        ([{K: (b"", 20)}, {b"other-key": (b"v", 1)}], 20,
         [(K, b"", 20), (b"other-key", b"v", 1)]),
        # A negative cutoff holds every tombstone, as the mask's does.
        ([{K: (b"row", 10)}, {K: (b"", 20)}], -5, [(K, b"", 20)]),
    ],
    ids=["row-newer", "row-newer-in-the-later-table", "tombstone-newer",
         "tombstone-newer-below-the-cutoff", "tombstone-at-the-cutoff",
         "tombstone-above-the-cutoff", "two-tombstones-newer-kept",
         "two-tombstones-both-old", "lonely-dropped", "lonely-kept",
         "negative-cutoff"],
)
def test_a_tombstone_tied_with_its_row_or_alone(tmp_dir, tables, cutoff, want):
    records, _ = _vs_heap(
        tmp_dir, _write_tables(tmp_dir, tables), False, cutoff
    )
    assert records == sorted(want)


def test_a_partition_and_a_merge_with_no_survivor(tmp_dir, monkeypatch):
    """Every key of the keyspace's lower half is deleted and old: those
    partitions hand the writer nothing; with the upper half deleted too
    the merge writes an empty table, as the heap merge does."""
    monkeypatch.setattr(pipeline_mod, "_MAX_P2", 64)
    rng = random.Random(3702)
    low = [b"\x10" + rng.randbytes(11) for _ in range(300)]
    high = [b"\xe0" + rng.randbytes(11) for _ in range(300)]
    rows = {k: (b"v", 1 + i) for i, k in enumerate(low + high)}
    gone_low = {k: (b"", 1_000 + i) for i, k in enumerate(low)}
    gone_high = {k: (b"", 2_000 + i) for i, k in enumerate(high)}
    records, decoded = _vs_heap(
        tmp_dir, _write_tables(tmp_dir, [rows, gone_low]), False, 1_500
    )
    assert [k for k, _v, _ts in records] == sorted(high)
    empty = [part.p for part, job, _t in decoded if job is None]
    assert empty and len(empty) < len(decoded)
    # The grace holds the upper half's deletes and nothing else.
    records, decoded = _vs_heap(
        tmp_dir, _write_tables(tmp_dir, [rows, gone_low, gone_high]),
        False, 2_000,
    )
    assert records == [(k, b"", gone_high[k][1]) for k in sorted(high)]
    records, decoded = _vs_heap(
        tmp_dir, _write_tables(tmp_dir, [rows, gone_low, gone_high]),
        False, None,
    )
    assert records == [] and all(job is None for _p, job, _t in decoded)


def test_kept_tombstones_are_untouched_and_uncounted(tmp_dir):
    """``keep_tombstones=True`` (a merge above the bottom level): every
    newest tombstone is written, whatever the cutoff, no tombstone work
    is done and ``tombstones_in`` stands still."""
    tables, writes = _queue_tables(3703, 8, 200, 0.45)
    before = compaction_stats.stats()
    records, _ = _vs_heap(
        tmp_dir, _write_tables(tmp_dir, tables), True, writes + 1
    )
    after = compaction_stats.stats()
    assert records == _expected(tables, True, None)
    assert any(not v for _k, v, _ts in records)
    rose = {
        k: after["shape"][k] - before["shape"][k] for k in PIPELINE_SHAPE
    }
    assert rose["rows_real"] == 8 * 200
    assert rose["tombstones_in"] == rose["tombstones_kept"] == 0
    spans = lambda s: s["stages"].get("pipeline", {}).get("tomb_gc", {"n": 0})
    assert spans(after)["n"] == spans(before)["n"]


def test_a_merge_with_tombstones_counts_them_and_spans_tomb_gc(tmp_dir):
    tables, writes = _queue_tables(3704, 8, 200, 0.45)
    before = compaction_stats.stats()
    records, decoded = _vs_heap(
        tmp_dir, _write_tables(tmp_dir, tables), False, writes // 2
    )
    after = compaction_stats.stats()
    rose = {
        k: after["shape"][k] - before["shape"][k] for k in PIPELINE_SHAPE
    }
    tombstones = sum(not v for t in tables for v, _ts in t.values())
    assert rose["tombstones_in"] == tombstones > 0
    assert rose["tombstones_kept"] == sum(not v for _k, v, _ts in records) > 0
    assert rose["entries_out"] == len(records)
    gc_before = before["stages"].get("pipeline", {}).get(
        "tomb_gc", {"s": 0.0, "n": 0}
    )
    gc, dec = (after["stages"]["pipeline"][s] for s in ("tomb_gc", "decode"))
    # One span a decoded partition, nested in the caller's decode.
    assert gc["n"] - gc_before["n"] == len(decoded)
    assert 0.0 < gc["s"] - gc_before["s"] <= dec["s"]
    # A merge that reads no tombstone does no tombstone work.
    rows_only, _ = _queue_tables(3705, 8, 200, 0.0)
    mid = compaction_stats.stats()
    _vs_heap(tmp_dir, _write_tables(tmp_dir, rows_only), False, 5)
    end = compaction_stats.stats()
    assert end["shape"]["tombstones_in"] == mid["shape"]["tombstones_in"]
    assert (
        end["stages"]["pipeline"]["tomb_gc"]["n"]
        == mid["stages"]["pipeline"]["tomb_gc"]["n"]
    )
    assert end["shape"]["rows_real"] - mid["shape"]["rows_real"] == 8 * 200


def _random_partition(rng, n_runs, n):
    """Run buffers of records (header, 4-byte key, value or none), and
    a decoded partition over them: every entry once, in any order."""
    datas, off_cat, tomb_cat, ts_cat, rid_cat = [], [], [], [], []
    per_run = rng.multinomial(n, np.ones(n_runs) / n_runs)
    for r, count in enumerate(per_run):
        blob, at = bytearray(), 0
        for _ in range(int(count)):
            tomb = bool(rng.random() < 0.5)
            # Around a cutoff of 1000, the edges of int64 and of u64.
            ts = int(rng.choice(
                [0, 1, 999, 1000, 1001, 2**62, -1, -(2**63),
                 int(rng.integers(0, 2000))]
            ))
            value = b"" if tomb else b"v" * int(rng.integers(1, 9))
            blob += struct.pack("<IIq", 4, len(value), ts) + b"kkkk" + value
            off_cat.append(at)
            tomb_cat.append(tomb)
            ts_cat.append(ts)
            rid_cat.append(r)
            at = len(blob)
        datas.append(np.frombuffer(bytes(blob) or b"\0", dtype=np.uint8))
    return datas, off_cat, tomb_cat, ts_cat, rid_cat


@pytest.mark.parametrize("cutoff", [None, 0, 1000, 1, 2**63, -7])
@pytest.mark.parametrize("n", [0, 1, 257, 70_000], ids=lambda n: f"n{n}")
def test_the_native_pass_against_drop_tombstones_mask(n, cutoff):
    """70,000 entries take the pass's worker threads."""
    lib = native.require()
    rng = np.random.default_rng(3706 + n)
    n_runs = 5
    datas, off_cat, tomb_cat, ts_cat, rid_cat = _random_partition(
        rng, n_runs, n
    )
    off_cat = np.array(off_cat, dtype=np.uint64)
    tomb_cat = np.array(tomb_cat, dtype=np.bool_)
    order = rng.permutation(n)
    gidx = order.astype(np.int64)
    rid = np.array(rid_cat, dtype=np.uint32)[order]
    keep = rng.random(n) < 0.7  # the tie pass has cleared older versions
    keep_before = keep.copy()
    u8p = ctypes.POINTER(ctypes.c_uint8)
    ptrs = (u8p * n_runs)(*(d.ctypes.data_as(u8p) for d in datas))
    sizes = np.array([d.size for d in datas], dtype=np.uint64)
    kept = lib.dbeel_pipe_drop_tombstones(
        n,
        gidx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        rid.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        ptrs,
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        off_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        tomb_cat.view(np.uint8).ctypes.data_as(u8p),
        0 if cutoff else 1,
        max(0, cutoff or 0),
        keep.view(np.uint8).ctypes.data_as(u8p),
    )
    stamps = np.array(ts_cat, dtype=np.int64).view(np.uint64)[order]
    drop = drop_tombstones_mask(tomb_cat[order], stamps, cutoff)
    assert (keep == (keep_before & ~drop)).all()
    assert kept == int((keep & tomb_cat[order]).sum())
    if n >= 257 and cutoff == 1000:
        assert 0 < kept < int((keep_before & tomb_cat[order]).sum())


def test_the_native_pass_refuses_a_header_outside_its_run():
    lib = native.require()
    u8p = ctypes.POINTER(ctypes.c_uint8)
    data = np.zeros(40, dtype=np.uint8)
    ptrs = (u8p * 1)(data.ctypes.data_as(u8p))
    gidx = np.arange(2, dtype=np.int64)
    rid = np.zeros(2, dtype=np.uint32)
    sizes = np.array([40], dtype=np.uint64)
    tomb = np.ones(2, dtype=np.uint8)

    def kept(last_offset):
        offs = np.array([0, last_offset], dtype=np.uint64)
        keep = np.ones(2, dtype=np.uint8)
        return lib.dbeel_pipe_drop_tombstones(
            2,
            gidx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            rid.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            ptrs,
            sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            tomb.ctypes.data_as(u8p),
            0, 5, keep.ctypes.data_as(u8p),
        )

    # Timestamps 0, below the cutoff: none kept; a header that ends
    # past the run's 40 bytes is refused.
    assert kept(24) == 0
    assert kept(25) == -1


def _loaded_and_spread_runs(rng, n_loaded, n_spread, per_run):
    """Prefixes of ``n_loaded`` tables written in key order (each holds
    all its entries in one sliver of the keyspace, side by side) and of
    ``n_spread`` tables whose entries lie anywhere."""
    space = 1 << 48
    runs = []
    for r in range(n_loaded):
        lo = r * space // n_loaded
        runs.append(lo + np.arange(per_run, dtype=np.uint64) * np.uint64(
            space // n_loaded // per_run
        ))
    for _ in range(n_spread):
        runs.append(np.sort(
            rng.integers(0, space, size=per_run, dtype=np.uint64)
        ))
    return runs


def test_partitions_of_tables_loaded_in_key_order(monkeypatch):
    """What the NEW-ORDER tree forced on the plan: eight of 64 tables hold
    their entries in an eighth of the keyspace each, so the cuts sampled
    over all runs overflow the kernel's rows many times over; every
    overflowing partition is split until each run's slice fits (the
    splitting once stopped after 64 rounds and declined the merge)."""
    monkeypatch.setattr(pipeline_mod, "_MAX_KP", 1 << 14)
    rng = np.random.default_rng(3707)
    prefixes = _loaded_and_spread_runs(rng, 8, 56, 2_500)
    runs = [
        pipeline_mod._Run(None, 0, None, None, None, pf) for pf in prefixes
    ]
    chosen = pipeline_mod._choose_partitions(runs)
    assert chosen is not None
    splitters, bounds, p2 = chosen
    assert p2 == pipeline_mod.max_partition_rows(64) == 256
    n_parts = len(bounds[0]) - 1
    assert n_parts == len(splitters) + 1 > 10 + 64
    assert (np.diff(splitters.astype(np.int64)) > 0).all()
    for pf, b in zip(prefixes, bounds):
        assert b[0] == 0 and b[-1] == len(pf) and (np.diff(b) >= 0).all()
        assert np.diff(b).max() <= p2
        # side="right" cuts: a prefix equal to a splitter lies left.
        assert (pf[b[1:-1] - 1] <= splitters)[b[1:-1] > 0].all()
        assert (pf[b[1:-1][b[1:-1] < len(pf)]]
                > splitters[b[1:-1] < len(pf)]).all()
    # No more than twice what the fullest table needs at the least.
    assert n_parts <= 2 * 8 * -(-2_500 // p2) + 56


def test_a_merge_of_loaded_and_spread_tables_with_deletes(tmp_dir, monkeypatch):
    """Such a tree end to end at a small size: two tables loaded in key
    order, six of the mix that deletes the loaded rows in order."""
    monkeypatch.setattr(pipeline_mod, "_MAX_P2", 32)
    keys = [b"\x93%c%c\xcd%c%c" % (w, d, o >> 8, o & 0xFF)
            for w in range(1, 5) for d in range(1, 11)
            for o in range(2101, 2131)]
    half = len(keys) // 2
    tables = [
        {k: (b"row", 1 + i) for i, k in enumerate(keys[:half])},
        {k: (b"row", 1 + half + i) for i, k in enumerate(keys[half:])},
    ]
    rng = random.Random(3708)
    place = len(keys)
    queue = sorted(keys, key=lambda k: (k[-2:], k))  # lowest order first
    for _ in range(6):
        table = {}
        for _ in range(150):
            place += 1
            if rng.random() < 0.47 and queue:
                table[queue.pop(0)] = (b"", place)
            else:
                w, d = rng.randrange(1, 5), rng.randrange(1, 11)
                o = 2131 + place
                table[b"\x93%c%c\xcd%c%c" % (w, d, o >> 8, o & 0xFF)] = (
                    b"row", place,
                )
        tables.append(table)
    cutoff = place - 300
    records, decoded = _vs_heap(
        tmp_dir, _write_tables(tmp_dir, tables), False, cutoff
    )
    assert records == _expected(tables, False, cutoff)
    assert len(decoded) > 16
    assert any(not v for _k, v, _ts in records)
    assert len(records) < sum(len(t) for t in tables) - 300
