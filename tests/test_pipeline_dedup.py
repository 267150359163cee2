"""The pipeline as a dedup (ISSUE 34): runs whose keys repeat, as an update
stream leaves them, merged by ``pipeline_merge`` and by the heap oracle,
byte for byte.  Two versions of one key always tie on the device key, so
every case here goes through the decode's tie pass
(``dbeel_pipe_resolve_ties``); the numpy fix-up it replaced is held to the
same order on random blocks.  The newest TIMESTAMP wins, whichever run
holds it.
"""

import ctypes
import os
import random
import struct
import sys

import numpy as np
import pytest

from dbeel_tpu.ops import pipeline as pipeline_mod
from dbeel_tpu.ops.device_compaction import DeviceMergeStrategy
from dbeel_tpu.storage import native
from dbeel_tpu.storage.compaction import (
    PIPELINE_SHAPE, compaction_stats, get_strategy,
)
from dbeel_tpu.storage.entry import ENTRY_HEADER_SIZE, file_name
from dbeel_tpu.storage.native import native_available
from dbeel_tpu.storage.sstable import SSTable

from conftest import write_sstable_fixture
from test_pipeline import _sha_triplet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native library unavailable"
)


@pytest.fixture(autouse=True)
def _through_the_pipeline(monkeypatch):
    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)


def _write_tables(tmp_dir, tables):
    """``tables``: per run a dict key -> (value, ts).  Returns the
    tables' indices."""
    for r, table in enumerate(tables):
        write_sstable_fixture(
            tmp_dir, 2 * r,
            [(k, v, ts) for k, (v, ts) in sorted(table.items())],
        )
    return [2 * r for r in range(len(tables))]


def _read_output(tmp_dir, oi):
    """The (key, value, ts) records of a merge's data file."""
    with open(f"{tmp_dir}/{file_name(oi, 'compact_data')}", "rb") as f:
        blob = f.read()
    out, at = [], 0
    while at < len(blob):
        ks, vs, ts = struct.unpack_from("<IIq", blob, at)
        at += ENTRY_HEADER_SIZE
        out.append((blob[at:at + ks], blob[at + ks:at + ks + vs], ts))
        at += ks + vs
    return out


def _vs_heap(tmp_dir, idxs, keep_tomb=False, drop_before=None):
    """Heap and device merges of the same tables: equal triplets, the
    device's from the pipeline.  Returns (the output's records, what
    every ``_decode`` call was given and gave: (part, job, ties))."""
    decoded = []
    real = pipeline_mod._decode

    def spy(lib, inputs, plan, part, *rest):
        job, ties, tomb_kept = real(lib, inputs, plan, part, *rest)
        decoded.append((part, job, ties))
        return job, ties, tomb_kept

    results = {}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(pipeline_mod, "_decode", spy)
        for name, oi in (("heap", 101), ("device", 103)):
            strat = get_strategy(name)
            strat.tombstone_drop_before = drop_before
            srcs = [SSTable(tmp_dir, i, None) for i in idxs]
            try:
                res = strat.merge(srcs, tmp_dir, oi, None, keep_tomb, 1)
            finally:
                for s in srcs:
                    s.close()
            results[name] = (
                _sha_triplet(tmp_dir, oi), res.entry_count, res.data_size,
                res.wrote_bloom,
            )
    assert results["heap"] == results["device"]
    assert decoded, "the device merge did not take the pipeline"
    records = _read_output(tmp_dir, 103)
    assert len(records) == results["device"][1]
    return records, decoded


def _zipfian_tables(seed, nruns, per_run, records):
    """Flushed memtables of one zipfian update stream, by the
    benchmark's own draw (harness/zipf_runs.py): key -> (value, ts) of
    the newest write each table saw."""
    from benchmark.harness import zipf_runs

    rng = np.random.default_rng(seed)
    cdf = zipf_runs.rank_cdf(records, 0.99)
    salts = rng.integers(0, 1 << 63, size=2, dtype=np.uint64)
    pending = np.zeros(0, dtype=np.int64)
    tables, written = [], 0
    for r in range(nruns):
        ranks, pending = zipf_runs.next_table(rng, cdf, per_run, pending)
        uniq, last = zipf_runs.newest_writes(ranks)
        keys = zipf_runs.rank_keys(uniq, salts)
        tables.append({
            bytes(k): (b"r%d-w%d" % (r, w), written + int(w))
            for k, w in zip(keys, last)
        })
        written += len(ranks)
    return tables


def _newest(tables):
    """The plain model: per key the version of the newest timestamp,
    a later table winning equal timestamps."""
    best = {}
    for table in tables:
        for k, (v, ts) in table.items():
            if k not in best or ts >= best[k][1]:
                best[k] = (v, ts)
    return best


def test_a_zipfian_update_stream_is_deduplicated_as_the_heap_merge(tmp_dir):
    tables = _zipfian_tables(3401, nruns=8, per_run=400, records=3000)
    records, decoded = _vs_heap(tmp_dir, _write_tables(tmp_dir, tables))
    best = _newest(tables)
    assert [(k, v, ts) for k, v, ts in records] == [
        (k, *best[k]) for k in sorted(best)
    ]
    entries_in = sum(len(t) for t in tables)
    # Most entries are older versions, and every version of a repeated
    # key was left tied by the device order.
    assert len(records) < 0.6 * entries_in
    repeated = sum(
        sum(k in t for t in tables) for k in best
        if sum(k in t for t in tables) > 1
    )
    assert sum(ties for _p, _j, ties in decoded) >= repeated


def test_a_key_in_all_of_64_runs_keeps_its_newest_version(tmp_dir):
    rng = random.Random(3402)
    hot = [rng.randbytes(16) for _ in range(5)]
    tables = []
    for r in range(64):
        table = {rng.randbytes(16): (b"cold", 10_000 + r) for _ in range(30)}
        for k in hot:
            table[k] = (b"hot-%d" % r, 100 * r + 7)
        tables.append(table)
    records, _ = _vs_heap(tmp_dir, _write_tables(tmp_dir, tables))
    got = {k: (v, ts) for k, v, ts in records}
    assert len(records) == 64 * 30 + 5
    for k in hot:
        assert got[k] == (b"hot-63", 6307)


def test_an_older_run_that_holds_the_newest_timestamp_wins(tmp_dir):
    """Hints, migration and leaderless replication put newer writes
    into older tables.  A survivor chosen by the run's index fails
    here: the value that must survive is run 0's."""
    rng = random.Random(3403)
    key, other = rng.randbytes(16), rng.randbytes(16)
    tables = [
        {key: (b"from-run-0-newest", 9_000), other: (b"o0", 5)},
        {key: (b"from-run-1", 100), rng.randbytes(16): (b"x", 1)},
        {key: (b"from-run-2", 8_999), other: (b"o2", 4)},
        {key: (b"from-run-3-oldest", 1)},
    ]
    records, _ = _vs_heap(tmp_dir, _write_tables(tmp_dir, tables))
    got = {k: (v, ts) for k, v, ts in records}
    assert got[key] == (b"from-run-0-newest", 9_000)
    assert got[other] == (b"o0", 5)
    assert len(records) == 3


def test_equal_timestamps_fall_to_the_newest_source(tmp_dir):
    rng = random.Random(3404)
    key = rng.randbytes(16)
    tables = [
        {key: (b"run-0", 777)},
        {key: (b"run-1", 777), rng.randbytes(16): (b"y", 3)},
        {key: (b"run-2-older-ts", 776)},
    ]
    records, _ = _vs_heap(tmp_dir, _write_tables(tmp_dir, tables))
    assert (key, b"run-1", 777) in records and len(records) == 2


def test_a_tie_block_of_versions_a_shift_collision_and_a_longer_key(tmp_dir):
    """One block of the device order holds versions of key A, a key B
    whose prefix differs below the shift (equal shifted word, another
    key), and a key C that is A plus more bytes (equal 8-byte prefix
    AND equal first 16 bytes): sorted by the full key, only A's older
    versions go."""
    rng = random.Random(3405)
    prefix = 0x4000_0000_0000_0100
    a = prefix.to_bytes(8, "big") + b"samesame"
    b = (prefix + 3).to_bytes(8, "big") + b"another!"
    c = a + b"-and-longer"
    d = prefix.to_bytes(8, "big") + b"samesamf"  # A's prefix, other key
    spread = [rng.randrange(0, 1 << 64) for _ in range(900)]
    tables = []
    for r in range(3):
        table = {
            v.to_bytes(8, "big") + b"%08d" % i: (b"bg", 50 + r)
            for i, v in enumerate(rng.sample(spread, 400))
        }
        table[a] = (b"a-%d" % r, 1_000 - r)  # run 0 holds the newest
        if r != 1:
            table[c] = (b"c-%d" % r, 2_000 + r)
        if r == 1:
            table[b] = (b"b", 1)
        if r == 2:
            table[d] = (b"d", 2)
        tables.append(table)
    records, decoded = _vs_heap(tmp_dir, _write_tables(tmp_dir, tables))
    # The shift was taken (the one-word operand over a 2^64 span).
    assert all(part.mode32 and part.shift > 0 for part, _j, _t in decoded)
    got = [(k, v) for k, v, _ts in records if k in (a, b, c, d)]
    assert got == [(a, b"a-0"), (c, b"c-2"), (d, b"d"), (b, b"b")]


def test_partitions_never_cut_a_duplicate_group(monkeypatch):
    """``_choose_partitions`` cuts between prefixes, so every version
    of a key — equal key, equal prefix — lies in one partition, also
    after the overflow splits a small kernel forces."""
    monkeypatch.setattr(pipeline_mod, "_MAX_P2", 64)
    tables = _zipfian_tables(3406, nruns=16, per_run=300, records=1500)
    runs = []
    for table in tables:
        pf = np.array(
            sorted(int.from_bytes(k[:8], "big") for k in table), np.uint64
        )
        runs.append(pipeline_mod._Run(None, 0, None, None, None, pf))
    splitters, bounds, p2 = pipeline_mod._choose_partitions(runs)
    n_parts = len(bounds[0]) - 1
    assert n_parts > 8 and p2 <= 64
    for p in range(1, n_parts):
        left = max(
            (int(r.prefix64[b[p] - 1]) for r, b in zip(runs, bounds)
             if b[p] > 0), default=-1,
        )
        right = min(
            (int(r.prefix64[b[p]]) for r, b in zip(runs, bounds)
             if b[p] < r.prefix64.size), default=1 << 64,
        )
        assert left < right


@pytest.mark.parametrize(
    "keep_tomb,drop_before,survives",
    [(True, None, True), (False, None, False), (False, 500, True),
     (False, 5_000, False)],
    ids=["tombstones-kept", "tombstones-dropped", "inside-gc-grace",
         "outside-gc-grace"],
)
def test_a_newest_version_that_is_a_tombstone(
    tmp_dir, keep_tomb, drop_before, survives
):
    """The tombstone shadows the older values whether or not it is
    then dropped; a tombstone that an OLDER timestamp carries does not
    shadow a newer value."""
    rng = random.Random(3407)
    deleted, revived = rng.randbytes(16), rng.randbytes(16)
    tables = [
        {deleted: (b"old-value", 10), revived: (b"", 20)},
        {deleted: (b"", 1_000), revived: (b"written-again", 30)},
        {deleted: (b"older-still", 5), rng.randbytes(16): (b"z", 1)},
    ]
    records, _ = _vs_heap(
        tmp_dir, _write_tables(tmp_dir, tables), keep_tomb, drop_before
    )
    got = {k: (v, ts) for k, v, ts in records}
    assert got[revived] == (b"written-again", 30)
    assert (got.get(deleted) == (b"", 1_000)) == survives
    assert len(records) == 2 + survives


def test_a_partition_in_which_no_entry_survives(tmp_dir, monkeypatch):
    """Every key of the keyspace's lower half was deleted after its
    last write: those partitions hand the writer nothing."""
    monkeypatch.setattr(pipeline_mod, "_MAX_P2", 64)
    rng = random.Random(3408)
    low = [b"\x10" + rng.randbytes(15) for _ in range(300)]
    high = [b"\xe0" + rng.randbytes(15) for _ in range(300)]
    tables = [
        {k: (b"v0", 1) for k in low + high},
        {k: (b"", 2) for k in low},
        {k: (b"v2", 3) for k in high[:150]},
    ]
    records, decoded = _vs_heap(tmp_dir, _write_tables(tmp_dir, tables))
    assert sorted(k for k, _v, _ts in records) == sorted(high)
    empty = [part.p for part, job, _t in decoded if job is None]
    assert empty and len(empty) < len(decoded)


def test_duplicates_under_the_two_word_operand(tmp_dir):
    """A dense cluster and one far key: the shift would collapse the
    cluster, so the exact two-word operand is launched, and versions of
    one key tie on its 8-byte prefix."""
    tables = []
    for r in range(3):
        table = {
            v.to_bytes(8, "big") + b"tail": (b"v%d" % r, 100 * (3 - r) + v % 7)
            for v in range(r, 3000, 2)
        }
        if r == 0:
            table[(1 << 62).to_bytes(8, "big")] = (b"far", 1)
        tables.append(table)
    records, decoded = _vs_heap(tmp_dir, _write_tables(tmp_dir, tables))
    assert any(not part.mode32 for part, _j, _t in decoded)
    best = _newest(tables)
    assert [(k, v, ts) for k, v, ts in records] == [
        (k, *best[k]) for k in sorted(best)
    ]
    assert len(records) < sum(len(t) for t in tables)


def test_entries_out_counts_what_the_heap_merge_writes(tmp_dir):
    tables = _zipfian_tables(3409, nruns=6, per_run=250, records=1200)
    before = compaction_stats.stats()["shape"]
    records, decoded = _vs_heap(tmp_dir, _write_tables(tmp_dir, tables))
    after = compaction_stats.stats()["shape"]
    rose = {k: after[k] - before[k] for k in PIPELINE_SHAPE}
    assert rose["rows_real"] == 6 * 250
    assert rose["entries_out"] == len(records) == len(_newest(tables))
    assert rose["entries_out"] < rose["rows_real"]
    assert rose["tie_entries"] == sum(ties for _p, _j, ties in decoded)


def _random_blocks(seed, n, nruns):
    """A partition's decode, made up: ``n`` entries over ``nruns`` run
    buffers in an order whose tie flags chain random blocks — versions
    of one key, keys of one prefix, keys that extend another, equal
    timestamps.  Returns (an ``_Inputs`` with what the tie pass reads,
    gidx, rids, tie flags)."""
    rng = random.Random(seed)
    per_run = [[] for _ in range(nruns)]  # (key, ts) in run order
    order = []  # (run, position in the run) in decode order
    flags = []
    while len(order) < n:
        size = 1 if rng.random() < 0.4 else rng.randint(2, 2 * nruns)
        prefix = rng.randbytes(8)
        stems = [prefix + rng.randbytes(rng.choice((0, 3, 8, 8, 8, 20)))
                 for _ in range(3)]
        stems.append(stems[0] + b"\x00")  # a key that extends another
        block = []
        for _ in range(size):
            run = rng.randrange(nruns)
            per_run[run].append((rng.choice(stems), rng.randrange(4)))
            block.append((run, len(per_run[run]) - 1))
        # The device leaves ties in (run, position) order.
        block.sort()
        order += block
        flags += [0] + [1] * (size - 1)
    runs, offs, kss = [], [], []
    for entries in per_run:
        blob, off, ks = bytearray(), [], []
        for key, ts in entries:
            off.append(len(blob))
            ks.append(len(key))
            blob += struct.pack("<IIq", len(key), 1, ts) + key + b"v"
        data = np.frombuffer(bytes(blob) or b"\0", dtype=np.uint8)
        runs.append(pipeline_mod._Run(
            data, len(blob), np.array(off, np.uint64),
            np.array(ks, np.uint32), None,
        ))
        offs.append(runs[-1].offsets)
        kss.append(runs[-1].key_size)
    run_base = np.zeros(nruns + 1, np.int64)
    np.cumsum([len(e) for e in per_run], out=run_base[1:])
    u8p = ctypes.POINTER(ctypes.c_uint8)
    inputs = pipeline_mod._Inputs(
        runs, run_base, np.concatenate(offs), np.concatenate(kss), None,
        None, (u8p * nruns)(*[r.data.ctypes.data_as(u8p) for r in runs]),
        np.array([r.size for r in runs], np.uint64), len(order), 0,
    )
    gidx = np.array([run_base[run] + pos for run, pos in order], np.int64)
    rids = np.array([run for run, _pos in order], np.uint32)
    return inputs, gidx, rids, np.array(flags, np.uint8)


def _resolve_ties(inputs, gidx, rids, tie, keep):
    u8p = ctypes.POINTER(ctypes.c_uint8)
    return native.require().dbeel_pipe_resolve_ties(
        len(gidx), tie.ctypes.data_as(u8p),
        gidx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        rids.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        inputs.run_ptrs,
        inputs.run_sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        inputs.off_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        inputs.ks_cat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        ENTRY_HEADER_SIZE, keep.view(np.uint8).ctypes.data_as(u8p),
    )


@pytest.mark.parametrize(
    "seed,n,nruns",
    [(1, 2_000, 3), (2, 5_000, 64), (3, 150_000, 16)],
    ids=["three-runs", "sixty-four-runs", "large-enough-for-its-workers"],
)
def test_the_c_tie_pass_orders_and_marks_as_the_numpy_fix_up(seed, n, nruns):
    inputs, gidx, rids, tie = _random_blocks(seed, n, nruns)
    ours = (gidx.copy(), rids.copy(), np.zeros(len(gidx), np.bool_))
    theirs = (gidx.copy(), rids.copy(), np.zeros(len(gidx), np.bool_))
    tied = _resolve_ties(inputs, *ours[:2], tie, ours[2])
    assert tied == pipeline_mod._tie_fixup_numpy(
        inputs, *theirs[:2], tie, theirs[2]
    )
    assert 0 < tied < len(gidx)
    for got, want in zip(ours, theirs):
        assert (got == want).all()
    assert not ours[2].all() and ours[2][tie == 0].all()
    # Entries outside every block stay where the device put them.
    alone = (tie == 0) & (np.append(tie[1:], 0) == 0)
    assert (ours[0][alone] == gidx[alone]).all()


def test_a_tied_key_outside_its_run_is_refused():
    inputs, gidx, rids, tie = _random_blocks(4, 500, 4)
    inputs.run_sizes[:] = 8
    keep = np.zeros(len(gidx), np.bool_)
    assert _resolve_ties(inputs, gidx, rids, tie, keep) == -1
