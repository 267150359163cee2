"""The device pipeline's block pool (ops/block_pool.py): every merge
after a process's first runs in memory the pool kept, which is dirty.

Every merge of this file runs with the pool's test hook on: a block
that comes back is filled with 0xA5, so a stage that relied on fresh
(zero) pages, or that reads past what it wrote, cannot produce the
host merge's bytes.
"""

import hashlib
import os
import random
import threading

import numpy as np
import pytest

from dbeel_tpu.ops import block_pool
from dbeel_tpu.ops import pipeline as pipeline_mod
from dbeel_tpu.ops.device_compaction import DeviceMergeStrategy
from dbeel_tpu.storage import checksums
from dbeel_tpu.storage import native as native_mod
from dbeel_tpu.storage.compaction import compaction_stats, get_strategy
from dbeel_tpu.storage.entry import (
    DATA_FILE_EXT,
    INDEX_FILE_EXT,
    file_name,
)
from dbeel_tpu.storage.sstable import SSTable

from conftest import write_sstable_fixture

pytestmark = pytest.mark.skipif(
    not native_mod.native_available(), reason="native library unavailable"
)

OUTPUT_EXTS = ("compact_data", "compact_index", "compact_bloom", "compact_sums")


@pytest.fixture(autouse=True)
def poisoned_pool(monkeypatch):
    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    monkeypatch.setattr(pipeline_mod._POOL, "poison", 0xA5)


def _pool():
    return compaction_stats.stats()["pool"]


def _delta(before, after):
    return {k: after[k] - before[k] for k in after}


def _sha_output(d, oi):
    """SHA-256 over the whole output: data, index, bloom and sums."""
    h = hashlib.sha256()
    for ext in OUTPUT_EXTS:
        p = f"{d}/{file_name(oi, ext)}"
        if os.path.exists(p):
            with open(p, "rb") as f:
                h.update(ext.encode())
                h.update(f.read())
    return h.hexdigest()


def _merge(name, d, idxs, oi, keep_tomb=False):
    srcs = [SSTable(d, i, None) for i in idxs]
    try:
        res = get_strategy(name).merge(srcs, d, oi, None, keep_tomb, 1)
    finally:
        for s in srcs:
            s.close()
    out = (_sha_output(d, oi), res.entry_count, res.data_size, res.wrote_bloom)
    for ext in OUTPUT_EXTS:
        p = f"{d}/{file_name(oi, ext)}"
        if os.path.exists(p):
            os.unlink(p)
    return out


def _fixed_runs(d, seed, nruns=8, npr=1500, first=0):
    """``nruns`` runs of 16 B keys / 64 B values, the major cell's
    record, every run over the whole keyspace."""
    rng = random.Random(seed)
    idxs = []
    for r in range(nruns):
        keys = sorted({rng.randbytes(16) for _ in range(npr)})
        write_sstable_fixture(
            d, first + r * 2, [(k, rng.randbytes(64), 500 + r) for k in keys]
        )
        idxs.append(first + r * 2)
    return idxs


def _wide_runs(d, seed, nruns=64, npr=120, first=0):
    """The wide cell's shape with what its uniform keys never have:
    every key lives in about four runs (newest wins), 15 % of the
    entries are tombstones, a third of the keys share their first 8
    bytes (tie blocks), values of 8-159 B."""
    rng = random.Random(seed)
    pool = [
        (b"SHARED00" if rng.random() < 0.33 else rng.randbytes(8))
        + rng.randbytes(8)
        for _ in range(nruns * npr // 4)
    ]
    idxs = []
    for r in range(nruns):
        entries = []
        for k in sorted(rng.sample(pool, npr)):
            v = b"" if rng.random() < 0.15 else rng.randbytes(rng.randint(8, 159))
            entries.append((k, v, 1000 * r + rng.randrange(1000)))
        write_sstable_fixture(d, first + r * 2, entries)
        idxs.append(first + r * 2)
    return idxs


# ---- (a) byte identity out of a dirty pool ---------------------------


@pytest.mark.parametrize(
    "build,keep_tomb",
    [
        (_fixed_runs, False),
        (_wide_runs, False),
        (_wide_runs, True),
    ],
    ids=["8-fixed-width-runs", "64-varlen-runs-tombstones-dropped",
         "64-varlen-runs-tombstones-kept"],
)
def test_a_dirty_pool_changes_no_output_byte(tmp_dir, build, keep_tomb):
    idxs = build(tmp_dir, 2900)
    want = _merge("native", tmp_dir, idxs, 201, keep_tomb)
    # The first merge dirties the blocks (and, in a fresh process,
    # allocates them); the second runs in what the first gave back.
    before = _pool()
    first = _merge("device", tmp_dir, idxs, 203, keep_tomb)
    second = _merge("device", tmp_dir, idxs, 205, keep_tomb)
    assert first == want
    assert second == want
    got = _delta(before, _pool())
    assert got["hits"] >= got["leases"] // 2  # all of the second's


def test_a_smaller_merge_after_a_larger_one_is_byte_identical(tmp_dir):
    large = _fixed_runs(tmp_dir, 2901, nruns=8, npr=3000)
    small = _wide_runs(tmp_dir, 2902, nruns=16, npr=200, first=100)
    want_large = _merge("native", tmp_dir, large, 201)
    want_small = _merge("native", tmp_dir, small, 201)
    assert _merge("device", tmp_dir, large, 203) == want_large
    # Its arrays are heads of blocks the large merge filled to the end.
    assert _merge("device", tmp_dir, small, 205) == want_small
    assert _merge("device", tmp_dir, large, 207) == want_large


# ---- (b) the counters say the pool engages ---------------------------


def test_the_second_merge_of_a_shape_allocates_nothing(tmp_dir):
    idxs = _fixed_runs(tmp_dir, 2903)
    # Twice: what earlier merges of the process left idle is gone then.
    for _ in range(block_pool.IDLE_MERGES):
        _merge("device", tmp_dir, idxs, 203)
    before = _pool()
    assert before["leased_bytes"] == 0
    assert before["retained_bytes"] > 0
    _merge("device", tmp_dir, idxs, 205)
    after = _pool()
    got = _delta(before, after)
    assert got["bytes_fresh"] == 0
    assert got["leases"] > 0 and got["hits"] == got["leases"]
    assert got["bytes_leased"] > 0
    assert after["leased_bytes"] == 0
    assert after["retained_bytes"] == before["retained_bytes"]


@pytest.mark.parametrize("shrink", [1.0, 0.97, 0.91])
def test_runs_a_few_percent_apart_share_their_blocks(shrink):
    """A served node's tables are never equal, and the wide cell draws
    its value lengths per seed: a merge whose arrays are a few percent
    smaller leases the blocks of the last one, whatever their size."""
    seen = {"hits": 0, "leases": 0, "bytes_fresh": 0}

    def note(retained_bytes, leased_bytes, **adds):
        for k, v in adds.items():
            if k in seen:
                seen[k] += v

    pool = block_pool.BlockPool(note=note)
    sizes = [int(100_000 * 1.4**i) for i in range(20)]  # 100 KB - 60 MB
    first = pool.leases()
    for n in sizes:
        first.array(n)
    first.close()
    fresh = seen["bytes_fresh"]
    second = pool.leases()
    for n in sizes:
        second.array(int(n * shrink))
    second.close()
    assert seen == {"hits": 20, "leases": 40, "bytes_fresh": fresh}


# ---- (c) the O_DIRECT contract ---------------------------------------


def test_leased_run_buffers_keep_the_o_direct_contract(tmp_dir, monkeypatch):
    idxs = _fixed_runs(tmp_dir, 2906)
    path = f"{tmp_dir}/{file_name(idxs[0], DATA_FILE_EXT)}"
    try:
        os.close(os.open(path, os.O_RDONLY | os.O_DIRECT))
        direct = True
    except OSError:
        direct = False  # this filesystem refuses it to everyone
    seen = []
    real = pipeline_mod._read_run

    def spy(lib, source, buf, cols, scratch):
        seen.append((buf.ctypes.data, buf.size, source.data_size))
        return real(lib, source, buf, cols, scratch)

    monkeypatch.setattr(pipeline_mod, "_read_run", spy)
    _merge("device", tmp_dir, idxs, 203)  # so that the next one's are reused
    del seen[:]
    fallbacks = native_mod.odirect_fallbacks()
    _merge("device", tmp_dir, idxs, 205)
    assert len(seen) == len(idxs)
    for addr, cap, size in seen:
        assert addr % 4096 == 0 and cap % 4096 == 0 and cap >= size
    if direct:
        assert native_mod.odirect_fallbacks() == fallbacks


# ---- (d) two shards' merges at once ----------------------------------


def test_two_merges_at_once_never_share_a_block(tmp_dir, monkeypatch):
    a = _fixed_runs(tmp_dir, 2907)
    b = _fixed_runs(tmp_dir, 2908, first=100)
    want = {
        "a": _merge("native", tmp_dir, a, 201),
        "b": _merge("native", tmp_dir, b, 201),
    }
    # Both shapes' blocks are in the pool: the merges below compete
    # for them.
    _merge("device", tmp_dir, a, 203)
    pool = pipeline_mod._POOL
    out = {}  # address -> leases that hold it right now
    shared = []
    guard = threading.Lock()
    real_lease, real_give, real_drop = pool._lease, pool._give, pool._drop

    def lease(nbytes):
        blk = real_lease(nbytes)
        with guard:
            out[blk.addr] = out.get(blk.addr, 0) + 1
            if out[blk.addr] > 1:
                shared.append(blk.addr)
        return blk

    def back(real):
        def inner(blocks):
            with guard:
                for blk in blocks:
                    out[blk.addr] -= 1
            return real(blocks)

        return inner

    monkeypatch.setattr(pool, "_lease", lease)
    monkeypatch.setattr(pool, "_give", back(real_give))
    monkeypatch.setattr(pool, "_drop", back(real_drop))
    got = {}
    errors = []

    def shard(name, idxs, oi, rounds=3):
        try:
            got[name] = [
                _merge("device", tmp_dir, idxs, oi + 2 * i)
                for i in range(rounds)
            ]
        except BaseException as e:
            errors.append(e)

    threads = [
        threading.Thread(target=shard, args=("a", a, 301)),
        threading.Thread(target=shard, args=("b", b, 401)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert not shared
    assert any(out.values()) is False
    assert got["a"] == [want["a"]] * 3
    assert got["b"] == [want["b"]] * 3
    assert _pool()["leased_bytes"] == 0


def test_many_threads_on_one_pool_never_hold_one_block_twice():
    """More threads than cores, a short switch interval, each thread a
    'merge' that leases, writes its own mark, checks it and closes: a
    block handed to two of them at once would show another's mark, a
    lost update would leave the gauges off."""
    import sys
    import time

    seen = {}

    def note(retained_bytes, leased_bytes, **adds):
        seen.update(retained_bytes=retained_bytes, leased_bytes=leased_bytes)
        for k, v in adds.items():
            seen[k] = seen.get(k, 0) + v

    pool = block_pool.BlockPool(note=note)
    errors = []
    deadline = time.monotonic() + 3.0

    def merges(mark):
        rng = random.Random(mark)
        try:
            while time.monotonic() < deadline:
                mem = pool.leases()
                arrs = [
                    mem.array(rng.choice((4096, 5000, 70_000, 300_000)))
                    for _ in range(rng.randint(1, 8))
                ]
                for a in arrs:
                    a.fill(mark)
                if rng.random() < 0.3:
                    mem.give(arrs.pop())
                for a in arrs:
                    if not (a == mark).all():
                        errors.append(f"thread {mark} found another's bytes")
                mem.close(drop=rng.random() < 0.1)
        except BaseException as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [
            threading.Thread(target=merges, args=(i + 1,))
            for i in range(min(64, 4 * (os.cpu_count() or 4)))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    assert seen["leased_bytes"] == 0
    assert seen["hits"] <= seen["leases"]
    assert seen["retained_bytes"] == sum(
        blk.cap for free in pool._free.values() for blk in free
    )


# ---- (e) retention ---------------------------------------------------


def test_the_pool_gives_a_large_merges_memory_back(tmp_dir):
    """After one large merge and three small ones the pool keeps no
    more than the small merges had leased at once (their blocks), and
    holds nothing out."""
    large = _fixed_runs(tmp_dir, 2909, nruns=8, npr=4000)
    small = _fixed_runs(tmp_dir, 2910, nruns=4, npr=300, first=100)
    _merge("device", tmp_dir, large, 203)
    after_large = _pool()
    per_small = None
    for i in range(3):
        before = _pool()
        _merge("device", tmp_dir, small, 205 + 2 * i)
        per_small = _delta(before, _pool())["bytes_leased"]
    after = _pool()
    assert after["leased_bytes"] == 0
    # Every block of a small merge is leased until its end, so the
    # capacity one leases is the most it holds at once.
    assert 0 < after["retained_bytes"] <= per_small
    assert after["retained_bytes"] < after_large["retained_bytes"] // 4
    # And the large merge still runs: on fresh blocks again.
    before = _pool()
    _merge("device", tmp_dir, large, 211)
    assert _delta(before, _pool())["bytes_fresh"] > 0


def test_retention_rule_on_the_pool_alone():
    """IDLE_MERGES merges without a lease release a free block; the
    free total stays within what the recent merges leased at once."""
    seen = {}
    pool = block_pool.BlockPool(
        note=lambda **kw: seen.update(
            {k: kw[k] for k in ("retained_bytes", "leased_bytes")}
        )
    )
    big = pool.leases()
    big.array(1 << 22)
    big.array(1 << 22)
    big.close()
    assert seen == {"retained_bytes": 2 << 22, "leased_bytes": 0}
    for merges_since in range(1, block_pool.IDLE_MERGES + 1):
        small = pool.leases()
        small.array(1 << 16)
        small.close()
        if merges_since < block_pool.IDLE_MERGES:
            # Not idle yet, but over the 8 MiB the recent merges had
            # leased at once: the least recently held block goes.
            assert seen["retained_bytes"] == (1 << 22) + (1 << 16)
    assert seen == {"retained_bytes": 1 << 16, "leased_bytes": 0}


@pytest.mark.parametrize(
    "nbytes", [1, 4096, 4097, 100_000, (1 << 20) + 1, 123_456_789, 1 << 31]
)
def test_size_classes_are_aligned_and_waste_an_eighth_at_most(nbytes):
    cap = block_pool.size_class(nbytes)
    assert cap >= nbytes and cap % 4096 == 0
    assert cap <= max(4096, nbytes * 1.125 + 4096)
    assert block_pool.size_class(cap) == cap


def test_a_lease_takes_the_best_fit_within_the_waste_limit():
    pool = block_pool.BlockPool()
    first = pool.leases()
    sizes = [1 << 20, 9 << 17, 5 << 18, 1 << 21]  # 1, 1.125, 1.25, 2 MiB
    addrs = {
        n: first.array(n).__array_interface__["data"][0] for n in sizes
    }
    first.close()
    second = pool.leases()

    def addr(n):
        return second.array(n).__array_interface__["data"][0]

    # The smallest block that holds the request; never one more than
    # WASTE_LIMIT above it; a typed array starts at its block's base.
    assert addr(1 << 20) == addrs[1 << 20]
    assert addr(1 << 20) == addrs[9 << 17]
    assert addr(1 << 20) == addrs[5 << 18]
    assert addr(1 << 20) not in addrs.values()  # 2 MiB: too wasteful
    arr = second.array((3, 1000), np.uint64)
    assert arr.shape == (3, 1000) and arr.dtype == np.uint64
    assert arr.__array_interface__["data"][0] % 4096 == 0
    assert second.array(0, np.uint32).size == 0
    second.close()


# ---- (f) failures leave nothing leased -------------------------------


def test_a_failed_writer_leaves_nothing_leased(tmp_dir, monkeypatch):
    idxs = _fixed_runs(tmp_dir, 2911)
    want = _merge("native", tmp_dir, idxs, 201)
    assert _merge("device", tmp_dir, idxs, 203) == want
    lib = native_mod.require()
    with monkeypatch.context() as m:
        m.setattr(lib, "dbeel_writer_put", lambda *a: -1)
        with pytest.raises(Exception, match="gather-write failed"):
            _merge("device", tmp_dir, idxs, 205)
    assert _pool()["leased_bytes"] == 0
    assert _merge("device", tmp_dir, idxs, 207) == want
    assert _pool()["leased_bytes"] == 0


def test_a_declined_merge_leaves_nothing_leased(tmp_dir):
    """One equal-prefix group larger than the kernel rows: the pipeline
    declines on the data (after it has read the runs into leased
    blocks) and the single-shot path produces the output."""
    rows = pipeline_mod.max_partition_rows(2) + 40
    keys = [b"SAMEPFX_" + i.to_bytes(4, "big") for i in range(rows)]
    for r in range(2):
        write_sstable_fixture(
            tmp_dir, r * 2, [(k, b"v%d" % r, 700 + r) for k in keys]
        )
    want = _merge("native", tmp_dir, [0, 2], 201)
    declines = compaction_stats.stats()["pipeline_declines"]
    before = _pool()
    assert _merge("device", tmp_dir, [0, 2], 203) == want
    assert compaction_stats.stats()["pipeline_declines"] == declines + 1
    after = _pool()
    assert after["leases"] > before["leases"]
    assert after["leased_bytes"] == 0
    idxs = _fixed_runs(tmp_dir, 2912, first=100)
    assert _merge("device", tmp_dir, idxs, 205) == _merge(
        "native", tmp_dir, idxs, 201
    )


def test_a_wedged_thread_drops_its_blocks(tmp_dir, monkeypatch):
    """A merge that leaves with one of its threads still alive (the
    paths that leak the native handle) must not hand the blocks that
    thread may be writing to the next merge."""
    wedge = threading.Event()
    held = []

    def impl(*a, mem, threads, **kw):
        held.append(mem.array(1 << 20))
        held.append(mem.array((1 << 16,), np.uint64))
        for arr in held:
            arr.view(np.uint8).fill(0x11)  # what the wedged thread wrote
        t = threading.Thread(target=wedge.wait, daemon=True)
        threads.append(t)
        t.start()
        raise pipeline_mod._PipelineError("writer thread wedged")

    monkeypatch.setattr(pipeline_mod, "_pipeline_merge_impl", impl)
    before = _pool()
    try:
        with pytest.raises(pipeline_mod._PipelineError, match="wedged"):
            pipeline_mod.pipeline_merge([], tmp_dir, 203, False, 1)
        after = _pool()
        assert after["leased_bytes"] == 0
        assert after["retained_bytes"] <= before["retained_bytes"]
        free = {
            blk.addr
            for blocks in pipeline_mod._POOL._free.values()
            for blk in blocks
        }
        for arr in held:
            assert arr.__array_interface__["data"][0] not in free
            assert (arr.view(np.uint8) == 0x11).all()  # never given back
    finally:
        wedge.set()
    # A merge whose threads are all joined returns them.
    monkeypatch.setattr(
        pipeline_mod,
        "_pipeline_merge_impl",
        lambda *a, mem, **kw: mem.array(1 << 20) is None or None,
    )
    pipeline_mod.pipeline_merge([], tmp_dir, 205, False, 1)
    assert _pool()["leased_bytes"] == 0


# ---- SSTable.read_index_columns with destinations --------------------


@pytest.mark.parametrize("corrupt", [False, True], ids=["intact", "bit-flip"])
@pytest.mark.parametrize(
    "scratch", [False, True], ids=["fresh-bytes", "scratch-buffer"]
)
def test_read_index_columns_into_destinations(tmp_dir, scratch, corrupt):
    """The same three columns as without destinations, and the same
    refusal of a corrupt index (_verify_whole)."""
    _fixed_runs(tmp_dir, 2913, nruns=1, npr=700)
    paths = [
        f"{tmp_dir}/{file_name(0, ext)}" for ext in (DATA_FILE_EXT, INDEX_FILE_EXT)
    ]
    blobs = [open(p, "rb").read() for p in paths]
    checksums.write(
        tmp_dir,
        0,
        checksums.page_crcs(blobs[0]),
        checksums.page_crcs(blobs[1]),
        len(blobs[0]),
    )
    if corrupt:
        flipped = bytearray(blobs[1])
        flipped[len(flipped) // 2] ^= 0x10
        with open(paths[1], "wb") as f:
            f.write(flipped)
    table = SSTable(tmp_dir, 0, None)
    try:
        n = table.entry_count
        out = (
            np.full(n, 0xA5A5A5A5A5A5A5A5, np.uint64),
            np.full(n, 0xA5A5A5A5, np.uint32),
            np.full(n, 0xA5A5A5A5, np.uint32),
        )
        buf = np.full(n * 16 + 4096, 0xA5, np.uint8) if scratch else None
        if corrupt:
            with pytest.raises(Exception, match="CRC"):
                table.read_index_columns()
            with pytest.raises(Exception, match="CRC"):
                table.read_index_columns(out=out, scratch=buf)
            return
        want = table.read_index_columns()
        got = table.read_index_columns(out=out, scratch=buf)
    finally:
        table.close()
    assert all(g is o for g, o in zip(got, out))
    for w, g in zip(want, got):
        assert w.dtype == g.dtype and np.array_equal(w, g)
    assert int(want[0][-1]) + int(want[2][-1]) == len(blobs[0])
