"""Partitioned-pipeline golden tests vs the heap oracle.

The production gate only routes merges >=64MB through the pipeline
(ops/pipeline.py); here the gate is lowered so the full pipeline —
O_DIRECT reads, partition splitting, kernel dispatch, tie fixup,
native gather-writes — runs at test sizes and must produce
byte-identical outputs (data, index, bloom) to HeapMergeStrategy on
adversarial shapes.
"""

import hashlib
import os
import random

import pytest

from dbeel_tpu.ops.device_compaction import DeviceMergeStrategy
from dbeel_tpu.storage.compaction import get_strategy
from dbeel_tpu.storage.entry import file_name
from dbeel_tpu.storage.native import native_available
from dbeel_tpu.storage.sstable import SSTable

from conftest import write_sstable_fixture

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native library unavailable"
)


def _sha_triplet(d, oi):
    h = hashlib.sha256()
    for ext in ("compact_data", "compact_index", "compact_bloom"):
        p = f"{d}/{file_name(oi, ext)}"
        if os.path.exists(p):
            with open(p, "rb") as f:
                h.update(ext.encode())
                h.update(f.read())
    return h.hexdigest()


@pytest.mark.parametrize(
    "seed,kmin,kmax,nruns,npr,keep_tomb",
    [
        (0, 4, 8, 3, 300, False),  # short keys
        (1, 8, 8, 4, 400, True),  # exactly-8B keys, tombstones kept
        (2, 6, 24, 8, 500, False),  # long keys, shared prefixes, dups
        (3, 16, 16, 1, 200, False),  # single run
        (4, 12, 12, 2, 0, True),  # empty runs
        (5, 10, 40, 5, 350, False),  # wide length spread
    ],
)
def test_pipeline_byte_identical_to_heap(
    tmp_dir, monkeypatch, seed, kmin, kmax, nruns, npr, keep_tomb
):
    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    rng = random.Random(seed)
    for r in range(nruns):
        entries = {}
        for _ in range(npr):
            klen = rng.randint(kmin, kmax)
            if rng.random() < 0.3:
                k = b"PFX12345" + rng.randbytes(max(0, klen - 8))
            else:
                k = rng.randbytes(klen)
            v = (
                b""
                if rng.random() < 0.15
                else rng.randbytes(rng.randint(0, 40))
            )
            entries[k] = (v, rng.randint(100, 120))
        write_sstable_fixture(
            tmp_dir,
            r * 2,
            [(k, v, ts) for k, (v, ts) in sorted(entries.items())],
        )
    idxs = [r * 2 for r in range(nruns)]
    results = {}
    for name, oi in (("heap", 101), ("device", 103)):
        strat = get_strategy(name)
        srcs = [SSTable(tmp_dir, i, None) for i in idxs]
        res = strat.merge(srcs, tmp_dir, oi, None, keep_tomb, 1)
        for s in srcs:
            s.close()
        results[name] = (
            _sha_triplet(tmp_dir, oi),
            res.entry_count,
            res.data_size,
            res.wrote_bloom,
        )
    assert results["heap"] == results["device"], (
        f"seed {seed}: {results['heap']} != {results['device']}"
    )


def _golden_vs_heap(tmp_dir, idxs, keep_tomb=False, expect_pipeline=True):
    """Byte-identity vs the heap oracle + proof the pipeline actually
    produced the device output (a silent None fallback to the
    single-shot path would be byte-identical too, hiding a regression)."""
    from dbeel_tpu.ops import pipeline as pipeline_mod

    ran = []
    real_impl = pipeline_mod._pipeline_merge_impl

    def spy(*a, **kw):
        res = real_impl(*a, **kw)
        ran.append(res is not None)
        return res

    pipeline_mod._pipeline_merge_impl, saved = spy, real_impl
    try:
        results = {}
        for name, oi in (("heap", 101), ("device", 103)):
            strat = get_strategy(name)
            srcs = [SSTable(tmp_dir, i, None) for i in idxs]
            res = strat.merge(srcs, tmp_dir, oi, None, keep_tomb, 1)
            for s in srcs:
                s.close()
            results[name] = (
                _sha_triplet(tmp_dir, oi),
                res.entry_count,
                res.data_size,
                res.wrote_bloom,
            )
    finally:
        pipeline_mod._pipeline_merge_impl = saved
    assert results["heap"] == results["device"]
    if expect_pipeline:
        assert ran and ran[-1], "pipeline fell back to single-shot"
    return results["device"]


def _keys_from_u64(vals):
    return [int(v).to_bytes(8, "big") for v in vals]


def test_pipeline_wide_span_u32_collisions(tmp_dir, monkeypatch):
    """Partition span >= 2^32 forces the order-preserving right shift;
    keys planted within 2^shift of each other collide in the u32
    approximation and must be fixed up (and deduped) on the host."""
    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    rng = random.Random(11)
    base = []
    for _ in range(600):
        v = rng.randrange(0, 1 << 63)
        base.append(v)
        if rng.random() < 0.04:
            # sparse neighbours within 2^20 — far below the shift
            # granularity, so they collide in u32 without tripping
            # the exact-operand guard (_SHIFT_DUP_LIMIT)
            base.append(v + rng.randrange(1, 1 << 20))
    for r in range(3):
        sub = sorted(set(rng.sample(base, 500)))
        write_sstable_fixture(
            tmp_dir,
            r * 2,
            [
                (k, b"v%d" % r, 100 + r)
                for k in _keys_from_u64(sub)
            ],
        )
    _golden_vs_heap(tmp_dir, [0, 2, 4])


def test_pipeline_dense_cluster_exact_operand(tmp_dir, monkeypatch):
    """A dense sequential cluster plus one far outlier: the shift would
    collapse the cluster into one value (the _SHIFT_DUP_LIMIT guard
    keeps the exact 2-word operand), and the output must still match."""
    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    for r in range(2):
        vals = list(range(r, 4000, 2))  # dense, interleaved runs
        if r == 0:
            vals.append(1 << 62)  # outlier stretches the span
        write_sstable_fixture(
            tmp_dir,
            r * 2,
            [
                (k, b"x" * 5, 200 + r)
                for k in _keys_from_u64(sorted(vals))
            ],
        )
    _golden_vs_heap(tmp_dir, [0, 2])


def test_pipeline_tie_heavy_shared_prefixes(tmp_dir, monkeypatch):
    """~30 hot 8-byte prefixes with long keys differing past them, plus
    cross-run duplicate full keys: nearly every entry lands in a tie
    block.  Round 2 aborted such runs (_TieFallback) and re-read
    everything; round 3 must handle them inside the pipeline via the
    vectorized fixup, byte-identical to the heap oracle."""
    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    rng = random.Random(13)
    hot = [b"PF%06d" % (i * 7) for i in range(30)]
    shared = [
        rng.choice(hot) + rng.randbytes(rng.randint(4, 12))
        for _ in range(200)
    ]
    for r in range(4):
        keys = {
            rng.choice(hot) + rng.randbytes(rng.randint(4, 12))
            for _ in range(250)
        }
        keys |= set(rng.sample(shared, 120))  # cross-run duplicates
        write_sstable_fixture(
            tmp_dir,
            r * 2,
            [(k, b"v%d" % r, 300 + r) for k in sorted(keys)],
        )
    _golden_vs_heap(tmp_dir, [0, 2, 4, 6])


def test_pipeline_single_prefix_group_falls_back(tmp_dir, monkeypatch):
    """One equal-prefix group larger than the kernel rows is
    unsplittable: the pipeline must decline (None) and the single-shot
    path must still produce the oracle bytes."""
    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    rng = random.Random(19)
    for r in range(2):
        keys = sorted(
            b"ONEPREFX" + rng.randbytes(6) for _ in range(400)
        )
        write_sstable_fixture(
            tmp_dir, r * 2, [(k, b"v", 500 + r) for k in keys]
        )
    from dbeel_tpu.ops import pipeline as pipeline_mod

    monkeypatch.setattr(pipeline_mod, "_MAX_P2", 128)
    _golden_vs_heap(tmp_dir, [0, 2], expect_pipeline=False)


def test_pipeline_many_runs_wide_packing(tmp_dir, monkeypatch):
    """64 runs -> k2=64 -> 8-bit run-id packing (config-4's shape)."""
    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    rng = random.Random(17)
    for r in range(64):
        entries = {}
        for _ in range(40):
            k = rng.randbytes(rng.randint(8, 16))
            entries[k] = (rng.randbytes(rng.randint(0, 20)), 400 + r)
        write_sstable_fixture(
            tmp_dir,
            r * 2,
            [(k, v, ts) for k, (v, ts) in sorted(entries.items())],
        )
    _golden_vs_heap(tmp_dir, [r * 2 for r in range(64)])


def _plain_model():
    """The benchmark's numpy model of a merge (harness/varlen_runs.py),
    which the wide cell's ``correct`` holds every merge on the chip
    to."""
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark.harness import varlen_runs

    return varlen_runs.model


@pytest.mark.parametrize(
    "repeats,keep_tomb",
    [(False, False), (True, False), (True, True)],
    ids=["distinct-keys", "repeated-keys-tombstones-dropped",
         "repeated-keys-tombstones-kept"],
)
def test_wide_64_variable_length_runs_vs_heap_and_model(
    tmp_dir, monkeypatch, repeats, keep_tomb
):
    """BASELINE configs[3]'s shape at test size THROUGH THE PIPELINE
    (test_device_merge's wide test takes the single-shot path at its
    size): 64 overlapping runs of 16 B keys with values of 8-159 B, run
    r's timestamps above run r-1's.  The benchmark's cell draws uniform
    keys, which never repeat; with ``repeats`` every key lives in about
    four runs and 15 % of the entries are tombstones, so newest-wins and
    the tombstone drop are shown.  Triplet byte-identical to the heap
    oracle's, and the numpy model's entry count and data-file length
    equal to both."""
    import numpy as np

    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    rng = random.Random(2800 + repeats)
    nruns, npr = 64, 120
    pool = [rng.randbytes(16) for _ in range(nruns * npr // 4)]
    rows = []  # (key, ts, full size, tombstone) of every entry written
    for r in range(nruns):
        keys = (
            rng.sample(pool, npr)
            if repeats
            else [rng.randbytes(16) for _ in range(npr)]
        )
        entries = []
        for k in sorted(keys):
            tomb = repeats and rng.random() < 0.15
            v = b"" if tomb else rng.randbytes(rng.randint(8, 159))
            entries.append((k, v, 1000 * r + rng.randrange(1000)))
        write_sstable_fixture(tmp_dir, r * 2, entries)
        rows += [(k, ts, 32 + len(v), v == b"") for k, v, ts in entries]
    _sha, count, data_size, wrote_bloom = _golden_vs_heap(
        tmp_dir, [r * 2 for r in range(nruns)], keep_tomb=keep_tomb
    )
    assert wrote_bloom
    keys = np.frombuffer(b"".join(k for k, *_ in rows), np.uint8)
    model = _plain_model()(
        keys.reshape(len(rows), 16),
        np.array([ts for _k, ts, _f, _t in rows], dtype=np.int64),
        np.array([f for _k, _ts, f, _t in rows], dtype=np.uint32),
        None if keep_tomb else np.array([t for *_, t in rows]),
    )
    assert model == (count, data_size)
    if repeats:
        assert count < len(rows) // 2  # most entries were shadowed
    else:
        assert count == len(rows)


def test_pipeline_mesh_byte_identical(tmp_dir, monkeypatch):
    """The distributed strategy's big-merge path: the SAME partitioned
    pipeline with the launch-batch axis sharded over an 8-device mesh
    (pure keyspace data parallelism — no cross-device exchange).
    Output must be byte-identical to the heap oracle, and the pipeline
    (not the sample-sort single-shot path) must have produced it."""
    import numpy as np

    from dbeel_tpu.ops import pipeline as pipeline_mod
    from dbeel_tpu.parallel.dist_merge import DistributedMergeStrategy
    from dbeel_tpu.parallel.mesh import shard_mesh

    rng = random.Random(23)
    for r in range(6):
        entries = {}
        for _ in range(700):
            k = rng.randbytes(rng.randint(8, 20))
            entries[k] = (rng.randbytes(rng.randint(0, 30)), 600 + r)
        write_sstable_fixture(
            tmp_dir,
            r * 2,
            [(k, v, ts) for k, (v, ts) in sorted(entries.items())],
        )
    idxs = [r * 2 for r in range(6)]

    ran = []
    real_impl = pipeline_mod._pipeline_merge_impl

    def spy(*a, **kw):
        res = real_impl(*a, **kw)
        # a[-1] / kw["mesh"]: the mesh must actually be threaded in.
        mesh_arg = kw.get("mesh", a[5] if len(a) > 5 else None)
        ran.append((res is not None, mesh_arg))
        return res

    monkeypatch.setattr(pipeline_mod, "_pipeline_merge_impl", spy)

    strat = DistributedMergeStrategy(shard_mesh(8))
    monkeypatch.setattr(type(strat), "PIPELINE_MIN_BYTES", 0)
    results = {}
    for name, runner, oi in (
        ("heap", get_strategy("heap"), 101),
        ("mesh", strat, 103),
    ):
        srcs = [SSTable(tmp_dir, i, None) for i in idxs]
        res = runner.merge(srcs, tmp_dir, oi, None, False, 1)
        for s in srcs:
            s.close()
        results[name] = (
            _sha_triplet(tmp_dir, oi),
            res.entry_count,
            res.data_size,
        )
    assert results["heap"] == results["mesh"]
    assert ran and ran[-1][0], "mesh pipeline fell back"
    assert ran[-1][1] is not None and np.prod(
        ran[-1][1].devices.shape
    ) == 8, "pipeline did not receive the 8-device mesh"


def test_rid_pack_roundtrip():
    import numpy as np

    from dbeel_tpu.ops import bitonic

    for k2 in (1, 2, 4, 8, 16, 64, 256):
        bits = bitonic.rid_pack_bits(k2)
        assert k2 <= (1 << bits) <= 2 ** 16
        rng = random.Random(k2)
        n = 101
        rids = np.array(
            [rng.randrange(k2) for _ in range(n)], dtype=np.uint32
        )
        per = 32 // bits
        pad = (-n) % per
        padded = np.concatenate(
            [rids, np.full(pad, (1 << bits) - 1, np.uint32)]
        )
        shifts = np.arange(per, dtype=np.uint32) * np.uint32(bits)
        words = (
            (padded.reshape(-1, per) << shifts[None, :])
            .sum(axis=1)
            .astype(np.uint32)
        )
        out = bitonic.unpack_rids(words, bits, n)
        assert (out == rids).all()


def _write_random_runs(d, seed, nruns=4, npr=600):
    rng = random.Random(seed)
    for r in range(nruns):
        entries = {}
        for _ in range(npr):
            entries[rng.randbytes(rng.randint(8, 16))] = (
                rng.randbytes(rng.randint(0, 24)),
                900 + r,
            )
        write_sstable_fixture(
            d,
            r * 2,
            [(k, v, ts) for k, (v, ts) in sorted(entries.items())],
        )
    return [r * 2 for r in range(nruns)]


def _merge_with_bar(name, d, idxs, oi, bloom_min_size):
    srcs = [SSTable(d, i, None) for i in idxs]
    try:
        res = get_strategy(name).merge(
            srcs, d, oi, None, False, bloom_min_size
        )
    finally:
        for s in srcs:
            s.close()
    return _sha_triplet(d, oi), res.entry_count, res.data_size, res.wrote_bloom


def _merge(name, d, idxs, oi):
    return _merge_with_bar(name, d, idxs, oi, 1)[0]


def _both_launch_slots_are_free():
    """Every permit is back: both can be taken without waiting."""
    from dbeel_tpu.ops.pipeline import _LAUNCH_SLOTS

    assert _LAUNCH_SLOTS.acquire(timeout=0)
    assert _LAUNCH_SLOTS.acquire(timeout=0)
    _LAUNCH_SLOTS.release()
    _LAUNCH_SLOTS.release()


def test_launch_slots_bound_every_merge_of_the_process(
    tmp_dir, monkeypatch
):
    """_MAX_KP is sized for two launches on the chip, and one node runs
    every shard's merges at once in threads of one process: launches
    dispatched and not yet read back are counted here at the kernel
    and at the read-back, over two concurrent merges of several
    launches each, and never exceed two; every permit comes back."""
    import threading
    import time

    import numpy as np

    from dbeel_tpu.ops import bitonic
    from dbeel_tpu.ops import pipeline as pipeline_mod

    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    # Several launches per merge at test sizes.
    monkeypatch.setattr(pipeline_mod, "_MULTIBATCH_MIN_ROWS", 0)
    lock = threading.Lock()
    seen = {"unread": 0, "peak": 0, "launches": 0}

    class Unread:
        def __init__(self, out):
            self.out = out

        def __array__(self, dtype=None, copy=None):
            words = np.asarray(self.out)
            time.sleep(0.02)  # a read-back slower than a dispatch
            with lock:
                seen["unread"] -= 1
            return words

    def counting(kernel):
        def call(dev, counts, pack_bits):
            with lock:
                seen["unread"] += 1
                seen["launches"] += 1
                seen["peak"] = max(seen["peak"], seen["unread"])
            return Unread(kernel(dev, counts, pack_bits))

        return call

    for name in (
        "merge_runs_prefix32_packed_batch_kernel",
        "merge_runs_prefix64_packed_batch_kernel",
    ):
        monkeypatch.setattr(bitonic, name, counting(getattr(bitonic, name)))

    dirs = [os.path.join(tmp_dir, n) for n in ("a", "b")]
    idxs = []
    for seed, d in enumerate(dirs):
        os.makedirs(d)
        idxs.append(_write_random_runs(d, 40 + seed))
    got, errors = {}, []

    def one(d, ix):
        try:
            got[d] = _merge("device", d, ix, 103)
        except BaseException as e:  # surfaced below
            errors.append(e)

    threads = [
        threading.Thread(target=one, args=(d, ix))
        for d, ix in zip(dirs, idxs)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, errors
    for d, ix in zip(dirs, idxs):
        assert got[d] == _merge("heap", d, ix, 101)
    assert seen["launches"] >= 4, seen  # two or more per merge
    assert seen["peak"] <= 2 and seen["unread"] == 0, seen
    _both_launch_slots_are_free()


def test_launch_slots_come_back_from_a_failed_merge(tmp_dir, monkeypatch):
    """A launch that raises (the chip out of memory, a compile refused)
    fails its merge; its permit must not stay taken, or every later
    big merge of the process would wait for ever."""
    from dbeel_tpu.ops import bitonic

    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)

    def refuse(dev, counts, pack_bits):
        raise RuntimeError("RESOURCE_EXHAUSTED: injected")

    for name in (
        "merge_runs_prefix32_packed_batch_kernel",
        "merge_runs_prefix64_packed_batch_kernel",
    ):
        monkeypatch.setattr(bitonic, name, refuse)
    idxs = _write_random_runs(tmp_dir, 50)
    with pytest.raises(RuntimeError, match="injected"):
        _merge("device", tmp_dir, idxs, 103)
    _both_launch_slots_are_free()


# ---- a failure in each box, and the threads of a merge ---------------

OUTPUT_EXTS = ("compact_data", "compact_index", "compact_bloom", "compact_sums")


def _fail_a_reader(m, pipeline_mod, bitonic, lib):
    real = pipeline_mod._read_run

    def read(lib, source, *rest):
        if source.data_path.endswith(file_name(2, "data")):
            raise OSError("injected: read failed")
        return real(lib, source, *rest)

    m.setattr(pipeline_mod, "_read_run", read)


def _fail_the_launcher(m, pipeline_mod, bitonic, lib):
    def plan(*a):
        raise RuntimeError("injected: operand")

    m.setattr(pipeline_mod, "_plan_operand", plan)


def _fail_the_downloader(m, pipeline_mod, bitonic, lib):
    class Unreadable:
        def __array__(self, dtype=None, copy=None):
            raise RuntimeError("injected: read-back")

    for name in (
        "merge_runs_prefix32_packed_batch_kernel",
        "merge_runs_prefix64_packed_batch_kernel",
    ):
        m.setattr(bitonic, name, lambda dev, counts, bits: Unreadable())


def _fail_the_decode(m, pipeline_mod, bitonic, lib):
    m.setattr(lib, "dbeel_pipe_decode", lambda *a: -1)


def _fail_the_close(m, pipeline_mod, bitonic, lib):
    real = lib.dbeel_writer_close2

    def close(*a):
        real(*a)  # the files are closed and the handle freed
        return -1

    m.setattr(lib, "dbeel_writer_close2", close)


@pytest.mark.parametrize(
    "inject,error,message",
    [
        (_fail_a_reader, OSError, "injected: read failed"),
        (_fail_the_launcher, RuntimeError, "injected: operand"),
        (_fail_the_downloader, RuntimeError, "injected: read-back"),
        (_fail_the_decode, Exception, "decode mismatch"),
        (_fail_the_close, Exception, "writer close failed"),
    ],
    ids=["reader", "launcher", "downloader", "decode", "close"],
)
def test_a_failure_in_any_box_fails_the_merge_and_leaves_nothing(
    tmp_dir, monkeypatch, inject, error, message
):
    """Whichever box fails, on whichever thread: the merge raises that
    error on the calling thread within seconds, no file of the output
    is left, every block is back in the pool, both launch permits can
    be taken, no span is open, and the next merge of the same inputs
    is whole.  (The writer, the bloom write and a refused launch:
    test_a_failed_writer_leaves_nothing_leased,
    test_a_failed_bloom_write_..., test_launch_slots_come_back_...)"""
    import time

    from dbeel_tpu.ops import bitonic
    from dbeel_tpu.ops import pipeline as pipeline_mod
    from dbeel_tpu.storage import native
    from dbeel_tpu.storage.compaction import compaction_stats

    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    idxs = _write_random_runs(tmp_dir, 90)
    want = _merge("native", tmp_dir, idxs, 101)
    before = _pipeline_stages()
    t0 = time.monotonic()
    with monkeypatch.context() as m:
        inject(m, pipeline_mod, bitonic, native.require())
        with pytest.raises(error, match=message):
            _merge("device", tmp_dir, idxs, 103)
    assert time.monotonic() - t0 < 20
    _one_merge_closed(*before)
    for ext in OUTPUT_EXTS:
        assert not os.path.exists(f"{tmp_dir}/{file_name(103, ext)}"), ext
    assert compaction_stats.stats()["pool"]["leased_bytes"] == 0
    _both_launch_slots_are_free()
    assert not _pipeline_threads_alive()
    before = _pipeline_stages()
    assert _merge("device", tmp_dir, idxs, 105) == want
    _one_merge_closed(*before)
    assert compaction_stats.stats()["pool"]["leased_bytes"] == 0


def _pipeline_threads_alive():
    import threading

    return sorted(
        t.name
        for t in threading.enumerate()
        if t.name.startswith("dbeel-pipeline-")
    )


def test_a_merge_starts_its_named_threads_and_leaves_none(
    tmp_dir, monkeypatch
):
    """The boxes of ops/pipeline.py's docstring, by the threads that
    run them: two readers, upload, download, writer, bloom, close —
    those and no other, and none outlives the merge."""
    import threading

    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    idxs = _write_random_runs(tmp_dir, 91)
    want = _merge("native", tmp_dir, idxs, 101)
    assert _merge("device", tmp_dir, idxs, 103) == want  # compiled
    started = []
    real_start = threading.Thread.start

    def start(self):
        started.append(self.name)
        real_start(self)

    with monkeypatch.context() as m:
        m.setattr(threading.Thread, "start", start)
        assert _merge("device", tmp_dir, idxs, 105) == want
    assert sorted(started) == [
        "dbeel-pipeline-bloom",
        "dbeel-pipeline-close",
        "dbeel-pipeline-download",
        "dbeel-pipeline-read_0",
        "dbeel-pipeline-read_1",
        "dbeel-pipeline-upload",
        "dbeel-pipeline-writer",
    ]
    assert not _pipeline_threads_alive()


# ---- stage spans and stage-second counters (ops/spans.py) ------------

# The calling thread's stages: sequential, so they partition the outer
# span ``merge``.  ``throttle`` joins them where a throttle is attached.
CALLER_STAGES = (
    "read_stage", "plan", "wait_device", "decode", "wait_writer",
    "bloom", "close_wait", "sidecar",
)
# The other threads' stages, which overlap those.
THREAD_STAGES = (
    "read_run", "operand", "slot_wait", "h2d_dispatch", "d2h",
    "gather_write", "fsync", "bloom_hash", "bloom_set",
)
# On the calling thread INSIDE one of its stages (``read_stage``,
# ``decode``): counted beside the caller's sum, never in it.
NESTED_STAGES = ("stage_prefixes", "tie_fixup")


def _pipeline_stages():
    """(seconds, count) per stage so far in this process, and the sum
    of the pipeline's outer spans."""
    from dbeel_tpu.storage.compaction import compaction_stats

    block = compaction_stats.stats()
    stages = block["stages"].get("pipeline", {})
    return (
        {k: (v["s"], v["n"]) for k, v in stages.items()},
        block["pipeline_wall_s"],
    )


def _stage_deltas(before, after):
    return {
        k: (s - before.get(k, (0.0, 0))[0], n - before.get(k, (0.0, 0))[1])
        for k, (s, n) in after.items()
    }


def _one_merge_closed(before, wall_before):
    """Since ``before`` one pipeline merge ran, and however it ended
    its calling thread's stages sum to its wall.  Returns the deltas."""
    after, wall_after = _pipeline_stages()
    got = _stage_deltas(before, after)
    wall = wall_after - wall_before
    caller = sum(got.get(n, (0.0, 0))[0] for n in CALLER_STAGES)
    assert got["merge"][1] == 1
    assert wall > 0 and abs(caller - wall) <= 0.02 * wall, got
    return got


def test_caller_stages_partition_the_pipelines_wall(tmp_dir, monkeypatch):
    """After one pipeline merge every stage of the pipeline has been
    counted, and the calling thread's stages sum to the merge's wall:
    "where did the wall go" has one exact answer."""
    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    idxs = _write_random_runs(tmp_dir, 60)
    before, wall_before = _pipeline_stages()
    assert _merge("device", tmp_dir, idxs, 103) == _merge(
        "heap", tmp_dir, idxs, 101
    )
    after, wall_after = _pipeline_stages()
    got = _stage_deltas(before, after)
    wall = wall_after - wall_before
    for name in CALLER_STAGES + THREAD_STAGES + NESTED_STAGES:
        assert got[name][1] >= 1, (name, got)
        assert got[name][0] >= 0.0
    assert got["merge"][1] == 1 and got["merge"][0] == pytest.approx(wall)
    assert got["read_run"][1] == len(idxs)
    assert got["d2h"][1] == got["h2d_dispatch"][1]
    assert got["gather_write"][1] == got["decode"][1]
    # The bloom thread hashed every partition the writer wrote, and
    # set the bits once.
    assert got["bloom_hash"][1] == got["gather_write"][1]
    assert got["bloom_set"][1] == 1
    assert "throttle" not in got  # none attached
    caller = sum(got[name][0] for name in CALLER_STAGES)
    assert wall > 0 and abs(caller - wall) <= 0.02 * wall, (caller, wall)


def test_a_failed_merge_leaves_no_span_open(tmp_dir, monkeypatch):
    """A merge that raises mid-way (a refused launch) still closes
    every stage it opened: its own stages sum to its wall, and so do
    the next merge's."""
    from dbeel_tpu.ops import bitonic

    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    idxs = _write_random_runs(tmp_dir, 61)

    def refuse(dev, counts, pack_bits):
        raise RuntimeError("RESOURCE_EXHAUSTED: injected")

    before = _pipeline_stages()
    with monkeypatch.context() as m:
        for name in (
            "merge_runs_prefix32_packed_batch_kernel",
            "merge_runs_prefix64_packed_batch_kernel",
        ):
            m.setattr(bitonic, name, refuse)
        with pytest.raises(RuntimeError, match="injected"):
            _merge("device", tmp_dir, idxs, 103)
    _one_merge_closed(*before)
    before = _pipeline_stages()
    assert _merge("device", tmp_dir, idxs, 105) == _merge(
        "heap", tmp_dir, idxs, 101
    )
    _one_merge_closed(*before)


def test_shape_counters_rise_by_one_merges_shape(tmp_dir, monkeypatch):
    """``get_stats.compaction.shape`` across one pipeline merge of a
    known shape: every launch and its rows, the partitions, the runs
    and entries that went in, the entries the host tie fix-up took;
    and ``tie_fixup`` is nested in ``decode``: the caller's stages
    still sum to the merge's wall without it."""
    from dbeel_tpu.ops import bitonic
    from dbeel_tpu.storage.compaction import PIPELINE_SHAPE, compaction_stats

    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    nruns = 5
    idxs = _write_random_runs(tmp_dir, 62, nruns=nruns, npr=700)
    # Planted ties: ten keys under one 8-byte prefix in each of two
    # runs, so the device order leaves at least those twenty tied.
    planted = [b"TIEDTIED" + bytes([i]) for i in range(10)]
    for r in (1, 3):
        write_sstable_fixture(
            tmp_dir, 2 * nruns + 2 * r,
            [(k, b"v%d" % r, 950 + r) for k in planted],
        )
    idxs += [2 * nruns + 2, 2 * nruns + 6]
    srcs = [SSTable(tmp_dir, i, None) for i in idxs]
    entries_in = sum(s.entry_count for s in srcs)
    for s in srcs:
        s.close()
    launched = []  # operand shape of every launch
    for name in (
        "merge_runs_prefix32_packed_batch_kernel",
        "merge_runs_prefix64_packed_batch_kernel",
    ):
        real = getattr(bitonic, name)

        def spy(vals, counts, pack_bits, real=real):
            launched.append(vals.shape)
            return real(vals, counts, pack_bits)

        monkeypatch.setattr(bitonic, name, spy)

    before_shape = compaction_stats.stats()["shape"]
    assert set(before_shape) == set(PIPELINE_SHAPE)
    before = _pipeline_stages()
    assert _merge("device", tmp_dir, idxs, 103) == _merge(
        "heap", tmp_dir, idxs, 101
    )
    got = _one_merge_closed(*before)
    after_shape = compaction_stats.stats()["shape"]
    rose = {k: after_shape[k] - before_shape[k] for k in PIPELINE_SHAPE}
    assert launched and rose["launches"] == len(launched)
    assert rose["launches"] == got["h2d_dispatch"][1] == got["d2h"][1]
    assert rose["rows_launched"] == sum(
        j * k * p for j, k, p, *_words in launched
    )
    assert rose["rows_launched"] >= rose["rows_real"] == entries_in
    assert rose["runs_in"] == len(idxs)
    assert rose["partitions"] == got["decode"][1] >= 1
    assert 2 * len(planted) <= rose["tie_entries"] <= entries_in
    # One nested span per partition that held entries, inside decode.
    assert got["tie_fixup"][1] == got["gather_write"][1]
    assert 0.0 <= got["tie_fixup"][0] <= got["decode"][0]


# ---- the two-phase bloom build (bloom thread) -------------------------


def _bloom_thread_alive():
    import threading

    return any(
        t.name == "dbeel-pipeline-bloom" for t in threading.enumerate()
    )


@pytest.mark.parametrize(
    "num_bits,num_hashes",
    [
        (64, 7),  # the smallest filter: every step wraps
        (10007, 7),  # a prime: steps of h2 mod m, some of them 0
        (95850584, 7),  # what 10M keys get
        ((1 << 32) + 15, 7),  # wider than a hash: nothing is reduced
        (4099, 1),
        (1 << 20, 13),
    ],
)
def test_two_phase_bloom_sets_the_bits_add_batch_sets(num_bits, num_hashes):
    """``dbeel_bloom_hash_gather`` + ``dbeel_bloom_set_hashes`` (the
    pipeline's bloom thread) against ``dbeel_bloom_add_batch`` (the
    native merge, the single-shot path) and ``BloomFilter.add_batch``
    (numpy): the same bitmap, bit for bit, for keys of 1-40 bytes
    scattered over four run buffers and met in any order."""
    import ctypes

    import numpy as np

    from dbeel_tpu.storage import native
    from dbeel_tpu.storage.bloom import _SEED1, _SEED2, BloomFilter
    from dbeel_tpu.storage.entry import ENTRY_HEADER_SIZE

    lib = native.require()
    rng = random.Random(num_bits)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u64p = ctypes.POINTER(ctypes.c_uint64)

    # Records as the runs hold them: header, key, value.
    runs, where = [], []
    for r in range(4):
        buf = bytearray()
        for _ in range(rng.randint(60, 90)):
            key = rng.randbytes(rng.randint(1, 40))
            where.append((r, len(buf), key))
            buf += bytes(ENTRY_HEADER_SIZE) + key
            buf += rng.randbytes(rng.randint(0, 30))
        runs.append(np.frombuffer(bytes(buf), dtype=np.uint8))
    rng.shuffle(where)
    n = len(where)
    src_run = np.array([w[0] for w in where], dtype=np.uint32)
    src_off = np.array([w[1] for w in where], dtype=np.uint64)
    key_size = np.array([len(w[2]) for w in where], dtype=np.uint32)

    def bitmap():
        # Whole u64 words, so three sparse 512 MB bitmaps compare by
        # their nonzero words and their untouched pages stay untouched.
        return np.zeros(-(-num_bits // 64) * 8, dtype=np.uint8)

    def words(bits):
        w = bits.view(np.uint64)
        at = np.flatnonzero(w)
        return at.tolist(), w[at].tolist()

    two_phase = bitmap()
    run_ptrs = (u8p * len(runs))(*[r.ctypes.data_as(u8p) for r in runs])
    pairs = np.empty(2 * n, dtype=np.uint32)
    # In two calls, as the bloom thread makes one a partition.
    for lo, hi in ((0, n // 3), (n // 3, n)):
        lib.dbeel_bloom_hash_gather(
            run_ptrs,
            src_run[lo:hi].ctypes.data_as(u32p),
            src_off[lo:hi].ctypes.data_as(u64p),
            key_size[lo:hi].ctypes.data_as(u32p),
            hi - lo,
            ENTRY_HEADER_SIZE,
            _SEED1,
            _SEED2,
            pairs[2 * lo :].ctypes.data_as(u32p),
        )
    lib.dbeel_bloom_set_hashes(
        two_phase.ctypes.data_as(u8p), num_bits, num_hashes,
        pairs.ctypes.data_as(u32p), n,
    )

    one_call = bitmap()
    data = np.concatenate(runs)
    base = np.cumsum([0] + [r.size for r in runs[:-1]]).astype(np.uint64)
    key_at = base[src_run] + src_off + np.uint64(ENTRY_HEADER_SIZE)
    lib.dbeel_bloom_add_batch(
        one_call.ctypes.data_as(u8p),
        ctypes.c_uint64(num_bits),
        ctypes.c_uint32(num_hashes),
        data.ctypes.data_as(u8p),
        key_at.ctypes.data_as(u64p),
        key_size.ctypes.data_as(u32p),
        ctypes.c_uint64(n),
        ctypes.c_uint32(_SEED1),
        ctypes.c_uint32(_SEED2),
    )

    numpy_filter = BloomFilter(num_bits, num_hashes)
    assert numpy_filter.num_bits == num_bits
    numpy_filter.bits = bitmap()
    numpy_filter.add_batch([w[2] for w in where])

    got = words(two_phase)
    assert got[0], "no bit set"
    assert got == words(one_call)
    assert got == words(numpy_filter.bits)


@pytest.mark.parametrize(
    "bar,hashed,wrote",
    [
        ("under_the_output", True, True),
        ("between_output_and_input", True, False),
        ("over_the_input", False, False),
    ],
)
def test_bloom_is_written_iff_the_output_passes_the_bar(
    tmp_dir, monkeypatch, bar, hashed, wrote
):
    """Four runs of the same keys merge to a quarter of their bytes.
    The bloom thread hashes speculatively wherever the INPUT passes
    ``bloom_min_size``; the filter is written only where the OUTPUT
    does, as every other strategy decides it."""
    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    rng = random.Random(70)
    keys = sorted({rng.randbytes(rng.randint(8, 16)) for _ in range(500)})
    idxs = []
    for r in range(4):
        write_sstable_fixture(
            tmp_dir, r * 2, [(k, b"v" * 20, 900 + r) for k in keys]
        )
        idxs.append(r * 2)
    out_bytes = sum(16 + len(k) + 20 for k in keys)
    bloom_min_size = {
        "under_the_output": out_bytes,
        "between_output_and_input": out_bytes + 1,
        "over_the_input": 4 * out_bytes + 1,
    }[bar]
    heap = _merge_with_bar("heap", tmp_dir, idxs, 101, bloom_min_size)
    before = _pipeline_stages()
    device = _merge_with_bar("device", tmp_dir, idxs, 103, bloom_min_size)
    got = _one_merge_closed(*before)
    assert device == heap
    assert device[1:] == (len(keys), out_bytes, wrote)
    assert os.path.exists(
        f"{tmp_dir}/{file_name(103, 'compact_bloom')}"
    ) == wrote
    written = got["gather_write"][1]
    assert written >= 1
    assert got.get("bloom_hash", (0.0, 0))[1] == (written if hashed else 0)
    assert got.get("bloom_set", (0.0, 0))[1] == (1 if wrote else 0)
    assert not _bloom_thread_alive()


def test_a_failed_bloom_write_fails_the_merge_and_leaves_no_file(
    tmp_dir, monkeypatch
):
    """The bloom thread's set phase meets a full disk: the error is
    the merge's (re-raised on the calling thread), no file of the
    triplet stays behind looking complete, no bloom thread lives on,
    and the next merge is whole."""
    import errno

    from dbeel_tpu.ops import pipeline as pipeline_mod

    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    idxs = _write_random_runs(tmp_dir, 62)

    def full_disk(dir_path, output_index, bloom):
        # As _write_bloom fails: the file is there, partly written.
        path = f"{dir_path}/{file_name(output_index, 'compact_bloom')}"
        with open(path, "wb") as f:
            f.write(b"half")
        raise OSError(errno.ENOSPC, "injected: no space left on device")

    before = _pipeline_stages()
    with monkeypatch.context() as m:
        m.setattr(pipeline_mod, "_write_bloom", full_disk)
        with pytest.raises(OSError, match="injected") as failed:
            _merge("device", tmp_dir, idxs, 103)
    assert failed.value.errno == errno.ENOSPC
    got = _one_merge_closed(*before)
    assert got["bloom_set"][1] == 1  # it got as far as the set phase
    for ext in ("compact_data", "compact_index", "compact_bloom"):
        assert not os.path.exists(f"{tmp_dir}/{file_name(103, ext)}"), ext
    assert not _bloom_thread_alive()
    before = _pipeline_stages()
    assert _merge("device", tmp_dir, idxs, 105) == _merge(
        "heap", tmp_dir, idxs, 101
    )
    _one_merge_closed(*before)
    assert not _bloom_thread_alive()


def test_two_merges_at_once_both_leave_spans_in_one_profile(
    tmp_dir, monkeypatch
):
    """A 2-shard node runs two pipeline merges at once in one process,
    and JAX allows one profile at a time: the spans only annotate
    whatever profile is running, so both merges succeed inside it and
    its host planes hold ``dbeel.pipeline.*`` events of both, each with
    its merge's id."""
    import glob
    import threading

    import jax
    from jax.profiler import ProfileData

    monkeypatch.setattr(DeviceMergeStrategy, "PIPELINE_MIN_BYTES", 0)
    dirs = [os.path.join(tmp_dir, n) for n in ("a", "b")]
    idxs = []
    for seed, d in enumerate(dirs):
        os.makedirs(d)
        idxs.append(_write_random_runs(d, 70 + seed))
    got, errors = {}, []

    def one(d, ix):
        try:
            got[d] = _merge("device", d, ix, 103)
        except BaseException as e:  # surfaced below
            errors.append(e)

    threads = [
        threading.Thread(target=one, args=(d, ix))
        for d, ix in zip(dirs, idxs)
    ]
    trace_dir = os.path.join(tmp_dir, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(100)
    finally:
        jax.profiler.stop_trace()
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    for d, ix in zip(dirs, idxs):
        assert got[d] == _merge("heap", d, ix, 101)

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    by_merge: dict = {}  # merge id -> {event name: lines it is on}
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if not ev.name.startswith("dbeel.pipeline."):
                    continue
                stats = dict(ev.stats)
                assert ev.duration_ns >= 0 and "merge" in stats, ev.name
                by_merge.setdefault(stats["merge"], {}).setdefault(
                    ev.name[len("dbeel.pipeline."):], set()
                ).add((plane.name, li))
    assert len(by_merge) == 2, by_merge
    for events in by_merge.values():
        assert set(events) >= set(
            CALLER_STAGES + THREAD_STAGES + NESTED_STAGES + ("merge",)
        )
        # One merge's caller stages share its thread's line; the
        # upload, download and writer threads have lines of their own.
        caller_line = events["merge"]
        assert len(caller_line) == 1
        for name in CALLER_STAGES + NESTED_STAGES:
            assert events[name] == caller_line, name
        for name in ("operand", "d2h", "gather_write"):
            assert not events[name] & caller_line, name


def test_the_program_counts_its_own_compilations():
    """``get_stats.compaction.compiles``: an operator sees a node
    compiling without the benchmark's listener beside it."""
    import numpy as np

    from dbeel_tpu import device
    from dbeel_tpu.ops import bitonic
    from dbeel_tpu.storage.compaction import compaction_stats

    device.acquire()
    before = compaction_stats.stats()
    # A launch shape no merge of this suite asks for; the suite runs
    # with the compilation cache off.
    vals = np.full((3, 2, 8), 0xFFFFFFFF, dtype=np.uint32)
    vals[:, :, :2] = [[1, 5], [2, 3]]
    counts = np.full((3, 2), 2, dtype=np.uint32)
    out = bitonic.merge_runs_prefix32_packed_batch_kernel(vals, counts, 1)
    assert bitonic.unpack_rids(np.asarray(out)[0], 1, 4).tolist() == [
        0, 1, 1, 0,
    ]
    after = compaction_stats.stats()
    assert after["compiles"] >= before["compiles"] + 1
    assert after["compile_s"] > before["compile_s"]
    for key in ("compile_cache_hits", "compile_cache_misses"):
        assert after[key] >= before[key] >= 0


def test_compaction_module_and_its_stats_never_import_jax():
    """``--processes`` shards export the same block and never touch
    JAX: the span helper lives in ops/, the counters in storage/."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from dbeel_tpu.storage.compaction import compaction_stats\n"
        "compaction_stats.note_stage('pipeline', 'decode', 0.5)\n"
        "b = compaction_stats.stats()\n"
        "assert b['stages'] == {'pipeline': {'decode': {'s': 0.5, 'n': 1}}}\n"
        "assert b['pipeline_wall_s'] == 0.0 and b['compiles'] == 0\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, capture_output=True,
        text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr[-2000:]
