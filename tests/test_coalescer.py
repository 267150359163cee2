"""Coalesced compaction: concurrent shard merges share ONE batched
device launch and stay byte-identical to the oracle."""

import asyncio
import hashlib
import os

from dbeel_tpu.server.coalescer import (
    CoalescedDeviceMergeStrategy,
    CompactionCoalescer,
)
from dbeel_tpu.storage.compaction import HeapMergeStrategy
from dbeel_tpu.storage.lsm_tree import LSMTree

from conftest import run


async def _fill(tree, salt):
    for i in range(600):
        await tree.set_with_timestamp(
            f"{salt}-key{i % 250:05}".encode(),
            f"val{i}".encode(),
            1000 + i,
        )
    await tree.flush()


def _hashes(d):
    out = {}
    for f in sorted(os.listdir(d)):
        if f.endswith((".data", ".index")):
            with open(os.path.join(d, f), "rb") as fh:
                out[f] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_concurrent_compactions_coalesce_into_one_launch(tmp_dir):
    async def main():
        coalescer = CompactionCoalescer(window_s=0.05)
        trees = []
        for t in range(4):
            tree = LSMTree.open_or_create(
                f"{tmp_dir}/shard{t}",
                capacity=300,
                strategy=CoalescedDeviceMergeStrategy(coalescer),
            )
            await _fill(tree, f"s{t}")
            trees.append(tree)

        # 4 "shards" compact concurrently → one batched launch.
        async def compact(tree):
            idx = [i for i, _ in tree.sstable_indices_and_sizes()]
            await tree.compact(idx, max(idx) + 1, keep_tombstones=False)

        await asyncio.gather(*[compact(t) for t in trees])
        assert coalescer.launches == 1, coalescer.launches
        assert coalescer.jobs_coalesced == 4

        # Byte-identical to the heap oracle per shard.
        for t, tree in enumerate(trees):
            ref = LSMTree.open_or_create(
                f"{tmp_dir}/ref{t}",
                capacity=300,
                strategy=HeapMergeStrategy(),
            )
            await _fill(ref, f"s{t}")
            idx = [i for i, _ in ref.sstable_indices_and_sizes()]
            await ref.compact(idx, max(idx) + 1, keep_tombstones=False)
            assert _hashes(tree.dir_path) == _hashes(ref.dir_path)
            ref.close()
            tree.close()

    run(main(), timeout=120)


def test_single_job_still_works(tmp_dir):
    async def main():
        tree = LSMTree.open_or_create(
            f"{tmp_dir}/solo",
            capacity=300,
            strategy=CoalescedDeviceMergeStrategy(
                CompactionCoalescer(window_s=0.01)
            ),
        )
        await _fill(tree, "solo")
        idx = [i for i, _ in tree.sstable_indices_and_sizes()]
        await tree.compact(idx, max(idx) + 1, keep_tombstones=False)
        assert await tree.get(b"solo-key00001") is not None
        tree.close()

    run(main(), timeout=60)


def test_pack_jobs_vmap_shape_and_dryrun_parity(tmp_dir):
    """The vmap-ready packing (ISSUE 15): pack_jobs pads every job to
    one common pow2 (K, P) stack — the single compiled batch shape —
    and the coalesced permutation per job equals the
    DeviceMergeStrategy twin's (executed on the tests' CPU backend)."""
    import numpy as np

    from dbeel_tpu.ops.device_compaction import DeviceMergeStrategy
    from dbeel_tpu.server.coalescer import pack_jobs
    from dbeel_tpu.storage import columnar
    from dbeel_tpu.storage.entry_writer import EntryWriter
    from dbeel_tpu.storage.sstable import SSTable

    import random as _random

    rng = _random.Random(42)

    def stage(base_idx, runs, per):
        tabs = []
        for r in range(runs):
            w = EntryWriter(tmp_dir, base_idx + 2 * r, None)
            for k in sorted(
                f"{base_idx}-{rng.randrange(10**6):06d}".encode()
                for _ in range(per)
            ):
                w.write(k, b"v", rng.randrange(1, 10**9))
            w.close()
            tabs.append(SSTable(tmp_dir, base_idx + 2 * r, None))
        cols = columnar.load_columns(tabs)
        rc = np.bincount(cols.src).tolist() if len(cols) else []
        return cols, rc

    jobs = [stage(0, 2, 40), stage(100, 3, 25)]
    batch = pack_jobs([(c, rc, None) for c, rc in jobs])
    # One compiled shape: pow2 K covering the widest job, pow2 P
    # covering the longest run, stacked over jobs.
    assert batch.k >= 4 and batch.k & (batch.k - 1) == 0
    assert batch.p >= 64 and batch.p & (batch.p - 1) == 0
    # (jobs, K, P, words): the kernel's packed u32 prefix words.
    assert batch.prefixes.shape[:3] == (2, batch.k, batch.p)
    assert batch.counts.shape == (2, batch.k)
    assert 0.0 <= batch.pad_frac < 1.0

    async def main():
        from dbeel_tpu.server.coalescer import CompactionCoalescer

        co = CompactionCoalescer(window_s=0.01)
        twin = DeviceMergeStrategy()
        for cols, rc in jobs:
            perm = await co.submit(cols, rc)
            got, keep = columnar.fixup_and_dedup_prefix(
                cols, perm, words=2
            )
            want, want_keep = twin.sort_and_dedup(cols)
            assert np.array_equal(got[keep], want[want_keep])
        assert co.launches >= 1
        assert co.last_batch_k >= 1 and co.last_batch_p >= 8

    run(main(), timeout=30)
