"""Disk-fault-hardened storage plane (PR 3): block checksums,
quarantine + replica repair, degraded mode, scrub, and the stale-page
retirement regression.

Fast (tier-1) coverage of the durability plane:
  * sums sidecar round-trip + self-check demotion to legacy
  * on-disk bit flip → CorruptedFile → quarantine → counters + suspect
    reads, with fallback to surviving tables
  * WAL ENOSPC/EIO (fault seam) → ShardDegraded writes, reads serve
  * flush free-space back-off → degraded instead of torn triplets
  * drop/recreate collection never serves the dropped collection's
    cached pages (satellite: table-retirement invalidation)
  * the RF=3 kill-and-corrupt drill: one flipped bit on one node gives
    zero wrong client answers, quarantine + completed repair in
    get_stats, and a clean post-repair scrub
"""

import asyncio
import os
import sys

import pytest

from dbeel_tpu.client import DbeelClient, Consistency
from dbeel_tpu.errors import CorruptedFile, ShardDegraded
from dbeel_tpu.flow_events import FlowEvent
from dbeel_tpu.storage import checksums, file_io
from dbeel_tpu.storage.lsm_tree import LSMTree
from dbeel_tpu.storage.page_cache import PageCache, PartitionPageCache

from conftest import run
from harness import ClusterNode, make_config, next_node_config

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
from corrupt import flip_bytes  # noqa: E402


@pytest.fixture(autouse=True)
def _clear_seam():
    yield
    file_io.clear_faults()


# ----------------------------------------------------------------------
# Sidecar unit behavior
# ----------------------------------------------------------------------


def test_sums_roundtrip_and_self_check(tmp_dir):
    data = os.urandom(10_000)
    idx = os.urandom(4_096 * 2)
    checksums.write(
        tmp_dir,
        0,
        checksums.page_crcs(data),
        checksums.page_crcs(idx),
        len(data),
        b"bloooom",
    )
    sums = checksums.load(tmp_dir, 0)
    assert sums is not None
    assert sums.data_size == len(data)
    assert sums.has_bloom
    assert sums.verify_buffer("data", data, len(data))
    assert not sums.verify_buffer("data", b"x" + data[1:], len(data))
    # A corrupted sidecar fails its own trailer CRC and demotes the
    # table to legacy-unverified instead of quarantining good data.
    flip_bytes(checksums.sums_path(tmp_dir, 0), 3)
    assert checksums.load(tmp_dir, 0) is None


async def _tree_with_table(d, cache=None, n=200):
    tree = LSMTree.open_or_create(
        d, cache=cache, capacity=1 << 20, memtable_kind="sorted"
    )
    for i in range(n):
        await tree.set_with_timestamp(
            b"key%04d" % i, b"value-%04d" % i, 1000 + i
        )
    await tree.flush()
    return tree


def test_bitflip_detected_quarantined_and_fallback(tmp_dir):
    async def main():
        d = os.path.join(tmp_dir, "t")
        tree = await _tree_with_table(d)
        # An OLDER table holding one key the corrupt table lacks:
        # fallback must still serve it after the quarantine.
        table = tree._sstables.tables[0]
        assert table.verified, "flush must leave a sums sidecar"
        data_path = table.data_path
        tree.close()

        flip_bytes(data_path, os.path.getsize(data_path) // 2)

        tree = LSMTree.open_or_create(
            d, capacity=1 << 20, memtable_kind="sorted"
        )
        # Reading every key forces a full-record read over every data
        # page; the flipped page must trip the CRC, not msgpack.
        hits = 0
        for i in range(200):
            if await tree.get_entry(b"key%04d" % i) is not None:
                hits += 1
        assert tree.durability["checksum_failures"] >= 1
        assert tree.durability["quarantined_tables"] == 1
        assert tree.reads_suspect
        assert hits < 200  # the quarantined table's entries are gone
        # Files moved aside (never unlinked before repair).
        await asyncio.sleep(0.2)
        qdir = os.path.join(d, "quarantine")
        assert os.path.isdir(qdir) and len(os.listdir(qdir)) >= 2
        for t in tree._sstables.tables:
            assert t.index != 0
        # finish_repair retires them and clears the suspect state.
        tree.finish_repair(tree._quarantine_pending)
        await asyncio.sleep(0.2)
        assert not tree.reads_suspect
        assert not os.path.isdir(qdir)
        assert tree.durability["repairs_completed"] == 1
        tree.close()

    run(main(), timeout=30)


def test_legacy_table_without_sums_still_serves(tmp_dir):
    async def main():
        d = os.path.join(tmp_dir, "t")
        tree = await _tree_with_table(d)
        tree.close()
        os.unlink(checksums.sums_path(d, 0))
        tree = LSMTree.open_or_create(
            d, capacity=1 << 20, memtable_kind="sorted"
        )
        assert not tree._sstables.tables[0].verified
        assert await tree.get(b"key0007") == b"value-0007"
        tree.close()

    run(main(), timeout=30)


def test_seam_bitflip_on_read_path(tmp_dir):
    """The in-process fault seam corrupts page reads (disk intact):
    verification catches it before the page can enter the cache."""

    async def main():
        d = os.path.join(tmp_dir, "t")
        cache = PartitionPageCache("c", PageCache(1024))
        tree = await _tree_with_table(d, cache=cache)
        tree.close()
        tree = LSMTree.open_or_create(
            d,
            cache=PartitionPageCache("c", PageCache(1024)),
            capacity=1 << 20,
            memtable_kind="sorted",
        )
        table = tree._sstables.tables[0]
        file_io.set_fault(table.data_path, file_io.FAULT_BITFLIP)
        with pytest.raises(CorruptedFile):
            await table._data.read_at_async(0, 64)
        file_io.clear_faults()
        tree.close()

    run(main(), timeout=30)


# ----------------------------------------------------------------------
# Degraded mode
# ----------------------------------------------------------------------


def test_wal_enospc_flips_read_only(tmp_dir):
    async def main():
        d = os.path.join(tmp_dir, "t")
        tree = await _tree_with_table(d)
        seen = []
        tree.on_disk_error = seen.append
        file_io.set_fault(d, file_io.FAULT_ENOSPC)
        with pytest.raises(ShardDegraded):
            await tree.set_with_timestamp(b"newkey", b"v", 10**9)
        assert tree.read_only
        assert seen, "on_disk_error escalation must fire"
        # Reads keep serving (read-only degraded, not dead).
        file_io.clear_faults()
        assert await tree.get(b"key0003") == b"value-0003"
        # And writes stay rejected (sticky until restart).
        with pytest.raises(ShardDegraded):
            await tree.set_with_timestamp(b"newkey", b"v", 10**9)
        tree.close()

    run(main(), timeout=30)


def test_flush_backs_off_below_free_space_floor(tmp_dir):
    async def main():
        d = os.path.join(tmp_dir, "t")
        tree = LSMTree.open_or_create(
            d, capacity=1 << 20, memtable_kind="sorted"
        )
        await tree.set_with_timestamp(b"k", b"v", 1)
        file_io.set_fault(d, file_io.FAULT_NO_SPACE)
        await tree.flush()  # must back off, not tear a triplet
        assert tree.read_only
        assert tree.sstable_indices_and_sizes() == []
        file_io.clear_faults()
        tree.close()

    run(main(), timeout=30)


def test_compaction_backs_off_and_settles_below_free_space_floor(tmp_dir):
    """A merge the tree declines for disk space is not progress: the
    settle loop must return (and leave the retry to the next flush
    event), not re-issue the same refused merge forever on the
    shards' one event loop."""
    from dbeel_tpu.server.tasks import compact_tree, compact_until_settled

    async def main():
        d = os.path.join(tmp_dir, "t")
        tree = LSMTree.open_or_create(
            d, capacity=1 << 20, memtable_kind="sorted"
        )
        for t in range(4):
            for i in range(32):
                await tree.set_with_timestamp(
                    b"key%04d" % i, b"value-%d-%04d" % (t, i), 1000 + t
                )
            await tree.flush()
        before = tree.sstable_indices_and_sizes()
        assert len(before) == 4
        file_io.set_fault(d, file_io.FAULT_NO_SPACE)
        indices = [i for i, _ in before]
        assert await tree.compact(indices, 7, False) is False
        assert await compact_tree(tree, 2) == 0
        await asyncio.wait_for(compact_until_settled(tree, 2), 5)
        assert tree.sstable_indices_and_sizes() == before
        assert not tree.read_only  # a declined merge degrades nothing
        # Space is back: the next event's settle merges everything.
        file_io.clear_faults()
        await asyncio.wait_for(compact_until_settled(tree, 2), 30)
        assert len(tree.sstable_indices_and_sizes()) == 1
        assert await tree.get(b"key0003") == b"value-3-0003"
        tree.close()

    run(main(), timeout=60)


# ----------------------------------------------------------------------
# Satellite: table retirement must invalidate cached pages
# ----------------------------------------------------------------------


def test_drop_recreate_never_serves_stale_cached_pages(tmp_dir):
    """A re-created same-name collection recycles (name, file-id, page)
    cache keys from zero: purge must invalidate, or reads serve the
    DROPPED collection's pages."""

    async def main():
        shard_cache = PageCache(4096)

        async def build(value_tag: bytes):
            d = os.path.join(tmp_dir, "col-0")
            tree = LSMTree.open_or_create(
                d,
                cache=PartitionPageCache("col", shard_cache),
                capacity=1 << 20,
                memtable_kind="sorted",
            )
            for i in range(64):
                await tree.set_with_timestamp(
                    b"key%04d" % i, value_tag + b"-%04d" % i, 1000 + i
                )
            await tree.flush()
            return tree

        tree = await build(b"AAAA")
        # Read through the cache so pages are resident.
        assert (await tree.get(b"key0001")).startswith(b"AAAA")
        await tree.purge()

        tree = await build(b"BBBB")
        got = await tree.get(b"key0001")
        assert got == b"BBBB-0001", (
            f"stale page served after drop/recreate: {got!r}"
        )
        tree.close()

    run(main(), timeout=30)


# ----------------------------------------------------------------------
# The RF=3 kill-and-corrupt drill (acceptance criteria)
# ----------------------------------------------------------------------


def _three_cfgs(tmp_dir, **kw):
    cfg = make_config(tmp_dir, **kw)
    cfgs = [cfg]
    for i in (1, 2):
        cfgs.append(
            next_node_config(cfg, i, tmp_dir).replace(
                seed_nodes=[f"{cfg.ip}:{cfg.remote_shard_port}"], **kw
            )
        )
    return cfgs


def test_kill_and_corrupt_drill(tmp_dir):
    """RF=3: flip one bit in one node's sstable → zero wrong client
    answers, checksum_failures/quarantined_tables bump in get_stats, a
    completed replica repair, and a clean post-repair scrub; then an
    ENOSPC window on another node's WAL leaves the cluster serving
    reads and W=2 writes with degraded_mode=1 instead of crashing."""

    async def main():
        cfgs = _three_cfgs(
            tmp_dir,
            memtable_kind="sorted",
            memtable_capacity=1 << 20,
            anti_entropy_interval_ms=0,  # repair must do the work
        )
        nodes = [await ClusterNode(cfgs[0]).start()]
        for c in cfgs[1:]:
            alive = nodes[0].flow_event(0, FlowEvent.ALIVE_NODE_GOSSIP)
            nodes.append(await ClusterNode(c).start())
            await alive
        try:
            client = await DbeelClient.from_seed_nodes(
                [nodes[0].db_address]
            )
            created = [
                n.flow_event(0, FlowEvent.COLLECTION_CREATED)
                for n in nodes
            ]
            col = await client.create_collection(
                "drill", replication_factor=3
            )
            await asyncio.wait_for(asyncio.gather(*created), 10)

            expected = {}
            for i in range(120):
                key = f"k{i:04d}"
                expected[key] = {"v": i}
                await col.set(
                    key, {"v": i}, consistency=Consistency.ALL
                )

            victim = nodes[1].shards[0]
            vtree = victim.collections["drill"].tree
            await vtree.flush()
            assert vtree._sstables.tables, "victim must have a table"
            vtable = vtree._sstables.tables[0]
            assert vtable.verified

            repair_done = victim.flow.subscribe(FlowEvent.REPAIR_DONE)
            flip_bytes(
                vtable.data_path,
                os.path.getsize(vtable.data_path) // 2,
            )

            # Every key read at R=2 through the normal client: ZERO
            # wrong answers — the victim's corrupt replica answers
            # with a retryable error / quarantines, quorum merges the
            # clean copies.
            for key, want in expected.items():
                got = await col.get(
                    key, consistency=Consistency.fixed(2)
                )
                assert got == want, (key, got, want)
            # Force the victim itself over its whole table too (its
            # own coordinator path), so detection is deterministic
            # regardless of which node coordinated above.  Stored keys
            # are the msgpack encoding of the client-level key.
            import msgpack

            enc = lambda k: msgpack.packb(k, use_bin_type=True)  # noqa: E731
            for key in expected:
                await vtree.get_entry(enc(key))

            stats = victim.get_stats()["durability"]
            assert stats["checksum_failures"] >= 1, stats
            assert stats["quarantined_tables"] >= 1, stats

            await asyncio.wait_for(repair_done, 30)
            assert not vtree.reads_suspect
            assert (
                victim.get_stats()["durability"]["repairs_completed"]
                >= 1
            )

            # Post-repair scrub: flush the repaired range into a
            # fresh (checksummed) table, then verify it reads clean.
            await vtree.flush()
            from dbeel_tpu.server import tasks as server_tasks

            failures_before = vtree.durability["checksum_failures"]
            scrubbed_before = victim.scrub_bytes_verified
            for t in list(vtree._sstables.tables):
                if t.sums is not None:
                    await server_tasks._scrub_table(
                        victim, vtree, t, 1 << 30
                    )
            assert victim.scrub_bytes_verified > scrubbed_before
            assert (
                vtree.durability["checksum_failures"]
                == failures_before
            ), "post-repair scrub must report the range clean"

            # The repaired node serves the drilled keys locally again.
            for key in list(expected)[:10]:
                entry = await vtree.get_entry(enc(key))
                assert entry is not None, key

            # ---- ENOSPC window on node 2's WAL -------------------
            enospc_victim = nodes[2].shards[0]
            file_io.set_fault(
                cfgs[2].dir, file_io.FAULT_ENOSPC
            )
            # Writes at W=2 keep succeeding: the degraded node's
            # replica rejections don't break quorum.  Drive them
            # through healthy coordinators (keys the degraded node
            # does not own as primary) — degraded-coordinator walks
            # are the PR-1 client-failover tests' job, and each one
            # costs a full server timeout here.
            from dbeel_tpu.utils.murmur import hash_bytes

            healthy_keys = [
                k
                for k in expected
                if not enospc_victim.owns_key(hash_bytes(enc(k)), 0)
            ][:8]
            assert healthy_keys
            for i, key in enumerate(healthy_keys):
                expected[key] = {"v": 10_000 + i}
                await col.set(
                    key,
                    {"v": 10_000 + i},
                    consistency=Consistency.fixed(2),
                )
            # ...reads still serve everywhere...
            for key in healthy_keys:
                got = await col.get(
                    key, consistency=Consistency.fixed(2)
                )
                assert got == expected[key], (key, got)
            # ...and the node reports degraded_mode=1 instead of
            # having crashed.
            deadline = asyncio.get_event_loop().time() + 15
            while (
                not enospc_victim.degraded
                and asyncio.get_event_loop().time() < deadline
            ):
                await asyncio.sleep(0.05)
            stats2 = enospc_victim.get_stats()["durability"]
            assert stats2["degraded_mode"] == 1, stats2
            file_io.clear_faults()
        finally:
            file_io.clear_faults()
            for n in nodes:
                await n.stop()

    run(main(), timeout=110)
