"""Streaming scan/range query plane (PR 12).

Covers the ISSUE 12 semantics checklist: chunked iteration with
resumable cursors (including resume across a coordinator restart),
RF=3 newest-wins merge dedup after replica divergence, tombstone
exclusion, count/prefix pushdown, byte-budget honoring, hard-overload
shedding with a surviving cursor, and staged-vs-fallback storage
parity.
"""

import asyncio

import msgpack
import pytest

from conftest import run
from harness import ClusterNode, make_config, next_node_config
from dbeel_tpu.client import DbeelClient
from dbeel_tpu.errors import Overloaded
from dbeel_tpu.server.governor import LEVEL_HARD


def _keys(n, skip=()):
    return [
        f"key-{i:04d}" for i in range(n) if i not in set(skip)
    ]


async def _scan_all(col, **kw):
    return [kv async for kv in col.scan(**kw)]


# ---------------------------------------------------------------------
# Single-node semantics
# ---------------------------------------------------------------------


def test_scan_order_content_and_tombstones(tmp_dir):
    async def main():
        node = await ClusterNode(
            make_config(tmp_dir), num_shards=2
        ).start()
        client = await DbeelClient.from_seed_nodes(
            [node.db_address], op_deadline_s=5.0
        )
        col = await client.create_collection("c", 1)
        await col.multi_set(
            {k: {"v": k} for k in _keys(400)}
        )
        await col.delete("key-0007")
        got = await _scan_all(col)
        assert [k for k, _v in got] == _keys(400, skip=(7,))
        assert all(v == {"v": k} for k, v in got)
        # Byte-agreement with a sorted multi_get of the keyspace.
        values = await col.multi_get(_keys(400, skip=(7,)))
        assert [v for _k, v in got] == values
        client.close()
        await node.stop()

    run(main(), 60)


def test_scan_chunked_equals_full_and_budget(tmp_dir):
    async def main():
        cfg = make_config(tmp_dir, scan_bytes_per_slice=1 << 20)
        node = await ClusterNode(cfg, num_shards=1).start()
        client = await DbeelClient.from_seed_nodes(
            [node.db_address], op_deadline_s=5.0
        )
        col = await client.create_collection("c", 1)
        await col.multi_set({k: {"v": k} for k in _keys(300)})
        full = await _scan_all(col)
        # Tiny per-chunk budget: many cursor hops, same stream.
        small = await _scan_all(col, max_bytes=512)
        assert small == full
        stats = await client.get_stats(*node.db_address)
        sc = stats["scan"]
        assert sc["scans_started"] >= 2
        assert sc["cursor_resumes"] > 10  # 300 entries / ~512B chunks
        assert sc["chunks"] > sc["scans_started"]
        assert sc["bytes_streamed"] > 0
        assert sc["active_scans"] == 0
        # Byte budget honored: no chunk materially above the slice
        # budget → with 512B slices the per-chunk entry count stays
        # tiny (each entry ~30B encoded, ENTRY_OVERHEAD=16).
        assert sc["chunks"] >= 300 * 30 // 600
        client.close()
        await node.stop()

    run(main(), 60)


def test_scan_limit_and_prefix_and_count(tmp_dir):
    async def main():
        node = await ClusterNode(
            make_config(tmp_dir), num_shards=2
        ).start()
        client = await DbeelClient.from_seed_nodes(
            [node.db_address], op_deadline_s=5.0
        )
        col = await client.create_collection("c", 1)
        await col.multi_set({k: {"v": k} for k in _keys(300)})
        limited = await _scan_all(col, limit=25)
        assert [k for k, _v in limited] == _keys(300)[:25]
        # Raw encoded-key prefix: fixstr header byte + "key-00".
        pfx = msgpack.packb("key-0000")[:7]
        under = await _scan_all(col, prefix=pfx)
        assert [k for k, _v in under] == _keys(100)
        assert await col.count() == 300
        assert await col.count(prefix=pfx) == 100
        await col.delete("key-0042")
        assert await col.count(prefix=pfx) == 99
        # Scan chunks rotate across coordinators for load spread —
        # the counter lives on whichever shard served the final
        # count chunk.
        assert (
            sum(s.scan_plane.counts_served for s in node.shards)
            >= 1
        )
        client.close()
        await node.stop()

    run(main(), 60)


def test_scan_sheds_retryably_under_hard_overload(tmp_dir):
    async def main():
        node = await ClusterNode(
            make_config(tmp_dir), num_shards=1
        ).start()
        client = await DbeelClient.from_seed_nodes(
            [node.db_address], op_deadline_s=2.0
        )
        col = await client.create_collection("c", 1)
        await col.multi_set({k: {"v": k} for k in _keys(120)})
        shard = node.shards[0]
        # Start the scan, take one chunk, then force hard overload.
        agen = col.scan(max_bytes=512)
        first = await agen.__anext__()
        shard.governor.force_level(LEVEL_HARD)
        with pytest.raises(Overloaded):
            # The client walk retries with backoff but the level is
            # pinned: the final surfaced error stays retryable.
            while True:
                await agen.__anext__()
        sheds_while_hard = shard.scan_plane.sheds
        assert sheds_while_hard >= 1
        # Disarm: a FRESH scan (cursor state lives in the client's
        # request loop, which the raised generator closed) streams
        # the full keyspace — nothing was lost server-side.
        shard.governor.force_level(None)
        await agen.aclose()
        got = await _scan_all(col)
        assert len(got) == 120
        assert first is not None
        client.close()
        await node.stop()

    run(main(), 60)


def test_scan_max_concurrent_sheds(tmp_dir):
    async def main():
        node = await ClusterNode(
            make_config(tmp_dir), num_shards=1
        ).start()
        shard = node.shards[0]
        client = await DbeelClient.from_seed_nodes(
            [node.db_address], op_deadline_s=2.0
        )
        col = await client.create_collection("c", 1)
        await col.multi_set({k: {"v": k} for k in _keys(50)})
        # Saturate the gauge directly (deterministic: no timing).
        shard.scan_plane.active_scans = (
            shard.config.scan_max_concurrent
        )
        before = shard.scan_plane.sheds
        with pytest.raises(Overloaded):
            async for _ in col.scan():
                pass
        assert shard.scan_plane.sheds > before
        shard.scan_plane.active_scans = 0
        assert len(await _scan_all(col)) == 50
        client.close()
        await node.stop()

    run(main(), 60)


def test_traced_scan_records_stage_marks(tmp_dir):
    # Trace integration (PR 12 satellite): a client-stamped scan
    # records per-chunk stage marks (pace/iterate/merge/respond) in
    # the flight recorder, so `blackbox_bench.py --attribute`
    # decomposes scan latency exactly like point ops.
    async def main():
        node = await ClusterNode(
            make_config(tmp_dir), num_shards=1
        ).start()
        client = await DbeelClient.from_seed_nodes(
            [node.db_address], op_deadline_s=5.0
        )
        col = await client.create_collection("c", 1)
        await col.multi_set({k: {"v": k} for k in _keys(200)})
        got = [
            kv
            async for kv in col.scan(max_bytes=2048, trace_id=7070)
        ]
        assert len(got) == 200
        dump = await client.trace_dump(*node.db_address)
        spans = [
            e
            for e in dump["entries"]
            if e.get("sampled") and e["op"] in ("scan", "scan_next")
        ]
        assert spans, dump["entries"][-3:]
        stage_names = {
            s for e in spans for s, _us in e["stages"]
        }
        assert {"pace", "iterate", "merge", "respond"} <= stage_names
        for e in spans:
            # Strictly-sequential marks: the stage sum tracks the
            # span total (same invariant as point-op spans).
            assert sum(us for _s, us in e["stages"]) <= e[
                "total_us"
            ] + 1000
        client.close()
        await node.stop()

    run(main(), 60)


# ---------------------------------------------------------------------
# RF=3 merge semantics + cursor resume across restart
# ---------------------------------------------------------------------


async def _start_cluster(tmp_dir, n_nodes=3, **cfg_kw):
    cfg = make_config(tmp_dir, **cfg_kw)
    nodes = [await ClusterNode(cfg, num_shards=1).start()]
    for i in range(1, n_nodes):
        ncfg = next_node_config(cfg, i, tmp_dir).replace(
            seed_nodes=[nodes[0].seed_address]
        )
        nodes.append(await ClusterNode(ncfg, num_shards=1).start())
    # Let gossip converge the ring everywhere.
    for _ in range(100):
        if all(
            len(n.shards[0].shards) >= n_nodes for n in nodes
        ):
            break
        await asyncio.sleep(0.05)
    return nodes


def test_rf3_merge_dedup_newer_replica_wins(tmp_dir):
    async def main():
        nodes = await _start_cluster(tmp_dir, 3)
        client = await DbeelClient.from_seed_nodes(
            [nodes[0].db_address], op_deadline_s=8.0
        )
        col = await client.create_collection("c", 3)
        await asyncio.sleep(0.3)
        keys = _keys(60)
        for k in keys:
            await col.set(k, {"v": k, "gen": 0})
        # Diverge the replicas: write newer versions of some keys
        # DIRECTLY into one node's local tree (older ts stays on the
        # other two) — the scan merge must pick the newest and never
        # resurrect the stale copy.
        from dbeel_tpu.utils.timestamps import now_nanos

        shard = nodes[1].shards[0]
        tree = shard.collections["c"].tree
        newer = keys[:10]
        for k in newer:
            await tree.set_with_timestamp(
                msgpack.packb(k),
                msgpack.packb({"v": k, "gen": 1}),
                now_nanos(),
            )
        got = {k: v async for k, v in col.scan()}
        assert len(got) == 60
        for k in newer:
            assert got[k]["gen"] == 1, k
        for k in keys[10:]:
            assert got[k]["gen"] == 0, k
        # A tombstone on ONE replica newer than the others' live
        # value suppresses the key cluster-wide.
        dead = keys[20]
        await tree.set_with_timestamp(
            msgpack.packb(dead), b"", now_nanos()
        )
        got2 = {k async for k, _v in col.scan()}
        assert dead not in got2
        client.close()
        for n in nodes:
            await n.stop()

    run(main(), 90)


def test_scan_agrees_with_multi_get_after_replica_kill_heal(tmp_dir):
    async def main():
        nodes = await _start_cluster(
            tmp_dir,
            3,
            hint_drain_interval_ms=200,
            anti_entropy_interval_ms=0,
        )
        client = await DbeelClient.from_seed_nodes(
            [nodes[0].db_address], op_deadline_s=8.0
        )
        col = await client.create_collection("c", 3)
        await asyncio.sleep(0.3)
        keys = _keys(40)
        for k in keys[:20]:
            await col.set(k, {"v": k, "gen": 0})
        # Kill one replica, write through the survivors (W=2), heal.
        await nodes[2].crash()
        await asyncio.sleep(0.3)
        for k in keys[20:]:
            await col.set(k, {"v": k, "gen": 1}, consistency=(
                "fixed", 2
            ))
        restarted = await ClusterNode(
            nodes[2].config, num_shards=1
        ).start()
        nodes[2] = restarted
        await asyncio.sleep(1.0)  # alive gossip + hint replay window
        # Merge correctness under (possibly still-healing)
        # divergence: the scan must byte-agree with the quorum-read
        # view of every key.
        got = {k: v async for k, v in col.scan()}
        values = await col.multi_get(keys)
        expect = {
            k: v for k, v in zip(keys, values) if v is not None
        }
        assert got == expect
        assert set(got) == set(keys)
        client.close()
        for n in nodes:
            await n.stop()

    run(main(), 90)


def test_cursor_resumes_across_coordinator_restart(tmp_dir):
    async def main():
        nodes = await _start_cluster(tmp_dir, 2)
        client = await DbeelClient.from_seed_nodes(
            [nodes[0].db_address, nodes[1].db_address],
            op_deadline_s=8.0,
        )
        col = await client.create_collection("c", 2)
        await asyncio.sleep(0.3)
        keys = _keys(80)
        for k in keys:
            await col.set(k, {"v": k})
        # Pull a few chunks by hand so we hold a mid-scan cursor.
        req = {
            "type": "scan",
            "collection": "c",
            "max_bytes": 512,
        }
        chunk = await client._scan_chunk_request(req)
        seen = [k for k, _v in chunk["entries"]]
        cursor = chunk["cursor"]
        assert cursor
        # Restart the node that served the first chunk (cursors are
        # self-contained, so ANY node can continue; the client walk
        # retries through the other node while this one is down).
        await nodes[0].crash()
        restarted = await ClusterNode(
            nodes[0].config, num_shards=1
        ).start()
        nodes[0] = restarted
        while cursor:
            chunk = await client._scan_chunk_request(
                {"type": "scan_next", "cursor": cursor}
            )
            seen.extend(k for k, _v in chunk["entries"])
            cursor = chunk["cursor"]
        assert seen == keys
        client.close()
        for n in nodes:
            await n.stop()

    run(main(), 90)


# ---------------------------------------------------------------------
# Storage staging parity
# ---------------------------------------------------------------------


def test_staged_and_fallback_pages_agree(tmp_dir):
    import dbeel_tpu.storage.scan_stage as ss
    from dbeel_tpu.storage.lsm_tree import LSMTree

    async def main():
        tree = LSMTree.open_or_create(
            tmp_dir + "/t", capacity=128
        )
        for i in range(700):
            await tree.set_with_timestamp(
                msgpack.packb(f"k{i:05d}"),
                msgpack.packb({"v": i}),
                1000 + i,
            )
        await tree.flush()
        for i in range(100, 220):  # newer overwrites post-flush
            await tree.set_with_timestamp(
                msgpack.packb(f"k{i:05d}"),
                msgpack.packb({"v": -i}),
                9000 + i,
            )
        await tree.delete_with_timestamp(
            msgpack.packb("k00005"), 99000
        )

        async def page_all(**kw):
            out, sa = [], None
            while True:
                es, more = await tree.scan_page(
                    start_after=sa, **kw
                )
                out.extend(es)
                if not more or not es:
                    return out
                sa = es[-1][0]

        cases = [
            dict(start=0, end=0, prefix=None, limit=64,
                 max_bytes=4096, with_values=True),
            dict(start=123, end=2**31 + 7, prefix=None, limit=50,
                 max_bytes=2048, with_values=True),
            dict(start=0, end=0,
                 prefix=msgpack.packb("k00110")[:5], limit=1000,
                 max_bytes=1 << 20, with_values=False),
        ]
        for case in cases:
            staged = await page_all(**case)
            assert tree._scan_stage is not None
            old = ss.MIN_VECTORIZED_ENTRIES
            ss.MIN_VECTORIZED_ENTRIES = 10**9
            tree._drop_scan_stage()
            try:
                fallback = await page_all(**case)
            finally:
                ss.MIN_VECTORIZED_ENTRIES = old
            assert staged == fallback, case
        # Tombstone travels through both paths with value=b"".
        staged = await page_all(
            start=0, end=0, prefix=msgpack.packb("k00005"),
            limit=10, max_bytes=4096, with_values=True,
        )
        assert staged == [[msgpack.packb("k00005"), b"", 99000]]
        tree.close()

    run(main(), 60)


def test_concurrent_stage_builds_do_not_leak_reader_refs(tmp_dir):
    # Review regression: two cold-cache scan chunks racing through
    # _current_scan_stage must end with exactly ONE cached reader
    # ref on the sstable list — an orphaned ref would stall
    # compaction's reader drain forever.
    from dbeel_tpu.storage.lsm_tree import LSMTree

    async def main():
        tree = LSMTree.open_or_create(
            tmp_dir + "/t", capacity=4096
        )
        for i in range(700):
            await tree.set_with_timestamp(
                msgpack.packb(f"k{i:05d}"),
                msgpack.packb({"v": i}),
                1000 + i,
            )
        await tree.flush()
        assert tree._scan_stage is None  # cold cache
        await asyncio.gather(
            *[
                tree.scan_page(0, 0, None, None, 10, 4096, True)
                for _ in range(4)
            ]
        )
        lst = tree._scan_stage_list
        assert lst is not None
        assert lst.readers == 1  # the cache's ref, nothing orphaned
        tree._drop_scan_stage()
        assert lst.readers == 0  # compaction's drain can proceed
        tree.close()

    run(main(), 60)


def test_scan_stage_value_corruption_quarantines(tmp_dir):
    # The staged value path slices a memmap, not the page cache — it
    # must still verify pages against the CRC sidecar before serving
    # (one crc32 per touched page per stage), and a flipped value bit
    # must surface as retryable corruption + a quarantine, never as
    # corrupt client bytes.
    from dbeel_tpu.errors import CorruptedFile
    from dbeel_tpu.storage.lsm_tree import LSMTree

    async def main():
        tree = LSMTree.open_or_create(
            tmp_dir + "/t", capacity=4096
        )
        for i in range(800):
            await tree.set_with_timestamp(
                msgpack.packb(f"k{i:05d}"),
                msgpack.packb({"blob": "x" * 64, "i": i}),
                1000 + i,
            )
        await tree.flush()
        table = tree._sstables.tables[0]
        off, ksz, _fsz = table._index_record(400)
        flip_at = off + 16 + ksz + 8  # inside entry 400's value
        with open(table.data_path, "r+b") as f:
            f.seek(flip_at)
            b = f.read(1)
            f.seek(flip_at)
            f.write(bytes([b[0] ^ 0xFF]))
        with pytest.raises(CorruptedFile):
            await tree.scan_page(
                0, 0, None, None, 10**6, 1 << 22, True
            )
        assert tree.durability["checksum_failures"] >= 1
        assert tree.durability["quarantined_tables"] >= 1
        assert tree.reads_suspect  # repair owns the heal
        tree.close()

    run(main(), 60)


# ---------------------------------------------------------------------
# Query compute plane (PR 13): filter/aggregate pushdown correctness
# ---------------------------------------------------------------------


def test_filtered_scan_and_count_single_node(tmp_dir):
    async def main():
        node = await ClusterNode(
            make_config(tmp_dir), num_shards=2
        ).start()
        client = await DbeelClient.from_seed_nodes(
            [node.db_address], op_deadline_s=5.0
        )
        col = await client.create_collection("c", 1)
        await col.multi_set(
            {
                f"key-{i:04d}": {"v": i, "grp": i % 3}
                for i in range(400)
            }
        )
        await col.delete("key-0006")
        got = await _scan_all(
            col, filter=["cmp", "v", "<", 20]
        )
        assert [k for k, _v in got] == [
            f"key-{i:04d}" for i in range(20) if i != 6
        ]
        # AND/OR trees, prefix on the ENCODED key, tiny budgets
        # (cursor hops mid-filtered-stream).
        import msgpack as _mp

        pfx = _mp.packb("key-0150")[:7]  # header + "key-01"
        got2 = await _scan_all(
            col,
            max_bytes=512,
            filter=[
                "or",
                ["cmp", "grp", "==", 1],
                [
                    "and",
                    ["prefix", "$key", pfx],
                    ["range", "v", 150, 160],
                ],
            ],
        )
        exp = [
            f"key-{i:04d}"
            for i in range(400)
            if i != 6 and (i % 3 == 1 or 150 <= i < 160)
        ]
        assert [k for k, _v in got2] == exp
        # Filtered count (keys-only) + pushdown aggregate.
        assert await col.count(
            filter=["cmp", "v", ">=", 390]
        ) == 10
        total = await col.count(
            aggregate={"op": "sum", "field": "v"}
        )
        assert total == sum(
            i for i in range(400) if i != 6
        )
        # Scan chunks rotate across coordinators: the filter block
        # lives on whichever shards served them — and it is visible
        # through the client's get_stats verb.
        stats = await client.get_stats(*node.db_address)
        assert "filter" in stats["scan"]
        planes = [s.scan_plane for s in node.shards]
        assert sum(p.specs_served for p in planes) >= 4
        rows_scanned = sum(p.rows_scanned for p in planes)
        rows_returned = sum(p.rows_returned for p in planes)
        assert rows_scanned > rows_returned > 0
        assert sum(p.bytes_saved for p in planes) > 0
        assert (
            sum(p.fallback_evals + p.device_evals for p in planes)
            > 0
        )
        client.close()
        await node.stop()

    run(main(), 60)


def test_filter_newer_tombstone_suppresses_older_match(tmp_dir):
    # A tombstone on ONE replica, NEWER than the matching live
    # version held by the other replicas, must suppress the key from
    # a filtered scan/count — dedup happens before filter
    # accounting.
    async def main():
        from dbeel_tpu.utils.timestamps import now_nanos

        nodes = await _start_cluster(tmp_dir, 3)
        client = await DbeelClient.from_seed_nodes(
            [nodes[0].db_address], op_deadline_s=8.0
        )
        col = await client.create_collection("c", 3)
        await asyncio.sleep(0.3)
        keys = _keys(40)
        for k in keys:
            await col.set(k, {"v": 1})
        tree = nodes[1].shards[0].collections["c"].tree
        dead = keys[5]
        await tree.set_with_timestamp(
            msgpack.packb(dead), b"", now_nanos()
        )
        flt = ["cmp", "v", "==", 1]
        got = {k async for k, _v in col.scan(filter=flt)}
        assert dead not in got
        assert got == set(keys) - {dead}
        assert await col.count(filter=flt) == len(keys) - 1
        # ...and the aggregate path obeys the same suppression: the
        # tombstoned key's value contributes to no partial.
        assert await col.count(
            aggregate={"op": "sum", "field": "v"}, filter=flt
        ) == len(keys) - 1
        client.close()
        for n in nodes:
            await n.stop()

    run(main(), 90)


def test_filter_newer_nonmatching_version_suppresses_match(tmp_dir):
    # A NEWER version that does NOT match, written to one replica
    # while the others still hold an older matching version, must
    # keep the key out: predicate acceptance is decided on the
    # newest-wins winner, never on any stale copy.
    async def main():
        from dbeel_tpu.utils.timestamps import now_nanos

        nodes = await _start_cluster(tmp_dir, 3)
        client = await DbeelClient.from_seed_nodes(
            [nodes[0].db_address], op_deadline_s=8.0
        )
        col = await client.create_collection("c", 3)
        await asyncio.sleep(0.3)
        keys = _keys(30)
        for k in keys:
            await col.set(k, {"v": 1})
        tree = nodes[2].shards[0].collections["c"].tree
        moved = keys[:7]
        for k in moved:
            await tree.set_with_timestamp(
                msgpack.packb(k),
                msgpack.packb({"v": 2}),
                now_nanos(),
            )
        flt = ["cmp", "v", "==", 1]
        got = {k async for k, _v in col.scan(filter=flt)}
        assert got == set(keys) - set(moved)
        assert await col.count(filter=flt) == len(keys) - len(
            moved
        )
        # The inverse predicate sees exactly the moved keys (their
        # newest version matches v==2 even though two replicas
        # still say v==1).
        got2 = {
            k
            async for k, _v in col.scan(
                filter=["cmp", "v", "==", 2]
            )
        }
        assert got2 == set(moved)
        # Aggregate overlap rule: each key contributes its NEWEST
        # value exactly once, replica overlap notwithstanding.
        s = await col.count(
            aggregate={"op": "sum", "field": "v"}
        )
        assert s == (len(keys) - len(moved)) * 1 + len(moved) * 2
        client.close()
        for n in nodes:
            await n.stop()

    run(main(), 90)


def test_filtered_cursor_resumes_across_coordinator_kill(tmp_dir):
    # The s2 cursor is self-contained (spec + aggregate state ride
    # inside): a filtered scan interrupted by a coordinator SIGKILL
    # resumes on the other node with the same predicate.
    async def main():
        from dbeel_tpu import query as Q

        nodes = await _start_cluster(tmp_dir, 2)
        client = await DbeelClient.from_seed_nodes(
            [nodes[0].db_address, nodes[1].db_address],
            op_deadline_s=8.0,
        )
        col = await client.create_collection("c", 2)
        await asyncio.sleep(0.3)
        keys = _keys(90)
        for i, k in enumerate(keys):
            await col.set(k, {"v": i})
        w, a = Q.build_spec(["cmp", "v", "<", 60], None)
        req = {
            "type": "scan",
            "collection": "c",
            "max_bytes": 512,
            "spec": Q.pack_spec(w, a),
        }
        chunk = await client._scan_chunk_request(req)
        seen = [k for k, _v in chunk["entries"]]
        cursor = chunk["cursor"]
        assert cursor
        await nodes[0].crash()
        restarted = await ClusterNode(
            nodes[0].config, num_shards=1
        ).start()
        nodes[0] = restarted
        while cursor:
            chunk = await client._scan_chunk_request(
                {"type": "scan_next", "cursor": cursor}
            )
            seen.extend(k for k, _v in chunk["entries"])
            cursor = chunk["cursor"]
        assert seen == keys[:60]
        client.close()
        for n in nodes:
            await n.stop()

    run(main(), 90)


def test_malformed_spec_is_clean_error_not_shard_death(tmp_dir):
    async def main():
        from dbeel_tpu.errors import DbeelError

        node = await ClusterNode(
            make_config(tmp_dir), num_shards=1
        ).start()
        client = await DbeelClient.from_seed_nodes(
            [node.db_address], op_deadline_s=3.0
        )
        col = await client.create_collection("c", 1)
        await col.multi_set({k: {"v": 1} for k in _keys(20)})
        bad_specs = [
            b"\x00garbage",
            msgpack.packb(["q9", None, None]),  # unknown version
            msgpack.packb(
                ["q1", ["cmp", "v", "~~", 1], None]
            ),  # unsupported op
            msgpack.packb(
                ["q1", ["nand", ["cmp", "v", "==", 1]], None]
            ),  # unknown combinator
            msgpack.packb(["q1", None, None]),  # empty spec
            msgpack.packb(
                ["q1", None, {"op": "median", "field": "v"}]
            ),  # unsupported aggregate
        ]
        for bad in bad_specs:
            with pytest.raises(DbeelError):
                await client._scan_chunk_request(
                    {
                        "type": "scan",
                        "collection": "c",
                        "spec": bad,
                    }
                )
        # Client-side validation rejects bad filters before any wire.
        with pytest.raises(DbeelError):
            async for _ in col.scan(filter=["cmp", "v", "!", 1]):
                pass
        # The shard survived every one of them.
        got = await _scan_all(col)
        assert len(got) == 20
        stats = await client.get_stats(*node.db_address)
        assert stats["scan"]["active_scans"] == 0
        client.close()
        await node.stop()

    run(main(), 60)


def test_value_column_build_crc_flip_quarantines(tmp_dir):
    # The batched field-column decode reads every live value through
    # the lazy per-page CRC verify: a flipped bit under the build
    # must quarantine the table and surface retryably — never serve
    # a poisoned column.
    from dbeel_tpu.errors import CorruptedFile
    from dbeel_tpu.storage.lsm_tree import LSMTree
    from dbeel_tpu import query as Q

    async def main():
        tree = LSMTree.open_or_create(
            tmp_dir + "/t", capacity=4096
        )
        for i in range(800):
            await tree.set_with_timestamp(
                msgpack.packb(f"k{i:05d}"),
                msgpack.packb({"blob": "x" * 64, "i": i}),
                1000 + i,
            )
        await tree.flush()
        table = tree._sstables.tables[0]
        off, ksz, _fsz = table._index_record(400)
        flip_at = off + 16 + ksz + 8
        with open(table.data_path, "r+b") as f:
            f.seek(flip_at)
            b = f.read(1)
            f.seek(flip_at)
            f.write(bytes([b[0] ^ 0xFF]))
        with pytest.raises(CorruptedFile):
            await tree.scan_filter_page(
                0, 0, None, None, 10**6, 1 << 22, True,
                ["cmp", "i", ">=", 0], None, Q.MODE_DROP,
            )
        assert tree.durability["checksum_failures"] >= 1
        assert tree.durability["quarantined_tables"] >= 1
        assert tree.reads_suspect
        tree.close()

    run(main(), 60)


def _random_doc(rng, i):
    """Adversarial document mix: ints (incl. beyond-2^53), floats,
    strings, bytes (incl. trailing-NUL and oversized), bools,
    missing fields, non-map docs."""
    roll = rng.random()
    if roll < 0.05:
        return i  # not a map: matches no field leaf
    doc = {}
    if rng.random() < 0.9:
        doc["n"] = rng.choice(
            [
                rng.randrange(-50, 50),
                float(rng.randrange(-500, 500)) / 7.0,
                (1 << 54) + rng.randrange(100),
                -((1 << 55) + rng.randrange(100)),
                True,
            ]
        )
    if rng.random() < 0.85:
        doc["s"] = rng.choice(
            [
                "apple",
                "banana",
                "cherry" * rng.randrange(1, 3),
                b"raw\x00middle",
                b"trailing\x00",
                b"x" * 300,
                "",
            ]
        )
    if rng.random() < 0.3:
        doc["weird"] = [1, 2, 3]  # non-scalar: never comparable
    doc["i"] = i
    return doc


def _random_where(rng):
    def leaf():
        field = rng.choice(["$key", "n", "s", "i", "missing"])
        kind = rng.choice(["cmp", "prefix", "range"])
        if field == "$key":
            op1 = msgpack.packb(f"k{rng.randrange(900):05d}")
            op2 = msgpack.packb(f"k{rng.randrange(900):05d}")
            if kind == "cmp":
                return [
                    "cmp",
                    "$key",
                    rng.choice(
                        ["==", "!=", "<", "<=", ">", ">="]
                    ),
                    op1,
                ]
            if kind == "prefix":
                return ["prefix", "$key", op1[: rng.randrange(1, 6)]]
            lo, hi = sorted([op1, op2])
            return ["range", "$key", lo, hi]
        if kind == "cmp":
            operand = rng.choice(
                [
                    rng.randrange(-60, 60),
                    float(rng.randrange(-70, 70)) / 3.0,
                    (1 << 54) + 5,
                    "banana",
                    b"raw\x00middle",
                    b"trailing\x00",
                    "y" * 280,
                ]
            )
            return [
                "cmp",
                field,
                rng.choice(["==", "!=", "<", "<=", ">", ">="]),
                operand,
            ]
        if kind == "prefix":
            return [
                "prefix",
                field,
                rng.choice(
                    [b"app", b"che", b"raw", b"trailing\x00", b""]
                ),
            ]
        if rng.random() < 0.5:
            lo, hi = sorted(
                [rng.randrange(-60, 60), rng.randrange(-60, 60)]
            )
            return ["range", field, lo, hi]
        lo, hi = sorted([b"a", rng.choice([b"cherry", b"z"])])
        return ["range", field, lo, hi]

    def tree(depth):
        if depth == 0 or rng.random() < 0.4:
            return leaf()
        return [
            rng.choice(["and", "or"]),
            *[tree(depth - 1) for _ in range(rng.randrange(1, 4))],
        ]

    return tree(2)


def test_vectorized_filter_byte_identical_to_golden(tmp_dir):
    # The acceptance bar: on randomized adversarial specs over an
    # adversarial document mix, the staged vectorized evaluator
    # produces byte-identical pages (entries, covers, scanned
    # accounting, aggregate partial RESULTS) to the golden per-entry
    # walk, in both peer modes.
    import random

    import dbeel_tpu.storage.scan_stage as ss
    from dbeel_tpu import query as Q
    from dbeel_tpu.storage.lsm_tree import LSMTree

    async def main():
        rng = random.Random(1307)
        tree = LSMTree.open_or_create(
            tmp_dir + "/t", capacity=1024
        )
        for i in range(900):
            await tree.set_with_timestamp(
                msgpack.packb(f"k{i:05d}"),
                msgpack.packb(_random_doc(rng, i)),
                1000 + i,
            )
        await tree.flush()
        for i in range(200, 320):  # newer overwrites post-flush
            await tree.set_with_timestamp(
                msgpack.packb(f"k{i:05d}"),
                msgpack.packb(_random_doc(rng, -i)),
                9000 + i,
            )
        for i in (3, 250, 700):
            await tree.delete_with_timestamp(
                msgpack.packb(f"k{i:05d}"), 99000 + i
            )

        async def page_all(where, agg, mode, max_bytes):
            out, partials, sa = [], [], None
            covers = []
            while True:
                (
                    es, more, cover, srows, sbytes, partial, _p,
                ) = await tree.scan_filter_page(
                    0, 0, sa, None, 256, max_bytes, True,
                    where, agg, mode,
                )
                out.extend(es)
                covers.append((cover, srows, sbytes))
                if partial is not None:
                    partials.append(partial)
                if not more:
                    return out, covers, partials
                sa = cover

        def agg_result_of(agg, partials):
            st = Q.AggState(agg)
            for p in partials:
                st.fold_partial(p)
            return st.result()

        for trial in range(12):
            where = Q.validate_where(_random_where(rng))
            agg = None
            if trial % 3 == 2:
                agg = Q.validate_agg(
                    {
                        "op": rng.choice(
                            ["count", "sum", "min", "max", "avg"]
                        ),
                        "field": "n",
                        "group": rng.choice([0, 0, 3]),
                    }
                )
            mode = Q.MODE_DROP if trial % 2 == 0 else Q.MODE_MARK
            if agg is not None:
                mode = Q.MODE_DROP
            max_bytes = rng.choice([2048, 1 << 20])
            staged = await page_all(where, agg, mode, max_bytes)
            assert tree._scan_stage is not None, trial
            old = ss.MIN_VECTORIZED_ENTRIES
            ss.MIN_VECTORIZED_ENTRIES = 10**9
            tree._drop_scan_stage()
            try:
                golden = await page_all(
                    where, agg, mode, max_bytes
                )
            finally:
                ss.MIN_VECTORIZED_ENTRIES = old
            assert staged[0] == golden[0], (trial, where)
            assert staged[1] == golden[1], (trial, where)
            if agg is not None:
                assert agg_result_of(
                    agg, staged[2]
                ) == agg_result_of(agg, golden[2]), (trial, where)
        tree.close()

    run(main(), 120)


def _adversarial_f64():
    # One definition, shared with the chip's own check of the lane.
    from chip_smoke import adversarial_f64

    return adversarial_f64()


@pytest.mark.parametrize("op", ["==", "!=", "<", "<=", ">", ">="])
def test_device_filter_lane_is_exact_cmp(op, monkeypatch):
    # The jitted lane (forced onto the jax CPU backend) returns the
    # numpy float64 mask bit for bit — including 16777217.0 >
    # 16777216.0, which a float32 compare answers False.
    import numpy as np

    import dbeel_tpu.ops.query_kernels as qk

    monkeypatch.setenv("DBEEL_QUERY_DEVICE", "cpu_ok")
    special, vals, valid = _adversarial_f64()
    col = qk.StagedColumn(vals, valid)
    assert qk.serves(col.n)
    host_op = {
        "==": np.equal, "!=": np.not_equal, "<": np.less,
        "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal,
    }[op]
    with np.errstate(invalid="ignore"):
        for operand in [*special.tolist(), 0.25, 3.0]:
            dev = qk.eval_cmp(col, operand, op)
            host = host_op(vals, operand) & valid
            assert dev.shape == host.shape
            assert (dev == host).all(), (operand, op)
    if op == ">":
        at = int(np.flatnonzero(vals == 16777217.0)[0])
        probe = qk.StagedColumn(
            np.full(qk.MIN_DEVICE_ROWS, vals[at]),
            np.ones(qk.MIN_DEVICE_ROWS, dtype=bool),
        )
        assert qk.eval_cmp(probe, 16777216.0, ">").all()


def test_device_filter_lane_is_exact_range(monkeypatch):
    import numpy as np

    import dbeel_tpu.ops.query_kernels as qk

    monkeypatch.setenv("DBEEL_QUERY_DEVICE", "cpu_ok")
    _special, vals, valid = _adversarial_f64()
    col = qk.StagedColumn(vals, valid)
    with np.errstate(invalid="ignore"):
        for lo in (None, -0.5, 0.0, -0.0, 16777217.0, np.nan):
            for hi in (None, 0.5, 16777217.0, np.inf, np.nan):
                if lo is None and hi is None:
                    continue
                host = valid.copy()
                if lo is not None:
                    host &= vals >= lo
                if hi is not None:
                    host &= vals < hi
                dev = qk.eval_range(col, lo, hi)
                assert (dev == host).all(), (lo, hi)


def test_device_filter_lane_opens_only_on_a_held_accelerator(
    monkeypatch,
):
    # No probe and no JAX from the serving path: the lane follows what
    # the process acquired (or the tests' explicit force).
    import dbeel_tpu.ops.query_kernels as qk
    from dbeel_tpu import device

    monkeypatch.delenv("DBEEL_QUERY_DEVICE", raising=False)
    monkeypatch.setattr(device, "_held", None)
    assert not qk.available()
    monkeypatch.setattr(
        device,
        "_held",
        {"platform": "cpu", "device_kind": "cpu", "count": 8},
    )
    assert not qk.available()
    monkeypatch.setattr(
        device,
        "_held",
        {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1},
    )
    assert qk.available() and qk.serves(qk.MIN_DEVICE_ROWS)
    assert not qk.serves(qk.MIN_DEVICE_ROWS - 1)
    monkeypatch.setenv("DBEEL_QUERY_DEVICE", "off")
    assert not qk.available()


def test_staged_filter_through_device_lane_matches_golden(
    tmp_dir, monkeypatch
):
    # End to end: a tree big enough for the device lane (>= 4096
    # staged rows) holding integers above 2^24 answers a predicate
    # whose operand float32 cannot hold exactly like the golden
    # per-entry walk, and the evaluation is counted as a device one.
    import dbeel_tpu.storage.scan_stage as ss
    from dbeel_tpu import query as Q
    from dbeel_tpu.storage.lsm_tree import LSMTree

    monkeypatch.setenv("DBEEL_QUERY_DEVICE", "cpu_ok")
    base = 1 << 24
    n = 5000

    async def main():
        tree = LSMTree.open_or_create(tmp_dir + "/t", capacity=8192)
        for i in range(n):
            await tree.set_with_timestamp(
                msgpack.packb(f"k{i:05d}"),
                msgpack.packb({"big": base + i, "frac": i + 0.1}),
                1000 + i,
            )
        await tree.flush()

        async def count(where):
            total, sa, paths = 0, None, set()
            while True:
                (
                    es, more, cover, _sr, _sb, _partial, path,
                ) = await tree.scan_filter_page(
                    0, 0, sa, None, 1 << 20, 1 << 26, True,
                    where, None, Q.MODE_DROP,
                )
                total += len(es)
                paths.add(path)
                if not more:
                    return total, paths
                sa = cover

        for where, want in (
            (["cmp", "big", ">", base + 1], n - 2),
            (["cmp", "big", "==", base + 1], 1),
            (["range", "big", base + 1, base + 3], 2),
            (["cmp", "frac", "<=", 7.1], 8),
        ):
            where = Q.validate_where(where)
            got, paths = await count(where)
            assert got == want, where
            assert paths == {"device"}, (where, paths)
            old = ss.MIN_VECTORIZED_ENTRIES
            ss.MIN_VECTORIZED_ENTRIES = 10**9
            tree._drop_scan_stage()
            try:
                golden, _ = await count(where)
            finally:
                ss.MIN_VECTORIZED_ENTRIES = old
                tree._drop_scan_stage()
            assert golden == want, where
        tree.close()

    run(main(), 120)


def test_traced_filtered_scan_marks_filter_stage(tmp_dir):
    # Obs satellite (PR 13): a traced FILTERED scan separates
    # predicate/merge cost ("filter" stage) from page pulls
    # ("iterate"), so `blackbox_bench.py --attribute` can tell
    # where a slow filtered scan spends.
    async def main():
        node = await ClusterNode(
            make_config(tmp_dir), num_shards=1
        ).start()
        client = await DbeelClient.from_seed_nodes(
            [node.db_address], op_deadline_s=5.0
        )
        col = await client.create_collection("c", 1)
        await col.multi_set(
            {k: {"v": i} for i, k in enumerate(_keys(200))}
        )
        got = [
            kv
            async for kv in col.scan(
                max_bytes=2048,
                trace_id=8181,
                filter=["cmp", "v", "<", 150],
            )
        ]
        assert len(got) == 150
        dump = await client.trace_dump(*node.db_address)
        spans = [
            e
            for e in dump["entries"]
            if e.get("sampled") and e["op"] in ("scan", "scan_next")
        ]
        assert spans, dump["entries"][-3:]
        stage_names = {
            s for e in spans for s, _us in e["stages"]
        }
        assert {"pace", "iterate", "filter", "respond"} <= (
            stage_names
        )
        client.close()
        await node.stop()

    run(main(), 60)


def test_telemetry_rate_scan_rows_filtered(tmp_dir):
    # Obs satellite (PR 13): the telemetry ring derives
    # scan_rows_filtered_per_s from the scan.filter.rows_scanned
    # counter (sampled off the governor heartbeat).
    async def main():
        node = await ClusterNode(
            make_config(
                tmp_dir,
                telemetry_interval_ms=50,
                telemetry_ring=64,
            ),
            num_shards=1,
        ).start()
        client = await DbeelClient.from_seed_nodes(
            [node.db_address], op_deadline_s=5.0
        )
        col = await client.create_collection("c", 1)
        await col.multi_set(
            {k: {"v": i} for i, k in enumerate(_keys(300))}
        )
        for _ in range(3):
            assert (
                await col.count(filter=["cmp", "v", ">=", 0])
                == 300
            )
            await asyncio.sleep(0.12)
        ring = node.shards[0].telemetry.ring
        rates = ring.rates()
        assert "scan_rows_filtered_per_s" in rates
        # The sampled counter series saw the filter work.
        series = ring.series("scan.filter.rows_scanned")
        assert series and series[-1] >= 300 * 3
        client.close()
        await node.stop()

    run(main(), 60)


def test_agg_partial_combine_rules_exact():
    # The partial-state combine rules the cursor and per-arc merge
    # rely on: int exactness, Shewchuk float exactness under
    # arbitrary merge orders, min/max nil-identity.
    import math
    import random

    from dbeel_tpu import query as Q

    rng = random.Random(99)
    values = [
        rng.choice(
            [
                rng.randrange(-(10**18), 10**18),
                rng.uniform(-1e10, 1e10),
                1e-9 * rng.random(),
            ]
        )
        for _ in range(500)
    ]
    # One sequential golden fold...
    golden = Q.agg_new()
    for v in values:
        Q.agg_fold(golden, "sum", v)
    # ...vs a scattered fold merged in a shuffled order.
    parts = []
    for i in range(0, 500, 37):
        st = Q.agg_new()
        for v in values[i : i + 37]:
            Q.agg_fold(st, "sum", v)
        parts.append(st)
    rng.shuffle(parts)
    merged = Q.agg_new()
    for p in parts:
        Q.agg_merge(merged, p)
    assert Q.agg_result(merged, "sum") == Q.agg_result(
        golden, "sum"
    )
    assert merged[0] == golden[0] == 500
    # The float part is EXACTLY fsum of the float terms.
    floats = [v for v in values if isinstance(v, float)]
    ints = sum(v for v in values if isinstance(v, int))
    assert Q.agg_result(golden, "sum") == ints + math.fsum(floats)
    # min/max nil identity.
    empty = Q.agg_new()
    Q.agg_merge(empty, golden)
    assert empty[3] == golden[3] and empty[4] == golden[4]
    assert Q.agg_result(Q.agg_new(), "min") is None
    assert Q.agg_result(Q.agg_new(), "count") == 0


def test_stage_invalidated_by_writes_and_compaction(tmp_dir):
    from dbeel_tpu.storage.lsm_tree import LSMTree

    async def main():
        tree = LSMTree.open_or_create(
            tmp_dir + "/t", capacity=4096
        )
        for i in range(600):
            await tree.set_with_timestamp(
                msgpack.packb(f"k{i:05d}"),
                msgpack.packb({"v": i}),
                1000 + i,
            )
        es, _ = await tree.scan_page(
            0, 0, None, None, 10, 4096, True
        )
        assert tree._scan_stage is not None
        stage1 = tree._scan_stage
        # A write invalidates via the token...
        await tree.set_with_timestamp(
            msgpack.packb("zz"), msgpack.packb(1), 5
        )
        es2, _ = await tree.scan_page(
            0, 0, None, None, 10**6, 1 << 22, True
        )
        assert tree._scan_stage is not stage1
        assert any(e[0] == msgpack.packb("zz") for e in es2)
        # ...and a flush/table swap drops the cached stage EAGERLY
        # (compaction's reader drain must never wait on an idle
        # cached stage).
        assert tree._scan_stage is not None
        await tree.flush()
        assert tree._scan_stage is None
        assert tree._scan_stage_list is None
        tree.close()

    run(main(), 60)
