#!/usr/bin/env python3
"""Cluster chaos soak (VERDICT r4 #5).

3 nodes x 2 shards, RF=3 collection, sustained mixed quorum load
(consistency=2 sets / gets / deletes from single-writer-per-key
workers) while a churn loop SIGKILLs a random node and restarts it on
a cadence — so failure detection, Dead/Alive gossip, removal+addition
migration, hinted-handoff replay and bucketed anti-entropy all fire
repeatedly (the reference's longest test horizon is seconds,
test_utils/src/lib.rs:159-170; this is where matching becomes
beating).

Invariants checked at the end (exit 1 on violation):
  1. ZERO acked-write loss: every key's final value version >= the
     last version whose quorum Set was acked (reads at
     consistency=RF so all live replicas are consulted).
  2. Full convergence: after a quiet window, all RF replicas of every
     key answer the same get_digest (ts, value-hash) — byte-equal
     replica state, checked over the remote shard plane.
  3. Resource ceilings: per-process RSS growth, fd count and thread
     count are bounded across the whole run (threads must stay flat:
     the io_uring sync hub adds none per WAL).

Optional phases: ``--disk-faults`` (bit flip + ENOSPC window),
``--partition`` (asymmetric partition on one node during quorum
writes → WAL-backed hints → heal by clean restart → all replicas
byte-agree within the hint-drain SLO), and ``--churn`` (elastic
membership: >= 3 add/remove/replace cycles on the vnode ring under
open-loop load → zero acked loss, bounded p99, byte-agreement).

Every node is started with ``--compaction-backend native``: several
nodes share the host, a chip belongs to one process, and host merges
are what this soak has exercised all along.

Usage:  python chaos_soak.py [--duration 900] [--churn-period 75]
            [--down-time 18] [--report chaos_soak_report.json]
"""

import argparse
import asyncio
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

import msgpack  # noqa: E402

from dbeel_tpu.client import Consistency, DbeelClient  # noqa: E402
from dbeel_tpu.cluster.remote_comm import (  # noqa: E402
    RemoteShardConnection,
)
from dbeel_tpu.errors import (  # noqa: E402
    ERROR_CLASSES,
    CasConflict,
    classify_error,
)
from dbeel_tpu.cluster.messages import ShardRequest  # noqa: E402
from dbeel_tpu.utils.murmur import hash_bytes  # noqa: E402

PORT_BASE = 12700  # db ports 12700..; remote +10000; gossip +20000
N_NODES = 3
SHARDS = 2
RF = 3
COLLECTION = "soak"
# Tracing plane (ISSUE 9): soak nodes run with modest span sampling
# so the final report can attribute WHERE slow-tail time went (and
# the per-phase trace_dump files land as CI artifacts).
TRACE_SAMPLE = 256
# Telemetry plane (ISSUE 11): continuous time-series sampling on
# every soak node, so each phase's report block carries the health
# watchdog's verdict and the cluster_stats rollup (and the per-phase
# telemetry ring dumps land as CI artifacts beside the trace dumps).
TELEMETRY_INTERVAL_MS = 2000
# Elastic membership (ISSUE 18): every soak node runs a vnode ring —
# ownership moves in many small arcs on membership changes, which is
# the regime the --churn phase (and the token-aware digest scan)
# exist to exercise.  Migration streaming is governor-paced; the rate
# is generous so quick-mode convergence never stalls on the throttle.
VNODES = 8
MIGRATION_KEYS_PER_SEC = 4000


def log(*a):
    print(f"[soak {time.strftime('%H:%M:%S')}]", *a, flush=True)


class Node:
    def __init__(self, i):
        self.i = i
        self.name = f"soak{i}"
        self.dir = tempfile.mkdtemp(prefix=f"chaos-n{i}-")
        self.db_port = PORT_BASE + 10 * i
        self.remote_port = self.db_port + 10000
        self.gossip_port = self.db_port + 20000
        self.proc = None
        self.log_path = os.path.join(
            tempfile.gettempdir(), f"chaos_n{i}.log"
        )

    def start(self, seeds, extra_env=None, extra_argv=None):
        env = {
            **os.environ,
            "PYTHONPATH": REPO
            + (
                ":" + os.environ["PYTHONPATH"]
                if os.environ.get("PYTHONPATH")
                else ""
            ),
            # A clean restart must not inherit a fault armed for a
            # previous incarnation of this node.
            "DBEEL_DISK_FAULTS": "",
            "DBEEL_REMOTE_FAULTS": "",
            "DBEEL_REMOTE_FAULTS_DELAY_S": "",
            **(extra_env or {}),
        }
        argv = [
            sys.executable, "-m", "dbeel_tpu.server.run",
            "--dir", self.dir,
            "--name", self.name,
            "--port", str(self.db_port),
            "--remote-shard-port", str(self.remote_port),
            "--gossip-port", str(self.gossip_port),
            "--shards", str(SHARDS),
            # Several nodes share this host and a chip belongs to
            # one process: every node merges on the host.
            "--compaction-backend", "native",
            "--wal-sync",
            "--default-replication-factor", str(RF),
            "--failure-detection-interval", "500",
            "--anti-entropy-interval", "5000",
            "--trace-sample", str(TRACE_SAMPLE),
            "--telemetry-interval", str(TELEMETRY_INTERVAL_MS),
            "--vnodes", str(VNODES),
            "--migration-keys-per-sec", str(MIGRATION_KEYS_PER_SEC),
        ]
        if seeds:
            argv += ["--seed-nodes", *seeds]
        if extra_argv:
            argv += list(extra_argv)
        self.proc = subprocess.Popen(
            argv, env=env,
            stdout=open(self.log_path, "ab"),
            stderr=subprocess.STDOUT,
        )

    def kill(self):
        if self.proc and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)
            self.proc.wait()

    def alive(self):
        return self.proc is not None and self.proc.poll() is None

    def resources(self):
        """(rss_mb, n_fds, n_threads) or None when down."""
        if not self.alive():
            return None
        pid = self.proc.pid
        try:
            rss = threads = 0
            with open(f"/proc/{pid}/status") as f:
                for ln in f:
                    if ln.startswith("VmRSS:"):
                        rss = int(ln.split()[1]) // 1024
                    elif ln.startswith("Threads:"):
                        threads = int(ln.split()[1])
            fds = len(os.listdir(f"/proc/{pid}/fd"))
            return (rss, fds, threads)
        except OSError:
            return None


async def wait_port(port, timeout=90):
    dl = time.time() + timeout
    while time.time() < dl:
        try:
            _r, w = await asyncio.open_connection("127.0.0.1", port)
            w.close()
            return True
        except OSError:
            await asyncio.sleep(0.3)
    return False


class Acks:
    """Single-writer-per-key journal of ACKED operations."""

    def __init__(self):
        self.last = {}  # key -> ("set", version) | ("delete", version)
        self.sets = 0
        self.gets = 0
        self.deletes = 0
        self.errors = 0
        # Failure taxonomy (dbeel_tpu.errors.ERROR_CLASSES): every
        # client-visible error, by class — the soak is no longer
        # counting blind (VERDICT r5 weak #4).
        self.error_classes = {c: 0 for c in ERROR_CLASSES}

    def record_error(self, exc: BaseException) -> None:
        self.errors += 1
        cls = classify_error(exc)
        if cls is None:
            cls = "other"
        self.error_classes[cls] = self.error_classes.get(cls, 0) + 1


async def worker(wid, stop, acks: Acks, client):
    col = client.collection(COLLECTION)
    rng = random.Random(1000 + wid)
    version = 0
    keys = [f"w{wid}k{j:03d}" for j in range(40)]
    while not stop.is_set():
        key = rng.choice(keys)
        version += 1
        roll = rng.random()
        try:
            if roll < 0.70:
                await asyncio.wait_for(
                    col.set(key, {"v": version, "w": wid},
                            consistency=Consistency.fixed(2)),
                    20,
                )
                acks.last[key] = ("set", version)
                acks.sets += 1
            elif roll < 0.92:
                await asyncio.wait_for(
                    col.get(key, consistency=Consistency.fixed(2)), 20
                )
                acks.gets += 1
            else:
                try:
                    await asyncio.wait_for(
                        col.delete(
                            key, consistency=Consistency.fixed(2)
                        ),
                        20,
                    )
                    acks.last[key] = ("delete", version)
                    acks.deletes += 1
                except Exception as e:
                    # A delete that errored/timed out is AMBIGUOUS: it
                    # may still have landed with a timestamp newer
                    # than the previously acked set, making both
                    # KeyNotFound and the old value legitimate final
                    # reads.  Taint the key for invariant 1 (digest
                    # convergence still checks it) until a later
                    # acked op overwrites the journal entry.
                    if key in acks.last:
                        acks.last[key] = ("any", version)
                    raise e
        except Exception as e:
            # Not acked: no journal entry.  KeyNotFound on get/delete
            # of a deleted key is a legitimate outcome, count apart.
            if "KeyNotFound" not in repr(e):
                acks.record_error(e)
        await asyncio.sleep(0)


async def churn(
    nodes, stop, period, down_time, seeds, stats, scale_churn=False
):
    """Kill/restart a random base node each cycle; with
    ``scale_churn``, every other cycle instead ADDS a brand-new node
    (fresh dir — addition migration streams it its ranges under load)
    and SIGKILLs it at the end of the cycle (removal migration +
    failure detection), exercising the planner paths the membership
    fuzz checks, at soak scale."""
    rng = random.Random(7)
    cycle = 0
    extra_i = N_NODES
    while not stop.is_set():
        try:
            await asyncio.wait_for(stop.wait(), period)
            return
        except asyncio.TimeoutError:
            pass
        cycle += 1
        if scale_churn and cycle % 2 == 0:
            extra = Node(extra_i)
            extra_i += 1
            log(f"CHURN: scale-out {extra.name} joins")
            extra.start(seeds)
            if not await wait_port(extra.db_port):
                log(f"CHURN: {extra.name} never came up!")
                stats["restart_failures"] += 1
                extra.kill()  # don't leak an orphan past the soak
                continue
            stats["scale_outs"] += 1
            try:
                await asyncio.wait_for(
                    stop.wait(), max(down_time * 2, 25.0)
                )
            except asyncio.TimeoutError:
                pass
            log(f"CHURN: scale-in — SIGKILL {extra.name}")
            extra.kill()
            stats["kills"] += 1
            continue
        victim = rng.choice(nodes)
        log(f"CHURN: SIGKILL {victim.name}")
        victim.kill()
        stats["kills"] += 1
        try:
            await asyncio.wait_for(stop.wait(), down_time)
            break
        except asyncio.TimeoutError:
            pass
        log(f"CHURN: restart {victim.name}")
        victim.start(seeds)
        ok = await wait_port(victim.db_port)
        if not ok:
            log(f"CHURN: {victim.name} failed to come back!")
            stats["restart_failures"] += 1


async def monitor(nodes, stop, samples):
    while not stop.is_set():
        row = {}
        for n in nodes:
            r = n.resources()
            if r:
                row[n.name] = r
        samples.append((time.time(), row))
        try:
            await asyncio.wait_for(stop.wait(), 20)
        except asyncio.TimeoutError:
            pass


async def collect_traces(nodes, label, dump_dir=None):
    """Fetch every alive node's flight-recorder dump (shard-0 port).
    With ``dump_dir``, persist each as trace_<label>_<node>.json —
    the nightly soak uploads these as build artifacts so a tail
    regression is diagnosable post-hoc.  Returns {node: dump}."""
    dumps = {}
    for n in nodes:
        if not n.alive():
            continue
        cl = None
        try:
            cl = await DbeelClient.from_seed_nodes(
                [("127.0.0.1", n.db_port)], op_deadline_s=5.0
            )
            dumps[n.name] = await cl.trace_dump()
        except Exception as e:
            log(f"trace_dump from {n.name} failed: {e!r}")
        finally:
            if cl is not None:
                cl.close()
    if dump_dir:
        os.makedirs(dump_dir, exist_ok=True)
        for name, dump in dumps.items():
            path = os.path.join(
                dump_dir, f"trace_{label}_{name}.json"
            )
            with open(path, "w") as f:
                json.dump(dump, f, indent=1, default=repr)
    return dumps


async def collect_health(nodes, label, dump_dir=None):
    """Telemetry plane (ISSUE 11): one phase's health evidence — the
    gossip-aggregated cluster_stats rollup from the first alive node
    plus each alive node's own watchdog findings; with ``dump_dir``,
    each node's full telemetry ring persists as
    telemetry_<label>_<node>.json beside the trace dumps (nightly CI
    uploads both)."""
    block = {
        "cluster_nodes_seen": 0,
        "nodes_reporting": 0,
        "cluster_missing": [],
        "findings_by_kind": {},
        "per_node": {},
    }
    dumps = {}
    rollup_done = False
    for n in nodes:
        if not n.alive():
            continue
        cl = None
        try:
            cl = await DbeelClient.from_seed_nodes(
                [("127.0.0.1", n.db_port)], op_deadline_s=5.0
            )
            if not rollup_done:
                cs = await cl.cluster_stats()
                block["cluster_nodes_seen"] = len(cs["nodes"])
                block["cluster_missing"] = cs["missing"]
                for name, digest in cs["nodes"].items():
                    for kind in digest.get("findings") or ():
                        block["findings_by_kind"][kind] = (
                            block["findings_by_kind"].get(kind, 0) + 1
                        )
                rollup_done = True
            health = (await cl.get_stats())["health"]
            block["nodes_reporting"] += 1
            block["per_node"][n.name] = sorted(
                {f["kind"] for f in health["findings"]}
            )
            dumps[n.name] = await cl.telemetry_dump()
        except Exception as e:
            log(f"health from {n.name} failed: {e!r}")
        finally:
            if cl is not None:
                cl.close()
    if dump_dir:
        os.makedirs(dump_dir, exist_ok=True)
        for name, dump in dumps.items():
            path = os.path.join(
                dump_dir, f"telemetry_{label}_{name}.json"
            )
            with open(path, "w") as f:
                json.dump(dump, f, indent=1, default=repr)
    return block


def trace_report_block(dumps):
    """The report's ``trace`` block: recorder totals plus the top-3
    dominant stages among SLOW ops (staged spans weighted by stage
    µs; minimal slow records count toward slow_entries but carry no
    attribution)."""
    stage_us = {}
    slow_entries = 0
    sampled = 0
    captured = 0
    for dump in dumps.values():
        captured += len(dump.get("entries", ()))
        for e in dump.get("entries", ()):
            if e.get("sampled"):
                sampled += 1
            if not e.get("slow"):
                continue
            slow_entries += 1
            for stage, us in e.get("stages") or ():
                stage_us[stage] = stage_us.get(stage, 0) + us
    top = sorted(
        stage_us.items(), key=lambda kv: kv[1], reverse=True
    )[:3]
    total = sum(stage_us.values()) or 1
    return {
        "nodes_dumped": len(dumps),
        "entries": captured,
        "sampled_entries": sampled,
        "slow_entries": slow_entries,
        "dominant_stages": [
            [stage, round(us / total, 3)] for stage, us in top
        ],
    }


async def quiet_wait(nodes, base_s):
    """Hint-drain-aware quiet window (ISSUE 20 satellite).

    The fixed ``sleep(quiet_window)`` raced the last churn restart's
    hint replay: on a slow/loaded host the replayed hints were still
    in flight when final_checks ran its single quorum-read pass, and
    the pre-existing quick-soak ``acked_writes_lost`` flake was that
    race, not real loss.  Instead: floor-wait briefly, then poll
    every live shard's ``convergence.hints_queued`` and hold until
    the cluster-wide total stays zero for a settle period (or a hard
    deadline passes — convergence stays asymptotic, final_checks'
    own digest poll still backstops it).  Returns a report block.
    """
    floor_s = min(base_s, 10.0)
    settle_s = min(max(base_s * 0.25, 3.0), 10.0)
    deadline_s = max(base_s * 4.0, base_s + 30.0)
    t0 = time.time()
    polls = 0
    total = -1
    quiet_since = None
    drained = False
    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", nodes[0].db_port)]
    )
    try:
        await asyncio.sleep(floor_s)
        while time.time() - t0 < deadline_s:
            total = 0
            seen = 0
            for n in nodes:
                if not n.alive():
                    continue
                for sid in range(SHARDS):
                    try:
                        s = await client.get_stats(
                            "127.0.0.1", n.db_port + sid
                        )
                        total += s["convergence"]["hints_queued"]
                        seen += 1
                    except Exception:
                        pass
            polls += 1
            now = time.time()
            if seen and total == 0:
                if quiet_since is None:
                    quiet_since = now
                if now - quiet_since >= settle_s:
                    drained = True
                    break
            else:
                quiet_since = None
            await asyncio.sleep(2.0)
    finally:
        client.close()
    return {
        "base_s": base_s,
        "deadline_s": round(deadline_s, 1),
        "waited_s": round(time.time() - t0, 1),
        "polls": polls,
        "hints_queued_final": total,
        "drained": drained,
        "note": (
            "deadline-aware hint-drain poll replaces the fixed "
            "quiet-window sleep; repeated --quick runs no longer "
            "race the final quorum-read pass against the last "
            "restart's hint replay (the old acked_writes_lost flake)"
        ),
    }


async def final_checks(nodes, acks, report):
    """Invariants 1 + 2 after the quiet window."""
    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", nodes[0].db_port)]
    )
    col = client.collection(COLLECTION)

    lost = []
    for key, (op, version) in sorted(acks.last.items()):
        if op == "any":
            continue  # ambiguous delete outcome: see worker()
        try:
            got = await col.get(key, consistency=Consistency.fixed(RF))
            if op == "delete":
                lost.append((key, f"acked delete v{version}, read {got}"))
            elif got["v"] < version:
                lost.append(
                    (key, f"acked v{version}, read v{got['v']}")
                )
        except Exception as e:
            if op == "delete" and "KeyNotFound" in repr(e):
                continue
            lost.append((key, f"acked {op} v{version}: {repr(e)[:80]}"))
    report["acked_keys_checked"] = len(acks.last)
    report["acked_writes_lost"] = len(lost)
    report["loss_samples"] = lost[:20]
    by_worker = {}
    for k, _why in lost:
        wid = k.split("k", 1)[0]
        by_worker[wid] = by_worker.get(wid, 0) + 1
    report["lost_by_worker"] = by_worker
    if lost:
        log("ACKED-WRITE LOSS:", lost[:10])

    # Convergence: all RF replicas byte-agree on every key's digest
    # (_replica_digest_scan — the same walk the --partition phase
    # uses).  Post-churn convergence is ASYMPTOTIC (hint replay +
    # bucketed anti-entropy catch a just-restarted replica up over a
    # few cycles): poll until every key's replicas byte-agree and
    # report the time it took, instead of a single snapshot that
    # punishes a short quiet window.
    t_conv0 = time.time()
    deadline = t_conv0 + 150
    scan_conns: dict = {}
    try:
        while True:
            divergent = await _replica_digest_scan(
                client, sorted(acks.last), scan_conns
            )
            if not divergent or time.time() > deadline:
                break
            log(
                f"{len(divergent)} keys still divergent; waiting on "
                "anti-entropy ..."
            )
            await asyncio.sleep(5)
    finally:
        for c in scan_conns.values():
            c.close_pool()
    report["convergence_s"] = round(time.time() - t_conv0, 1)
    if lost:
        # Post-mortem: every node's view of the ring + where each
        # lost key's value lives (per-shard digest with ts).
        views = {}
        for n in nodes:
            try:
                cl = await DbeelClient.from_seed_nodes(
                    [("127.0.0.1", n.db_port)]
                )
                mdv = await cl.get_cluster_metadata()
                views[n.name] = sorted(m.name for m in mdv.nodes)
                cl.close()
            except Exception as e:
                views[n.name] = f"ERR {repr(e)[:60]}"
        report["ring_views"] = views
        log("ring views:", views)
        probe = {}
        for key, why in lost[:6]:
            key_b = msgpack.packb(key, use_bin_type=True)
            row = {}
            for n in nodes:
                for sid in range(SHARDS):
                    addr = f"127.0.0.1:{n.remote_port + sid}"
                    try:
                        conn = RemoteShardConnection(addr)
                        resp = await conn.send_request(
                            ShardRequest.get_digest(
                                COLLECTION, key_b
                            )
                        )
                        row[f"{n.name}-{sid}"] = resp[2]
                    except Exception as e:
                        row[f"{n.name}-{sid}"] = repr(e)[:40]
            probe[key] = {"why": why, "digests": row}
            log("probe", key, probe[key])
        report["loss_probe"] = probe
    report["keys_digest_checked"] = len(acks.last)
    report["divergent_keys"] = len(divergent)
    report["divergent_samples"] = [
        (k, o, [str(d) for d in ds]) for k, o, ds in divergent[:10]
    ]
    if divergent:
        log("DIVERGENT:", divergent[:5])
    client.close()
    return not lost and not divergent


async def disk_fault_phase(nodes, acks, seeds, report):
    """--disk-faults: (a) flip one bit in a random on-disk sstable of
    a running node and read back every acked key at R=2 asserting ZERO
    client-visible corrupt payloads (the checksum plane quarantines,
    quorum merges clean replicas); (b) restart one node with an
    ENOSPC fault armed on its whole store (DBEEL_DISK_FAULTS env →
    storage/file_io seam) and drive reads+writes through the window
    asserting the node SERVES instead of crashing and the cluster
    keeps taking W=2 writes."""
    import glob

    phase = {"bitflip": None, "enospc": None}
    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", nodes[0].db_port)]
    )
    col = client.collection(COLLECTION)
    rng = random.Random(99)

    # ---- (a) bit flip on a live node's sstable -----------------------
    candidates = []
    for n in nodes:
        for sid in range(SHARDS):
            d = os.path.join(n.dir, f"{COLLECTION}-{sid}")
            candidates += [
                (n, p) for p in glob.glob(os.path.join(d, "*.data"))
            ]
    if candidates:
        victim, path = rng.choice(candidates)
        offset = max(0, os.path.getsize(path) // 2)
        with open(path, "r+b") as f:
            f.seek(offset)
            b = f.read(1) or b"\x00"
            f.seek(offset)
            f.write(bytes([b[0] ^ 0x01]))
        log(f"DISK-FAULTS: flipped a bit in {victim.name}:{path}")
        checked = corrupt = op_errors = 0
        for key, (op, version) in sorted(acks.last.items()):
            if op != "set":
                continue
            checked += 1
            try:
                got = await asyncio.wait_for(
                    col.get(key, consistency=Consistency.fixed(2)), 20
                )
                if (
                    not isinstance(got, dict)
                    or got.get("v", -1) < version
                ):
                    corrupt += 1
            except Exception as e:
                if "KeyNotFound" not in repr(e):
                    op_errors += 1
        phase["bitflip"] = {
            "victim": victim.name,
            "file": os.path.basename(path),
            "keys_checked": checked,
            "corrupt_payloads": corrupt,
            "op_errors": op_errors,
        }
        log(f"DISK-FAULTS bitflip: {phase['bitflip']}")
    else:
        log("DISK-FAULTS: no sstable on disk yet; bitflip skipped")

    # ---- (b) ENOSPC window on one node's store -----------------------
    victim = nodes[-1]
    log(f"DISK-FAULTS: restarting {victim.name} with ENOSPC armed")
    victim.kill()
    victim.start(
        seeds,
        extra_env={"DBEEL_DISK_FAULTS": f"{victim.dir}={'enospc'}"},
    )
    await wait_port(victim.db_port)
    await asyncio.sleep(2)
    writes_ok = write_errors = reads_ok = read_errors = 0
    for i in range(40):
        key = f"dfk{i:03d}"
        try:
            await asyncio.wait_for(
                col.set(
                    key, {"v": i}, consistency=Consistency.fixed(2)
                ),
                20,
            )
            writes_ok += 1
        except Exception:
            write_errors += 1
        try:
            await asyncio.wait_for(
                col.get(key, consistency=Consistency.fixed(2)), 20
            )
            reads_ok += 1
        except Exception as e:
            if "KeyNotFound" not in repr(e):
                read_errors += 1
    alive = victim.alive()
    phase["enospc"] = {
        "victim": victim.name,
        "writes_ok": writes_ok,
        "write_errors": write_errors,
        "reads_ok": reads_ok,
        "read_errors": read_errors,
        "victim_alive": alive,
    }
    log(f"DISK-FAULTS enospc: {phase['enospc']}")
    # Clean restart for the final convergence checks.
    victim.kill()
    victim.start(seeds)
    await wait_port(victim.db_port)
    client.close()
    report["disk_faults"] = phase
    ok = alive
    if phase["bitflip"] is not None:
        b = phase["bitflip"]
        ok = ok and b["corrupt_payloads"] == 0
        # Bounded error rate: the replica walk must absorb the
        # quarantined replica (generous bound — host weather).
        ok = ok and b["op_errors"] <= max(3, b["keys_checked"] // 4)
    e = phase["enospc"]
    ok = ok and e["writes_ok"] >= 20 and e["reads_ok"] >= 20
    return ok


async def _replica_digest_scan(client, keys, conns=None):
    """Per-key replica digests over the remote shard plane: returns
    (key, owners, digests) for every key whose RF owners do NOT
    byte-agree on (ts, value-hash).  The ONE replica-ownership walk +
    digest comparison, shared by the final convergence check and the
    --partition phase.  Pollers pass a shared ``conns`` dict so the
    pooled replica connections persist across iterations (the caller
    closes them); otherwise connections are per-call."""
    import bisect

    from dbeel_tpu.utils.murmur import hash_string

    md = await client.get_cluster_metadata()
    node_md = {m.name: m for m in md.nodes}
    ring = []
    for m in md.nodes:
        tokens = getattr(m, "tokens", None)
        for i, sid in enumerate(m.ids):
            # Vnode dialect: nodes advertising token lists own one
            # ring position per token; legacy nodes derive the single
            # token from the shard name, exactly like the servers do.
            if tokens is not None and i < len(tokens):
                for tok in tokens[i]:
                    ring.append((tok, m.name, sid))
            else:
                ring.append(
                    (hash_string(f"{m.name}-{sid}"), m.name, sid)
                )
    ring.sort()
    hashes = [r[0] for r in ring]
    own_conns = conns is None
    if own_conns:
        conns = {}
    divergent = []
    for key in keys:
        key_b = msgpack.packb(key, use_bin_type=True)
        h = hash_bytes(key_b)
        start = bisect.bisect_left(hashes, h) % len(ring)
        owners = []
        seen = set()
        for off in range(len(ring)):
            _hh, name, sid = ring[(start + off) % len(ring)]
            if name in seen:
                continue
            seen.add(name)
            owners.append((name, sid))
            if len(owners) == RF:
                break
        digests = []
        for name, sid in owners:
            addr = (
                f"{node_md[name].ip}:"
                f"{node_md[name].remote_shard_base_port + sid}"
            )
            conn = conns.get(addr)
            if conn is None:
                conn = RemoteShardConnection(addr, pooled=True)
                conns[addr] = conn
            try:
                resp = await conn.send_request(
                    ShardRequest.get_digest(COLLECTION, key_b)
                )
                digests.append(resp[2])
            except Exception as e:
                digests.append(f"ERR {repr(e)[:60]}")
        if any(d != digests[0] for d in digests[1:]):
            divergent.append((key, owners, digests))
    if own_conns:
        for c in conns.values():
            c.close_pool()
    return divergent


async def partition_phase(nodes, seeds, report, quick):
    """--partition: restart one node with an ASYMMETRIC partition
    armed (DBEEL_REMOTE_FAULTS → the remote_comm.set_fault seam: the
    victim cannot reach any peer's shard plane; peers reach it fine),
    drive quorum writes through the window — victim-coordinated
    fan-outs fail/skip their replicas and queue WAL-backed hints —
    then heal with a CLEAN restart (the hint log must survive it) and
    assert every phase key's RF replicas byte-agree within the
    hint-drain SLO."""
    victim = nodes[1]
    peer_addrs = [
        f"127.0.0.1:{n.remote_port + sid}"
        for n in nodes
        if n is not victim
        for sid in range(SHARDS)
    ]
    spec = ",".join(f"{a}=blackhole" for a in peer_addrs)
    arm_delay = 6.0
    log(
        f"PARTITION: restarting {victim.name}; asymmetric partition "
        f"against {len(peer_addrs)} peer shards arms in {arm_delay}s"
    )
    victim.kill()
    # The partition arms AFTER boot (delay seam): the victim must
    # first rediscover its peers and rejoin — a node that never knew
    # its peers existed would neither stall nor hint.  Short remote
    # timeouts for this incarnation: the blackhole seam hangs for the
    # read timeout, and those stalls should cost seconds, not the
    # production 15 s.
    victim.start(
        seeds,
        extra_env={
            "DBEEL_REMOTE_FAULTS": spec,
            "DBEEL_REMOTE_FAULTS_DELAY_S": str(arm_delay),
        },
        extra_argv=[
            "--remote-shard-connect-timeout", "1000",
            "--remote-shard-read-timeout", "2000",
            "--remote-shard-write-timeout", "2000",
        ],
    )
    await wait_port(victim.db_port)
    # Confirm the victim rejoined before the partition drops.
    rejoin_cl = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", victim.db_port)]
    )
    for _ in range(30):
        try:
            md = await rejoin_cl.get_cluster_metadata()
            if len(md.nodes) >= N_NODES:
                break
        except Exception:
            pass
        await asyncio.sleep(0.5)
    rejoin_cl.close()
    # Let the partition arm and the victim's failure detector declare
    # the unreachable peers dead (ring removal → departed-node
    # hinting takes over for the write fan-outs).
    await asyncio.sleep(arm_delay + (6 if quick else 10))

    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", victim.db_port)]
    )
    col = client.collection(COLLECTION)
    n_keys = 24 if quick else 60
    keys = [f"pk{i:03d}" for i in range(n_keys)]
    writes_ok = write_errors = 0
    for i, key in enumerate(keys):
        try:
            await asyncio.wait_for(
                col.set(
                    key, {"v": i, "p": 1},
                    consistency=Consistency.fixed(2),
                ),
                20,
            )
            writes_ok += 1
        except Exception:
            write_errors += 1
    hints_during = -1
    try:
        stats = await client.get_stats("127.0.0.1", victim.db_port)
        hints_during = stats["convergence"]["hints_queued"]
    except Exception as e:
        log(f"PARTITION: victim stats failed: {repr(e)[:80]}")
    client.close()
    log(
        f"PARTITION: {writes_ok}/{n_keys} writes acked; victim "
        f"hints_queued={hints_during}"
    )

    # Heal: clean restart — hints reload from the WAL-backed log and
    # the periodic drain replays them once peers are rediscovered.
    log(f"PARTITION: healing (clean restart of {victim.name})")
    victim.kill()
    victim.start(seeds)
    await wait_port(victim.db_port)
    slo_s = 60.0 if quick else 120.0
    t0 = time.time()
    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", nodes[0].db_port)]
    )
    scan_conns: dict = {}
    try:
        while True:
            divergent = await _replica_digest_scan(
                client, keys, scan_conns
            )
            if not divergent or time.time() - t0 > slo_s:
                break
            log(
                f"PARTITION: {len(divergent)} keys still divergent; "
                "waiting on hint drain ..."
            )
            await asyncio.sleep(3)
    finally:
        for c in scan_conns.values():
            c.close_pool()
    convergence_s = round(time.time() - t0, 1)
    hints_replayed = 0
    for n in nodes:
        for sid in range(SHARDS):
            try:
                s = await client.get_stats(
                    "127.0.0.1", n.db_port + sid
                )
                hints_replayed += s["convergence"]["hints_replayed"]
            except Exception:
                pass
    client.close()
    phase = {
        "victim": victim.name,
        "keys": n_keys,
        "writes_ok": writes_ok,
        "write_errors": write_errors,
        "hints_queued_during": hints_during,
        "hints_replayed_total": hints_replayed,
        "hint_drain_slo_s": slo_s,
        "convergence_s": convergence_s,
        "divergent_after_slo": len(divergent),
        "divergent_samples": [
            (k, o, [str(d) for d in ds])
            for k, o, ds in divergent[:5]
        ],
    }
    report["partition"] = phase
    log(f"PARTITION: {phase}")
    ok = not divergent and writes_ok >= max(1, n_keys // 2)
    phase["pass"] = ok
    return ok


async def overload_phase(nodes, report, quick):
    """--overload: measure the SAME-SESSION sustainable closed-loop
    rate, then offer >= 3x that in OPEN LOOP (ops launch on a fixed
    schedule, never paced by responses) against the live cluster.
    Gates:
      * every node stays alive (sheds, never collapses/OOMs);
      * goodput (acked ops/s) stays >= 70% of the sustainable
        baseline, OR the node is honestly shedding (overload-class
        errors / shed counters) with admitted p99 still bounded —
        on a 2-core CI host the generator and the server contend for
        the SAME cpu at 3x offered load, so absolute goodput under
        pressure is host weather (BENCH.md r8), while "alive, honest,
        bounded" is the actual overload-control contract;
      * p99 of ADMITTED ops stays bounded (<= max(20x baseline p99,
        1s)) — queues cannot silently stretch into minutes;
      * overload surfaces honestly: overload-class client errors or
        server-side shed counters, never silent hangs;
      * the get_stats ``overload`` block is visible through BOTH
        clients (Python and compiled C)."""
    from dbeel_tpu.errors import ERROR_CLASS_OVERLOAD

    # 4s budget: admitted quorum ops need headroom over the baseline
    # p99 (hundreds of ms on this host class) while still making
    # stretched completions read as DEAD work server-side.
    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", nodes[0].db_port)], op_deadline_s=4.0
    )
    col = client.collection(COLLECTION)
    loop = asyncio.get_event_loop()

    # ---- same-session sustainable baseline (closed loop) -------------
    base_dur = 4.0 if quick else 8.0
    base_lat = []
    base_ok = 0
    base_stop = loop.time() + base_dur

    async def base_worker(wid):
        nonlocal base_ok
        i = 0
        while loop.time() < base_stop:
            i += 1
            t0 = time.perf_counter()
            try:
                await asyncio.wait_for(
                    col.set(
                        f"ovb{wid}x{i}", {"v": i},
                        consistency=Consistency.fixed(2),
                    ),
                    10,
                )
                base_lat.append(time.perf_counter() - t0)
                base_ok += 1
            except Exception:
                pass

    t0 = time.time()
    await asyncio.gather(*[base_worker(w) for w in range(8)])
    base_wall = max(0.001, time.time() - t0)
    sustainable = base_ok / base_wall
    base_lat.sort()
    base_p99 = (
        base_lat[int(0.99 * (len(base_lat) - 1))]
        if base_lat
        else 0.05
    )
    log(
        f"OVERLOAD: sustainable {sustainable:,.0f} ops/s, "
        f"baseline p99 {base_p99 * 1000:.1f} ms"
    )

    # ---- open-loop offered load >= 3x --------------------------------
    multiplier = 3.0
    offered = max(20.0, sustainable * multiplier)
    dur = 8.0 if quick else 15.0
    max_outstanding = 3000  # client memory bound, counted when hit
    inflight = set()
    ok = 0
    lat = []
    err: dict = {}
    not_launched = 0
    launched = 0

    async def one(i):
        nonlocal ok
        t0 = time.perf_counter()
        try:
            await asyncio.wait_for(
                col.set(
                    f"ovl{i}", {"v": i},
                    consistency=Consistency.fixed(2),
                ),
                10,
            )
            lat.append(time.perf_counter() - t0)
            ok += 1
        except Exception as e:
            cls = classify_error(e) or "other"
            err[cls] = err.get(cls, 0) + 1

    t_start = loop.time()
    tick = 0.02
    per_tick = offered * tick
    carry = 0.0
    while loop.time() - t_start < dur:
        carry += per_tick
        n = int(carry)
        carry -= n
        for _ in range(n):
            if len(inflight) >= max_outstanding:
                not_launched += 1
                continue
            launched += 1
            t = asyncio.ensure_future(one(launched))
            inflight.add(t)
            t.add_done_callback(inflight.discard)
        await asyncio.sleep(tick)
    wall = loop.time() - t_start
    if inflight:
        await asyncio.wait(inflight, timeout=15)
    goodput = ok / wall
    lat.sort()
    adm_p99 = lat[int(0.99 * (len(lat) - 1))] if lat else float("inf")
    p99_bound = max(20 * base_p99, 1.0)

    # ---- two-class open loop (QoS plane, ISSUE 14) -------------------
    # interactive + batch generators each offered 1.5x sustainable
    # (3x total): the class-priority contract says the HIGH class's
    # goodput share holds while the LOW class sheds first.  Gated
    # only when anyone actually shed — a host that absorbs 3x (the
    # r8 "absorbed regime") proves nothing about priority.
    cls_dur = 6.0 if quick else 12.0
    cls_clients = {}
    for cname in ("interactive", "batch"):
        cls_clients[cname] = await DbeelClient.from_seed_nodes(
            [("127.0.0.1", nodes[0].db_port)],
            op_deadline_s=4.0,
            qos_class=cname,
        )
    cls_stats = {
        cname: {"ok": 0, "launched": 0, "err": {}, "lat": []}
        for cname in cls_clients
    }
    # PER-CLASS outstanding caps (review r14): with one shared pool,
    # the class launched first each tick claims every freed slot —
    # the gates would then measure client launch ordering, not the
    # server's class priority.  Separate pools keep the OFFERED load
    # symmetric; only the server decides who gets served.
    cls_inflight = {cname: set() for cname in cls_clients}
    per_class_outstanding = max_outstanding // 2

    async def one_cls(cname, i):
        st = cls_stats[cname]
        t0 = time.perf_counter()
        try:
            await asyncio.wait_for(
                cls_clients[cname]
                .collection(COLLECTION)
                .set(
                    f"ovc-{cname}-{i}", {"v": i},
                    consistency=Consistency.fixed(2),
                ),
                10,
            )
            st["lat"].append(time.perf_counter() - t0)
            st["ok"] += 1
        except Exception as e:
            ecls = classify_error(e) or "other"
            st["err"][ecls] = st["err"].get(ecls, 0) + 1

    per_class_rate = max(10.0, sustainable * 1.5)
    t_start = loop.time()
    carry_i = carry_b = 0.0
    while loop.time() - t_start < cls_dur:
        carry_i += per_class_rate * tick
        carry_b += per_class_rate * tick
        for cname, carry in (
            ("interactive", int(carry_i)),
            ("batch", int(carry_b)),
        ):
            if cname == "interactive":
                carry_i -= carry
            else:
                carry_b -= carry
            st = cls_stats[cname]
            pool = cls_inflight[cname]
            for _ in range(carry):
                if len(pool) >= per_class_outstanding:
                    continue
                st["launched"] += 1
                t = asyncio.ensure_future(
                    one_cls(cname, st["launched"])
                )
                pool.add(t)
                t.add_done_callback(pool.discard)
        await asyncio.sleep(tick)
    cls_wall = loop.time() - t_start
    remaining = set().union(*cls_inflight.values())
    if remaining:
        await asyncio.wait(remaining, timeout=15)
    for c_ in cls_clients.values():
        c_.close()

    def _cls_block(cname):
        st = cls_stats[cname]
        l_ = sorted(st["lat"])
        return {
            "launched": st["launched"],
            "ok": st["ok"],
            "goodput_ops_per_s": round(st["ok"] / cls_wall, 1),
            "overload_errors": st["err"].get(
                ERROR_CLASS_OVERLOAD, 0
            ),
            "errors_by_class": dict(st["err"]),
            "admitted_p99_ms": round(
                (l_[int(0.99 * (len(l_) - 1))] * 1000)
                if l_
                else float("inf"),
                2,
            ),
        }

    i_blk = _cls_block("interactive")
    b_blk = _cls_block("batch")
    total_cls_sheds = (
        i_blk["overload_errors"] + b_blk["overload_errors"]
    )
    total_cls_ok = i_blk["ok"] + b_blk["ok"]
    i_share = (
        i_blk["ok"] / total_cls_ok if total_cls_ok else 0.0
    )
    # Gates (only binding when the load actually shed): the low
    # class's sheds dominate, and the high class holds at least its
    # fair (equal-offered) share of the served goodput.
    sheds_ordered = (
        total_cls_sheds == 0
        or b_blk["overload_errors"] >= i_blk["overload_errors"]
    )
    share_held = total_cls_sheds == 0 or i_share >= 0.45
    classes_pass = (
        sheds_ordered and share_held and total_cls_ok > 0
    )
    classes_block = {
        "offered_multiplier_per_class": 1.5,
        "duration_s": round(cls_wall, 1),
        "interactive": i_blk,
        "batch": b_blk,
        "interactive_goodput_share": round(i_share, 3),
        "batch_sheds_dominate": sheds_ordered,
        "share_held": share_held,
        "pass": classes_pass,
    }

    # ---- server-side counters + both clients' stats blocks -----------
    server_sheds = server_deadline_drops = bg_delays = 0
    py_block = True
    for n_ in nodes:
        for sid in range(SHARDS):
            try:
                s = await client.get_stats(
                    "127.0.0.1", n_.db_port + sid
                )
                ov = s.get("overload")
                if not isinstance(ov, dict) or not isinstance(
                    s.get("qos"), dict
                ):
                    py_block = False
                    continue
                server_sheds += ov.get("shed_ops", 0)
                server_deadline_drops += ov.get(
                    "deadline_drops", 0
                ) + ov.get("replica_deadline_drops", 0)
                bg_delays += ov.get("bg_delays", 0)
            except Exception as e:
                log(f"OVERLOAD: stats {n_.name}-{sid}: {repr(e)[:60]}")
                py_block = False
    native_block = False
    try:
        from dbeel_tpu.client.native_client import NativeDbeelClient

        ncli = NativeDbeelClient("127.0.0.1", nodes[0].db_port)
        nstats = ncli.get_stats()
        native_block = isinstance(
            nstats.get("overload"), dict
        ) and isinstance(nstats.get("qos"), dict)
        ncli.close()
    except Exception as e:
        log(f"OVERLOAD: native client stats failed: {repr(e)[:80]}")
    client.close()

    total_err = sum(err.values())
    overload_visible = (
        total_err == 0
        or err.get(ERROR_CLASS_OVERLOAD, 0) > 0
        or server_sheds > 0
        or server_deadline_drops > 0
    )
    alive = all(n_.alive() for n_ in nodes)
    phase = {
        "sustainable_ops_per_s": round(sustainable, 1),
        "baseline_p99_ms": round(base_p99 * 1000, 2),
        "offered_multiplier": multiplier,
        "offered_ops_per_s": round(offered, 1),
        "duration_s": round(wall, 1),
        "launched": launched,
        "not_launched_outstanding_cap": not_launched,
        "ok": ok,
        "errors_by_class": dict(err),
        "goodput_ops_per_s": round(goodput, 1),
        "goodput_ratio": round(goodput / max(1e-9, sustainable), 3),
        "admitted_p99_ms": round(adm_p99 * 1000, 2),
        "p99_bound_ms": round(p99_bound * 1000, 1),
        "server_sheds": server_sheds,
        "server_deadline_drops": server_deadline_drops,
        "bg_delays": bg_delays,
        "stats_overload_block_py": py_block,
        "stats_overload_block_native": native_block,
        "nodes_alive": alive,
        # QoS plane (ISSUE 14): the two-class open loop — high class
        # holds its goodput share, low class sheds first.
        "classes": classes_block,
    }
    # Honest shedding: the server visibly refused work (shed counters
    # or overload-class client errors) rather than hanging.  When the
    # node sheds honestly and admitted p99 stays bounded, absolute
    # goodput is generator-vs-server cpu weather on this host class
    # (BENCH.md r8), not an overload-control regression.
    honest_shed = (
        err.get(ERROR_CLASS_OVERLOAD, 0) > 0
        or server_sheds > 0
        or server_deadline_drops > 0
    )
    ok_gate = (
        alive
        and (goodput >= 0.70 * sustainable or honest_shed)
        and adm_p99 <= p99_bound
        and overload_visible
        and py_block
        and native_block
        and classes_pass
    )
    phase["pass"] = ok_gate
    report["overload"] = phase
    log(f"OVERLOAD: {phase}")
    return ok_gate


async def _await_member_count(probe, want, timeout):
    """Poll the serving node's cluster metadata until it advertises
    ``want`` members.  Returns (reached, last_seen) — callers report
    a timeout rather than hard-failing on it: the membership gates
    are loss/p99/convergence, not gossip timing."""
    dl = time.time() + timeout
    last = -1
    while time.time() < dl:
        try:
            md = await probe.get_cluster_metadata()
            last = len(md.nodes)
            if last == want:
                return True, last
        except Exception:
            pass
        await asyncio.sleep(1.0)
    return False, last


async def membership_churn_phase(nodes, seeds, report, quick):
    """--churn (elastic membership plane, ISSUE 18): >= 3 full
    add/remove/replace membership cycles against the vnode ring,
    under sustained OPEN-LOOP foreground load (ops launch on a fixed
    schedule, never paced by responses — membership changes cannot
    hide behind a slowed generator).  Each cycle: a brand-new node
    joins (addition migration streams its arcs, governor-paced), a
    base node is SIGKILLed while the newcomer holds its data
    (removal migration — the newcomer IS the replacement), the base
    node rejoins, and the newcomer scales back in.  Gates:
      * ZERO acked-write loss: every open-loop write acked at W=2
        during the churn reads back at consistency=RF at its acked
        version or newer;
      * foreground p99 of ACKED ops stays bounded vs the
        SAME-SESSION closed-loop baseline (<= max(20x baseline p99,
        1s)) — migration streaming must ride the governor instead of
        starving the data plane;
      * after the dust settles, all RF replicas of every journal key
        byte-agree (token-aware digest scan, polled to a convergence
        deadline);
      * the serving node's membership epoch GREW with the changes
        (>= 1 bump per cycle) and migrations actually ran — the
        epoch fence and the get_stats membership block are live, not
        decorative;
      * every base node is alive at the end, every added node came
        up."""
    probe = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", nodes[0].db_port)], op_deadline_s=5.0
    )
    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", nodes[0].db_port)], op_deadline_s=8.0
    )
    col = client.collection(COLLECTION)
    loop = asyncio.get_event_loop()
    t_phase0 = time.time()

    # ---- same-session foreground baseline (closed loop) --------------
    base_dur = 3.0 if quick else 8.0
    base_lat = []
    base_ok = 0
    base_stop = loop.time() + base_dur

    async def base_worker(wid):
        nonlocal base_ok
        i = 0
        while loop.time() < base_stop:
            i += 1
            t0 = time.perf_counter()
            try:
                await asyncio.wait_for(
                    col.set(
                        f"mcb{wid}x{i}", {"v": i},
                        consistency=Consistency.fixed(2),
                    ),
                    10,
                )
                base_lat.append(time.perf_counter() - t0)
                base_ok += 1
            except Exception:
                pass

    t0 = time.time()
    await asyncio.gather(*[base_worker(w) for w in range(4)])
    base_wall = max(0.001, time.time() - t0)
    sustainable = base_ok / base_wall
    base_lat.sort()
    base_p99 = (
        base_lat[int(0.99 * (len(base_lat) - 1))]
        if base_lat
        else 0.05
    )
    log(
        f"MEMBERSHIP: baseline {sustainable:,.0f} ops/s, "
        f"p99 {base_p99 * 1000:.1f} ms"
    )

    md0 = await probe.get_cluster_metadata()
    epoch0 = md0.epoch

    # ---- open-loop foreground load across every cycle ----------------
    # Half the sustainable rate: enough pressure that a starved data
    # plane shows up in p99, low enough that the generator itself
    # never becomes the bottleneck on a 2-core CI host.
    rate = max(25.0, min(sustainable * 0.5, 300.0))
    journal = {}  # key -> last acked monotone version
    lat = []
    fg_errors: dict = {}
    stop_load = asyncio.Event()

    async def one_op(i):
        key = f"mc{i % 500}"
        t0 = time.perf_counter()
        try:
            await asyncio.wait_for(
                col.set(
                    key, {"v": i},
                    consistency=Consistency.fixed(2),
                ),
                20,
            )
            lat.append(time.perf_counter() - t0)
            prev = journal.get(key, -1)
            if i > prev:
                journal[key] = i
        except Exception as e:
            cls = classify_error(e) or "other"
            fg_errors[cls] = fg_errors.get(cls, 0) + 1

    async def generator():
        inflight = set()
        seq = 0
        carry = 0.0
        tick = 0.02
        while not stop_load.is_set():
            carry += rate * tick
            n = int(carry)
            carry -= n
            for _ in range(n):
                if len(inflight) >= 800:
                    break  # bounded client memory; counted as p99 risk
                seq += 1
                t = asyncio.ensure_future(one_op(seq))
                inflight.add(t)
                t.add_done_callback(inflight.discard)
            await asyncio.sleep(tick)
        if inflight:
            await asyncio.wait(inflight, timeout=25)

    gen_task = asyncio.create_task(generator())

    # ---- add / remove / replace cycles -------------------------------
    cycles = 3 if quick else 4
    settle = 3.0 if quick else 6.0
    down = 4.0 if quick else 10.0
    join_to = 20.0 if quick else 60.0
    adds = removes = replaces = 0
    restart_failures = 0
    member_wait_timeouts = 0
    events = []
    for j in range(cycles):
        extra = Node(50 + j)  # ports clear of base + scale-churn nodes
        log(f"MEMBERSHIP: cycle {j + 1}/{cycles} — add {extra.name}")
        extra.start(seeds)
        if not await wait_port(extra.db_port):
            log(f"MEMBERSHIP: {extra.name} never came up!")
            restart_failures += 1
            extra.kill()
            continue
        adds += 1
        reached, _ = await _await_member_count(
            probe, N_NODES + 1, join_to
        )
        member_wait_timeouts += 0 if reached else 1
        await asyncio.sleep(settle)  # addition migration under load

        victim = nodes[1 + (j % (N_NODES - 1))]
        log(f"MEMBERSHIP: remove (SIGKILL) {victim.name}")
        victim.kill()
        removes += 1
        await asyncio.sleep(down)  # death gossip + removal migration

        log(f"MEMBERSHIP: replace — restart {victim.name}")
        victim.start(seeds)
        if await wait_port(victim.db_port):
            replaces += 1
        else:
            log(f"MEMBERSHIP: {victim.name} failed to come back!")
            restart_failures += 1
        reached, _ = await _await_member_count(
            probe, N_NODES + 1, join_to
        )
        member_wait_timeouts += 0 if reached else 1

        log(f"MEMBERSHIP: scale-in — SIGKILL {extra.name}")
        extra.kill()
        removes += 1
        reached, _ = await _await_member_count(
            probe, N_NODES, join_to * 2
        )
        member_wait_timeouts += 0 if reached else 1
        await asyncio.sleep(settle)
        events.append(
            {
                "added": extra.name,
                "removed": victim.name,
                "replaced_by": extra.name,
                "rejoined": victim.name,
            }
        )

    stop_load.set()
    await gen_task
    window_s = time.time() - t_phase0

    lat.sort()
    churn_p99 = (
        lat[int(0.99 * (len(lat) - 1))] if lat else float("inf")
    )
    p99_bound = max(20 * base_p99, 1.0)
    p99_ok = churn_p99 <= p99_bound

    md1 = await probe.get_cluster_metadata()
    epoch1 = md1.epoch
    epoch_ok = (epoch1 - epoch0) >= cycles

    # ---- zero acked-write loss ---------------------------------------
    lost = []
    for key, version in sorted(journal.items()):
        try:
            got = await asyncio.wait_for(
                col.get(key, consistency=Consistency.fixed(RF)), 20
            )
            if got["v"] < version:
                lost.append(
                    (key, f"acked v{version}, read v{got['v']}")
                )
        except Exception as e:
            lost.append(
                (key, f"acked v{version}: {repr(e)[:80]}")
            )
    if lost:
        log("MEMBERSHIP ACKED-WRITE LOSS:", lost[:10])

    # ---- replicas byte-agree after the dust settles ------------------
    t_conv0 = time.time()
    conv_deadline = t_conv0 + (120 if quick else 180)
    scan_conns: dict = {}
    try:
        while True:
            divergent = await _replica_digest_scan(
                probe, sorted(journal), scan_conns
            )
            if not divergent or time.time() > conv_deadline:
                break
            log(
                f"MEMBERSHIP: {len(divergent)} keys divergent; "
                "waiting on anti-entropy ..."
            )
            await asyncio.sleep(5)
    finally:
        for c in scan_conns.values():
            c.close_pool()
    convergence_s = round(time.time() - t_conv0, 1)

    # ---- membership stats block + migration evidence -----------------
    membership_block = None
    migrations_started = 0
    keys_migrated = 0
    fence_refusals = 0
    for n in nodes:
        if not n.alive():
            continue
        cl = None
        try:
            cl = await DbeelClient.from_seed_nodes(
                [("127.0.0.1", n.db_port)], op_deadline_s=5.0
            )
            mb = (await cl.get_stats()).get("membership")
            if mb:
                if membership_block is None:
                    membership_block = mb
                migrations_started += mb.get(
                    "migrations_started", 0
                )
                keys_migrated += mb.get("keys_migrated", 0)
                fence_refusals += mb.get("fence_refusals", 0)
        except Exception as e:
            log(f"membership stats from {n.name} failed: {e!r}")
        finally:
            if cl is not None:
                cl.close()
    stats_block_ok = bool(membership_block) and {
        "epoch",
        "vnodes",
        "arcs_owned",
        "migrations_active",
        "keys_migrated",
        "fence_refusals",
    } <= set(membership_block or ())
    migrations_seen = migrations_started > 0

    nodes_alive = all(n.alive() for n in nodes)
    ok_gate = (
        nodes_alive
        and not lost
        and not divergent
        and p99_ok
        and epoch_ok
        and migrations_seen
        and stats_block_ok
        and restart_failures == 0
        and adds == cycles
    )
    report["churn"] = {
        "window_s": round(window_s, 1),
        "cycles": cycles,
        "adds": adds,
        "removes": removes,
        "replaces": replaces,
        "events": events,
        "member_wait_timeouts": member_wait_timeouts,
        "restart_failures": restart_failures,
        "open_loop_ops_per_s": round(rate, 1),
        "fg_acked": len(lat),
        "fg_errors_by_class": fg_errors,
        "baseline_p99_ms": round(base_p99 * 1000, 1),
        "churn_p99_ms": (
            round(churn_p99 * 1000, 1)
            if churn_p99 != float("inf")
            else None
        ),
        "p99_bound_ms": round(p99_bound * 1000, 1),
        "p99_ok": p99_ok,
        "journal_keys": len(journal),
        "acked_writes_lost": len(lost),
        "loss_samples": lost[:10],
        "divergent_keys": len(divergent),
        "convergence_s": convergence_s,
        "epoch_initial": epoch0,
        "epoch_final": epoch1,
        "epoch_ok": epoch_ok,
        "migrations_started": migrations_started,
        "keys_migrated": keys_migrated,
        "fence_refusals": fence_refusals,
        "stats_membership_block": stats_block_ok,
        "migrations_seen": migrations_seen,
        "nodes_alive": nodes_alive,
        "pass": ok_gate,
    }
    log("MEMBERSHIP churn:", json.dumps(report["churn"])[:800])
    probe.close()
    client.close()
    return ok_gate


async def scan_phase(nodes, seeds, acks, report, quick):
    """--scan (streaming scan plane, ISSUE 12; filtered stream,
    ISSUE 13): full-collection scans AND predicate-pushdown scans
    WHILE a node churns (SIGKILL + restart mid-stream).  Gates:
    (1) both stream kinds keep completing through the outage — the
    cursor walk retries retryable chunks and every completed stream
    is sorted and duplicate-free, with every filtered result
    SATISFYING the predicate; (2) after the heal + a short quiet
    window, the scan's view byte-agrees with quorum multi_gets of
    the journal's acked keys, and the FILTERED view equals the
    quorum-read ground truth under the same predicate (a healed
    replica's stale copy must neither leak a non-matching doc in nor
    suppress a matching one); (3) the scan + filter stats blocks are
    visible through the client."""
    from dbeel_tpu import query as Q

    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", nodes[0].db_port)], op_deadline_s=12.0
    )
    col = client.collection(COLLECTION)
    victim = nodes[1]
    window_s = 20.0 if quick else 60.0
    down_s = 6.0 if quick else 15.0
    scans_completed = 0
    filtered_scans_completed = 0
    scan_errors = 0
    order_violations = 0
    predicate_violations = 0
    last_entries = 0
    # Workers write {"v": version, "w": wid}: a partial-selectivity
    # predicate over the worker lane (validated once, reused as the
    # ground-truth matcher below).
    wpred = Q.validate_where(["cmp", "w", "<=", 2])

    async def churner():
        await asyncio.sleep(2.0)
        log("SCAN: killing victim mid-scan")
        victim.kill()
        await asyncio.sleep(down_s)
        victim.start(seeds)
        await wait_port(victim.db_port)

    churn_task = asyncio.create_task(churner())
    t0 = time.time()
    flip = 0
    while time.time() - t0 < window_s:
        filtered = flip % 2 == 1
        flip += 1
        try:
            keys = []
            if filtered:
                async for k, v in col.scan(filter=wpred):
                    keys.append(k)
                    if not (
                        isinstance(v, dict) and v.get("w", 99) <= 2
                    ):
                        predicate_violations += 1
                filtered_scans_completed += 1
            else:
                async for k, _v in col.scan():
                    keys.append(k)
                scans_completed += 1
                last_entries = len(keys)
            # Stream order is ENCODED-key byte order (the storage
            # order) by contract — compare in that domain: python
            # string order diverges on mixed-length keys (fixstr
            # headers sort all 4-char keys before any 5-char one,
            # e.g. the overload phase's ovl9 < ovl10 on the wire but
            # not in str order).
            enc = [msgpack.packb(k, use_bin_type=True) for k in keys]
            if enc != sorted(enc) or len(enc) != len(set(enc)):
                order_violations += 1
        except Exception as e:
            scan_errors += 1
            log(f"SCAN: stream failed ({classify_error(e)}): {e!r}")
            await asyncio.sleep(1.0)
    await churn_task
    await asyncio.sleep(5.0 if quick else 15.0)  # heal window

    # Merge correctness under (possibly still-healing) divergence:
    # the scan and a quorum multi_get must tell the same story for
    # the journal's keys.
    final = {}
    async for k, v in col.scan():
        final[k] = v
    filtered_final = {}
    async for k, v in col.scan(filter=wpred):
        filtered_final[k] = v
    filtered_count = await col.count(filter=wpred)
    journal_keys = sorted(acks.last)[:400]
    got = await col.multi_get(journal_keys)
    disagree = []
    filtered_disagree = []
    for k, v in zip(journal_keys, got):
        if v is None:
            if k in final:
                disagree.append(k)
        elif final.get(k) != v:
            disagree.append(k)
        # Healed filtered view == quorum ground truth under the SAME
        # predicate (golden evaluator both sides).
        matches = v is not None and Q.match_entry(
            wpred, msgpack.packb(k), msgpack.packb(v)
        )
        if matches != (k in filtered_final) or (
            matches and filtered_final.get(k) != v
        ):
            filtered_disagree.append(k)
    stats = await client.get_stats(
        "127.0.0.1", nodes[0].db_port
    )
    block = stats.get("scan") or {}
    filter_block = block.get("filter") or {}
    client.close()
    alive = all(n_.alive() for n_ in nodes)
    ok_gate = (
        alive
        and scans_completed >= 1
        and filtered_scans_completed >= 1
        and order_violations == 0
        and predicate_violations == 0
        and not disagree
        and not filtered_disagree
        and filtered_count == len(filtered_final)
        and block.get("chunks", 0) > 0
    )
    phase = {
        "window_s": window_s,
        "scans_completed": scans_completed,
        "filtered_scans_completed": filtered_scans_completed,
        "scan_errors_during_churn": scan_errors,
        "order_violations": order_violations,
        "predicate_violations": predicate_violations,
        "final_scan_entries": last_entries,
        "filtered_final_entries": len(filtered_final),
        "filtered_count_verb": filtered_count,
        "journal_keys_compared": len(journal_keys),
        "scan_vs_multiget_disagreements": disagree[:10],
        "filtered_vs_quorum_disagreements": filtered_disagree[:10],
        "stats_scan_block": {
            k: block.get(k)
            for k in (
                "scans_started",
                "chunks",
                "bytes_streamed",
                "cursor_resumes",
                "sheds",
                "replica_errors",
            )
        },
        "stats_filter_block": {
            k: filter_block.get(k)
            for k in (
                "specs_served",
                "rows_scanned",
                "rows_returned",
                "bytes_saved",
            )
        },
        "nodes_alive": alive,
        "pass": ok_gate,
    }
    report["scan"] = phase
    log(f"SCAN: {phase}")
    return ok_gate


async def cas_phase(nodes, seeds, report, quick):
    """--cas (atomic plane, ISSUE 19): the lost-update gate.  N
    closed-loop clients drive counter increments THROUGH the CAS
    plane (read -> cas(expect_value=current) -> on conflict re-read
    and retry) plus an expect_absent uniqueness workload, while the
    cluster takes a replica SIGKILL, an asymmetric partition + heal,
    and one membership add/remove cycle.  Every counter value embeds
    a per-client slot map ``{"n": total, "by": {wid: count}}`` so the
    gate is exact even for AMBIGUOUS outcomes (timeout after the
    decider may or may not have applied):
      * zero lost updates:  by[wid] >= unambiguously-acked[wid];
      * zero double-applies: by[wid] <= acked[wid] + ambiguous[wid];
      * internal consistency: n == sum(by.values()) on every counter;
      * uniqueness: per key at most ONE acked expect_absent winner,
        an acked winner's value is what reads back, and whatever
        reads back was written by an acked-or-ambiguous claimant;
      * all RF replicas byte-agree after convergence;
      * contention was real (server cas_conflicts moved) and the
        get_stats atomic block is live."""
    cons = Consistency.fixed(2)
    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", nodes[0].db_port)], op_deadline_s=10.0
    )
    col = client.collection(COLLECTION)

    # Baseline atomic counters (the soak may run other phases first).
    async def _atomic_totals():
        tot = {"cas_served": 0, "cas_conflicts": 0,
               "batches_committed": 0, "batches_refused": 0}
        block_keys = None
        for n in nodes:
            if not n.alive():
                continue
            for sid in range(SHARDS):
                try:
                    s = await client.get_stats(
                        "127.0.0.1", n.db_port + sid
                    )
                    blk = s.get("atomic") or {}
                    if blk and block_keys is None:
                        block_keys = set(blk)
                    for k in tot:
                        tot[k] += blk.get(k, 0)
                except Exception:
                    pass
        return tot, block_keys

    atomic0, _ = await _atomic_totals()

    n_clients = 4 if quick else 6
    n_counters = 4 if quick else 8
    counters = [f"casctr{i}" for i in range(n_counters)]
    n_uniq = 16 if quick else 40
    uniq_keys = [f"casuniq{i:03d}" for i in range(n_uniq)]

    acked = [dict((c, 0) for c in counters) for _ in range(n_clients)]
    ambiguous = [
        dict((c, 0) for c in counters) for _ in range(n_clients)
    ]
    conflicts_seen = [0] * n_clients
    uniq_acked: dict = {}       # key -> [wid, ...] acked winners
    uniq_ambiguous: dict = {}   # key -> [wid, ...] unknown outcomes
    stop = asyncio.Event()

    async def ctr_worker(wid):
        rng = random.Random(7000 + wid)
        while not stop.is_set():
            key = rng.choice(counters)
            me = str(wid)
            try:
                cur = None
                try:
                    cur = await asyncio.wait_for(
                        col.get(key, consistency=cons), 15
                    )
                except Exception as e:
                    if "KeyNotFound" not in repr(e):
                        raise
                if cur is None:
                    new = {"n": 1, "by": {me: 1}}
                    await asyncio.wait_for(
                        col.cas(
                            key, new, expect_absent=True,
                            consistency=cons,
                        ),
                        15,
                    )
                else:
                    by = dict(cur["by"])
                    by[me] = by.get(me, 0) + 1
                    new = {"n": cur["n"] + 1, "by": by}
                    await asyncio.wait_for(
                        col.cas(
                            key, new, expect_value=cur,
                            consistency=cons,
                        ),
                        15,
                    )
                acked[wid][key] += 1
            except CasConflict:
                # A decided refusal: definitively NOT applied — the
                # compliant retry is simply the next loop iteration's
                # fresh read.
                conflicts_seen[wid] += 1
            except Exception:
                # Timeout / not-owned walk exhaustion / overload
                # AFTER the decider may have applied: the slot map
                # settles the truth at the end of the phase.
                ambiguous[wid][key] += 1
                await asyncio.sleep(0.3)
            await asyncio.sleep(0)

    async def uniq_worker(wid, order):
        for key in order:
            if stop.is_set():
                return
            try:
                await asyncio.wait_for(
                    col.cas(
                        key, wid, expect_absent=True,
                        consistency=cons,
                    ),
                    15,
                )
                uniq_acked.setdefault(key, []).append(wid)
            except CasConflict:
                pass  # somebody else holds it: the designed outcome
            except Exception:
                uniq_ambiguous.setdefault(key, []).append(wid)
                await asyncio.sleep(0.2)
            await asyncio.sleep(0.05 if quick else 0.1)

    workers = [
        asyncio.create_task(ctr_worker(w)) for w in range(n_clients)
    ]
    for w in range(n_clients):
        order = list(uniq_keys)
        random.Random(8000 + w).shuffle(order)
        workers.append(asyncio.create_task(uniq_worker(w, order)))

    # ---- fault schedule under the CAS load ---------------------------
    settle = 3.0 if quick else 6.0
    await asyncio.sleep(settle)  # contention baseline, no faults

    # 1. Replica SIGKILL + restart: deciders die mid-stream; standby
    #    deciders may only stand in once the walk predecessors are
    #    marked Dead, and the restarted decider sits out its barrier.
    victim = nodes[2]
    log(f"CAS: SIGKILL {victim.name}")
    victim.kill()
    await asyncio.sleep(6.0 if quick else 12.0)
    victim.start(seeds)
    await wait_port(victim.db_port)
    await asyncio.sleep(settle)

    # 2. Asymmetric partition on another node + clean-restart heal:
    #    decided-but-unacked CAS outcomes must ride the hint log.
    victim = nodes[1]
    peer_addrs = [
        f"127.0.0.1:{n.remote_port + sid}"
        for n in nodes
        if n is not victim
        for sid in range(SHARDS)
    ]
    log(f"CAS: partitioning {victim.name} (asymmetric blackhole)")
    victim.kill()
    victim.start(
        seeds,
        extra_env={
            "DBEEL_REMOTE_FAULTS": ",".join(
                f"{a}=blackhole" for a in peer_addrs
            ),
            "DBEEL_REMOTE_FAULTS_DELAY_S": "3",
        },
        extra_argv=[
            "--remote-shard-connect-timeout", "1000",
            "--remote-shard-read-timeout", "2000",
            "--remote-shard-write-timeout", "2000",
        ],
    )
    await wait_port(victim.db_port)
    await asyncio.sleep(8.0 if quick else 16.0)
    log(f"CAS: healing {victim.name} (clean restart)")
    victim.kill()
    victim.start(seeds)
    await wait_port(victim.db_port)
    await asyncio.sleep(settle)

    # 3. One membership churn cycle: arcs move, the epoch fence and
    #    mid-migration not-owned refusals hit live CAS traffic.
    extra = Node(70)
    log(f"CAS: membership cycle — add {extra.name}")
    extra.start(seeds)
    cycle_ok = await wait_port(extra.db_port)
    if cycle_ok:
        probe = await DbeelClient.from_seed_nodes(
            [("127.0.0.1", nodes[0].db_port)], op_deadline_s=5.0
        )
        await _await_member_count(
            probe, N_NODES + 1, 20.0 if quick else 60.0
        )
        await asyncio.sleep(settle)  # addition migration under CAS
        log(f"CAS: membership cycle — scale {extra.name} back in")
        extra.kill()
        await _await_member_count(
            probe, N_NODES, 40.0 if quick else 120.0
        )
        probe.close()
    await asyncio.sleep(settle)

    stop.set()
    await asyncio.gather(*workers, return_exceptions=True)

    # ---- ring reconvergence: every node re-advertises the base ring --
    # An asymmetric false removal (a CPU-starved node dropping a peer
    # that never dropped it) heals via gossip re-announce, but racing
    # the digest scan / the caller's base-workload verify against that
    # heal turns a ring-view transient into phantom "lost" reads
    # refused with not-owned.  Wait it out, per node, bounded.
    ring_ok = True
    for n in nodes:
        try:
            pr = await DbeelClient.from_seed_nodes(
                [("127.0.0.1", n.db_port)], op_deadline_s=5.0
            )
            reached, last = await _await_member_count(
                pr, N_NODES, 60.0 if quick else 120.0
            )
            pr.close()
            if not reached:
                ring_ok = False
                log(f"CAS: {n.name} ring stuck at {last} members")
        except Exception as e:
            ring_ok = False
            log(f"CAS: ring probe {n.name} failed: {e!r}")

    # ---- convergence: replicas byte-agree on every phase key ---------
    all_keys = counters + uniq_keys
    t0 = time.time()
    conv_deadline = t0 + (90 if quick else 180)
    scan_conns: dict = {}
    try:
        while True:
            divergent = await _replica_digest_scan(
                client, all_keys, scan_conns
            )
            if not divergent or time.time() > conv_deadline:
                break
            log(
                f"CAS: {len(divergent)} keys divergent; waiting on "
                "hints/anti-entropy ..."
            )
            await asyncio.sleep(4)
    finally:
        for c in scan_conns.values():
            c.close_pool()
    convergence_s = round(time.time() - t0, 1)

    # ---- the lost-update / double-apply gate -------------------------
    lost = []       # acked increments missing from the slot map
    doubled = []    # slot counts above acked + ambiguous
    internal = []   # n != sum(by)
    final_counts = {}
    for key in counters:
        try:
            val = await asyncio.wait_for(
                col.get(key, consistency=Consistency.fixed(RF)), 20
            )
        except Exception as e:
            if "KeyNotFound" in repr(e) and not any(
                acked[w][key] for w in range(n_clients)
            ):
                continue  # never successfully created
            lost.append((key, f"unreadable: {repr(e)[:80]}"))
            continue
        by = val.get("by", {})
        final_counts[key] = val.get("n")
        if val.get("n") != sum(by.values()):
            internal.append((key, val.get("n"), dict(by)))
        for w in range(n_clients):
            applied = by.get(str(w), 0)
            if applied < acked[w][key]:
                lost.append(
                    (key, f"w{w} acked {acked[w][key]}, "
                          f"applied {applied}")
                )
            if applied > acked[w][key] + ambiguous[w][key]:
                doubled.append(
                    (key, f"w{w} applied {applied} > acked "
                          f"{acked[w][key]} + ambiguous "
                          f"{ambiguous[w][key]}")
                )

    uniq_double_acks = [
        (k, ws) for k, ws in uniq_acked.items() if len(ws) > 1
    ]
    uniq_lost = []
    uniq_foreign = []
    uniq_winners = 0
    for key in uniq_keys:
        try:
            got = await asyncio.wait_for(
                col.get(key, consistency=Consistency.fixed(RF)), 20
            )
        except Exception as e:
            if "KeyNotFound" in repr(e):
                if uniq_acked.get(key):
                    uniq_lost.append(
                        (key, f"acked by w{uniq_acked[key]}, "
                              "reads absent")
                    )
                continue
            uniq_lost.append((key, f"unreadable: {repr(e)[:80]}"))
            continue
        uniq_winners += 1
        ok_writers = set(uniq_acked.get(key, [])) | set(
            uniq_ambiguous.get(key, [])
        )
        if uniq_acked.get(key) and got != uniq_acked[key][0]:
            uniq_lost.append(
                (key, f"acked winner w{uniq_acked[key][0]}, "
                      f"reads {got!r}")
            )
        elif got not in ok_writers:
            uniq_foreign.append((key, got))

    atomic1, atomic_block_keys = await _atomic_totals()
    conflicts_server = (
        atomic1["cas_conflicts"] - atomic0["cas_conflicts"]
    )
    stats_block_ok = bool(atomic_block_keys) and {
        "cas_served",
        "cas_conflicts",
        "batches_committed",
        "batches_refused",
        "barrier_remaining_ms",
    } <= (atomic_block_keys or set())

    total_acked = sum(
        acked[w][c] for w in range(n_clients) for c in counters
    )
    total_ambiguous = sum(
        ambiguous[w][c] for w in range(n_clients) for c in counters
    )
    nodes_alive = all(n.alive() for n in nodes)
    ok = (
        nodes_alive
        and ring_ok
        and not lost
        and not doubled
        and not internal
        and not divergent
        and not uniq_double_acks
        and not uniq_lost
        and not uniq_foreign
        and total_acked > 0
        and conflicts_server > 0
        and stats_block_ok
    )
    report["cas"] = {
        "clients": n_clients,
        "counters": n_counters,
        "uniq_keys": n_uniq,
        "acked_increments": total_acked,
        "ambiguous_outcomes": total_ambiguous,
        "client_conflicts": sum(conflicts_seen),
        "server_cas_conflicts": conflicts_server,
        "server_cas_served": (
            atomic1["cas_served"] - atomic0["cas_served"]
        ),
        "final_counts": final_counts,
        "lost_updates": len(lost),
        "lost_samples": lost[:10],
        "double_applies": len(doubled),
        "double_samples": doubled[:10],
        "internal_mismatches": len(internal),
        "uniq_winners": uniq_winners,
        "uniq_double_acks": len(uniq_double_acks),
        "uniq_lost": len(uniq_lost),
        "uniq_lost_samples": uniq_lost[:10],
        "uniq_foreign_values": len(uniq_foreign),
        "divergent_keys": len(divergent),
        "convergence_s": convergence_s,
        "stats_atomic_block": stats_block_ok,
        "ring_reconverged": ring_ok,
        "nodes_alive": nodes_alive,
        "pass": ok,
    }
    log("CAS:", json.dumps(report["cas"])[:900])
    client.close()
    return ok


async def watch_phase(nodes, seeds, report, quick):
    """--watch: the Watch/CDC plane's loss gate (ISSUE 20).

    N subscribers stream a fresh RF=3 collection through a
    mid-stream replica SIGKILL+restart, an asymmetric partition +
    heal on a second node, and one scale-out/scale-in membership
    cycle — all while writers keep acking unique-key quorum writes.
    Each subscriber keeps a ledger of delivered (key, value); at the
    end every acked write must be present in EVERY ledger with the
    acked value (exactly-once or explicitly dup-flagged: a key
    re-delivered WITHOUT the dup flag is a protocol violation), and
    the client-side cursor monotonicity audit must count zero
    regressions.  Ambiguous (errored) writes may appear in ledgers —
    that's at-least-once on the write path, not a watch defect."""
    wcol_name = "soakw"
    n_subs = 3 if quick else 8
    n_writers = 2 if quick else 4
    seed_addrs = [("127.0.0.1", n.db_port) for n in nodes]

    setup = await DbeelClient.from_seed_nodes(seed_addrs)
    await setup.create_collection(wcol_name, replication_factor=RF)
    await asyncio.sleep(1)

    acked = {}  # key -> value dict (unique keys: written once)
    write_errors = 0
    writer_stop = asyncio.Event()

    async def writer(wid):
        nonlocal write_errors
        wcol = setup.collection(wcol_name)
        seq = 0
        while not writer_stop.is_set():
            seq += 1
            key = f"wk{wid}-{seq:05d}"
            value = {"v": seq, "w": wid}
            try:
                await asyncio.wait_for(
                    wcol.set(
                        key, value, consistency=Consistency.fixed(2)
                    ),
                    20,
                )
                acked[key] = value
            except Exception:
                # Not acked → not in the ledger contract.  The write
                # may still have landed (ambiguous); subscribers may
                # legitimately see it.
                write_errors += 1
            await asyncio.sleep(0.05)

    sub_stop = asyncio.Event()
    subs = []  # per-subscriber state dicts

    async def subscriber(si):
        state = {
            "got": {},
            "unflagged_dups": 0,
            "dup_samples": [],
            "poll_errors": 0,
            "watcher": None,
        }
        subs.append(state)
        cl = await DbeelClient.from_seed_nodes(seed_addrs)
        w = cl.collection(wcol_name).watcher(wait_ms=300)
        state["watcher"] = w
        try:
            while not sub_stop.is_set():
                try:
                    events = await asyncio.wait_for(
                        w.next_events(), 30
                    )
                except Exception:
                    # Retryable turbulence (killed coordinator,
                    # partition timeout, shed, fence): the cursor is
                    # intact in the watcher — back off and resume.
                    state["poll_errors"] += 1
                    await asyncio.sleep(0.5)
                    continue
                for key, value, ts, flags in events:
                    prev = state["got"].get(key)
                    if (
                        prev is not None
                        and not (flags & 1)
                        and int(ts) <= prev[1]
                    ):
                        # Same-or-older COMMIT redelivered without
                        # the dup flag: a protocol violation.  A
                        # strictly newer ts is a legitimate new
                        # version of the key (the writer client's
                        # internal retry re-committing after a lost
                        # ack under soak turbulence) — the stream
                        # must deliver both, unflagged.
                        state["unflagged_dups"] += 1
                        if len(state["dup_samples"]) < 5:
                            state["dup_samples"].append(
                                [key, int(ts), prev[1], flags]
                            )
                    if prev is None or int(ts) >= prev[1]:
                        state["got"][key] = (value, int(ts))
        finally:
            cl.close()

    log(f"WATCH: {n_subs} subscribers, {n_writers} writers")
    tasks = [
        asyncio.create_task(subscriber(i)) for i in range(n_subs)
    ]
    wtasks = [
        asyncio.create_task(writer(i)) for i in range(n_writers)
    ]
    await asyncio.sleep(3 if quick else 8)

    # Event 1: SIGKILL a replica mid-stream, then restart it.
    victim = nodes[2]
    log(f"WATCH: SIGKILL {victim.name} mid-stream")
    victim.kill()
    kills = 1
    await asyncio.sleep(4 if quick else 10)
    victim.start(seeds)
    assert await wait_port(victim.db_port)
    await asyncio.sleep(4 if quick else 8)

    # Event 2: asymmetric partition on a second node (its fan-outs
    # blackhole; peers still reach it), then heal by clean restart.
    pvictim = nodes[1]
    peer_addrs = [
        f"127.0.0.1:{n.remote_port + sid}"
        for n in nodes
        if n is not pvictim
        for sid in range(SHARDS)
    ]
    arm_delay = 4.0
    log(f"WATCH: asymmetric partition on {pvictim.name}")
    pvictim.kill()
    pvictim.start(
        seeds,
        extra_env={
            "DBEEL_REMOTE_FAULTS": ",".join(
                f"{a}=blackhole" for a in peer_addrs
            ),
            "DBEEL_REMOTE_FAULTS_DELAY_S": str(arm_delay),
        },
        extra_argv=[
            "--remote-shard-connect-timeout", "1000",
            "--remote-shard-read-timeout", "2000",
            "--remote-shard-write-timeout", "2000",
        ],
    )
    assert await wait_port(pvictim.db_port)
    kills += 1
    await asyncio.sleep(arm_delay + (6 if quick else 10))
    log(f"WATCH: healing {pvictim.name} (clean restart)")
    pvictim.kill()
    pvictim.start(seeds)
    assert await wait_port(pvictim.db_port)
    kills += 1
    partition_heals = 1
    await asyncio.sleep(3 if quick else 8)

    # Event 3: one membership churn cycle — a brand-new node joins
    # (addition migration moves arcs under live subscriptions), then
    # SIGKILL it (removal migration + failure detection).
    extra = Node(9)
    log(f"WATCH: scale-out {extra.name} joins")
    extra.start(seeds)
    churn_cycles = 0
    if await wait_port(extra.db_port):
        await asyncio.sleep(12 if quick else 25)
        log(f"WATCH: scale-in — SIGKILL {extra.name}")
        extra.kill()
        kills += 1
        churn_cycles = 1
    else:
        log(f"WATCH: {extra.name} never came up")
        extra.kill()
    await asyncio.sleep(3)

    writer_stop.set()
    await asyncio.gather(*wtasks, return_exceptions=True)
    log(
        f"WATCH: writers stopped — {len(acked)} acked, "
        f"{write_errors} errors; draining hints..."
    )
    t_drain0 = time.time()
    qw = await quiet_wait(nodes, 8.0 if quick else 20.0)

    # Ledger completion: poll until every subscriber holds every
    # acked write (hint replay may still be feeding tails).
    deadline = 60.0 if quick else 150.0
    t0 = time.time()
    while time.time() - t0 < deadline:
        incomplete = [
            s
            for s in subs
            if any(
                (s["got"].get(k) or (None,))[0] != v
                for k, v in acked.items()
            )
        ]
        if not incomplete:
            break
        await asyncio.sleep(1.5)
    drain_wait_s = round(time.time() - t_drain0, 1)
    sub_stop.set()
    await asyncio.gather(*tasks, return_exceptions=True)

    lost = 0
    lost_samples = []
    unflagged = 0
    dup_samples = []
    mono = 0
    dupf = 0
    poll_errors = 0
    for si, s in enumerate(subs):
        missing = [
            (k, v, s["got"].get(k))
            for k, v in sorted(acked.items())
            if (s["got"].get(k) or (None,))[0] != v
        ]
        lost += len(missing)
        lost_samples.extend(
            (si, k, f"acked {v}, got {g}") for k, v, g in missing[:3]
        )
        unflagged += s["unflagged_dups"]
        dup_samples.extend(
            [si] + smp for smp in s["dup_samples"][:3]
        )
        poll_errors += s["poll_errors"]
        w = s["watcher"]
        if w is not None:
            mono += w.monotonicity_violations
            dupf += w.dup_flagged

    # Server-side rollup of the watch stats block (informational:
    # counters reset with each restart, so these are floors).
    rollup = {
        k: 0
        for k in (
            "events_delivered",
            "catchup_replays",
            "handoff_resumes",
            "ring_evictions",
            "sheds",
            "dup_flagged",
        )
    }
    for n in nodes:
        for sid in range(SHARDS):
            try:
                st = await setup.get_stats(
                    "127.0.0.1", n.db_port + sid
                )
                for k in rollup:
                    rollup[k] += st["watch"][k]
            except Exception:
                pass
    setup.close()

    nodes_alive = all(n.alive() for n in nodes)
    ok = (
        len(acked) > 0
        and lost == 0
        and unflagged == 0
        and mono == 0
        and nodes_alive
    )
    report["watch"] = {
        "subscribers": n_subs,
        "writers": n_writers,
        "acked_writes": len(acked),
        "write_errors": write_errors,
        "delivered_lost": lost,
        "lost_samples": lost_samples[:10],
        "unflagged_duplicates": unflagged,
        "unflagged_dup_samples": dup_samples[:10],
        "cursor_monotonicity_violations": mono,
        "dup_flagged_events": dupf,
        "poll_errors": poll_errors,
        "kills": kills,
        "partition_heals": partition_heals,
        "churn_cycles": churn_cycles,
        "drain_wait_s": drain_wait_s,
        "quiet_wait": qw,
        "stats_watch_block": rollup,
        "nodes_alive": nodes_alive,
        "pass": ok,
    }
    log("WATCH:", json.dumps(report["watch"])[:900])
    return ok


async def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=900.0)
    ap.add_argument("--churn-period", type=float, default=75.0)
    ap.add_argument("--down-time", type=float, default=18.0)
    ap.add_argument("--workers", type=int, default=6)
    ap.add_argument("--quiet-window", type=float, default=30.0)
    ap.add_argument("--report", default="chaos_soak_report.json")
    ap.add_argument(
        "--keep-on-fail", action="store_true",
        help="leave the cluster running when invariants fail "
        "(live autopsy); prints the ports",
    )
    ap.add_argument(
        "--scale-churn", action="store_true",
        help="every other churn cycle adds a brand-new node under "
        "load (addition migration), then SIGKILLs it (removal)",
    )
    ap.add_argument(
        "--disk-faults", action="store_true",
        help="after churn: flip a bit in a live node's sstable "
        "(asserting zero corrupt client payloads) and run an ENOSPC "
        "window on one node's store (asserting it degrades to "
        "read-only instead of crashing)",
    )
    ap.add_argument(
        "--partition", action="store_true",
        help="after churn: impose an asymmetric partition on one node "
        "during quorum writes (its fan-outs fail and hint), heal it "
        "with a clean restart, and assert all replicas of every phase "
        "key byte-agree within the hint-drain SLO",
    )
    ap.add_argument(
        "--overload", action="store_true",
        help="after churn: offer >= 3x the same-session sustainable "
        "rate in open loop; assert the node sheds with retryable "
        "overload errors instead of hanging/OOMing, goodput stays >= "
        "70%% of sustainable (or the node is honestly shedding with "
        "admitted p99 still bounded), and both clients surface the "
        "get_stats overload block",
    )
    ap.add_argument(
        "--churn", action="store_true",
        help="after the base kill/restart loop: >= 3 full add/remove/"
        "replace membership cycles on the vnode ring under open-loop "
        "foreground load; assert zero acked-write loss, foreground "
        "p99 bounded vs the same-session baseline, replicas byte-"
        "agree within the convergence deadline, and the membership "
        "epoch + migration counters moved",
    )
    ap.add_argument(
        "--cas", action="store_true",
        help="after churn: N clients drive CAS-retry counter "
        "increments and an expect_absent uniqueness workload through "
        "a replica kill, a partition heal, and one membership cycle; "
        "assert zero lost updates, zero double-applies, at most one "
        "acked winner per unique key, and replica byte-agreement "
        "after convergence",
    )
    ap.add_argument(
        "--scan", action="store_true",
        help="after churn: full-collection streaming scans while one "
        "node SIGKILLs and heals mid-stream — scans must keep "
        "completing (sorted, duplicate-free), and after the heal the "
        "scan view must agree with quorum multi_gets of the acked "
        "journal keys",
    )
    ap.add_argument(
        "--watch", action="store_true",
        help="after churn: N subscribers stream a fresh collection "
        "through a mid-stream replica SIGKILL+restart, an asymmetric "
        "partition+heal, and one membership cycle while writers run; "
        "assert every acked write lands in every subscriber ledger "
        "exactly once or explicitly dup-flagged, and the resumable "
        "cursor audit counts zero monotonicity regressions",
    )
    ap.add_argument(
        "--trace-dump-dir", default="",
        help="persist each phase's final trace_dump per node as "
        "trace_<phase>_<node>.json here (nightly CI uploads them as "
        "build artifacts)",
    )
    ap.add_argument(
        "--quick", action="store_true",
        help="~60s smoke mode (reduced churn cadence): exercises the "
        "full report schema incl. the per-class error breakdown "
        "without the soak horizon; the error-rate gate is waived "
        "(sample too small)",
    )
    args = ap.parse_args()
    if args.quick:
        args.duration = min(args.duration, 60.0)
        args.churn_period = min(args.churn_period, 20.0)
        args.down_time = min(args.down_time, 6.0)
        args.quiet_window = min(args.quiet_window, 12.0)
        args.workers = min(args.workers, 4)

    nodes = [Node(i) for i in range(N_NODES)]
    seeds = [f"127.0.0.1:{nodes[0].remote_port}"]
    nodes[0].start([])
    assert await wait_port(nodes[0].db_port)
    for n in nodes[1:]:
        n.start(seeds)
    for n in nodes[1:]:
        assert await wait_port(n.db_port)
    await asyncio.sleep(3)

    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", nodes[0].db_port)]
    )
    await client.create_collection(COLLECTION, replication_factor=RF)
    await asyncio.sleep(1)

    acks = Acks()
    stop = asyncio.Event()
    stats = {"kills": 0, "restart_failures": 0, "scale_outs": 0}
    samples = []
    t0 = time.time()
    tasks = [
        asyncio.create_task(worker(w, stop, acks, client))
        for w in range(args.workers)
    ]
    tasks.append(
        asyncio.create_task(
            churn(
                nodes, stop, args.churn_period, args.down_time,
                seeds, stats, args.scale_churn,
            )
        )
    )
    tasks.append(asyncio.create_task(monitor(nodes, stop, samples)))

    while time.time() - t0 < args.duration:
        await asyncio.sleep(15)
        log(
            f"t={time.time() - t0:.0f}s acked: {acks.sets} sets,"
            f" {acks.gets} gets, {acks.deletes} deletes,"
            f" {acks.errors} errors, kills={stats['kills']}"
        )
    stop.set()
    await asyncio.gather(*tasks, return_exceptions=True)
    client.close()

    # Everyone back up for the final convergence check.
    for n in nodes:
        if not n.alive():
            n.start(seeds)
            await wait_port(n.db_port)
    log(
        f"quiet window: hint-drain-aware poll "
        f"(base {args.quiet_window:.0f}s)..."
    )
    quiet_block = await quiet_wait(nodes, args.quiet_window)
    log(f"quiet window: {quiet_block}")
    if args.scale_churn:
        # The last scale-churn node may still be gossiped Dead /
        # migrating out: wait until metadata is back to the base set.
        cl = await DbeelClient.from_seed_nodes(
            [("127.0.0.1", nodes[0].db_port)]
        )
        for _ in range(60):
            md = await cl.get_cluster_metadata()
            if len(md.nodes) == N_NODES:
                break
            await asyncio.sleep(1.0)
        cl.close()

    attempted = acks.sets + acks.gets + acks.deletes + acks.errors
    error_rate = acks.errors / attempted if attempted else 0.0
    report = {
        "duration_s": round(time.time() - t0, 1),
        "quick": args.quick,
        "workers": args.workers,
        "acked_sets": acks.sets,
        "acked_gets": acks.gets,
        "acked_deletes": acks.deletes,
        "op_errors_during_churn": acks.errors,
        "op_errors_by_class": dict(acks.error_classes),
        "client_error_rate": round(error_rate, 6),
        # The failure-aware request plane's headline gate: client
        # replica-walk failover + dead-peer fast-fail must make a
        # single dead node invisible when W acks of RF can mask it.
        "error_rate_ok": error_rate < 0.002,
        "kills": stats["kills"],
        "scale_outs": stats["scale_outs"],
        "restart_failures": stats["restart_failures"],
        "quiet_wait": quiet_block,
    }
    ok = True
    # Telemetry plane (ISSUE 11): per-phase watchdog findings +
    # cluster_stats rollup at each phase end (and telemetry ring
    # dumps as artifacts beside the trace dumps).
    health_phases = {}
    health_phases["churn"] = await collect_health(
        nodes, "churn", args.trace_dump_dir
    )
    if args.disk_faults:
        ok = await disk_fault_phase(nodes, acks, seeds, report)
        # Let quarantine repair + anti-entropy re-converge the
        # bit-flipped replica before the divergence scan.
        await asyncio.sleep(min(args.quiet_window, 15.0))
        await collect_traces(nodes, "disk_faults",
                             args.trace_dump_dir)
        health_phases["disk_faults"] = await collect_health(
            nodes, "disk_faults", args.trace_dump_dir
        )
    if args.partition:
        ok = (
            await partition_phase(nodes, seeds, report, args.quick)
        ) and ok
        await collect_traces(nodes, "partition", args.trace_dump_dir)
        health_phases["partition"] = await collect_health(
            nodes, "partition", args.trace_dump_dir
        )
    if args.overload:
        ok = (
            await overload_phase(nodes, report, args.quick)
        ) and ok
        await collect_traces(nodes, "overload", args.trace_dump_dir)
        health_phases["overload"] = await collect_health(
            nodes, "overload", args.trace_dump_dir
        )
        # Let the shed/backlogged writes' hints drain and windows
        # recover before the byte-equality scan.
        await asyncio.sleep(min(args.quiet_window, 15.0))
    if args.scan:
        ok = (
            await scan_phase(nodes, seeds, acks, report, args.quick)
        ) and ok
        await collect_traces(nodes, "scan", args.trace_dump_dir)
        health_phases["scan"] = await collect_health(
            nodes, "scan", args.trace_dump_dir
        )
    if args.cas:
        ok = (
            await cas_phase(nodes, seeds, report, args.quick)
        ) and ok
        await collect_traces(nodes, "cas", args.trace_dump_dir)
        health_phases["cas"] = await collect_health(
            nodes, "cas", args.trace_dump_dir
        )
        # Let lingering decided-but-unacked hints drain before any
        # later phase's divergence scan.
        await asyncio.sleep(min(args.quiet_window, 10.0))
    if args.churn:
        ok = (
            await membership_churn_phase(
                nodes, seeds, report, args.quick
            )
        ) and ok
        await collect_traces(nodes, "membership", args.trace_dump_dir)
        health_phases["membership"] = await collect_health(
            nodes, "membership", args.trace_dump_dir
        )
        # Let hinted handoff / anti-entropy settle the churn phase's
        # writes before the final whole-journal divergence scan.
        await asyncio.sleep(min(args.quiet_window, 10.0))
    if args.watch:
        ok = (
            await watch_phase(nodes, seeds, report, args.quick)
        ) and ok
        await collect_traces(nodes, "watch", args.trace_dump_dir)
        health_phases["watch"] = await collect_health(
            nodes, "watch", args.trace_dump_dir
        )
        # The watch phase's own kills/heals queue hints too; let them
        # drain before the final whole-journal divergence scan.
        await asyncio.sleep(min(args.quiet_window, 10.0))
    ok = (await final_checks(nodes, acks, report)) and ok
    # Tracing plane (ISSUE 9): where did the slow tail's time go?
    final_dumps = await collect_traces(
        nodes, "final", args.trace_dump_dir
    )
    report["trace"] = trace_report_block(final_dumps)
    report["health"] = {
        "phases": health_phases,
        "final": await collect_health(
            nodes, "final", args.trace_dump_dir
        ),
    }
    if not args.quick:
        # Quick mode waives the rate gate: one unlucky op in a tiny
        # sample would dominate the percentage.
        ok = ok and report["error_rate_ok"]

    # Invariant 3: resource ceilings.
    res = {}
    for n in nodes:
        series = [row[n.name] for _t, row in samples if n.name in row]
        if series:
            res[n.name] = {
                "rss_mb_first": series[0][0],
                "rss_mb_max": max(s[0] for s in series),
                "rss_mb_last": series[-1][0],
                "rss_mb_series": [s[0] for s in series],
                "fds_max": max(s[1] for s in series),
                "threads_max": max(s[2] for s in series),
            }
    report["resources"] = res
    threads_flat = all(
        r["threads_max"] <= 24 for r in res.values()
    )
    fds_ok = all(r["fds_max"] <= 512 for r in res.values())
    report["threads_flat"] = threads_flat
    report["fds_bounded"] = fds_ok
    ok = ok and threads_flat and fds_ok and not stats["restart_failures"]
    report["pass"] = ok

    with open(args.report, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    log(json.dumps(report, indent=1))
    if not ok and args.keep_on_fail:
        log("KEEPING CLUSTER UP for autopsy:",
            [(n.name, n.db_port, n.proc.pid if n.proc else None)
             for n in nodes])
        return 1
    for n in nodes:
        n.kill()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
