#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the store still starts, serves,
flushes, compacts and filters on the chip.

A smoke, not a measurement: every wall time it prints is an observation of
one cold run, never a rate under a metric's name.

    python chip_smoke.py              # one chip: serve, then major
    python chip_smoke.py --chips 4    # four chips: the mesh phase only
    python chip_smoke.py --tiny --rehearsal   # CPU rehearsal (tests)

The parent never imports JAX.  Each phase that needs the chip is ONE child
process at a time, and the next starts only after the last has exited:

serve   one node through ``python -m dbeel_tpu.server.run`` (2 shards, one
        process, ``--compaction-backend auto``).  The parent is the client:
        it loads YCSB-shaped documents (10 fields x 100 B plus one integer
        above 2^24), reads a seeded sample back against a model, overwrites
        and deletes some, checks ``count()``, checks filtered ``count`` and
        ``scan`` whose operands float32 cannot hold (on a client stamped
        ``interactive``), reports what one unstamped count met from the
        governor, and reads ``get_stats`` to see that the device did the
        merges and the masks.
major   the library surface at BASELINE config 2 (8 runs, 16 B keys, 64 B
        values): ``get_strategy("device")`` against ``get_strategy(
        "native")``, SHA-256 of the output triplet equal; then the filter
        lane's adversarial values against numpy on the chip.
mesh    (``--chips 4`` only) the distributed sample sort and the pipeline's
        ``mesh=`` form against the single-device strategy and the native
        oracle, byte-identical, with all four devices holding data.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
On a host whose JAX reports the cpu the run FAILS (``"ok": false``,
non-zero exit) unless ``--rehearsal`` says it is one — and a rehearsal says
so in that line; a cpu run is never printed as the chip.
"""

import argparse
import asyncio
import hashlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Outside the repository this import fails, and the script ends non-zero
# with no result line.
from dbeel_tpu.client import DbeelClient  # noqa: E402

# The node's listeners: below the kernel's range of source ports and
# above the blocks tests/harness.py hands out.
PORT_BLOCKS = range(17000, 19000, 16)
LOAD_BUDGET_S = 300.0
IDLE_BUDGET_S = 540.0
SCAN_BUDGET_S = 240.0
PROBE_BUDGET_S = 45.0
PIPELINE_MIN_BYTES = 64 << 20  # DeviceMergeStrategy.PIPELINE_MIN_BYTES

_MASK = (1 << 64) - 1


def say(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def sizes(tiny: bool, chips: int) -> tuple:
    """(documents the serve phase loads, keys the merge phase builds):
    a function of the two options alone, so the parent and its
    children agree without handing numbers to each other."""
    if chips == 4:
        # ~1M keys: what the sample sort's compile time allows.
        return 0, 32_768 if tiny else 1 << 20
    return (12_000, 40_000) if tiny else (1_000_000, 10_000_000)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    say(f"  ok: {what}")


# ----------------------------------------------------------------------
# Documents: YCSB's record (10 fields x 100 B) plus one integer field
# above 2^24, every byte a function of (--seed, index, version).
# ----------------------------------------------------------------------


def mix64(x):
    """splitmix64 finalizer — a bijection on 64-bit words, written so
    that Python ints and numpy uint64 arrays give the same bits."""
    if isinstance(x, np.ndarray):
        with np.errstate(over="ignore"):
            x = x.astype(np.uint64)
            x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
            return x ^ (x >> np.uint64(31))
    x &= _MASK
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK
    return x ^ (x >> 31)


class Docs:
    FIELDS, FIELD_BYTES = 10, 100
    N_BASE, N_SPAN = (1 << 24) + 1, 1 << 26

    def __init__(self, seed: int) -> None:
        self.salt = mix64(seed + 0x9E3779B97F4A7C15)
        rng = np.random.default_rng(seed)
        self.pool = (
            rng.integers(97, 123, size=(1 << 20) + 128, dtype=np.uint8)
            .tobytes()
            .decode("ascii")
        )

    def key(self, i: int) -> str:
        return f"user{mix64(i ^ self.salt):020d}"

    def n_of(self, i, version):
        """The integer field: above 2^24, where float32 holds only
        even numbers (and above 2^25 only multiples of four)."""
        if isinstance(i, np.ndarray):
            word = (
                i.astype(np.uint64) * np.uint64(4)
                + version.astype(np.uint64)
            ) ^ np.uint64(self.salt)
            return (
                mix64(word) % np.uint64(self.N_SPAN)
            ).astype(np.int64) + self.N_BASE
        return mix64((i * 4 + version) ^ self.salt) % self.N_SPAN + (
            self.N_BASE
        )

    def doc(self, i: int, version: int) -> dict:
        span = len(self.pool) - self.FIELD_BYTES
        out = {}
        for j in range(self.FIELDS):
            off = mix64(((i * 16 + j) * 4 + version) ^ self.salt) % span
            out[f"field{j}"] = self.pool[off : off + self.FIELD_BYTES]
        out["n"] = self.n_of(i, version)
        return out


# ----------------------------------------------------------------------
# Children
# ----------------------------------------------------------------------


def child_env(extra=None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env.update(extra or {})
    return env


def run_child(phase: str, args, work: str) -> dict:
    """Run one chip-holding phase as a child of this script; its last
    line of output is its JSON report."""
    argv = [
        sys.executable, os.path.abspath(__file__),
        "--child", phase, "--work", work,
        "--seed", str(args.seed), "--chips", str(args.chips),
    ]
    if args.tiny:
        argv.append("--tiny")
    proc = subprocess.Popen(
        argv, env=child_env(), stdout=subprocess.PIPE, text=True
    )
    last = ""
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line:
                if last:
                    say(last)
                last = line
        rc = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    try:
        report = json.loads(last)
    except ValueError:
        say(last)
        raise SmokeFailure(f"{phase} child printed no report (exit {rc})")
    if rc != 0 or not report.get("ok"):
        raise SmokeFailure(
            f"{phase} child failed (exit {rc}): {report.get('error')}"
        )
    return report


# ----------------------------------------------------------------------
# serve
# ----------------------------------------------------------------------


def free_port_block() -> int:
    """First block whose db (+0, +1), remote (+4, +5) and gossip (+8)
    ports can all be bound."""
    for base in PORT_BLOCKS:
        socks = []
        try:
            for off in (0, 1, 4, 5, 8):
                s = socket.socket()
                socks.append(s)
                s.bind(("127.0.0.1", base + off))
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise SmokeFailure("no free port block for the node")


def wait_port(port: int, proc, budget_s: float) -> None:
    t0 = time.time()
    while time.time() - t0 < budget_s:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"the node exited with code {proc.returncode} "
                "before it listened"
            )
        try:
            socket.create_connection(("127.0.0.1", port), 1).close()
            return
        except OSError:
            time.sleep(0.25)
    raise SmokeFailure(f"port {port} never opened in {budget_s:.0f}s")


def compile_lines(log_path: str) -> list:
    """The server child's XLA compilations (JAX_LOG_COMPILES)."""
    out = []
    with open(log_path, errors="replace") as f:
        for line in f:
            at = line.find("Finished XLA compilation of ")
            # JAX's logger and the root logger both print each one.
            if at >= 0 and line[at + 28 :].strip() not in out[-1:]:
                out.append(line[at + 28 :].strip())
    return out


def filter_operand(docs: Docs, loaded: int, version, deleted):
    """(n of every document, which are live, the operand): a stored
    value, odd and above 2^25, which float32 would move two or three
    integers away."""
    live = ~deleted
    n_all = docs.n_of(np.arange(loaded), version)
    odd = np.flatnonzero(live & (n_all % 2 == 1) & (n_all > 1 << 25))
    return n_all, live, int(n_all[odd[len(odd) // 2]])


async def scan_checks(col, docs: Docs, loaded: int, version, deleted):
    """count(), and a filtered count and two filtered scans whose
    operands float32 cannot hold, against the model."""
    n_all, live, t_val = filter_operand(docs, loaded, version, deleted)
    check(
        await col.count() == int(live.sum()),
        f"count() == {int(live.sum())} (the model)",
    )
    check(
        float(np.float32(t_val)) != float(t_val),
        f"filter operand {t_val} is not exact in float32",
    )
    want = int((live & (n_all >= t_val)).sum())
    got = await col.count(filter=["cmp", "n", ">=", t_val])
    check(got == want, f"count(n >= {t_val}) == {want} (the model)")
    want_eq = {
        docs.key(int(i)): docs.doc(int(i), int(version[i]))
        for i in np.flatnonzero(live & (n_all == t_val))
    }
    got_eq = {
        k: v async for k, v in col.scan(filter=["cmp", "n", "==", t_val])
    }
    check(
        got_eq == want_eq and len(want_eq) >= 1,
        f"scan(n == {t_val}) returns the model's "
        f"{len(want_eq)} document(s)",
    )
    lo_v, hi_v = t_val - 4001, t_val + 4001
    want_rng = {
        docs.key(int(i))
        for i in np.flatnonzero(live & (n_all >= lo_v) & (n_all < hi_v))
    }
    got_rng = {
        k
        async for k, _v in col.scan(filter=["range", "n", lo_v, hi_v])
    }
    check(
        got_rng == want_rng,
        f"scan({lo_v} <= n < {hi_v}) returns the model's "
        f"{len(want_rng)} keys",
    )


async def shard_stats(client, db_port: int) -> dict:
    """get_stats of both shards, by port (scan and overload counters
    are per shard; compaction's are the process's)."""
    return {
        port: await client.get_stats("127.0.0.1", port)
        for port in (db_port, db_port + 1)
    }


def scan_counters(shards: dict) -> dict:
    out = {}
    for shard in shards.values():
        for name, value in shard["scan"]["filter"].items():
            out[name] = out.get(name, 0) + value
        for name in ("chunks", "paced", "paced_s"):
            out[name] = out.get(name, 0) + shard["scan"][name]
    return out


async def default_client_probe(
    client, db_port: int, docs: Docs, loaded, version, deleted, before
) -> None:
    """One filtered count as an ordinary client sends it — unstamped,
    so batch-class — with what the node's governor made of it.  A
    report, not a check of speed: the governor's reading of a resting
    node is the QoS plane's policy, and this script only shows it.  The
    answer, where one comes inside the budget, is checked."""
    for port, shard in (await shard_stats(client, db_port)).items():
        say(
            f"  shard :{port} at rest: overload.signals="
            f"{json.dumps(shard['overload']['signals'], sort_keys=True)} "
            f"batch-class level="
            f"{shard['qos']['classes']['batch']['level']}"
        )
    n_all, live, t_val = filter_operand(docs, loaded, version, deleted)
    plain = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", db_port)], op_deadline_s=120.0
    )
    t0 = time.time()
    try:
        got = await asyncio.wait_for(
            plain.collection("usertable").count(
                filter=["cmp", "n", ">=", t_val]
            ),
            PROBE_BUDGET_S,
        )
    except asyncio.TimeoutError:
        got = None
    finally:
        plain.close()
    after = scan_counters(await shard_stats(client, db_port))
    met = (
        f"{after['chunks'] - before['chunks']} chunks, "
        f"{after['paced'] - before['paced']} of them paced for "
        f"{after['paced_s'] - before['paced_s']:.1f}s"
    )
    if got is None:
        say(
            f"PACED: default (batch-class) client: count(n >= {t_val}) "
            f"not done in {PROBE_BUDGET_S:.0f}s ({met}); the interactive "
            "client's same count is checked above"
        )
        return
    say(
        f"  default (batch-class) client: count(n >= {t_val}) in "
        f"{time.time() - t0:.1f}s wall ({met}) [smoke]"
    )
    check(
        got == int((live & (n_all >= t_val)).sum()),
        "the default client's filtered count equals the model",
    )


async def serve_checks(
    args, docs: Docs, n_docs: int, rehearsal: bool, db_port: int
):
    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", db_port)], op_deadline_s=120.0, pipeline_window=8
    )
    try:
        col = await client.create_collection("usertable", 1)
        stats = await client.get_stats("127.0.0.1", db_port)
        held = stats["compaction"]
        say(
            f"  node holds: platform={held['platform']} "
            f"kind={held['device_kind']} count={held['device_count']}"
        )
        if held["platform"] == "cpu" and not rehearsal:
            raise SmokeFailure(
                "JAX found no accelerator: the node holds the cpu"
            )

        # ---- load ---------------------------------------------------
        batch = 48  # ~53 KB of documents: one frame under the u16 bound
        next_start = 0
        t0 = time.time()
        cut_at = None

        async def loader():
            nonlocal next_start, cut_at
            while next_start < n_docs:
                if time.time() - t0 > LOAD_BUDGET_S:
                    cut_at = next_start if cut_at is None else cut_at
                    return
                lo = next_start
                hi = next_start = min(n_docs, lo + batch)
                await col.multi_set(
                    [(docs.key(i), docs.doc(i, 0)) for i in range(lo, hi)]
                )

        await asyncio.gather(*[loader() for _ in range(16)])
        loaded = next_start
        load_s = time.time() - t0
        if loaded < n_docs:
            say(
                f"CUT: load stopped at {loaded} of {n_docs} documents "
                f"after {load_s:.0f}s (budget {LOAD_BUDGET_S:.0f}s)"
            )
        say(
            f"  loaded {loaded} documents (~{loaded * 1.1 / 1e6:.2f} GB "
            f"acknowledged) in {load_s:.1f}s wall [smoke, not a rate]"
        )
        version = np.zeros(loaded, dtype=np.int64)
        deleted = np.zeros(loaded, dtype=bool)

        # ---- read back a seeded sample of acknowledged writes -------
        rng = np.random.default_rng(args.seed + 1)
        sample = rng.choice(loaded, size=min(loaded, 2000), replace=False)

        async def read_and_compare(indices, what):
            got = []
            for lo in range(0, len(indices), 200):
                part = indices[lo : lo + 200]
                got.extend(
                    await col.multi_get([docs.key(int(i)) for i in part])
                )
            bad = [
                int(i)
                for i, g in zip(indices, got)
                if g
                != (
                    None
                    if deleted[i]
                    else docs.doc(int(i), int(version[i]))
                )
            ]
            check(not bad, f"{what}: {len(indices)} reads equal the model")

        await read_and_compare(sample, "read-back of acknowledged writes")

        # ---- overwrite and delete some, re-read ---------------------
        touched = rng.choice(loaded, size=min(loaded, 1200), replace=False)
        over, gone = touched[: len(touched) // 2], touched[len(touched) // 2 :]
        version[over] = 1
        for lo in range(0, len(over), batch):
            await col.multi_set(
                [
                    (docs.key(int(i)), docs.doc(int(i), 1))
                    for i in over[lo : lo + batch]
                ]
            )
        await asyncio.gather(*[col.delete(docs.key(int(i))) for i in gone])
        deleted[gone] = True
        await read_and_compare(touched, "re-read after overwrite/delete")

        # ---- wait for compaction to go idle -------------------------
        # (before the scans: while merges are in debt the governor reads
        # soft overload and parks every scan chunk for up to 2 s)
        t_idle = time.time()
        # Longer than the 5 s the governor's bg_gate holds each merge
        # back while table debt reads as soft overload.
        quiet_s = 6.5
        last, last_change = None, time.time()
        while True:
            stats = await client.get_stats("127.0.0.1", db_port)
            comp = stats["compaction"]
            now = (comp["merge_passes"], comp["flush_passes"])
            if now != last or comp["merges_running"]:
                last, last_change = now, time.time()
            elif time.time() - last_change > quiet_s:
                break
            if time.time() - t_idle > IDLE_BUDGET_S:
                raise SmokeFailure(
                    f"compaction still busy after {IDLE_BUDGET_S:.0f}s: "
                    f"{json.dumps(comp, sort_keys=True)}"
                )
            await asyncio.sleep(1.0)
        say(
            f"  compaction idle after {time.time() - t_idle:.0f}s: "
            f"{json.dumps(comp, sort_keys=True)}"
        )
        # ---- count and filtered count/scan --------------------------
        # Stamped interactive.  An unstamped scan is batch-class, the
        # class the governor paces first, and a freshly loaded node
        # rests above that class's soft bars (memtable fill over 42.5 %,
        # or more than 8 tables): default_client_probe below shows what
        # such a scan meets.
        scans = await DbeelClient.from_seed_nodes(
            [("127.0.0.1", db_port)],
            op_deadline_s=120.0,
            qos_class="interactive",
        )
        try:
            await asyncio.wait_for(
                scan_checks(
                    scans.collection("usertable"), docs, loaded,
                    version, deleted,
                ),
                SCAN_BUDGET_S,
            )
        except asyncio.TimeoutError:
            for port, shard in (await shard_stats(client, db_port)).items():
                say(
                    f"  shard :{port} overload={json.dumps(shard['overload'])} "
                    f"scan={json.dumps(shard['scan'])}"
                )
            raise SmokeFailure(
                f"the count and filtered scans took over "
                f"{SCAN_BUDGET_S:.0f}s"
            )
        finally:
            scans.close()
        comp = (await client.get_stats("127.0.0.1", db_port))["compaction"]
        filt = scan_counters(await shard_stats(client, db_port))
        say(f"  scan.filter, both shards: {json.dumps(filt, sort_keys=True)}")
        await default_client_probe(
            client, db_port, docs, loaded, version, deleted, filt
        )
        return held, comp, filt, loaded
    finally:
        client.close()


def _node_preexec() -> None:
    """In the node, before exec: SIGINT back to its default (a shell
    that started this script in the background left it ignored, and
    the node is stopped with it), and SIGKILL if this script dies first
    (no node outlives the smoke on its ports)."""
    import ctypes

    signal.signal(signal.SIGINT, signal.SIG_DFL)
    ctypes.CDLL(None).prctl(1, signal.SIGKILL)  # PR_SET_PDEATHSIG


def phase_serve(args, work: str, rehearsal: bool) -> dict:
    say("== serve ==")
    docs = Docs(args.seed)
    n_docs, _keys = sizes(args.tiny, args.chips)
    node_dir = os.path.join(work, "node")
    log_path = os.path.join(work, "node.log")
    db_port = free_port_block()
    argv = [
        sys.executable, "-m", "dbeel_tpu.server.run",
        "--dir", node_dir, "--name", "smoke",
        "--port", str(db_port),
        "--remote-shard-port", str(db_port + 4),
        "--gossip-port", str(db_port + 8),
        "--shards", "2",
        # The rehearsal names the device backend, so that on the cpu
        # the same device paths run (auto would select native there).
        "--compaction-backend", "device" if rehearsal else "auto",
    ]
    if args.tiny:
        argv += ["--memtable-capacity", "1024"]
    say("  node: " + " ".join(argv[1:]))
    log_f = open(log_path, "wb")
    node_env = {"JAX_LOG_COMPILES": "1"}
    if rehearsal:
        # The filter lane opens on a held accelerator; on the cpu the
        # rehearsal forces it, as the parity tests do.
        node_env["DBEEL_QUERY_DEVICE"] = "cpu_ok"
    proc = subprocess.Popen(
        argv,
        env=child_env(node_env),
        stdout=log_f,
        stderr=subprocess.STDOUT,
        preexec_fn=_node_preexec,
    )
    try:
        # Cold start: JAX initialises and native/ is built from source.
        wait_port(db_port + 1, proc, 300)
        held, comp, filt, loaded = asyncio.run(
            serve_checks(args, docs, n_docs, rehearsal, db_port)
        )
        proc.send_signal(signal.SIGINT)
        rc = proc.wait(timeout=120)
        check(rc == 0, "the node stopped cleanly on SIGINT (exit 0)")
    except BaseException:
        log_f.flush()
        with open(log_path, errors="replace") as f:
            tail = f.read()[-6000:]
        say("---- node log (tail) ----\n" + tail)
        raise
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        log_f.close()
    for line in compile_lines(log_path):
        say(f"  node compiled: {line}")
    paths = comp["paths"]
    if not rehearsal:
        check(comp["platform"] == "tpu", "get_stats.compaction.platform is tpu")
    check(paths["single_shot"] > 0, f"single_shot passes: {paths['single_shot']}")
    biggest = loaded // 2 * 1100  # one shard's whole tree, in bytes
    if biggest >= 2 * PIPELINE_MIN_BYTES:
        check(paths["pipeline"] > 0, f"pipeline passes: {paths['pipeline']}")
    else:
        say(
            "CUT: no served merge reaches PIPELINE_MIN_BYTES at this "
            "size; the major phase carries the pipeline assertion"
        )
    host = {k: paths[k] for k in ("native", "columnar", "heap")}
    check(not any(host.values()), f"no host-merge passes: {host}")
    check(comp["merges_failed"] == 0, "no merge failed")
    check(
        filt["device_evals"] > 0,
        f"filter masks evaluated on the device: {filt['device_evals']}",
    )
    shutil.rmtree(node_dir, ignore_errors=True)
    return {
        "platform": held["platform"],
        "kind": held["device_kind"],
        "count": held["device_count"],
    }


# ----------------------------------------------------------------------
# Children that hold the chip: major, mesh
# ----------------------------------------------------------------------


class Compiles:
    """Backend compile seconds and persistent-cache hits and misses,
    as JAX reports them."""

    def __init__(self) -> None:
        import jax

        self.seconds, self.hits, self.misses = [], 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, name, secs, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.seconds.append(round(secs, 1))

    def _on_event(self, name, **_kw) -> None:
        if name.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("compilation_cache/cache_misses"):
            self.misses += 1

    def take(self) -> str:
        out = (
            f"backend compiles {self.seconds} s, compile cache "
            f"{self.hits} hit(s) {self.misses} miss(es)"
        )
        self.seconds, self.hits, self.misses = [], 0, 0
        return out


def merge_and_hash(strategy, work: str, indices, out_index: int):
    """One merge through the CompactionStrategy seam; (sha256 of the
    compact_* triplet, wall seconds, entries out)."""
    from dbeel_tpu.storage.entry import file_name
    from dbeel_tpu.storage.sstable import SSTable

    sources = [SSTable(work, i, None) for i in indices]
    t0 = time.perf_counter()
    result = strategy.merge(sources, work, out_index, None, False, 1)
    wall = time.perf_counter() - t0
    for s in sources:
        s.close()
    digest = hashlib.sha256()
    for ext in ("compact_data", "compact_index", "compact_bloom"):
        path = os.path.join(work, file_name(out_index, ext))
        with open(path, "rb") as f:
            while True:
                block = f.read(1 << 24)
                if not block:
                    break
                digest.update(block)
        os.unlink(path)
    sums = os.path.join(work, file_name(out_index, "compact_sums"))
    if os.path.exists(sums):
        os.unlink(sums)
    return digest.hexdigest(), wall, result.entry_count


def device_report(held: dict) -> dict:
    return {
        "platform": held["platform"],
        "kind": held["device_kind"],
        "count": held["count"],
    }


def adversarial_f64():
    """(special, vals, valid): values the float32 rounding of a float64
    column gets wrong — integers above 2^24, fractions float32 cannot
    hold, signed zeros, denormals, infinities, NaN — over a seeded
    bulk.  tests/test_scan_plane.py compares the lanes on the same set."""
    special = np.array(
        [
            0.0, -0.0, np.nan, np.inf, -np.inf,
            16777216.0, 16777217.0, 16777218.0, -16777217.0,
            0.1, 0.1 + 2.0**-55, 1.0, 1.0000000000000002,
            -1.0000000000000002, 5e-324, -5e-324,
            2.0**53, -(2.0**53), 1e308, -1e308,
        ]
    )
    rng = np.random.default_rng(7)
    vals = np.concatenate(
        [
            special,
            rng.normal(size=6000),
            rng.integers(1 << 24, 1 << 40, size=3000).astype(np.float64),
            rng.choice(special, size=2000),
        ]
    )
    return special, vals, rng.random(vals.size) < 0.85


def filter_lane_on_device() -> None:
    """The mask kernels against numpy on values float32 gets wrong."""
    import dbeel_tpu.ops.query_kernels as qk

    special, vals, valid = adversarial_f64()
    col = qk.StagedColumn(vals, valid)
    host_ops = {"==": np.equal, "!=": np.not_equal, "<": np.less,
                "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal}
    with np.errstate(invalid="ignore"):
        for op, fn in host_ops.items():
            for operand in special.tolist():
                dev = qk.eval_cmp(col, operand, op)
                if not (dev == (fn(vals, operand) & valid)).all():
                    raise SmokeFailure(f"filter lane inexact: {op} {operand}")
        dev = qk.eval_range(col, 16777217.0, 1e308)
        if not (dev == (valid & (vals >= 16777217.0) & (vals < 1e308))).all():
            raise SmokeFailure("filter lane inexact: range")
    one = qk.StagedColumn(
        np.full(4096, 16777217.0), np.ones(4096, dtype=bool)
    )
    check(
        bool(qk.eval_cmp(one, 16777216.0, ">").all()),
        "16777217.0 > 16777216.0 is True on the device lane, and the "
        "adversarial set equals numpy bit for bit",
    )


def child_major(args, work: str) -> dict:
    from dbeel_tpu import device

    held = device.acquire()
    import jax

    import bench
    from dbeel_tpu.ops.device_compaction import DeviceMergeStrategy
    from dbeel_tpu.storage.compaction import compaction_stats, get_strategy

    compiles = Compiles()
    say("== major ==")
    say(
        f"  device: {held}; compile cache at {device.compile_cache_dir()}"
    )
    keys = sizes(args.tiny, args.chips)[1]
    if args.tiny:
        # Steered here, not by an option of the program: the tiny input
        # must still take the pipeline.
        DeviceMergeStrategy.PIPELINE_MIN_BYTES = 1 << 20
        say("  tiny: PIPELINE_MIN_BYTES lowered to 1 MiB in this child")
    check(
        keys * bench.RECORD >= DeviceMergeStrategy.PIPELINE_MIN_BYTES,
        f"{keys} keys x {bench.RECORD} B of input reach PIPELINE_MIN_BYTES",
    )
    t0 = time.perf_counter()
    indices = bench.build_runs(work, keys, 8, seed=args.seed)
    say(f"  built 8 runs x {keys // 8} keys in {time.perf_counter() - t0:.1f}s")

    native_sha, native_s, native_n = merge_and_hash(
        get_strategy("native"), work, indices, 101
    )
    say(f"  native: {native_n} out, {native_s:.2f}s wall [smoke]")
    strategy = get_strategy("device")
    cold_sha, cold_s, cold_n = merge_and_hash(strategy, work, indices, 103)
    cold_compiles = compiles.take()
    warm_sha, warm_s, _n = merge_and_hash(strategy, work, indices, 103)
    say(
        f"  device cold: {cold_n} out, {cold_s:.2f}s wall, "
        f"{cold_compiles}; warm: {warm_s:.2f}s wall, {compiles.take()} "
        "[smoke, not a measurement]"
    )
    mem = jax.devices()[0].memory_stats() or {}
    say(f"  peak_bytes_in_use: {mem.get('peak_bytes_in_use')}")
    check(
        cold_sha == native_sha and warm_sha == native_sha,
        f"device output triplet SHA-256 equals native ({native_sha[:16]}…)",
    )
    paths = compaction_stats.stats()["paths"]
    check(
        paths["pipeline"] == 2 and paths["native"] == 1,
        f"path counters: {paths}",
    )
    filter_lane_on_device()
    return {"ok": True, "device": device_report(held)}


def child_mesh(args, work: str) -> dict:
    from dbeel_tpu import device

    held = device.acquire()
    import jax

    import bench
    from dbeel_tpu.ops import bitonic
    from dbeel_tpu.ops.device_compaction import DeviceMergeStrategy
    from dbeel_tpu.parallel import dist_merge
    from dbeel_tpu.parallel.mesh import shard_mesh
    from dbeel_tpu.storage.compaction import compaction_stats, get_strategy

    compiles = Compiles()
    say("== mesh ==")
    say(f"  device: {held}; compile cache at {device.compile_cache_dir()}")
    check(held["count"] == 4, "the process holds four devices")
    if args.tiny:
        DeviceMergeStrategy.PIPELINE_MIN_BYTES = 1 << 20
    keys = sizes(args.tiny, args.chips)[1]
    indices = bench.build_runs(work, keys, 8, seed=args.seed)
    mesh = shard_mesh()

    native_sha, native_s, native_n = merge_and_hash(
        get_strategy("native"), work, indices, 101
    )
    say(f"  native: {native_n} out, {native_s:.2f}s wall [smoke]")

    single_sha, single_s, _n = merge_and_hash(
        DeviceMergeStrategy(), work, indices, 103
    )
    say(
        f"  single-device pipeline: {single_s:.2f}s wall cold, "
        f"{compiles.take()}"
    )

    # The pipeline's mesh= form: spy on the batch kernels to see where
    # their operands and results live.
    seen = []

    def spying(kernel):
        def call(dev, counts, pack_bits):
            out = kernel(dev, counts, pack_bits)
            seen.append(
                (
                    len(dev.sharding.device_set),
                    len(out.sharding.device_set),
                    np.asarray(counts).sum(axis=1).tolist(),
                )
            )
            return out

        return call

    real32 = bitonic.merge_runs_prefix32_packed_batch_kernel
    real64 = bitonic.merge_runs_prefix64_packed_batch_kernel
    bitonic.merge_runs_prefix32_packed_batch_kernel = spying(real32)
    bitonic.merge_runs_prefix64_packed_batch_kernel = spying(real64)
    try:
        if held["platform"] == "cpu":
            # The rehearsal: auto selects native on the cpu, so the
            # strategy auto would pick on a mesh is built by hand.
            auto = DeviceMergeStrategy(mesh=mesh)
        else:
            auto = get_strategy("auto")
            check(
                isinstance(auto, DeviceMergeStrategy)
                and auto.mesh is not None,
                "auto on four devices is the mesh pipeline above "
                "PIPELINE_MIN_BYTES and one device below it",
            )
        meshp_sha, meshp_s, _n = merge_and_hash(auto, work, indices, 105)
    finally:
        bitonic.merge_runs_prefix32_packed_batch_kernel = real32
        bitonic.merge_runs_prefix64_packed_batch_kernel = real64
    say(
        f"  mesh pipeline: {meshp_s:.2f}s wall cold, {compiles.take()}; "
        f"launches (operand devices, result devices, rows per slot): "
        f"{seen}"
    )
    check(
        seen and all(a == 4 and b == 4 for a, b, _ in seen),
        "every mesh launch's operand and result are sharded over 4 devices",
    )
    check(
        all(r > 0 for _, _, rows in seen[:1] for r in rows),
        "each of the four devices merged rows of its own",
    )

    # The distributed sample sort (what an explicit `distributed`
    # backend runs below PIPELINE_MIN_BYTES), steered here past the
    # pipeline so the same input takes it.
    dist = get_strategy("distributed")
    check(dist.name == "distributed", "get_strategy('distributed') on 4 devices")
    type(dist).PIPELINE_MIN_BYTES = 1 << 62
    shards_rows = []
    real_dist = dist_merge._dist_kernel

    def dist_spy(stack, **kw):
        out, same, overflow = real_dist(stack, **kw)
        shards_rows.append(
            [
                int((np.asarray(s.data)[:, 8] != 0xFFFFFFFF).sum())
                for s in out.addressable_shards
            ]
        )
        return out, same, overflow

    dist_merge._dist_kernel = dist_spy
    try:
        dist_sha, dist_s, _n = merge_and_hash(dist, work, indices, 107)
    finally:
        dist_merge._dist_kernel = real_dist
    say(
        f"  distributed sample sort ({keys} rows, {-(-keys // 4)} per "
        f"device): {dist_s:.2f}s wall cold, {compiles.take()}; rows held "
        f"per device after the exchange: {shards_rows}"
    )
    check(
        len(shards_rows) == 1
        and len(shards_rows[0]) == 4
        and all(r > 0 for r in shards_rows[0])
        and sum(shards_rows[0]) == keys,
        "all four devices held rows of the sorted output, none lost",
    )
    mem = [d.memory_stats() or {} for d in jax.devices()]
    say(f"  peak_bytes_in_use per device: {[m.get('peak_bytes_in_use') for m in mem]}")
    paths = compaction_stats.stats()["paths"]
    say(f"  path counters: {paths}")
    check(paths["distributed_overflow"] == 0, "no exchange overflow on uniform keys")
    check(
        paths["pipeline"] == 2 and paths["distributed"] == 1,
        "two pipeline outputs and one distributed output were counted",
    )
    check(
        single_sha == native_sha
        and meshp_sha == native_sha
        and dist_sha == native_sha,
        "single-device, mesh-pipeline and distributed outputs all equal "
        f"native ({native_sha[:16]}…)",
    )
    return {"ok": True, "device": device_report(held)}


# ----------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--tiny", action="store_true",
                    help="tiny sizes (the CPU rehearsal of tests/)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="a cpu run is expected; the result says so")
    ap.add_argument("--child", choices=("major", "mesh"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        try:
            fn = child_major if args.child == "major" else child_mesh
            report = fn(args, args.work)
        except SmokeFailure as e:
            report = {"ok": False, "error": str(e)}
        print(json.dumps(report), flush=True)
        return 0 if report["ok"] else 1

    docs, keys = sizes(args.tiny, args.chips)
    say(
        f"chip_smoke: chips={args.chips} seed={args.seed} "
        f"tiny={args.tiny} rehearsal={args.rehearsal}; "
        f"{docs} documents, {keys} keys"
    )
    work = tempfile.mkdtemp(prefix="dbeel_smoke_")
    device = None
    try:
        if args.chips == 4:
            os.makedirs(os.path.join(work, "mesh"))
            device = run_child("mesh", args, os.path.join(work, "mesh"))[
                "device"
            ]
        else:
            device = phase_serve(args, work, args.rehearsal)
            os.makedirs(os.path.join(work, "major"))
            major = run_child("major", args, os.path.join(work, "major"))
            check(
                major["device"] == device,
                "both phases held the same device",
            )
        on_chip = device["platform"] != "cpu"
        if not on_chip and not args.rehearsal:
            raise SmokeFailure("JAX found no accelerator")
        check(device["count"] == args.chips, f"device count is {args.chips}")
        result = {"ok": True, "device": device}
        if not on_chip:
            result["rehearsal"] = True
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        result = {"ok": False, "error": str(e)}
        if device is not None:
            result["device"] = device
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
