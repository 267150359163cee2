"""Deployment ``one_node``: one node through the normal entry point
(``dbeel_tpu.server.run.main``, inside node_host.py so that the runner can
have it traced), one process that owns the chip, the configuration's flags;
loaded with the configuration's records and left to settle.  This process
is the client's side and never initialises a JAX backend."""

from __future__ import annotations

import asyncio
import json
import os
import queue
import signal
import socket
import subprocess
import sys
import threading
import time

from benchmark.harness.common import (
    BENCH, ROOT, BenchFailure, Run, child_preexec, say,
)
from benchmark.harness.docs import Docs

# The node's listeners: below the kernel's range of source ports and
# above the blocks tests/harness.py hands out.
PORT_BLOCKS = range(17000, 19000, 16)
START_BUDGET_S = 600.0  # a cold start builds native/ from source
LOAD_BUDGET_S = 300.0
SETTLE_BUDGET_S = 900.0  # a cold cache compiles every merge shape
COLLECTION = "usertable"


def free_port_block() -> int:
    """First block whose db (+0, +1), remote (+4, +5) and gossip (+8)
    ports can all be bound (a copy of chip_smoke.free_port_block)."""
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
    raise BenchFailure("no free port block for the node")


class OneNode:
    def __init__(self, run: Run) -> None:
        self.run = run
        cfg = run.config
        self.shards = int(cfg["shards"])
        self.records = int(cfg["recordcount"])
        self.collection = COLLECTION
        self.docs = Docs(run.seed, cfg["fields"], cfg["field_bytes"])
        # A caller that hands out blocks of its own (the tests) is
        # trusted: the node binds with SO_REUSEADDR, this probe does not.
        self.port = run.port_block or free_port_block()
        self.log_path = os.path.join(run.work, "node.log")
        self.proc = None
        self._replies: queue.Queue = queue.Queue()
        self._loop = asyncio.new_event_loop()
        self.client = None
        self.col = None

        flags = list(cfg["node_flags"])
        if run.trace:
            flags += list(cfg.get("node_flags_traced", []))
        if run.rehearsal:
            # On the cpu `auto` selects the host merge; the rehearsal
            # names the device backend so the same paths run.
            at = flags.index("--compaction-backend")
            flags[at + 1] = "device"
        argv = [
            sys.executable, os.path.join(BENCH, "node_host.py"),
            "--dir", os.path.join(run.work, "node"), "--name", "bench",
            "--port", str(self.port),
            "--remote-shard-port", str(self.port + 4),
            "--gossip-port", str(self.port + 8),
            "--shards", str(self.shards),
        ] + flags
        say("node: " + " ".join(argv[1:]))
        t0 = time.time()
        self._log_f = open(self.log_path, "wb")
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        self.proc = subprocess.Popen(
            argv, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._log_f, text=True, preexec_fn=child_preexec,
        )
        threading.Thread(
            target=self._drain_stdout, daemon=True, name="node-stdout"
        ).start()
        try:
            self._wait_port(self.port + self.shards - 1, START_BUDGET_S)
            run.facts["setup_node_start_s"] = time.time() - t0
            self._loop.run_until_complete(self._connect_and_load())
        except BaseException:
            self.stop(failed=True)
            raise

    # -- the node process ----------------------------------------------

    def _drain_stdout(self) -> None:
        for line in self.proc.stdout:
            if line.startswith("@ctl "):
                self._replies.put(json.loads(line[5:]))

    def command(self, line: str, budget_s: float = 120.0) -> dict:
        """One command to node_host.py and its answer."""
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()
        try:
            reply = self._replies.get(timeout=budget_s)
        except queue.Empty:
            raise BenchFailure(f"the node did not answer {line!r}")
        if not reply.get("ok"):
            raise BenchFailure(f"the node refused {line!r}: {reply}")
        return reply

    def _wait_port(self, port: int, budget_s: float) -> None:
        t0 = time.time()
        while time.time() - t0 < budget_s:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"the node exited with code {self.proc.returncode} "
                    "before it listened"
                )
            try:
                socket.create_connection(("127.0.0.1", port), 1).close()
                return
            except OSError:
                time.sleep(0.1)
        raise BenchFailure(f"port {port} never opened in {budget_s:.0f}s")

    # -- load and settle -------------------------------------------------

    async def _connect_and_load(self) -> None:
        from dbeel_tpu.client import DbeelClient

        run = self.run
        self.client = await DbeelClient.from_seed_nodes(
            [("127.0.0.1", self.port)], op_deadline_s=120.0,
            pipeline_window=8,
        )
        self.col = await self.client.create_collection(
            COLLECTION, int(run.config["replication_factor"])
        )
        held = (await self.stats())["node"]["compaction"]
        run.check_device(
            held["platform"], held["device_kind"], held["device_count"]
        )
        say(f"node holds: {run.device}")

        batch = 48  # ~53 KB of records: one frame under the u16 bound
        docs, n = self.docs, self.records
        next_start = 0
        t0 = time.time()

        async def loader():
            nonlocal next_start
            while next_start < n:
                if time.time() - t0 > LOAD_BUDGET_S:
                    raise BenchFailure(
                        f"load not done in {LOAD_BUDGET_S:.0f}s "
                        f"({next_start} of {n})"
                    )
                lo = next_start
                hi = next_start = min(n, lo + batch)
                await self.col.multi_set(
                    [(docs.key(i), docs.doc(i, 0)) for i in range(lo, hi)]
                )

        await asyncio.gather(*[loader() for _ in range(16)])
        run.facts["setup_load_s"] = time.time() - t0
        say(f"set-up: loaded {n} records in {time.time() - t0:.1f}s")

        # Settle: until no merge runs and the pass counters have been
        # quiet for longer than the 5 s the governor's bg_gate holds a
        # merge back under soft overload (chip_smoke's idle wait).
        t_idle = time.time()
        quiet_s = 6.5
        last, last_change = None, time.time()
        while True:
            comp = (await self.stats())["node"]["compaction"]
            now = (comp["merge_passes"], comp["flush_passes"])
            if now != last or comp["merges_running"]:
                last, last_change = now, time.time()
            elif time.time() - last_change > quiet_s:
                break
            if time.time() - t_idle > SETTLE_BUDGET_S:
                raise BenchFailure(
                    f"compaction still busy after {SETTLE_BUDGET_S:.0f}s: "
                    f"{json.dumps(comp, sort_keys=True)}"
                )
            await asyncio.sleep(0.5)
        run.facts["setup_settle_s"] = time.time() - t_idle
        say(
            f"set-up: compaction idle after {time.time() - t_idle:.0f}s: "
            f"paths {json.dumps(comp['paths'])}, merges_failed "
            f"{comp['merges_failed']}"
        )

    # -- what the generator and the readers ask --------------------------

    async def stats(self) -> dict:
        shards = [
            await self.client.get_stats("127.0.0.1", self.port + s)
            for s in range(self.shards)
        ]
        return {"node": shards[0], "shards": shards}

    def counters(self) -> dict:
        return self._loop.run_until_complete(self.stats())

    def read_back(self, ordinals) -> list:
        """The stored records of ``ordinals`` (None where absent)."""

        async def go():
            out = []
            for lo in range(0, len(ordinals), 200):
                part = ordinals[lo : lo + 200]
                out.extend(
                    await self.col.multi_get(
                        [self.docs.key(int(i)) for i in part]
                    )
                )
            return out

        return self._loop.run_until_complete(go())

    def memory_peak_bytes(self) -> int:
        return int(self.command("memory")["memory_peak_bytes"])

    def stop(self, failed: bool = False) -> None:
        if self.client is not None:
            self.client.close()
            self.client = None
            # One turn of the loop, so the closed connections' reader
            # tasks end before the loop is closed.
            self._loop.run_until_complete(asyncio.sleep(0))
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if self.proc is not None and (failed or self.proc.returncode != 0):
            self._log_f.flush()
            with open(self.log_path, errors="replace") as f:
                say("---- node log (tail) ----\n" + f.read()[-4000:])
        self._log_f.close()
        self._loop.close()


def start(run: Run) -> OneNode:
    return OneNode(run)
