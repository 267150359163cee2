"""In-process cluster harness.

Role parity with /root/reference/test_utils/src/lib.rs:44-182: run 1..N
real shards (and multiple "nodes") inside the test process, with port
arithmetic per node, flow-event subscription helpers, and crash-at-end
mode (cancel instead of graceful stop).
"""

from __future__ import annotations

import asyncio
import itertools
import os
from typing import List, Optional

from dbeel_tpu.config import Config
from dbeel_tpu.flow_events import FlowEvent
from dbeel_tpu.cluster.local_comm import LocalShardConnection
from dbeel_tpu.server.run import create_shard, run_shard
from dbeel_tpu.server.shard import MyShard

_port_block = itertools.count(0)

# Port plan: 64 blocks of 192 ports (db / remote / gossip sub-blocks
# of 64) from 3700 up to 15988.  Each pytest-xdist worker owns 8 of
# them, so workers running test files side by side never open the
# same listener; without xdist the process is worker 0.
_PORT_BASE = 3700
_BLOCK_PORTS = 192
_BLOCKS_PER_WORKER = 8
_MAX_WORKERS = 8


def _worker_index() -> int:
    """This process's xdist worker number (``gw3`` -> 3), 0 without
    xdist."""
    worker = os.environ.get("PYTEST_XDIST_WORKER", "")
    digits = "".join(ch for ch in worker if ch.isdigit())
    index = int(digits) if digits else 0
    if index >= _MAX_WORKERS:
        raise RuntimeError(
            f"the harness has port blocks for {_MAX_WORKERS} xdist "
            f"workers; {worker!r} would share another worker's"
        )
    return index


def port_block(n: int, worker: Optional[int] = None) -> int:
    """First port of this worker's ``n``-th block (cycling through the
    worker's own blocks: tests of one worker run one after another,
    so a reused block only ever meets closed listeners)."""
    if worker is None:
        worker = _worker_index()
    slot = worker * _BLOCKS_PER_WORKER + n % _BLOCKS_PER_WORKER
    return _PORT_BASE + slot * _BLOCK_PORTS


def make_config(tmp_dir: str, **kw) -> Config:
    """Fresh config with a unique port block (peace between tests).

    Every listen port stays below 16000, out of the kernel's range of
    source ports for outgoing connections: a listener inside that
    range can be squatted by any connection's source port, which
    showed as a mid-suite EADDRINUSE "shard task died during
    startup"."""
    block = port_block(next(_port_block))
    defaults = dict(
        name="dbeel-test",
        dir=f"{tmp_dir}/db",
        port=block,
        remote_shard_port=block + 64,
        gossip_port=block + 128,
        failure_detection_interval_ms=50,
        memtable_capacity=64,
    )
    defaults.update(kw)
    return Config(**defaults)


def next_node_config(cfg: Config, offset: int, tmp_dir: str) -> Config:
    """Port/dir/name offsets for an extra node on one host
    (test_utils/src/lib.rs:172-182)."""
    # Stride by 8: per-shard ports are base+shard_id, so nodes need
    # non-overlapping blocks (up to 8 shards per test node).
    return cfg.replace(
        name=f"{cfg.name}-n{offset}",
        dir=f"{tmp_dir}/db-n{offset}",
        port=cfg.port + offset * 8,
        remote_shard_port=cfg.remote_shard_port + offset * 8,
        gossip_port=cfg.gossip_port + offset * 8,
    )


class ClusterNode:
    """All shards of one node, running as tasks on the current loop."""

    def __init__(self, config: Config, num_shards: int = 1) -> None:
        self.config = config
        self.num_shards = num_shards
        self.shards: List[MyShard] = []
        self.tasks: List[asyncio.Task] = []

    async def start(self, wait_started: bool = True) -> "ClusterNode":
        connections = [
            LocalShardConnection(i) for i in range(self.num_shards)
        ]
        self.shards = [
            create_shard(self.config, i, connections)
            for i in range(self.num_shards)
        ]
        started = [
            s.flow.subscribe(FlowEvent.START_TASKS) for s in self.shards
        ]
        self.tasks = [
            asyncio.ensure_future(run_shard(s, i == 0))
            for i, s in enumerate(self.shards)
        ]
        if wait_started:
            # Race the started-events against the shard tasks: a
            # shard that dies during startup (bind failure, startup
            # bug) would otherwise leave the events unresolved and
            # this await hanging until the test timeout, SWALLOWING
            # the real exception.
            started_all = asyncio.ensure_future(
                asyncio.gather(*started)
            )
            await asyncio.wait(
                [started_all, *self.tasks],
                return_when=asyncio.FIRST_COMPLETED,
            )
            dead = [t for t in self.tasks if t.done()]
            if dead and not started_all.done():
                # ANY finished shard task (exception, cancellation,
                # clean return) before START_TASKS means startup
                # failed — surface it instead of hanging, and tear
                # the sibling shards down so they don't leak into
                # later tests.
                started_all.cancel()
                cause = next(
                    (
                        t.exception()
                        for t in dead
                        if not t.cancelled() and t.exception()
                    ),
                    None,
                )
                for t in self.tasks:
                    t.cancel()
                await asyncio.gather(
                    *self.tasks, return_exceptions=True
                )
                raise RuntimeError(
                    "shard task died during startup"
                ) from cause
            await started_all
            await asyncio.sleep(0)  # let listeners settle
        return self

    async def stop(self) -> None:
        """Graceful stop: death gossip is sent."""
        for s in self.shards:
            await s.stop()
        await asyncio.gather(*self.tasks, return_exceptions=True)

    async def crash(self) -> None:
        """Hard crash (test_utils/src/lib.rs:159-170): cancel without
        stop events — no death gossip, sockets just vanish."""
        for s in self.shards:
            s.crashed = True
        for t in self.tasks:
            t.cancel()
        await asyncio.gather(*self.tasks, return_exceptions=True)
        for s in self.shards:
            s.close()

    def flow_event(self, shard_index: int, event: FlowEvent):
        return self.shards[shard_index].flow.subscribe(event)

    @property
    def db_address(self):
        return (self.config.ip, self.config.port)

    @property
    def seed_address(self) -> str:
        return f"{self.config.ip}:{self.config.remote_shard_port}"
