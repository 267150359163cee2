"""The harness's port plan under pytest-xdist: no two workers share a
block, and every listener stays below 16000."""

from harness import (
    _BLOCK_PORTS,
    _BLOCKS_PER_WORKER,
    _MAX_WORKERS,
    _worker_index,
    port_block,
)


def test_port_blocks_disjoint_across_workers(monkeypatch):
    owners = {}
    for worker in range(_MAX_WORKERS):
        # Two laps: a worker cycles through its OWN blocks only.
        for n in range(2 * _BLOCKS_PER_WORKER):
            block = port_block(n, worker)
            assert 1024 < block and block + _BLOCK_PORTS <= 16000
            assert owners.setdefault(block, worker) == worker
    assert len(owners) == _MAX_WORKERS * _BLOCKS_PER_WORKER
    starts = sorted(owners)
    assert all(
        b - a >= _BLOCK_PORTS for a, b in zip(starts, starts[1:])
    )
    monkeypatch.setenv("PYTEST_XDIST_WORKER", "gw5")
    assert _worker_index() == 5
    monkeypatch.delenv("PYTEST_XDIST_WORKER")
    assert _worker_index() == 0
