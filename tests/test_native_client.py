"""Compiled C++ smart client (native/src/dbeel_client.cpp) against a
real server process: bootstrap, ring routing across shards, set/get/
delete round trips, KeyNotFound, and the KeyNotOwned resync walk.
Parity target: /root/reference/dbeel_client/src/lib.rs:85-152,336-417.
"""

import os
import socket
import subprocess
import sys
import time

import pytest

from dbeel_tpu.client import native_client

pytestmark = pytest.mark.skipif(
    not native_client.available(), reason="native client not built"
)

def _free_port_block() -> int:
    """A db port such that db/db+1 (2 shards), remote (+10000/+1) and
    gossip (+20000) are all bindable.  Chosen from [20000, 28000) —
    above the harness's 11000+64n blocks, and the derived ports stay
    under 65536 (an ephemeral-range port would push gossip past it)."""
    import random
    import socket as _socket

    rng = random.Random()
    for _ in range(128):
        # Stay clear of the harness/server bands: dbs live around
        # 10000-13000 so their remote planes occupy 20000-23000 and
        # gossip 30000-33000 mid-suite; this block's +10000/+20000
        # probes must not land there either.
        port = rng.randrange(34000, 39000, 2)
        probes = (port, port + 1, port + 10000, port + 10001,
                  port + 20000)
        ok = True
        for p in probes:
            s = _socket.socket()
            try:
                s.bind(("127.0.0.1", p))
            except OSError:
                ok = False
                break
            finally:
                s.close()
        if ok:
            return port
    raise RuntimeError("no free port block")


PORT = _free_port_block()


def _wait_port(port, deadline=120.0):
    t0 = time.time()
    while time.time() - t0 < deadline:
        try:
            socket.create_connection(
                ("127.0.0.1", port), timeout=1
            ).close()
            return
        except OSError:
            time.sleep(0.2)
    raise RuntimeError(f"port {port} never opened")


@pytest.fixture
def server(tmp_dir):
    env = {
        **os.environ,
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(os.path.dirname(__file__))]
            + ([os.environ["PYTHONPATH"]] if "PYTHONPATH" in os.environ else [])
        ),
    }
    proc = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "dbeel_tpu.server.run",
            "--dir",
            tmp_dir,
            "--port",
            str(PORT),
            "--remote-shard-port",
            str(PORT + 10000),
            "--gossip-port",
            str(PORT + 20000),
            "--shards",
            "2",
            # The C client is under test, not the merge backend: host
            # merges keep JAX out of every per-test boot.
            "--compaction-backend",
            "native",
        ],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT,
    )
    try:
        _wait_port(PORT)
        _wait_port(PORT + 1)
        yield proc
    finally:
        proc.terminate()
        proc.wait(timeout=20)


def test_native_client_end_to_end(server):
    with native_client.NativeDbeelClient("127.0.0.1", PORT) as cli:
        # Two shards on one node -> two ring points.
        assert cli.ring_size == 2
        cli.create_collection("nc", replication_factor=1)
        time.sleep(0.3)  # local fan-out to shard 1

        # Round-trip assorted msgpack value shapes through both shards
        # (keys spread across the ring, so routing MUST work).
        values = {
            "a": 1,
            "b": "text",
            "c": {"nested": [1, 2, 3]},
            "d": None,
            **{f"k{i}": i for i in range(40)},
        }
        for k, v in values.items():
            cli.set("nc", k, v)
        for k, v in values.items():
            assert cli.get("nc", k) == v

        cli.delete("nc", "a")
        from dbeel_tpu.errors import KeyNotFound

        with pytest.raises(KeyNotFound):
            cli.get("nc", "a")
        with pytest.raises(KeyNotFound):
            cli.get("nc", "never-written")


def test_native_client_routing_matches_python_ring(server):
    """The C++ replica walk must route exactly like the Python client:
    verify by checking every key lands (gets succeed) AND the ring
    hash layout agrees with the Python-side computation."""
    from dbeel_tpu.utils.murmur import hash_string

    with native_client.NativeDbeelClient("127.0.0.1", PORT) as cli:
        assert cli.ring_size == 2
        cli.create_collection("rt", replication_factor=1)
        time.sleep(0.3)
        # Python-side ring hashes for the two shards of node "dbeel".
        hashes = sorted(
            hash_string(f"dbeel-{sid}") for sid in (0, 1)
        )
        assert len(set(hashes)) == 2
        for i in range(64):
            cli.set("rt", f"route{i}", i)
            assert cli.get("rt", f"route{i}") == i


def test_native_client_latency_yardstick(server):
    """The compiled path exists to beat the interpreted client on
    per-op overhead; record that a round trip completes comfortably
    under the Python client's measured floor (no hard perf assert —
    shared CI host — but catch pathological regressions)."""
    with native_client.NativeDbeelClient("127.0.0.1", PORT) as cli:
        cli.create_collection("lat", replication_factor=1)
        time.sleep(0.3)
        cli.set("lat", "warm", 1)
        t0 = time.perf_counter()
        n = 200
        for i in range(n):
            cli.set("lat", "warm", i)
        per_op = (time.perf_counter() - t0) / n
        assert per_op < 0.05, f"set round trip {per_op*1e6:.0f}us"


def test_native_client_large_value_grows_buffer(server):
    """A value larger than the current get buffer must round-trip via
    the grow-and-retry protocol (C reports the needed size).  The u16
    request frame caps doc-API values at ~64KB — under the default
    initial buffer — so the path is exercised by shrinking the buffer
    first (values beyond it can still enter trees via the inter-shard
    planes, whose frames are u32)."""
    import ctypes

    with native_client.NativeDbeelClient("127.0.0.1", PORT) as cli:
        cli.create_collection("big", replication_factor=1)
        time.sleep(0.3)
        big = "x" * 4096
        cli.set("big", "k", big)
        cli._buf = (ctypes.c_uint8 * 16)()  # force the -10 grow path
        assert cli.get("big", "k") == big
        assert len(cli._buf) >= 4096  # grown to the reported size

        # And an oversized SET is rejected loudly by the frame bound.
        from dbeel_tpu.errors import DbeelError

        with pytest.raises(DbeelError, match="frame too large"):
            cli.set("big", "k2", "x" * 70000)


def test_native_client_scan_and_count(server):
    """Scan plane (PR 12) through the compiled client: chunked
    cursor-resumed scan + keys-only count, same stream semantics as
    the Python client's DbeelCollection.scan/count."""
    import msgpack

    with native_client.NativeDbeelClient("127.0.0.1", PORT) as cli:
        cli.create_collection("sc", replication_factor=1)
        time.sleep(0.3)
        items = {f"key-{i:04d}": {"v": i} for i in range(150)}
        cli.multi_set("sc", items)
        cli.delete("sc", "key-0003")
        got = cli.scan("sc")
        assert [k for k, _v in got] == sorted(
            k for k in items if k != "key-0003"
        )
        assert all(v == items[k] for k, v in got)
        assert cli.count("sc") == 149
        # Raw encoded-key prefix pushdown (fixstr header + "key-00").
        pfx = msgpack.packb("key-0000")[:7]
        assert cli.count("sc", prefix=pfx) == 99
        assert [k for k, _v in cli.scan("sc", prefix=pfx)] == sorted(
            f"key-{i:04d}" for i in range(100) if i != 3
        )
        # Tiny chunks: many cursor hops, identical stream.
        assert cli.scan("sc", max_bytes=512) == got
        # Query compute plane (PR 13): the C client forwards the
        # packed spec verbatim — filtered scan, filtered count, and
        # a pushdown aggregate, matching the Python-side semantics.
        flt = ["and", ["cmp", "v", ">=", 10], ["cmp", "v", "<", 30]]
        assert [k for k, _v in cli.scan("sc", filter=flt)] == [
            f"key-{i:04d}" for i in range(10, 30)
        ]
        assert cli.count("sc", filter=["cmp", "v", "<", 10]) == 9
        assert cli.count(
            "sc", aggregate={"op": "sum", "field": "v"}
        ) == sum(i for i in range(150) if i != 3)
        assert cli.count(
            "sc",
            aggregate={"op": "max", "field": "v"},
            filter=["cmp", "v", "<", 100],
        ) == 99
        # The filter stats block is visible through the C client's
        # get_stats pass-through too.
        stats = cli.get_stats()
        assert "filter" in stats["scan"]
        assert set(stats["scan"]["filter"]) >= {
            "specs_served",
            "rows_scanned",
            "rows_returned",
            "bytes_saved",
            "agg_partials",
            "device_evals",
            "fallback_evals",
        }
