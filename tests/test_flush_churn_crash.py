"""Crash safety of the round-4 off-loop WAL disposal: the retired
WAL's close/unlink now runs on an executor thread, so the on-disk
invariant — never more than TWO WALs (recovery treats a third as
corruption) — is held by flush awaiting the previous disposal.  This
test SIGKILLs a wal-sync server mid-flush-churn (memtable capacity 48
=> a rotation every ~48 writes) at random moments and proves every
acked write survives recovery and the node reopens cleanly."""

import asyncio
import os
import signal
import socket
import struct
import subprocess
import sys
import time

import msgpack
import pytest

from harness import make_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _req(port, obj, timeout=30.0):  # suite-load tolerant
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    b = msgpack.packb(obj, use_bin_type=True)
    s.sendall(struct.pack("<H", len(b)) + b)
    hdr = b""
    while len(hdr) < 4:
        c = s.recv(4 - len(hdr))
        assert c, "closed"
        hdr += c
    (n,) = struct.unpack("<I", hdr)
    body = b""
    while len(body) < n:
        c = s.recv(n - len(body))
        assert c, "closed"
        body += c
    s.close()
    return body[-1], msgpack.unpackb(body[:-1], raw=False)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _start(cfg, log_path):
    env = {
        **os.environ,
        "PYTHONPATH": REPO
        + (
            ":" + os.environ["PYTHONPATH"]
            if os.environ.get("PYTHONPATH")
            else ""
        ),
    }
    # Popen dups the fd; close ours right after so nothing leaks.
    log_fd = os.open(
        log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644
    )
    try:
        return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "dbeel_tpu.server.run",
            "--dir",
            cfg.dir,
            "--port",
            str(cfg.port),
            "--remote-shard-port",
            str(cfg.remote_shard_port),
            "--gossip-port",
            str(cfg.gossip_port),
            "--shards",
            "1",
            # This drill is about the WAL, not the merge backend:
            # host merges keep JAX out of every restart.
            "--compaction-backend",
            "native",
            "--wal-sync",
            "--memtable-capacity",
            "48",
        ],
        env=env,
        stdout=log_fd,
        stderr=subprocess.STDOUT,
        )
    finally:
        os.close(log_fd)


def _wait_up(port, deadline=90.0):
    t0 = time.time()
    while time.time() - t0 < deadline:
        try:
            _req(port, {"type": "get_cluster_metadata"})
            return
        except OSError:
            time.sleep(0.2)
    raise AssertionError(
        f"server never came up on {port} within {deadline}s"
    )


@pytest.mark.parametrize("kill_after_ops", [60, 137, 301])
def test_sigkill_mid_flush_churn_loses_no_acked_writes(
    tmp_dir, kill_after_ops
):
    # OS-assigned free ports: collision-free even across concurrent
    # pytest processes (the harness allocator is only per-process).
    cfg = make_config(tmp_dir).replace(
        port=_free_port(),
        remote_shard_port=_free_port(),
        gossip_port=_free_port(),
    )
    port = cfg.port
    d = cfg.dir
    log_path = os.path.join(tmp_dir, "server.log")
    proc = _start(cfg, log_path)
    acked = []
    try:
        _wait_up(port)
        t, _ = _req(port, {"type": "create_collection", "name": "c"})
        assert t == 2
        # Each write acked => fdatasync'd (wal-sync).  At capacity 48
        # this churns through several full rotations (swap, native
        # flush, async disposal of the retired WAL) before the kill.
        for i in range(kill_after_ops):
            t, v = _req(
                port,
                {
                    "type": "set",
                    "collection": "c",
                    "key": f"k{i:05}",
                    "value": {"i": i},
                },
            )
            assert t == 2 and v == "OK", (i, t, v)
            acked.append(i)
    finally:
        # Hard crash at an arbitrary churn point (never graceful).
        try:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
        except Exception:
            pass

    # The on-disk WAL invariant: recovery tolerates at most 2 WALs
    # (".memtable" files — storage/entry.py MEMTABLE_FILE_EXT).
    wals = [
        f
        for f in os.listdir(os.path.join(d, "c-0"))
        if f.endswith(".memtable")
    ]
    assert 1 <= len(wals) <= 2, f"WAL invariant broken: {wals}"

    proc2 = _start(cfg, log_path)
    try:
        _wait_up(port)
        lost = []
        for i in acked:
            t, v = _req(
                port, {"type": "get", "collection": "c", "key": f"k{i:05}"}
            )
            if not (t == 1 and v == {"i": i}):
                lost.append((i, t, v))
        if lost:
            with open(log_path, "rb") as f:
                tail = f.read()[-2000:]
            raise AssertionError(
                f"lost {len(lost)} acked writes: {lost[:5]}; "
                f"server log tail: {tail!r}"
            )
    finally:
        proc2.terminate()
        try:
            proc2.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc2.kill()
