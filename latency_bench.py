"""Set latency under a concurrent major compaction (BENCH.md row for
the intra-merge latency classes; /root/reference's analog is glommio's
Latency::Matters serving queue, src/tasks/db_server.rs:466-471).

Phase "quiet":      Sets against an idle single-shard node.
Phase "compacting": the same load while the node major-compacts
                    --keys synthetic keys at startup (the compaction
                    scheduler's startup pass picks up the pre-built
                    even-index sstables immediately).

Prints one JSON line with p50/p99 for both phases and the compaction
evidence (odd-index output present).  Usage:

    python latency_bench.py [--keys 10000000] [--runs 8] \
        [--backend native] [--port 12600] [--duration 8]

Every node is started with an explicit ``--compaction-backend``
(``--backend``, default ``native``): the cluster shape starts several
nodes on this host, a chip belongs to one process, and host merges are
what this bench has measured all along.
"""

import argparse
import json
import os
import socket
import struct
import subprocess
import sys
import tempfile
import time

import msgpack

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def req(port, obj, timeout=10.0):
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    b = msgpack.packb(obj, use_bin_type=True)
    s.sendall(struct.pack("<H", len(b)) + b)
    hdr = b""
    while len(hdr) < 4:
        c = s.recv(4 - len(hdr))
        assert c, "connection closed"
        hdr += c
    (n,) = struct.unpack("<I", hdr)
    body = b""
    while len(body) < n:
        c = s.recv(n - len(body))
        assert c, "connection closed"
        body += c
    s.close()
    return body[-1], body[:-1]


def wait_up(port, deadline=120.0):
    t0 = time.time()
    while time.time() - t0 < deadline:
        try:
            t, _ = req(port, {"type": "get_cluster_metadata"})
            return
        except OSError:
            time.sleep(0.3)
    raise SystemExit("server never came up")


def run_load(port, duration, tag, op="set", key_count=0):
    """Connect-per-request Sets or Gets (the reference client
    dialect) for ``duration`` seconds; returns (sorted latency list
    in seconds, outliers) — outliers are (offset_s, latency_ms) for
    every op over 30 ms, time-stamped from the phase start so stalls
    can be correlated with server events (flush, compaction end).
    Get phases cycle over the ``key_count`` keys a previous set phase
    wrote under the same ``tag``."""
    lat = []
    outliers = []
    t0 = time.time()
    t_end = t0 + duration
    i = 0
    while time.time() < t_end:
        ta = time.time()
        if op == "set":
            body = {
                "type": "set",
                "collection": "c",
                "key": f"lb{tag}{i:08d}",
                "value": i,
            }
            t, b = req(port, body)
            assert t == 2, (t, b)
        else:
            body = {
                "type": "get",
                "collection": "c",
                "key": f"lb{tag}{i % max(1, key_count):08d}",
            }
            t, b = req(port, body)
            assert t == 1, (t, b)  # the key was written: must hit
        dt = time.time() - ta
        lat.append(dt)
        if dt > 0.03:
            outliers.append((round(ta - t0, 3), round(dt * 1e3, 1)))
        i += 1
    lat.sort()
    return lat, outliers


def pct(lat, p):
    return lat[min(len(lat) - 1, int(len(lat) * p))]


def summary(lat):
    return {
        "ops": len(lat),
        "p50_us": round(pct(lat, 0.50) * 1e6, 1),
        "p90_us": round(pct(lat, 0.90) * 1e6, 1),
        "p99_us": round(pct(lat, 0.99) * 1e6, 1),
        "p999_us": round(pct(lat, 0.999) * 1e6, 1),
        "max_ms": round(lat[-1] * 1e3, 2),
    }


def start_server(d, port, backend, extra=()):
    env = {
        **os.environ,
        "PYTHONPATH": REPO
        + (
            ":" + os.environ["PYTHONPATH"]
            if os.environ.get("PYTHONPATH")
            else ""
        ),
    }
    # DBEEL_SERVER_LOG=<path>: capture server stderr (e.g. the
    # DBEEL_LOOP_WATCHDOG stall stacks) instead of discarding it.
    log_path = os.environ.get("DBEEL_SERVER_LOG")
    out = (
        open(f"{log_path}.{port}", "wb")
        if log_path
        else subprocess.DEVNULL
    )
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "dbeel_tpu.server.run",
            "--dir",
            d,
            "--port",
            str(port),
            "--remote-shard-port",
            str(port + 10000),
            "--gossip-port",
            str(port + 20000),
            "--shards",
            "1",
            "--compaction-backend",
            backend,
            *extra,
        ],
        env=env,
        stdout=out,
        stderr=subprocess.STDOUT,
    )


def start_cluster_node(
    d, port, backend, name, seeds, shards=2, extra=()
):
    """One cluster node as its own OS process (config-5 shape)."""
    env = {
        **os.environ,
        "PYTHONPATH": REPO
        + (
            ":" + os.environ["PYTHONPATH"]
            if os.environ.get("PYTHONPATH")
            else ""
        ),
    }
    log_path = os.environ.get("DBEEL_SERVER_LOG")
    out = (
        open(f"{log_path}.{port}", "wb")
        if log_path
        else subprocess.DEVNULL
    )
    argv = [
        sys.executable,
        "-m",
        "dbeel_tpu.server.run",
        "--dir",
        d,
        "--name",
        name,
        "--port",
        str(port),
        "--remote-shard-port",
        str(port + 10000),
        "--gossip-port",
        str(port + 20000),
        "--shards",
        str(shards),
        "--compaction-backend",
        backend,
        *(("--seed-nodes", *seeds) if seeds else ()),
        *extra,
    ]
    return subprocess.Popen(
        argv, env=env, stdout=out, stderr=subprocess.STDOUT
    )


def run_quorum_load(port, duration, tag, op="set", key_count=0):
    """Connect-per-request quorum ops (consistency=2 on an RF=3
    collection) against the coordinator node."""
    lat = []
    outliers = []
    t0 = time.time()
    i = 0
    # All six shard ports (3 nodes x 2 shards, contiguous): the naive
    # replica walk needs the key's owning shard, which is anywhere on
    # the ring.
    ports = tuple(range(port, port + 6))
    while time.time() < t0 + duration:
        ta = time.time()
        body = {
            "collection": "c",
            "key": f"qb{tag}{i:08d}"
            if op == "set"
            else f"qb{tag}{i % max(1, key_count):08d}",
            "consistency": 2,
        }
        if op == "set":
            body["type"] = "set"
            body["value"] = i
        else:
            body["type"] = "get"
        # Naive-client replica walk: try each shard port until the
        # key is owned (KeyNotOwnedByShard punts to the next).
        ok = False
        for p in ports:
            t, b = req(p, body)
            if t == 0:
                err = msgpack.unpackb(b, raw=False)
                if err and err[0] == "KeyNotOwnedByShard":
                    continue
                if op == "get" and err and err[0] == "KeyNotFound":
                    ok = True  # raced a not-yet-written key: fine
                    break
                raise AssertionError(err)
            ok = True
            break
        assert ok, "no shard owned the key"
        dt = time.time() - ta
        lat.append(dt)
        if dt > 0.03:
            outliers.append((round(ta - t0, 3), round(dt * 1e3, 1)))
        i += 1
    lat.sort()
    return lat, outliers


def quorum_main(args):
    """BASELINE config-5-shaped latency run (VERDICT r3 #9): RF=3
    quorum Sets AND Gets measured while the coordinator node
    major-compacts pre-built runs — the BgThrottle story on the
    replicated plane."""
    base = tempfile.mkdtemp(prefix="latbench_q_")
    dirs = [os.path.join(base, f"n{i}") for i in range(3)]
    for d in dirs:
        os.makedirs(d)
    # Every shard of every node discovers collection "c" from disk
    # (metadata + per-shard dir); the pre-built runs live only in the
    # coordinator node's shard 0, whose startup compaction majors
    # them during the measurement.
    for d in dirs:
        with open(os.path.join(d, "c.metadata"), "wb") as f:
            f.write(msgpack.packb({"replication_factor": 3}))
        for sid in (0, 1):
            os.makedirs(os.path.join(d, f"c-{sid}"))
    col_dir = os.path.join(dirs[0], "c-0")
    print(
        f"building {args.runs} runs x {args.keys // args.runs} keys ...",
        file=sys.stderr,
    )
    from bench import build_runs

    build_runs(col_dir, args.keys, args.runs)

    p0 = args.port
    procs = [
        start_cluster_node(
            dirs[0], p0, args.backend, "n0", [], extra=args.server_arg
        )
    ]
    try:
        wait_up(p0)
        seed = f"127.0.0.1:{p0 + 10000}"
        for i in (1, 2):
            procs.append(
                start_cluster_node(
                    dirs[i],
                    p0 + 2 * i,
                    args.backend,
                    f"n{i}",
                    [seed],
                    extra=args.server_arg,
                )
            )
            wait_up(p0 + 2 * i)
        # Let discovery/gossip settle and compaction start.
        time.sleep(2.0)
        qset, qset_out = run_quorum_load(p0, args.duration, "s")
        qget, qget_out = run_quorum_load(
            p0, args.duration, "s", op="get", key_count=len(qset)
        )
        # Merge evidence: output files land only late in a big merge
        # (the throttled read phase writes nothing), so also accept
        # the coordinator shard's background-work counters.
        compacted = any(
            n.split(".")[0].isdigit() and int(n.split(".")[0]) % 2 == 1
            for n in os.listdir(col_dir)
        ) or any("compact" in n for n in os.listdir(col_dir))
        if not compacted:
            try:
                t, b = req(p0, {"type": "get_stats"})
                sched = msgpack.unpackb(b, raw=False)["scheduler"]
                compacted = (
                    sched.get("background_precharged_s", 0) > 0
                    or sched.get("background_busy_s", 0) > 0
                )
            except Exception:
                pass
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()


    for name, outs in (("quorum set", qset_out), ("quorum get", qget_out)):
        if outs:
            print(
                f"{name} outliers >30ms (offset_s, ms): {outs}",
                file=sys.stderr,
            )
    print(
        json.dumps(
            {
                "metric": "quorum_latency_under_major_compaction",
                "unit": "us",
                "keys": args.keys,
                "backend": args.backend,
                "server_args": args.server_arg,
                "quorum_set": summary(qset),
                "quorum_get": summary(qget),
                "compaction_observed": compacted,
            }
        )
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=10_000_000)
    ap.add_argument("--runs", type=int, default=8)
    ap.add_argument("--backend", default="native")
    ap.add_argument("--port", type=int, default=12600)
    ap.add_argument("--duration", type=float, default=8.0)
    ap.add_argument(
        "--quorum",
        action="store_true",
        help="config-5 shape: 3 nodes x 2 shards, RF=3, quorum "
        "set/get latency during the coordinator's major compaction",
    )
    ap.add_argument(
        "--server-arg",
        action="append",
        default=[],
        help="extra args passed to the server (repeatable), e.g. "
        "--server-arg=--background-tasks-shares=1000000 to neutralize "
        "the merge throttle for comparison",
    )
    args = ap.parse_args()
    if args.quorum:
        quorum_main(args)
        return

    from bench import build_runs  # noqa: E402 (repo-root import)

    # ---- quiet phase ------------------------------------------------
    d1 = tempfile.mkdtemp(prefix="latbench_quiet_")
    p1 = start_server(d1, args.port, args.backend, args.server_arg)
    try:
        wait_up(args.port)
        t, _ = req(args.port, {"type": "create_collection", "name": "c"})
        assert t == 2, "create failed"
        quiet, quiet_out = run_load(args.port, args.duration, "q")
        quiet_get, quiet_get_out = run_load(
            args.port, args.duration, "q", op="get", key_count=len(quiet)
        )
    finally:
        p1.terminate()
        p1.wait(timeout=20)

    # ---- compacting phase ------------------------------------------
    # Pre-build the big even-index runs + collection metadata, then
    # start the node: its startup compaction pass majors them while we
    # measure the same Set load.
    d2 = tempfile.mkdtemp(prefix="latbench_compact_")
    col_dir = os.path.join(d2, "c-0")
    os.makedirs(col_dir)
    with open(os.path.join(d2, "c.metadata"), "wb") as f:
        f.write(msgpack.packb({"replication_factor": 1}))
    print(
        f"building {args.runs} runs x {args.keys // args.runs} keys ...",
        file=sys.stderr,
    )
    build_runs(col_dir, args.keys, args.runs)

    port2 = args.port + 1
    p2 = start_server(d2, port2, args.backend, args.server_arg)
    compacted = False
    try:
        wait_up(port2)
        # Give the startup compaction a beat to actually begin.
        time.sleep(0.5)
        busy, busy_out = run_load(port2, args.duration, "b")
        busy_get, busy_get_out = run_load(
            port2, args.duration, "b", op="get", key_count=len(busy)
        )
        # Compaction evidence: an odd output index exists (in-flight
        # compact_* or finished .data).
        names = os.listdir(col_dir)
        compacted = any(
            n.split(".")[0].isdigit() and int(n.split(".")[0]) % 2 == 1
            for n in names
        ) or any("compact" in n for n in names)
        # Wait for the merge to finish so teardown is clean; the odd
        # output index appearing IS the compaction evidence (it may
        # land after the measurement window — the merge only writes
        # its compact_* files at the end).
        deadline = time.time() + 600
        while time.time() < deadline:
            names = os.listdir(col_dir)
            if any(
                n.endswith(".data")
                and int(n.split(".")[0]) % 2 == 1
                for n in names
            ) and not any("compact_" in n for n in names):
                compacted = True
                break
            time.sleep(1.0)
    finally:
        p2.terminate()
        p2.wait(timeout=30)


    for name, outs in (
        ("quiet set", quiet_out),
        ("quiet get", quiet_get_out),
        ("compacting set", busy_out),
        ("compacting get", busy_get_out),
    ):
        if outs:
            print(
                f"{name} outliers >30ms (offset_s, ms): {outs}",
                file=sys.stderr,
            )

    out = {
        "metric": "set_p99_under_major_compaction",
        "unit": "us",
        "keys": args.keys,
        "backend": args.backend,
        "server_args": args.server_arg,
        "quiet": summary(quiet),
        "quiet_get": summary(quiet_get),
        "compacting": summary(busy),
        "compacting_get": summary(busy_get),
        "compaction_observed": compacted,
        "p99_ratio": round(
            pct(busy, 0.99) / max(pct(quiet, 0.99), 1e-9), 2
        ),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
