#!/usr/bin/env python3
"""Black-box load generator.

Role parity with /root/reference/blackbox_bench/src/main.rs: N concurrent
clients x M requests each against a running cluster, shuffled key order,
a Set phase then a Get phase, and a min/p50/p90/p99/p999/max latency
report per phase (the README numbers in BASELINE.md come from this
shape of run: 20 clients x 5000 requests).

Usage:
    python -m dbeel_tpu.server.run --dir /tmp/bb --shards 4 \
        --compaction-backend native &
    python blackbox_bench.py --clients 20 --requests 5000

The operator starts the node(s).  Several nodes on one host all get
``--compaction-backend native``: a chip belongs to one process, and
every figure this generator has reported was taken on host merges.
"""

import argparse
import asyncio
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from dbeel_tpu.client import Consistency, DbeelClient  # noqa: E402


def percentiles(samples):
    samples = sorted(samples)
    n = len(samples)

    def at(q):
        return samples[min(n - 1, int(q * n))] * 1000  # ms

    return (
        f"min: {samples[0]*1000:.3f}ms "
        f"p50: {at(0.50):.3f}ms p90: {at(0.90):.3f}ms "
        f"p99: {at(0.99):.3f}ms p999: {at(0.999):.3f}ms "
        f"max: {samples[-1]*1000:.3f}ms"
    )


async def run_phase(
    client, collection, op, keys, n_clients, value, consistency=None,
    batch=0,
):
    """``batch=N`` switches the workers to multi_set/multi_get frames
    of N keys each (per-op latency then reports the whole batch's
    round trip for each constituent key — the honest cost of riding a
    batch)."""
    latencies = []

    async def worker(worker_keys):
        col = client.collection(collection)
        if batch:
            for i in range(0, len(worker_keys), batch):
                group = worker_keys[i : i + batch]
                t0 = time.perf_counter()
                if op == "set":
                    await col.multi_set(
                        [(k, value) for k in group], consistency
                    )
                else:
                    got = await col.multi_get(group, consistency)
                    assert all(v is not None for v in got)
                dt = time.perf_counter() - t0
                latencies.extend([dt] * len(group))
            return
        for k in worker_keys:
            t0 = time.perf_counter()
            if op == "set":
                await col.set(k, value, consistency)
            else:
                await col.get(k, consistency)
            latencies.append(time.perf_counter() - t0)

    chunk = (len(keys) + n_clients - 1) // n_clients
    t0 = time.perf_counter()
    await asyncio.gather(
        *[
            worker(keys[i * chunk : (i + 1) * chunk])
            for i in range(n_clients)
        ]
    )
    total = time.perf_counter() - t0
    return total, latencies


async def main_async(args):
    client = await DbeelClient.from_seed_nodes(
        [(args.host, args.port)],
        pipeline_window=args.pipeline or None,
    )
    from dbeel_tpu.errors import CollectionAlreadyExists

    try:
        await client.create_collection(
            args.collection, args.replication_factor
        )
    except CollectionAlreadyExists:
        pass

    keys = [f"key-{i:08}" for i in range(args.clients * args.requests)]
    rng = random.Random(args.seed)
    rng.shuffle(keys)
    value = {"blob": "x" * args.value_size}

    consistency = {
        "default": None,
        "quorum": Consistency.QUORUM,
        "all": Consistency.ALL,
        "one": Consistency.fixed(1),
    }[args.consistency]
    total, lat = await run_phase(
        client, args.collection, "set", keys, args.clients, value,
        consistency, batch=args.batch,
    )
    print(
        f"set: total {total:.3f}s "
        f"({len(keys)/total:,.0f} ops/s)  {percentiles(lat)}"
    )

    rng.shuffle(keys)
    total, lat = await run_phase(
        client, args.collection, "get", keys, args.clients, value,
        consistency, batch=args.batch,
    )
    print(
        f"get: total {total:.3f}s "
        f"({len(keys)/total:,.0f} ops/s)  {percentiles(lat)}"
    )
    client.close()


def main_native(args):
    """Compiled-client mode: N OS threads, each with its own
    NativeDbeelClient (blocking C round trips; the GIL releases during
    socket syscalls, so threads overlap like the reference's
    executor-pinned clients)."""
    import threading

    from dbeel_tpu.client.native_client import NativeDbeelClient
    from dbeel_tpu.errors import DbeelError

    boot = NativeDbeelClient(args.host, args.port)
    rf = args.replication_factor or 1
    try:
        boot.create_collection(args.collection, rf)
    except DbeelError as e:
        if "CollectionAlreadyExists" not in str(e):
            raise
    consistency = {
        "default": 0,
        "one": 1,
        "quorum": rf // 2 + 1,
        "all": rf,
    }[args.consistency]
    time.sleep(0.3)  # collection fan-out to sibling shards

    keys = [f"key-{i:08}" for i in range(args.clients * args.requests)]
    rng = random.Random(args.seed)
    rng.shuffle(keys)
    value = {"blob": "x" * args.value_size}

    def phase(op):
        lats = [[] for _ in range(args.clients)]
        errors = []
        chunk = (len(keys) + args.clients - 1) // args.clients

        def worker(wi):
            try:
                cli = NativeDbeelClient(args.host, args.port)
            except Exception as e:
                errors.append(e)
                return
            try:
                my_keys = keys[wi * chunk : (wi + 1) * chunk]
                if args.pipeline:
                    # Windowed pipelining, one C call per train of
                    # 1000 ops (the call releases the GIL for the
                    # whole train).  Per-op latency reports the
                    # train's wall clock spread over its ops — the
                    # honest cost of riding a train.
                    train = 1000
                    for i in range(0, len(my_keys), train):
                        group = my_keys[i : i + train]
                        t0 = time.perf_counter()
                        fails = cli.pipe_run(
                            args.collection,
                            op,
                            group,
                            [value] * len(group)
                            if op == "set"
                            else None,
                            consistency,
                            rf,
                            args.pipeline,
                        )
                        if fails:
                            raise RuntimeError(
                                f"{fails} pipelined ops failed"
                            )
                        dt = time.perf_counter() - t0
                        lats[wi].extend(
                            [dt / max(1, len(group))] * len(group)
                        )
                elif args.batch:
                    for i in range(0, len(my_keys), args.batch):
                        group = my_keys[i : i + args.batch]
                        t0 = time.perf_counter()
                        if op == "set":
                            cli.multi_set(
                                args.collection,
                                [(k, value) for k in group],
                                consistency,
                                rf,
                            )
                        else:
                            got = cli.multi_get(
                                args.collection, group,
                                consistency, rf,
                            )
                            if any(v is None for v in got):
                                raise RuntimeError(
                                    "multi_get missed a written key"
                                )
                        dt = time.perf_counter() - t0
                        lats[wi].extend([dt] * len(group))
                else:
                    for k in my_keys:
                        t0 = time.perf_counter()
                        if op == "set":
                            cli.set(
                                args.collection, k, value,
                                consistency, rf,
                            )
                        else:
                            cli.get(
                                args.collection, k, consistency, rf
                            )
                        lats[wi].append(time.perf_counter() - t0)
            except Exception as e:
                errors.append(e)
            finally:
                cli.close()

        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(args.clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = time.perf_counter() - t0
        if errors:
            # A failed run must not print inflated full-count
            # throughput (the async path aborts visibly too).
            raise errors[0]
        return total, [x for w in lats for x in w]

    for op in ("set", "get"):
        if op == "get":
            rng.shuffle(keys)
        total, lat = phase(op)
        print(
            f"{op}: total {total:.3f}s "
            f"({len(keys)/total:,.0f} ops/s)  {percentiles(lat)}"
        )
    boot.close()


async def main_native_floor(args):
    """--native-floor: the all-native serving path's headline number.
    Runs pipelined RF=1 sets+gets and batched multi_set/multi_get
    against the running server and reports, PER PHASE, the throughput
    and latency percentiles alongside the interval
    ``native_served_frac`` (frames answered without entering the
    Python dispatcher, from get_stats.native_path deltas).  For the
    same-session Python-path baseline (BENCH host-weather rule), run
    the same phase against a server started with DBEEL_NO_DATAPLANE=1
    (whole interpreted path) or DBEEL_DP_NO_MULTI=1 (interpreted
    multi fallback only) and compare in-session."""
    from dbeel_tpu.errors import CollectionAlreadyExists

    client = await DbeelClient.from_seed_nodes(
        [(args.host, args.port)],
        pipeline_window=args.pipeline or 32,
    )
    try:
        await client.create_collection(args.collection, 1)
    except CollectionAlreadyExists:
        pass

    keys = [f"nf-{i:08}" for i in range(args.clients * args.requests)]
    rng = random.Random(args.seed)
    value = {"blob": "x" * args.value_size}
    batch = args.batch or 64

    async def snap():
        stats = await client.get_stats(args.host, args.port)
        np_ = stats.get("native_path") or {}
        return {
            "served": dict(np_.get("served") or {}),
            "totals": dict(np_.get("totals") or {}),
            "frac": np_.get("native_served_frac"),
            "python_sheds": np_.get("python_sheds"),
            "native_sheds": np_.get("native_sheds"),
        }

    def interval_frac(before, after, verbs):
        served = sum(
            after["served"].get(v, 0) - before["served"].get(v, 0)
            for v in verbs
        )
        total = sum(
            after["totals"].get(v, 0) - before["totals"].get(v, 0)
            for v in verbs
        )
        if total <= 0:
            return None
        return min(1.0, served / total)

    phases = (
        ("pipelined set", "set", 0, ("write",)),
        ("pipelined get", "get", 0, ("get",)),
        ("batched multi_set", "set", batch, ("multi_set",)),
        ("batched multi_get", "get", batch, ("multi_get",)),
    )
    for label, op, phase_batch, verbs in phases:
        rng.shuffle(keys)
        before = await snap()
        total, lat = await run_phase(
            client, args.collection, op, keys, args.clients, value,
            None, batch=phase_batch,
        )
        after = await snap()
        frac = interval_frac(before, after, verbs)
        frac_s = "n/a (no dataplane)" if frac is None else f"{frac:.4f}"
        print(
            f"{label}: total {total:.3f}s "
            f"({len(keys)/total:,.0f} ops/s)  {percentiles(lat)}  "
            f"native_served_frac[{'+'.join(verbs)}]: {frac_s}"
        )
    final = await snap()
    print(
        f"server: native_served_frac={final['frac']} "
        f"served={final['served']} totals={final['totals']} "
        f"native_sheds={final['native_sheds']} "
        f"python_sheds={final['python_sheds']}"
    )
    client.close()


async def main_overload_knee(args):
    """--overload-knee: the overload-control plane's headline curve.
    Measure the SAME-SESSION sustainable closed-loop rate, then sweep
    open-loop offered load across multiples of it, recording goodput
    and p99-of-admitted per step — the knee: goodput should plateau
    (not collapse) and tail latency should stay bounded as offered
    load crosses sustainable, because the governor sheds instead of
    queueing.  Rows go to BENCH.md with the mandatory same-session
    baseline (ROADMAP "host weather" rule)."""
    import time as _time

    from dbeel_tpu.errors import (
        ERROR_CLASS_OVERLOAD,
        CollectionAlreadyExists,
        classify_error,
    )

    client = await DbeelClient.from_seed_nodes(
        [(args.host, args.port)], op_deadline_s=1.5
    )
    try:
        await client.create_collection(
            args.collection, args.replication_factor
        )
    except CollectionAlreadyExists:
        pass
    col = client.collection(args.collection)
    value = {"blob": "x" * args.value_size}
    loop = asyncio.get_event_loop()

    # Same-session sustainable baseline: closed loop, N workers.
    base_dur = 6.0
    base_ok = 0
    base_lat = []
    stop_at = loop.time() + base_dur

    async def base_worker(wid):
        nonlocal base_ok
        i = 0
        while loop.time() < stop_at:
            i += 1
            t0 = _time.perf_counter()
            try:
                await col.set(f"kb{wid}x{i}", value)
                base_lat.append(_time.perf_counter() - t0)
                base_ok += 1
            except Exception:
                pass

    t0 = _time.time()
    await asyncio.gather(
        *[base_worker(w) for w in range(args.clients)]
    )
    wall = max(0.001, _time.time() - t0)
    sustainable = base_ok / wall
    base_lat.sort()
    base_p99 = (
        base_lat[int(0.99 * (len(base_lat) - 1))] if base_lat else 0.0
    )
    print(
        f"sustainable (closed loop, {args.clients} clients): "
        f"{sustainable:,.0f} ops/s  p99 {base_p99 * 1000:.2f}ms"
    )
    print(
        f"{'offered x':>9} {'offered/s':>10} {'goodput/s':>10} "
        f"{'ratio':>6} {'p99 ms':>8} {'overload':>9} {'other err':>9}"
    )

    # Open-loop generators run as SUBPROCESSES: one Python client
    # process saturates ITSELF (~ms/op of pack+syscall+asyncio) long
    # before the native serving path saturates the server — measured
    # on this host: a single-process "3x" sweep collapsed its own
    # goodput with the server half idle.  N processes also contend
    # with the server for CPU, which is exactly how real co-located
    # overload presents.
    import json as _json
    import subprocess as _sp
    import sys as _sys

    # --classes (QoS plane, ISSUE 14): the TWO-CLASS sweep — at each
    # multiple, half the offered load is stamped `interactive` and
    # half `batch`; the per-class knee is the lowest multiple where
    # that class's overload-class errors exceed 1% of its launched
    # ops.  The contract under test: the interactive knee sits at a
    # STRICTLY higher multiple than batch, with batch sheds
    # dominating below it.
    classes = (
        ("interactive", "batch") if args.classes else (None,)
    )
    gen_procs = 3
    sweep_rows = []
    knees: dict = {}
    for mult in (0.5, 1.0, 1.5, 2.0, 3.0, 4.0):
        offered = max(10.0, sustainable * mult)
        dur = 8.0
        procs = []
        for ci, cname in enumerate(classes):
            share = offered / len(classes)
            procs.extend(
                (
                    cname,
                    _sp.Popen(
                        [
                            _sys.executable,
                            os.path.abspath(__file__),
                            "--overload-knee-worker",
                            "--knee-rate", str(share / gen_procs),
                            "--knee-duration", str(dur),
                            "--host", args.host,
                            "--port", str(args.port),
                            "--collection", args.collection,
                            "--value-size", str(args.value_size),
                            "--seed",
                            str(args.seed + ci * 100 + wi),
                        ]
                        + (
                            ["--knee-class", cname]
                            if cname is not None
                            else []
                        ),
                        stdout=_sp.PIPE,
                        text=True,
                    ),
                )
                for wi in range(gen_procs)
            )
        per_class: dict = {
            cname: {"ok": 0, "launched": 0, "lat": [], "err": {}}
            for cname in classes
        }
        for cname, p in procs:
            out, _ = p.communicate(timeout=dur + 60)
            row = _json.loads(out.strip().splitlines()[-1])
            st = per_class[cname]
            st["ok"] += row["ok"]
            st["launched"] += row["launched"]
            st["lat"].extend(row["lat_ms"])
            for k, v in row["err"].items():
                st["err"][k] = st["err"].get(k, 0) + v
        ok = sum(st["ok"] for st in per_class.values())
        launched = sum(
            st["launched"] for st in per_class.values()
        )
        lat = sorted(
            x for st in per_class.values() for x in st["lat"]
        )
        err: dict = {}
        for st in per_class.values():
            for k, v in st["err"].items():
                err[k] = err.get(k, 0) + v
        p99 = lat[int(0.99 * (len(lat) - 1))] if lat else float("nan")
        overload_errs = err.get(ERROR_CLASS_OVERLOAD, 0)
        other_errs = sum(err.values()) - overload_errs
        print(
            f"{mult:>9.1f} {offered:>10,.0f} {ok / dur:>10,.0f} "
            f"{ok / dur / max(1e-9, sustainable):>6.2f} "
            f"{p99:>8.1f} {overload_errs:>9} {other_errs:>9}"
        )
        row_out = {
            "mult": mult,
            "offered_per_s": round(offered, 1),
            "goodput_per_s": round(ok / dur, 1),
            "p99_ms": None if lat == [] else p99,
            "overload_errs": overload_errs,
            "other_errs": other_errs,
        }
        for cname in classes:
            if cname is None:
                continue
            st = per_class[cname]
            clat = sorted(st["lat"])
            c_ov = st["err"].get(ERROR_CLASS_OVERLOAD, 0)
            shed_frac = c_ov / max(1, st["launched"])
            row_out[cname] = {
                "launched": st["launched"],
                "ok": st["ok"],
                "goodput_per_s": round(st["ok"] / dur, 1),
                "p99_ms": clat[int(0.99 * (len(clat) - 1))]
                if clat
                else None,
                "overload_errs": c_ov,
                "shed_frac": round(shed_frac, 4),
            }
            if cname not in knees and shed_frac > 0.01:
                knees[cname] = mult
            print(
                f"          {cname:>12}: goodput "
                f"{st['ok'] / dur:>8,.0f}/s  sheds {c_ov:>7} "
                f"({100 * shed_frac:.1f}%)  p99 "
                f"{row_out[cname]['p99_ms'] or 0:.1f}ms"
            )
        sweep_rows.append(row_out)
    if args.classes:
        b_knee = knees.get("batch")
        i_knee = knees.get("interactive")
        print(
            f"knees: batch={b_knee}x interactive={i_knee}x "
            f"(None = never shed in the sweep)"
        )
        result = {
            "sustainable_ops_per_s": round(sustainable, 1),
            "baseline_p99_ms": round(base_p99 * 1000, 2),
            "clients": args.clients,
            "replication_factor": args.replication_factor,
            "sweep": sweep_rows,
            "knee_batch_mult": b_knee,
            "knee_interactive_mult": i_knee,
            "interactive_knee_strictly_higher": (
                b_knee is not None
                and (i_knee is None or i_knee > b_knee)
            ),
        }
        if args.json_out:
            with open(args.json_out, "w") as f:
                _json.dump(result, f, indent=1, sort_keys=True)
            print(f"wrote {args.json_out}")
    # The governor's view after the sweep.
    stats = await client.get_stats(args.host, args.port)
    ov = stats.get("overload", {})
    sig = ov.get("signals", {})
    np_ = stats.get("native_path") or {}
    print(
        f"server: sheds={ov.get('shed_ops')} "
        f"deadline_drops={ov.get('deadline_drops')} "
        f"dead_completions={ov.get('dead_completions')} "
        f"window_min_seen={ov.get('window_min_seen')} "
        f"bg_delays={ov.get('bg_delays')} "
        f"loop_lag_ms={sig.get('loop_lag_ms')} "
        # All-native shed gate: shed frames answered in C vs the
        # interpreted residue (the zero-Python-dispatch claim).
        f"native_sheds={np_.get('native_sheds')} "
        f"python_sheds={np_.get('python_sheds')} "
        f"native_deadline_drops={np_.get('native_deadline_drops')}"
    )
    qs = stats.get("qos") or {}
    if args.classes and qs:
        for cname, lane in (qs.get("classes") or {}).items():
            print(
                f"server qos {cname}: "
                f"admitted={lane.get('admitted')} "
                f"shed={lane.get('shed')} "
                f"native_sheds={lane.get('native_sheds')} "
                f"window={lane.get('window')} "
                f"level={lane.get('level')}"
            )
    client.close()


async def main_knee_worker(args):
    """One open-loop generator process (see main_overload_knee):
    paces ops at --knee-rate for --knee-duration, prints one JSON
    row of outcomes."""
    import json as _json
    import time as _time

    from dbeel_tpu.errors import classify_error

    # Pipelined transport: one socket, multiplexed — the cheapest
    # per-op client path in Python, so the generator's own ceiling
    # sits well above the closed-loop sustainable rate.
    client = await DbeelClient.from_seed_nodes(
        [(args.host, args.port)],
        op_deadline_s=1.5,
        pipeline_window=256,
        # Two-class sweep (QoS plane): this generator's lane.
        qos_class=args.knee_class or None,
    )
    col = client.collection(args.collection)
    value = {"blob": "x" * args.value_size}
    loop = asyncio.get_event_loop()
    inflight: set = set()
    ok = launched = 0
    lat: list = []
    err: dict = {}

    async def one(i):
        nonlocal ok
        t0 = _time.perf_counter()
        try:
            await asyncio.wait_for(
                col.set(f"ko{args.seed}x{i}", value), 10
            )
            lat.append(
                round((_time.perf_counter() - t0) * 1000, 2)
            )
            ok += 1
        except Exception as e:
            cls = classify_error(e) or "other"
            err[cls] = err.get(cls, 0) + 1

    t_start = loop.time()
    tick = 0.02
    carry = 0.0
    while loop.time() - t_start < args.knee_duration:
        carry += args.knee_rate * tick
        n = int(carry)
        carry -= n
        for _ in range(n):
            if len(inflight) >= 1500:
                continue
            launched += 1
            t = asyncio.ensure_future(one(launched))
            inflight.add(t)
            t.add_done_callback(inflight.discard)
        await asyncio.sleep(tick)
    if inflight:
        await asyncio.wait(inflight, timeout=15)
    client.close()
    print(
        _json.dumps(
            {
                "ok": ok,
                "launched": launched,
                "lat_ms": lat,
                "err": err,
            }
        )
    )


def _us_pct(samples, q):
    if not samples:
        return 0
    samples = sorted(samples)
    return samples[min(len(samples) - 1, int(q * len(samples)))]


async def main_attribute(args):
    """--attribute (tracing plane, ISSUE 9): run a short mixed
    set/get load against an RF>=2 collection on a server started
    with --trace-sample, then print a per-op per-stage p50/p99
    breakdown assembled from every shard's flight recorder — where
    the time went, not just how much there was.  Run the same
    command against a --trace-sample 0 server for the same-session
    tracing-off baseline (throughput printed per phase either way)."""
    client = await DbeelClient.from_seed_nodes(
        [(args.host, args.port)],
        pipeline_window=args.pipeline or None,
    )
    from dbeel_tpu.errors import CollectionAlreadyExists

    rf = args.replication_factor or 2
    try:
        await client.create_collection(args.collection, rf)
    except CollectionAlreadyExists:
        pass
    keys = [f"key-{i:08}" for i in range(args.clients * args.requests)]
    rng = random.Random(args.seed)
    rng.shuffle(keys)
    value = {"blob": "x" * args.value_size}
    for op in ("set", "get"):
        total, lat = await run_phase(
            client, args.collection, op, keys, args.clients, value
        )
        print(
            f"{op}: total {total:.3f}s "
            f"({len(keys)/total:,.0f} ops/s)  {percentiles(lat)}"
        )
        rng.shuffle(keys)

    # Every shard's recorder (the client ring knows all listeners).
    addrs = sorted({(s.ip, s.db_port) for s in client._ring})
    spans, rtts, rep_stages = [], [], []
    sample_every = None
    for a in addrs:
        try:
            dump = await client.trace_dump(*a)
        except Exception as e:
            print(f"trace_dump from {a} failed: {e!r}")
            continue
        sample_every = dump.get("sample_every")
        for e in dump["entries"]:
            if not e.get("sampled"):
                continue
            spans.append(e)
            for r in e.get("replicas") or ():
                rtts.append(r["rtt_us"])
                if r.get("stages"):
                    rep_stages.append(r["stages"])
    if not spans:
        print(
            "no sampled spans recorded — start the server with "
            "--trace-sample N for the attribution table"
        )
        client.close()
        return
    print(
        f"\nstage attribution from {len(spans)} sampled spans "
        f"(server sample_every={sample_every}, {len(addrs)} shards):"
    )
    by_op = {}
    for e in spans:
        stages = by_op.setdefault(e["op"], {})
        for stage, us in e["stages"]:
            stages.setdefault(stage, []).append(us)
        stages.setdefault("TOTAL", []).append(e["total_us"])
    for op in sorted(by_op):
        stages = by_op[op]
        n = len(stages["TOTAL"])
        total_sum = sum(stages["TOTAL"]) or 1
        print(f"  {op} (n={n}):")
        order = sorted(
            (s for s in stages if s != "TOTAL"),
            key=lambda s: -sum(stages[s]),
        ) + ["TOTAL"]
        for stage in order:
            xs = stages[stage]
            share = (
                sum(xs) / total_sum if stage != "TOTAL" else 1.0
            )
            print(
                f"    {stage:<10} p50 {_us_pct(xs, 0.5):>8}us  "
                f"p99 {_us_pct(xs, 0.99):>8}us  "
                f"share {share:>5.1%}"
            )
    if rtts:
        print(
            f"  replica rtt (n={len(rtts)}): "
            f"p50 {_us_pct(rtts, 0.5)}us p99 {_us_pct(rtts, 0.99)}us"
        )
    if rep_stages:
        q = [s[0] for s in rep_stages]
        w = [s[1] for s in rep_stages]
        print(
            f"  replica stages: queue p50 {_us_pct(q, 0.5)}us "
            f"p99 {_us_pct(q, 0.99)}us | serve p50 "
            f"{_us_pct(w, 0.5)}us p99 {_us_pct(w, 0.99)}us"
        )
    client.close()


async def main_scan_filter(args):
    """--scan-filter (query compute plane, ISSUE 13): selectivity
    sweep comparing PREDICATE PUSHDOWN against client-side filtering
    of the same stream, same session.  At each selectivity
    (100% / 10% / 0.1%) both sides scan the identical keyspace; the
    gate compares (a) client-received wire bytes (the server's
    emitted-chunk accounting) and (b) keys-SCANNED/s — pushdown must
    reduce bytes >= 50x at 0.1% selectivity and never lose on
    throughput.  A grouped-aggregate pass (sum over a value field,
    grouped by key prefix) measures the no-values-at-all path."""
    from dbeel_tpu.errors import CollectionAlreadyExists

    client = await DbeelClient.from_seed_nodes(
        [(args.host, args.port)],
        pipeline_window=args.pipeline or 32,
    )
    rf = args.replication_factor or 1
    try:
        await client.create_collection(args.collection, rf)
    except CollectionAlreadyExists:
        pass
    col = client.collection(args.collection)
    n = args.clients * args.requests
    keys = [f"key-{i:08}" for i in range(n)]

    # Docs carry a numeric selectivity lane + the blob payload the
    # wire-byte gate weighs.  One batched writer (load is no gate).
    t0 = time.perf_counter()
    for i in range(0, n, 256):
        await col.multi_set(
            {
                keys[j]: {"v": j, "blob": "x" * args.value_size}
                for j in range(i, min(i + 256, n))
            }
        )
    print(f"load: {n} keys in {time.perf_counter() - t0:.2f}s")

    async def scan_stats():
        s = await client.get_stats(args.host, args.port)
        sc = s["scan"]
        return (
            sc["bytes_streamed"],
            sc["filter"]["rows_scanned"],
            sc["filter"]["bytes_saved"],
        )

    def pred_for(frac):
        cut = max(1, int(n * frac))
        return ["cmp", "v", "<", cut], cut

    # Warm the staged value column once (a count touches no values
    # on the wire): the batched per-stage field decode is a ONE-TIME
    # cost any multi-chunk scan amortizes; the sweep measures the
    # steady state, not the first-ever spec against a cold stage.
    await col.count(filter=["cmp", "v", ">=", 0])

    report = {"n_keys": n, "value_size": args.value_size,
              "selectivity": {}}
    for label, frac in (
        ("100%", 1.0), ("10%", 0.10), ("0.1%", 0.001),
    ):
        pred, cut = pred_for(frac)
        await asyncio.sleep(0.4)  # let share pacing windows lapse
        # Pushdown side.
        b0, _r0, _s0 = await scan_stats()
        t0 = time.perf_counter()
        got = 0
        async for _k, _v in col.scan(filter=pred):
            got += 1
        t_push = time.perf_counter() - t0
        b1, _r1, _s1 = await scan_stats()
        push_bytes = b1 - b0
        assert got == cut, (got, cut)
        await asyncio.sleep(0.4)
        # Client-side filtering of the full stream (what PR 12
        # offered): ship everything, test locally.
        t0 = time.perf_counter()
        got_c = 0
        async for _k, v in col.scan():
            if v["v"] < cut:
                got_c += 1
        t_client = time.perf_counter() - t0
        b2, _r2, _s2 = await scan_stats()
        client_bytes = b2 - b1
        assert got_c == cut, (got_c, cut)
        rate_push = n / t_push
        rate_client = n / t_client
        byte_ratio = client_bytes / max(1, push_bytes)
        print(
            f"selectivity {label:>5}: pushdown {t_push:.3f}s "
            f"({rate_push:,.0f} keys-scanned/s, "
            f"{push_bytes:,}B to client)  |  client-side "
            f"{t_client:.3f}s ({rate_client:,.0f} keys/s, "
            f"{client_bytes:,}B)  ->  bytes x{byte_ratio:,.1f} "
            f"smaller, speedup x{rate_push / rate_client:.2f}"
        )
        report["selectivity"][label] = {
            "pushdown_s": round(t_push, 4),
            "pushdown_keys_scanned_per_s": round(rate_push),
            "pushdown_client_bytes": push_bytes,
            "client_side_s": round(t_client, 4),
            "client_side_keys_per_s": round(rate_client),
            "client_side_bytes": client_bytes,
            "bytes_reduction_x": round(byte_ratio, 1),
            "speedup_x": round(rate_push / rate_client, 2),
        }

    # Grouped aggregate: sum(v) grouped by a key prefix — replica
    # partials only, no keys and no values on the wire.
    await asyncio.sleep(0.4)
    b0, _r, _s = await scan_stats()
    t0 = time.perf_counter()
    import msgpack as _mp

    gp = len(_mp.packb(keys[0])) - 2  # group on all but last 2 chars
    grouped = await col.count(
        aggregate={"op": "sum", "field": "v", "group": gp}
    )
    t_agg = time.perf_counter() - t0
    b1, _r, _s = await scan_stats()
    t0 = time.perf_counter()
    acc = {}
    async for k, v in col.scan():
        acc[k[:-2]] = acc.get(k[:-2], 0) + v["v"]
    t_aggc = time.perf_counter() - t0
    assert len(grouped) == len(acc) and sum(
        grouped.values()
    ) == sum(acc.values())
    print(
        f"grouped aggregate (sum/v, {len(grouped)} groups): "
        f"pushdown {t_agg:.3f}s ({n / t_agg:,.0f} keys/s, "
        f"{b1 - b0:,}B) vs client-side {t_aggc:.3f}s "
        f"({n / t_aggc:,.0f} keys/s)  "
        f"speedup x{t_aggc / t_agg:.2f}"
    )
    report["grouped_aggregate"] = {
        "groups": len(grouped),
        "pushdown_s": round(t_agg, 4),
        "pushdown_keys_per_s": round(n / t_agg),
        "pushdown_client_bytes": b1 - b0,
        "client_side_s": round(t_aggc, 4),
        "client_side_keys_per_s": round(n / t_aggc),
        "speedup_x": round(t_aggc / t_agg, 2),
    }
    stats = await client.get_stats(args.host, args.port)
    print(f"server filter block: {stats['scan']['filter']}")
    report["server_filter_block"] = stats["scan"]["filter"]
    client.close()
    print("SCAN_FILTER_REPORT " + json.dumps(report))


async def main_cas(args):
    """--cas (atomic plane, ISSUE 19): same-session CAS cost profile
    against a running server.

    Phase A: plain-set baseline (the LWW floor CAS must be judged
    against).  Phase B: UNCONTENDED CAS — each worker chains
    expect_value updates on its own key, so the delta vs phase A is
    the pure decide cost (owner read + arc lock + replication).
    Phase C: the contention knee — 1/4/16 writers incrementing ONE
    hot key through the compliant read→cas→on-conflict-re-read loop;
    reports acked increments/s, the conflict ratio, attempts per
    acked increment, and the acked p99 of the WHOLE retry cycle (the
    price a real hot-key workload pays).  Correctness is asserted in
    passing: the hot counter's final value must equal total acked
    increments.  --json-out writes the BENCH_r19.json artifact."""
    from dbeel_tpu.errors import (
        CasConflict,
        CollectionAlreadyExists,
        KeyNotFound,
    )

    client = await DbeelClient.from_seed_nodes(
        [(args.host, args.port)]
    )
    try:
        await client.create_collection(
            args.collection, args.replication_factor
        )
    except CollectionAlreadyExists:
        pass
    col = client.collection(args.collection)
    dur = args.cas_duration
    loop = asyncio.get_event_loop()
    report = {
        "duration_per_cell_s": dur,
        "clients": args.clients,
        "value_size": args.value_size,
    }

    value = {"blob": "x" * args.value_size}
    # Fresh keys per run: expect_absent creates and the final-count
    # assertion both assume nothing is left over from a prior run.
    run = f"{int(time.time()) % 1000000}"

    # ---- A: plain-set baseline --------------------------------------
    async def timed_cell(worker_fn, n_workers):
        lat = []
        stop_at = loop.time() + dur
        counts = await asyncio.gather(
            *[worker_fn(w, stop_at, lat) for w in range(n_workers)]
        )
        return sum(counts), lat

    async def set_worker(w, stop_at, lat):
        i = ok = 0
        while loop.time() < stop_at:
            i += 1
            t0 = time.perf_counter()
            await col.set(f"casb{w}x{i}", value)
            lat.append(time.perf_counter() - t0)
            ok += 1
        return ok

    ok, lat = await timed_cell(set_worker, args.clients)
    report["set_baseline"] = {
        "ops_per_s": round(ok / dur, 1),
        "p99_ms": round(
            sorted(lat)[int(0.99 * (len(lat) - 1))] * 1000, 3
        ) if lat else None,
    }
    print(
        f"set baseline: {report['set_baseline']['ops_per_s']:,.0f} "
        f"ops/s  {percentiles(lat)}"
    )

    # ---- B: uncontended CAS chains ----------------------------------
    async def chain_worker(w, stop_at, lat):
        key = f"caschain{run}w{w}"
        cur = value | {"w": w, "i": 0}
        t0 = time.perf_counter()
        await col.cas(key, cur, expect_absent=True)
        lat.append(time.perf_counter() - t0)
        ok = 1
        while loop.time() < stop_at:
            nxt = value | {"w": w, "i": cur["i"] + 1}
            t0 = time.perf_counter()
            await col.cas(key, nxt, expect_value=cur)
            lat.append(time.perf_counter() - t0)
            cur = nxt
            ok += 1
        return ok

    ok, lat = await timed_cell(chain_worker, args.clients)
    report["cas_uncontended"] = {
        "ops_per_s": round(ok / dur, 1),
        "p99_ms": round(
            sorted(lat)[int(0.99 * (len(lat) - 1))] * 1000, 3
        ) if lat else None,
        "vs_set_baseline": round(
            (ok / dur) / max(report["set_baseline"]["ops_per_s"], 1e-9),
            3,
        ),
    }
    print(
        f"cas uncontended: "
        f"{report['cas_uncontended']['ops_per_s']:,.0f} ops/s "
        f"({report['cas_uncontended']['vs_set_baseline']:.2f}x of "
        f"plain set)  {percentiles(lat)}"
    )

    # ---- C: hot-key contention knee ---------------------------------
    report["contention_knee"] = []
    for n_writers in (1, 4, 16):
        hot = f"cashot{run}w{n_writers}"
        attempts = [0]
        conflicts = [0]

        async def hot_worker(w, stop_at, lat):
            acked = 0
            while loop.time() < stop_at:
                t_cycle = time.perf_counter()
                while True:
                    cur = None
                    try:
                        cur = await col.get(hot)
                    except KeyNotFound:
                        pass
                    attempts[0] += 1
                    try:
                        if cur is None:
                            await col.cas(
                                hot, {"n": 1},
                                expect_absent=True,
                            )
                        else:
                            await col.cas(
                                hot, {"n": cur["n"] + 1},
                                expect_value=cur,
                            )
                        break
                    except CasConflict:
                        conflicts[0] += 1
                        if loop.time() >= stop_at:
                            return acked
                lat.append(time.perf_counter() - t_cycle)
                acked += 1
            return acked

        acked, lat = await timed_cell(hot_worker, n_writers)
        final = (await col.get(hot))["n"]
        cell = {
            "writers": n_writers,
            "acked_increments_per_s": round(acked / dur, 1),
            "acked_p99_ms": round(
                sorted(lat)[int(0.99 * (len(lat) - 1))] * 1000, 3
            ) if lat else None,
            "attempts_per_acked": round(
                attempts[0] / max(acked, 1), 3
            ),
            "conflict_ratio": round(
                conflicts[0] / max(attempts[0], 1), 4
            ),
            "final_count": final,
            "acked_total": acked,
            "zero_lost_updates": final == acked,
        }
        assert cell["zero_lost_updates"], (
            f"hot key {hot}: final {final} != acked {acked}"
        )
        report["contention_knee"].append(cell)
        print(
            f"knee w={n_writers}: "
            f"{cell['acked_increments_per_s']:,.0f} incr/s, "
            f"{cell['attempts_per_acked']:.2f} attempts/acked, "
            f"conflict ratio {cell['conflict_ratio']:.3f}, "
            f"acked p99 {cell['acked_p99_ms']}ms"
        )

    print("CAS_REPORT " + json.dumps(report))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
    client.close()


async def main_scan_filter_indexed(args):
    """--scan-filter-indexed (secondary indexes, ISSUE 17):
    same-session A/B of the persisted-index scan planner against
    scan-everything on the SAME tree and the SAME predicate, at
    0.1%/1%/10% selectivity.

    Storage-level by design (like --compaction): the planner's win is
    a per-shard scan-path number, and the host-weather rule makes
    only the same-session pair meaningful.  Every indexed page is
    asserted BYTE-identical (entries, covers, scanned accounting) to
    its non-indexed twin before its timing counts.  Acceptance:
    indexed keys-matched/s >= 10x scan-everything at 0.1%
    selectivity, read_amplification ~1.0 (index maintenance added
    zero extra data reads), maintenance amplification reported."""
    import shutil
    import tempfile

    import msgpack

    from dbeel_tpu import query as Q
    from dbeel_tpu.storage import secondary_index as si
    from dbeel_tpu.storage.compaction import compaction_stats
    from dbeel_tpu.storage.lsm_tree import LSMTree

    rng = random.Random(args.seed)
    n = args.clients * args.requests
    d = tempfile.mkdtemp(prefix="dbeel-fidx-bench-")
    base = compaction_stats.stats()
    report = {
        "n_keys": n,
        "value_size": args.value_size,
        "selectivity": {},
    }

    tree = LSMTree.open_or_create(
        d + "/t",
        capacity=1 << 14,
        index_fields=["v"],
        memtable_kind="sorted",
    )
    try:
        t0 = time.perf_counter()
        order = list(range(n))
        rng.shuffle(order)
        for j in order:
            await tree.set_with_timestamp(
                msgpack.packb(f"key-{j:08}"),
                msgpack.packb(
                    {"v": j, "blob": "x" * args.value_size}
                ),
                1000 + j,
            )
        await tree.flush()
        live = [i for i, _ in tree.sstable_indices_and_sizes()]
        await tree.compact(live, max(live) + 1, False)
        print(
            f"load: {n} keys, {len(live)} runs merged in "
            f"{time.perf_counter() - t0:.2f}s"
        )

        async def page_all(where):
            out, covers, paths, sa = [], [], [], None
            while True:
                (
                    es, more, cover, srows, sbytes, _p, path,
                ) = await tree.scan_filter_page(
                    0, 0, sa, None, 1 << 16, 1 << 24, True,
                    where, None, Q.MODE_DROP,
                )
                out.extend(es)
                covers.append((cover, srows, sbytes))
                paths.append(path)
                if not more:
                    return out, covers, paths
                sa = cover

        async def warm():
            # Build the SHARED vectorized-stage lanes (key/offset
            # extraction) outside the timed region — the A/B mode
            # toggle drops the stage cache, and both evaluators pay
            # that identical setup.  Predicate state stays cold on
            # both sides: scan-everything re-extracts the field
            # column (a msgpack decode of EVERY row's value) after any
            # stage rebuild, while the indexed path reads the
            # persisted .fidx runs — exactly the cost the persistent
            # index exists to eliminate, so it belongs in the timing.
            await tree.scan_filter_page(
                0, 0, None, None, 1, 1 << 16, True,
                None, None, Q.MODE_DROP,
            )

        for label, frac in (
            ("0.1%", 0.001), ("1%", 0.01), ("10%", 0.10),
        ):
            cut = max(1, int(n * frac))
            where = Q.validate_where(["cmp", "v", "<", cut])
            # Indexed side.
            await warm()
            t0 = time.perf_counter()
            got_i = await page_all(where)
            t_idx = time.perf_counter() - t0
            assert "indexed" in got_i[2], got_i[2]
            assert len(got_i[0]) == cut, (len(got_i[0]), cut)
            # Scan-everything twin, same session, same tree.
            tree.index_fields = None
            tree._drop_scan_stage()
            try:
                await warm()
                t0 = time.perf_counter()
                got_s = await page_all(where)
                t_scan = time.perf_counter() - t0
            finally:
                tree.index_fields = ["v"]
                tree._drop_scan_stage()
            assert got_i[0] == got_s[0], "entries diverged"
            assert got_i[1] == got_s[1], "covers/accounting diverged"
            rate_idx = cut / t_idx
            rate_scan = cut / t_scan
            speedup = rate_idx / rate_scan
            print(
                f"selectivity {label:>5}: indexed {t_idx:.3f}s "
                f"({rate_idx:,.0f} keys-matched/s) | "
                f"scan-everything {t_scan:.3f}s "
                f"({rate_scan:,.0f} keys-matched/s) -> "
                f"speedup x{speedup:.1f}  [byte-identical]"
            )
            report["selectivity"][label] = {
                "matched": cut,
                "indexed_s": round(t_idx, 4),
                "indexed_keys_matched_per_s": round(rate_idx),
                "scan_everything_s": round(t_scan, 4),
                "scan_keys_matched_per_s": round(rate_scan),
                "speedup_x": round(speedup, 2),
                "byte_identical": True,
            }

        now = compaction_stats.stats()
        # Maintenance cost: the merge pass read exactly its inputs
        # even while emitting index runs (zero extra data reads).
        extra_reads = (now["bytes_read"] - base["bytes_read"]) - (
            now["merge_input_bytes"] - base["merge_input_bytes"]
        )
        report["compaction"] = {
            "read_amplification": now["read_amplification"],
            "extra_data_bytes_read_for_index": extra_reads,
            "index_bytes_written": now["index_bytes_written"]
            - base["index_bytes_written"],
            "index_maintenance_amplification": now[
                "index_maintenance_amplification"
            ],
        }
        report["index"] = si.index_stats.stats()
        assert extra_reads == 0, extra_reads
        gate = report["selectivity"]["0.1%"]["speedup_x"]
        report["gate_speedup_0p1_x"] = gate
        report["gate_pass"] = bool(gate >= 10.0)
        print(
            f"compaction: read_amplification="
            f"{now['read_amplification']} "
            f"index_maintenance_amplification="
            f"{now['index_maintenance_amplification']} "
            f"extra data reads for index: {extra_reads}B"
        )
        print(
            f"GATE 0.1%: speedup x{gate:.1f} "
            f"({'PASS' if report['gate_pass'] else 'FAIL'} >= x10)"
        )
        print(
            "SCAN_FILTER_INDEXED_REPORT " + json.dumps(report)
        )
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump(report, f, indent=1)
            print(f"wrote {args.json_out}")
    finally:
        tree.close()
        shutil.rmtree(d, ignore_errors=True)


async def main_watch(args):
    """--watch (Watch/CDC plane, ISSUE 20): commit→delivery latency
    and the idle-subscriber interference gate, same-session.

    Phase A: point-set goodput baseline with zero watchers attached
    (the hot collection's native fast path is pre-suspended first so
    A and C both measure the interpreted write path — attaching a
    watcher suspends it anyway, and an A/B across different planes
    would be meaningless).
    Phase B: commit→delivery — one measuring subscriber tails the
    written collection while a paced writer stamps a send time into
    every doc; p50/p99 of (delivery − send), measured with 1 / 64 /
    1024 TOTAL attached subscribers.  The extras are IDLE: they
    long-poll a second, never-written collection, so the cells
    isolate the cost of merely-attached watchers (registry,
    long-poll parks, per-collection wakeups) — not event fan-out.
    Phase C: the interference gate — the SAME closed-loop set
    workload as A with the 1024 idle watchers still parked.
    Acceptance: goodput within 10%% of the no-watcher baseline."""
    import time as _time

    from dbeel_tpu.errors import CollectionAlreadyExists

    client = await DbeelClient.from_seed_nodes(
        [(args.host, args.port)]
    )
    rf = args.replication_factor or 1
    hot = args.collection + "hot"
    quiet = args.collection + "idle"
    for name in (hot, quiet):
        try:
            await client.create_collection(name, rf)
        except CollectionAlreadyExists:
            pass
    hotcol = client.collection(hot)
    dur = args.watch_duration
    loop = asyncio.get_event_loop()
    value = {"blob": "x" * args.value_size}
    report = {
        "duration_per_cell_s": dur,
        "clients": args.clients,
        "value_size": args.value_size,
        "idle_poll": {"wait_ms": 1000, "interval_s": "6-10 jittered"},
    }

    # Pre-suspend the hot collection's native plane: one throwaway
    # watch chunk is enough (sticky), so phase A's writes take the
    # same interpreted path phase C's will.
    pre = hotcol.watcher(wait_ms=0)
    await pre.next_events()

    async def set_goodput(dur_s):
        """Closed-loop sets from args.clients workers: (ops/s,
        p99 ms, errors).  Timeouts/sheds count as errors, not
        crashes — under heavy watcher load they ARE the
        interference signal."""
        lat = []
        errs = [0]
        stop_at = loop.time() + dur_s

        async def one(wid):
            i = 0
            while loop.time() < stop_at:
                i += 1
                t1 = _time.perf_counter()
                try:
                    await hotcol.set(f"g{wid}-{i:07d}", value)
                except Exception:
                    errs[0] += 1
                    continue
                lat.append(_time.perf_counter() - t1)

        await asyncio.gather(
            *(one(w) for w in range(args.clients))
        )
        lat.sort()
        p99 = (
            lat[int(0.99 * (len(lat) - 1))] * 1000 if lat else 0.0
        )
        return len(lat) / dur_s, round(p99, 3), errs[0]

    base_rate, base_p99, base_errs = await set_goodput(dur)
    report["baseline_set"] = {
        "ops_per_s": round(base_rate, 1),
        "p99_ms": base_p99,
        "errors": base_errs,
    }
    print(
        f"baseline set (no watchers): {base_rate:,.0f} ops/s  "
        f"p99 {base_p99:.2f}ms"
    )

    # ---- idle-watcher pool (attach incrementally per cell) ----------
    # Each idle subscriber holds a registered watch on the quiet
    # collection and re-polls on a jittered ~8 s cadence (well under
    # the 60 s registration TTL).  A hot re-poll loop would be
    # dishonest here: with the harness and server sharing this
    # host's cores, 1024 watchers re-polling the instant each 2 s
    # park expires measure harness self-interference, not server
    # cost — and the resulting shed/retry connection storm can SYN-
    # flood the listener.  One pooled client per 64 watchers keeps
    # connection reuse sane.
    import random as _random

    idle_clients: list = []
    idle_stop = asyncio.Event()
    idle_tasks: list = []

    async def idle_loop(w):
        while not idle_stop.is_set():
            try:
                await w.next_events()
            except Exception:
                await asyncio.sleep(1.0)
                continue
            try:
                await asyncio.wait_for(
                    idle_stop.wait(), 6.0 + 4.0 * _random.random()
                )
            except asyncio.TimeoutError:
                pass

    async def subs_gauge():
        """Registered-subscriber count summed over the node's
        shards (`get_stats.watch.subscribers`)."""
        total = 0
        for sid in range(args.shards or 1):
            try:
                st = await client.get_stats(
                    args.host, args.port + sid
                )
                total += (st.get("watch") or {}).get(
                    "subscribers", 0
                )
            except Exception:
                pass
        return total

    async def ensure_idle(n):
        while len(idle_tasks) < n:
            batch = min(64, n - len(idle_tasks))
            cl = await DbeelClient.from_seed_nodes(
                [(args.host, args.port)], op_deadline_s=30.0
            )
            idle_clients.append(cl)
            icol = cl.collection(quiet)
            ws = [
                icol.watcher(wait_ms=1000) for _ in range(batch)
            ]
            # First poll registers the subscriber and parks at tail.
            for w in ws:
                idle_tasks.append(
                    asyncio.create_task(idle_loop(w))
                )
            # Registration is real work (a cursor round trip each);
            # on a small host a 1024-watcher attach storm can starve
            # everything else for tens of seconds.  Gate each batch
            # on the server-side subscriber gauge so cells start
            # with the pool actually parked, not mid-stampede.
            target = len(idle_tasks)
            settle = loop.time() + 120
            while loop.time() < settle:
                if await subs_gauge() >= target:
                    break
                await asyncio.sleep(0.5)

    # The measuring subscriber gets its own client with a patient
    # op deadline: at the 1024-watcher cell the harness and server
    # share this host's cores, and a register round queued behind
    # hundreds of idle polls is congestion to MEASURE, not a
    # failure to retry into.
    meas_client = await DbeelClient.from_seed_nodes(
        [(args.host, args.port)], op_deadline_s=60.0
    )
    meas_hotcol = meas_client.collection(hot)

    async def delivery_cell(n_total):
        await ensure_idle(n_total - 1)
        await asyncio.sleep(1.0)  # pool settles into its parks
        w = meas_hotcol.watcher(wait_ms=1000)
        for attempt in range(5):
            try:
                await w.next_events()  # register + position at tail
                break
            except Exception:
                # Attach-storm aftershock: the register round can
                # still time out right after a big ensure_idle.
                if attempt == 4:
                    raise
                await asyncio.sleep(2.0)
        lats: list = []
        done = asyncio.Event()

        async def tail():
            while not done.is_set():
                try:
                    events = await asyncio.wait_for(
                        w.next_events(), 10
                    )
                except asyncio.TimeoutError:
                    continue
                now = _time.perf_counter()
                for _k, v, _ts, _fl in events:
                    if isinstance(v, dict) and "t" in v:
                        lats.append(now - v["t"])

        tail_task = asyncio.create_task(tail())
        sent = 0
        werrs = 0
        stop_at = loop.time() + dur
        while loop.time() < stop_at:
            try:
                await meas_hotcol.set(
                    f"d{n_total}-{sent:06d}",
                    {"t": _time.perf_counter(), "pad": "x" * 32},
                )
                sent += 1
            except Exception:
                werrs += 1
            await asyncio.sleep(0.01)
        await asyncio.sleep(1.5)  # let the last deliveries land
        done.set()
        try:
            await asyncio.wait_for(tail_task, 15)
        except asyncio.TimeoutError:
            tail_task.cancel()
        lats.sort()
        cell = {
            "subscribers_total": n_total,
            "idle_watchers": n_total - 1,
            "writes_sent": sent,
            "write_errors": werrs,
            "events_timed": len(lats),
            "p50_ms": round(
                lats[len(lats) // 2] * 1000, 3
            ) if lats else None,
            "p99_ms": round(
                lats[int(0.99 * (len(lats) - 1))] * 1000, 3
            ) if lats else None,
        }
        print(
            f"delivery @ {n_total} subscribers: "
            f"{cell['events_timed']}/{sent} timed  "
            f"p50 {cell['p50_ms']}ms  p99 {cell['p99_ms']}ms"
        )
        return cell

    # ---- Phases B+C interleaved: delivery cells, and the goodput
    # interference point right after each pool size is attached
    # (watchers cannot detach before their TTL, so the pool only
    # grows — measure on the way up).
    cells = []
    interference = []
    for n in (1, 64, 1024):
        cells.append(await delivery_cell(n))
        if n > 1:
            on_rate, on_p99, on_errs = await set_goodput(dur)
            ratio = on_rate / max(1e-9, base_rate)
            point = {
                "idle_watchers": len(idle_tasks),
                "ops_per_s": round(on_rate, 1),
                "p99_ms": on_p99,
                "errors": on_errs,
                "vs_baseline": round(ratio, 3),
                "within_10pct": ratio >= 0.9,
            }
            interference.append(point)
            print(
                f"set with {len(idle_tasks)} idle watchers: "
                f"{on_rate:,.0f} ops/s  p99 {on_p99:.2f}ms  "
                f"(x{ratio:.3f} vs baseline, within_10pct="
                f"{ratio >= 0.9})"
            )
    report["delivery_latency"] = cells
    report["goodput_interference"] = interference[-1]
    report["goodput_interference_curve"] = interference
    try:
        report["host_nproc"] = os.cpu_count()
    except Exception:
        pass

    idle_stop.set()
    await asyncio.sleep(0.1)
    for t in idle_tasks:
        t.cancel()
    await asyncio.gather(*idle_tasks, return_exceptions=True)
    # Per-shard watch blocks: subscribers register on whichever
    # shard coordinates their chunks, so the gauge only sums up
    # across all of them.
    blocks = []
    for sid in range(args.shards or 1):
        try:
            st = await client.get_stats(args.host, args.port + sid)
            blocks.append(st.get("watch"))
        except Exception as e:
            blocks.append({"error": str(e)[:120]})
    report["server_watch_blocks"] = blocks
    print(f"server watch blocks: {blocks}")
    print("WATCH_REPORT " + json.dumps(report))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"wrote {args.json_out}")
    meas_client.close()
    for cl in idle_clients:
        cl.close()
    client.close()


async def main_scan(args):
    """--scan (streaming scan plane, ISSUE 12): the two acceptance
    gates, same-session.  (1) Throughput: stream the whole keyspace
    through the scan plane vs fetching the SAME keys via batched
    multi_get — the scan must win on keys/s (its pages come off the
    vectorized columnar stage; multi_get pays per-key probes), and
    its view must byte-agree with the multi_get view.  (2) Isolation:
    point-get p99 with one concurrent full-collection scan looping
    must stay bounded vs the same-session scan-off baseline — the
    governor pacing gate (byte-budgeted, individually-admitted
    chunks), not an assertion."""
    import time as _time

    from dbeel_tpu.errors import CollectionAlreadyExists

    client = await DbeelClient.from_seed_nodes(
        [(args.host, args.port)],
        pipeline_window=args.pipeline or 32,
    )
    rf = args.replication_factor or 1
    try:
        await client.create_collection(args.collection, rf)
    except CollectionAlreadyExists:
        pass
    col = client.collection(args.collection)
    n = args.clients * args.requests
    keys = [f"key-{i:08}" for i in range(n)]
    value = {"blob": "x" * args.value_size}
    rng = random.Random(args.seed)

    # Load the keyspace (batched writes; not part of any gate).
    t0 = time.perf_counter()
    total, _lat = await run_phase(
        client, args.collection, "set", keys, args.clients, value,
        None, batch=args.batch or 64,
    )
    print(f"load: {n} keys in {total:.2f}s")

    # Gate 1a: batched multi_get of the whole (sorted) keyspace in
    # the analytics-client shape — ONE consumer pulling every key
    # (what a scan replaces).  The args.clients-worker concurrent
    # sweep is printed for context; the gate compares like for like
    # (one scan stream is one consumer).
    total_mg, _lat = await run_phase(
        client, args.collection, "get", sorted(keys), 1,
        value, None, batch=args.batch or 64,
    )
    mg_rate = n / total_mg
    print(
        f"multi_get sweep (1 consumer): total {total_mg:.3f}s "
        f"({mg_rate:,.0f} keys/s, batch={args.batch or 64})"
    )
    total_mgn, _lat = await run_phase(
        client, args.collection, "get", sorted(keys), args.clients,
        value, None, batch=args.batch or 64,
    )
    print(
        f"multi_get sweep ({args.clients} workers): total "
        f"{total_mgn:.3f}s ({n / total_mgn:,.0f} keys/s)"
    )

    # Gate 1b: one streaming scan of the same keyspace.  Let the
    # share-pacing window from the multi_get sweep expire first: the
    # throughput gate measures a scan on an otherwise idle server
    # (the isolation gate below measures the paced case).
    await asyncio.sleep(0.5)
    t0 = time.perf_counter()
    scanned = []
    async for k, _v in col.scan():
        scanned.append(k)
    total_scan = time.perf_counter() - t0
    scan_rate = len(scanned) / total_scan
    agree = scanned == sorted(keys)
    print(
        f"scan sweep: total {total_scan:.3f}s "
        f"({scan_rate:,.0f} keys/s)  "
        f"speedup vs multi_get: {scan_rate / mg_rate:.2f}x  "
        f"byte-agree: {agree}"
    )
    t0 = time.perf_counter()
    cnt = await col.count()
    print(
        f"count pushdown: {cnt} keys in "
        f"{time.perf_counter() - t0:.3f}s (no values moved)"
    )

    # Gate 2: point-get p99, scan OFF vs scan ON (same session).
    # ONE closed-loop prober: the gate is per-request latency under a
    # concurrent scan, and on this single-core host class a multi-
    # worker prober measures its own client-side queueing, not the
    # server's pacing.
    async def point_get_p99(dur_s: float) -> tuple:
        lat: list = []
        stop_at = asyncio.get_event_loop().time() + dur_s
        r = random.Random(1)
        while asyncio.get_event_loop().time() < stop_at:
            k = keys[r.randrange(n)]
            t1 = _time.perf_counter()
            await col.get(k)
            lat.append(_time.perf_counter() - t1)
        lat.sort()
        p99 = lat[int(0.99 * (len(lat) - 1))] if lat else 0.0
        return len(lat) / dur_s, p99

    dur = 6.0
    off_rate, off_p99 = await point_get_p99(dur)
    print(
        f"point gets, scan OFF: {off_rate:,.0f} ops/s  "
        f"p99 {off_p99 * 1000:.2f}ms"
    )

    # The concurrent scanner runs in its OWN process: a same-loop
    # scanner would park the prober behind every chunk's client-side
    # decode (cooperative scheduling), billing client CPU to the
    # server's pacing.  A separate process gets OS-preemptive
    # timeslices instead — on a single-core host the measured p99
    # still includes genuine CPU sharing with the scanner's decode
    # (host constraint, not server queueing: the server's loop_lag
    # printed below is the direct pacing signal).
    import subprocess as _sp
    import sys as _sys

    scanner = _sp.Popen(
        [
            _sys.executable,
            "-c",
            (
                "import asyncio,sys\n"
                "sys.path.insert(0, %r)\n"
                "from dbeel_tpu.client import DbeelClient\n"
                "async def main():\n"
                "    cl = await DbeelClient.from_seed_nodes([(%r, %d)])\n"
                "    col = cl.collection(%r)\n"
                "    n = 0\n"
                "    while True:\n"
                "        async for _kv in col.scan():\n"
                "            pass\n"
                "        n += 1\n"
                "        print(n, flush=True)\n"
                "asyncio.run(main())\n"
            )
            % (
                os.path.dirname(os.path.abspath(__file__)),
                args.host,
                args.port,
                args.collection,
            ),
        ],
        stdout=_sp.PIPE,
        text=True,
    )
    await asyncio.sleep(0.3)  # scanner boot + first chunks in flight
    try:
        on_rate, on_p99 = await point_get_p99(dur)
    finally:
        scanner.terminate()
        out, _ = scanner.communicate(timeout=20)
    loops = out.strip().splitlines()
    print(
        "concurrent full scans completed during window: "
        f"{loops[-1] if loops else 0}"
    )
    ratio = on_p99 / max(1e-9, off_p99)
    print(
        f"point gets, scan ON:  {on_rate:,.0f} ops/s  "
        f"p99 {on_p99 * 1000:.2f}ms  (x{ratio:.2f} vs scan-off)"
    )
    stats = await client.get_stats(args.host, args.port)
    sig = (stats.get("overload") or {}).get("signals") or {}
    print(
        f"server during window: loop_lag_ms={sig.get('loop_lag_ms')} "
        f"level={(stats.get('overload') or {}).get('level')}"
    )
    print(f"server scan block: {stats.get('scan')}")
    rng.shuffle(keys)
    client.close()


async def main_telemetry_overhead(args):
    """--telemetry-overhead (telemetry plane, ISSUE 11): the
    zero-cost-when-off gate.  Runs the standard lockstep set/get
    phases and prints throughput plus the server's telemetry state
    (enabled/interval/samples over the run) read from get_stats.  Run
    it once against a --telemetry-interval 0 server and once against
    a telemetry-on server in the SAME session (BENCH convention: this
    host's CPU budget swings ~10x between rounds, so only same-
    session pairs mean anything) — the off-run throughput is the
    baseline the on-run must match within noise."""
    client = await DbeelClient.from_seed_nodes([(args.host, args.port)])
    from dbeel_tpu.errors import CollectionAlreadyExists

    try:
        await client.create_collection(
            args.collection, args.replication_factor or 1
        )
    except CollectionAlreadyExists:
        pass
    before = await client.get_stats()
    t = before["telemetry"]
    print(
        f"server telemetry: enabled={t['enabled']} "
        f"interval_ms={t['interval_ms']} "
        f"ring={t['ring']['len']}/{t['ring']['capacity']}"
    )
    keys = [f"key-{i:08}" for i in range(args.clients * args.requests)]
    rng = random.Random(args.seed)
    rng.shuffle(keys)
    value = {"blob": "x" * args.value_size}
    for op in ("set", "get"):
        total, lat = await run_phase(
            client, args.collection, op, keys, args.clients, value
        )
        print(
            f"{op}: total {total:.3f}s "
            f"({len(keys)/total:,.0f} ops/s)  {percentiles(lat)}"
        )
        rng.shuffle(keys)
    after = await client.get_stats()
    taken = (
        after["telemetry"]["ring"]["samples_taken"]
        - t["ring"]["samples_taken"]
    )
    print(
        f"telemetry samples during the run: {taken} "
        f"(health findings now: "
        f"{[f['kind'] for f in after['health']['findings']]})"
    )
    client.close()


def main_compaction(args):
    """Single-pass compaction phase (ISSUE 15): same-session A/B of a
    major compaction through the native merge —

      posthoc      the pre-PR pipeline: merge writes the triplet with
                   NO inline sidecar, then the whole freshly-written
                   output is re-read and summed (checksums.
                   compute_and_write), roughly doubling read
                   amplification;
      single_pass  the PR pipeline: per-page CRCs accumulated while
                   the output is still in RAM, sidecar written
                   inline, inputs loaded by the overlapped io_uring
                   reader.

    Storage-level by design (no server): major-compaction keys/s is a
    background-pass number, and the host-weather rule makes only the
    same-session pair meaningful.  Acceptance: single_pass keys/s
    >= 1.2x posthoc, outputs byte-identical."""
    import shutil
    import tempfile

    from dbeel_tpu.storage import checksums
    from dbeel_tpu.storage.compaction import compaction_stats
    from dbeel_tpu.storage.entry import file_name
    from dbeel_tpu.storage.entry_writer import EntryWriter
    from dbeel_tpu.storage.native import (
        NativeMergeStrategy,
        native_available,
        read_overlap_stats,
    )
    from dbeel_tpu.storage.sstable import SSTable

    if not native_available():
        print("compaction phase SKIPPED: native library unavailable")
        return

    rng = random.Random(args.seed)
    d = tempfile.mkdtemp(prefix="dbeel-compaction-bench-")
    try:
        ntab = args.compaction_tables
        per = args.compaction_keys
        print(
            f"building {ntab} input tables x {per} keys "
            f"(value {args.value_size}B) ..."
        )
        sources = []
        for t in range(ntab):
            idx = t * 2
            w = EntryWriter(d, idx, None)
            keys = sorted(
                f"key-{rng.randrange(1 << 48):014d}-{t}".encode()
                for _ in range(per)
            )
            for k in keys:
                w.write(
                    k,
                    bytes(rng.getrandbits(8) for _ in range(8))
                    * (args.value_size // 8 + 1),
                    rng.randrange(1, 1 << 60),
                )
            w.close()
            checksums.compute_and_write(
                d,
                idx,
                os.path.join(d, file_name(idx, "data")),
                os.path.join(d, file_name(idx, "index")),
                os.path.join(d, file_name(idx, "bloom")),
            )
            sources.append(SSTable(d, idx, None))
        total_keys = sum(s.entry_count for s in sources)
        input_bytes = sum(
            s.data_size + s.entry_count * 16 for s in sources
        )
        print(
            f"inputs: {total_keys} keys, "
            f"{input_bytes / 1e6:.1f} MB (data+index)"
        )

        def clean(out_index):
            for ext in (
                "compact_data",
                "compact_index",
                "compact_bloom",
                "compact_sums",
                "sums",
            ):
                p = os.path.join(d, file_name(out_index, ext))
                if os.path.exists(p):
                    os.unlink(p)

        real_write = checksums.write

        def run_once(out_index, single_pass):
            clean(out_index)
            s = NativeMergeStrategy()
            t0 = time.perf_counter()
            if single_pass:
                s.merge(sources, d, out_index, None, True, 1)
            else:
                # Pre-PR semantics: serial input reads (overlap
                # disabled), the merge writes NO inline sidecar
                # (checksums.write patched out for the duration),
                # then the post-hoc re-read sums the whole triplet.
                checksums.write = lambda *a, **k: None
                os.environ["DBEEL_NO_OVERLAP_READS"] = "1"
                try:
                    s.merge(sources, d, out_index, None, True, 1)
                finally:
                    checksums.write = real_write
                    os.environ.pop("DBEEL_NO_OVERLAP_READS", None)
                checksums.compute_and_write(
                    d,
                    out_index,
                    os.path.join(
                        d, file_name(out_index, "compact_data")
                    ),
                    os.path.join(
                        d, file_name(out_index, "compact_index")
                    ),
                    os.path.join(
                        d, file_name(out_index, "compact_bloom")
                    ),
                    "compact_sums",
                )
            return time.perf_counter() - t0

        rounds = args.compaction_rounds
        best = {}
        for mode, single in (("posthoc", False), ("single_pass", True)):
            times = [
                run_once(9 if single else 7, single)
                for _ in range(rounds)
            ]
            best[mode] = min(times)
            print(
                f"{mode:12s} best {best[mode]:.3f}s of "
                f"{[f'{t:.3f}' for t in times]} "
                f"({total_keys / best[mode]:,.0f} keys/s)"
            )

        # Output byte-identity across the two pipelines (the sidecar
        # route must never change the triplet).
        for ext in ("compact_data", "compact_index", "compact_bloom",
                    "compact_sums"):
            a = open(os.path.join(d, file_name(7, ext)), "rb").read()
            b = open(os.path.join(d, file_name(9, ext)), "rb").read()
            assert a == b, f"{ext} differs between pipelines"
        gain = best["posthoc"] / best["single_pass"] - 1.0
        uring, serial = read_overlap_stats()
        print(
            f"single-pass speedup: +{gain * 100:.1f}% keys/s "
            f"(overlapped input passes: uring={uring} "
            f"serial={serial})"
        )
        print(f"compaction stats: {compaction_stats.stats()}")
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump(
                    {
                        "phase": "compaction",
                        "tables": ntab,
                        "keys": total_keys,
                        "input_mb": round(input_bytes / 1e6, 1),
                        "posthoc_s": round(best["posthoc"], 4),
                        "single_pass_s": round(
                            best["single_pass"], 4
                        ),
                        "keys_per_s_posthoc": round(
                            total_keys / best["posthoc"]
                        ),
                        "keys_per_s_single_pass": round(
                            total_keys / best["single_pass"]
                        ),
                        "gain_frac": round(gain, 4),
                        "overlap_uring_passes": uring,
                        "overlap_serial_passes": serial,
                    },
                    f,
                    indent=2,
                )
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument(
        "--shards", type=int, default=1,
        help="server shard count (consecutive ports from --port); "
        "the --watch phase sums per-shard subscriber gauges",
    )
    ap.add_argument("--port", type=int, default=10000)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--requests", type=int, default=5000)
    ap.add_argument("--collection", default="blackbox")
    ap.add_argument("--value-size", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--replication-factor", type=int, default=None,
        help="replication factor when creating the collection",
    )
    ap.add_argument(
        "--consistency",
        choices=("default", "quorum", "all", "one"),
        default="default",
    )
    ap.add_argument(
        "--native-client",
        action="store_true",
        help="drive the load through the compiled C++ client "
        "(native/src/dbeel_client.cpp) on OS threads",
    )
    ap.add_argument(
        "--pipeline",
        type=int,
        default=0,
        metavar="WINDOW",
        help="pipelined mode: keep WINDOW requests in flight per "
        "connection instead of lockstep round trips",
    )
    ap.add_argument(
        "--batch",
        type=int,
        default=0,
        metavar="N",
        help="batched mode: multi_set/multi_get frames of N keys "
        "grouped by owning node",
    )
    ap.add_argument(
        "--native-floor",
        action="store_true",
        help="all-native serving path phase: pipelined RF=1 sets/"
        "gets + batched multi ops, reporting throughput, latency, "
        "and the interval native_served_frac per phase (run again "
        "vs DBEEL_NO_DATAPLANE=1 / DBEEL_DP_NO_MULTI=1 servers for "
        "the same-session Python-path baseline)",
    )
    ap.add_argument(
        "--attribute",
        action="store_true",
        help="tracing-plane phase: short RF>=2 mixed load, then a "
        "per-op per-stage p50/p99 breakdown from the shards' flight "
        "recorders (server must run with --trace-sample N; run "
        "again vs a --trace-sample 0 server for the tracing-off "
        "baseline)",
    )
    ap.add_argument(
        "--scan",
        action="store_true",
        help="streaming-scan phase (scan plane): full-keyspace scan "
        "throughput vs batched multi_get of the same keys "
        "(byte-agreement checked), count pushdown, and point-get p99 "
        "with a concurrent full-collection scan ON vs OFF — the "
        "governor pacing gate, all same-session",
    )
    ap.add_argument(
        "--scan-filter",
        action="store_true",
        help="query-compute-plane phase (ISSUE 13): selectivity "
        "sweep (100%%/10%%/0.1%%) of predicate pushdown vs "
        "client-side filtering on client-received bytes and "
        "keys-scanned/s, plus grouped-aggregate pushdown throughput "
        "— all same-session",
    )
    ap.add_argument(
        "--scan-filter-indexed",
        action="store_true",
        help="secondary-index phase (ISSUE 17): same-session A/B of "
        "the persisted-index scan planner vs scan-everything on the "
        "same tree at 0.1%%/1%%/10%% selectivity, byte-identity "
        "asserted per page.  Gates the x10 keys-matched/s win at "
        "0.1%% and zero extra data reads for index maintenance.  "
        "Storage-level; needs no server.  --json-out writes the "
        "BENCH_r17.json artifact",
    )
    ap.add_argument(
        "--cas",
        action="store_true",
        help="atomic-plane phase (ISSUE 19): same-session plain-set "
        "baseline, uncontended CAS chains, and the hot-key "
        "contention knee (1/4/16 writers on one key via the "
        "read-cas-retry loop) — acked increments/s, conflict ratio, "
        "attempts per acked op, and the zero-lost-updates check.  "
        "--json-out writes the BENCH_r19.json artifact",
    )
    ap.add_argument(
        "--cas-duration",
        type=float,
        default=6.0,
        help="seconds per --cas cell",
    )
    ap.add_argument(
        "--telemetry-overhead",
        action="store_true",
        help="telemetry-plane A/B phase: lockstep set/get throughput "
        "plus the server's telemetry state — run once against a "
        "--telemetry-interval 0 server and once against a "
        "telemetry-on server in the same session; the pair bounds "
        "the plane's serving-path cost (acceptance: no measurable "
        "regression)",
    )
    ap.add_argument(
        "--overload-knee",
        action="store_true",
        help="offered-load sweep (open loop, multiples of the "
        "same-session sustainable rate) recording goodput + p99 vs "
        "load — the overload-control knee curve",
    )
    ap.add_argument(
        "--classes",
        action="store_true",
        help="with --overload-knee (QoS plane, ISSUE 14): the "
        "TWO-CLASS sweep — half the offered load stamped "
        "interactive, half batch; records both knees (the lowest "
        "multiple where a class's sheds exceed 1%% of its launched "
        "ops).  Acceptance: the interactive knee sits strictly "
        "higher, with batch sheds dominating below it",
    )
    ap.add_argument(
        "--json-out",
        default="",
        help="with --overload-knee --classes: write the sweep + "
        "knee verdict as JSON (the BENCH_r14.json artifact)",
    )
    ap.add_argument(
        "--watch",
        action="store_true",
        help="watch/CDC phase (ISSUE 20): commit→delivery p50/p99 "
        "with 1/64/1024 attached subscribers (extras idle on a "
        "quiet collection), plus the interference gate — point-set "
        "goodput with 1024 idle watchers parked vs the no-watcher "
        "baseline (acceptance: within 10%%)",
    )
    ap.add_argument(
        "--watch-duration",
        type=float,
        default=6.0,
        help="seconds per --watch cell",
    )
    ap.add_argument(
        "--compaction",
        action="store_true",
        help="single-pass compaction phase (ISSUE 15): same-session "
        "A/B of a major native-merge compaction — pre-PR post-hoc "
        "sidecar re-read vs inline single-pass sidecar + overlapped "
        "io_uring input reads — reporting keys/s, the speedup, "
        "output byte-identity, and get_stats.compaction counters.  "
        "Storage-level; needs no server",
    )
    ap.add_argument(
        "--compaction-tables",
        type=int,
        default=4,
        help="input tables for the --compaction merge",
    )
    ap.add_argument(
        "--compaction-keys",
        type=int,
        default=120000,
        help="keys per input table for --compaction",
    )
    ap.add_argument(
        "--compaction-rounds",
        type=int,
        default=3,
        help="rounds per pipeline for --compaction (best-of)",
    )
    ap.add_argument(
        "--overload-knee-worker",
        action="store_true",
        help=argparse.SUPPRESS,  # internal: one generator subprocess
    )
    ap.add_argument(
        "--knee-rate", type=float, default=0.0, help=argparse.SUPPRESS
    )
    ap.add_argument(
        "--knee-duration",
        type=float,
        default=8.0,
        help=argparse.SUPPRESS,
    )
    ap.add_argument(
        "--knee-class", default="", help=argparse.SUPPRESS
    )
    args = ap.parse_args()
    if args.pipeline and args.batch:
        ap.error("--pipeline and --batch are separate phases")
    if args.compaction:
        main_compaction(args)
    elif args.overload_knee_worker:
        asyncio.run(main_knee_worker(args))
    elif args.telemetry_overhead:
        asyncio.run(main_telemetry_overhead(args))
    elif args.watch:
        asyncio.run(main_watch(args))
    elif args.cas:
        asyncio.run(main_cas(args))
    elif args.scan_filter_indexed:
        asyncio.run(main_scan_filter_indexed(args))
    elif args.scan_filter:
        asyncio.run(main_scan_filter(args))
    elif args.scan:
        asyncio.run(main_scan(args))
    elif args.attribute:
        asyncio.run(main_attribute(args))
    elif args.native_floor:
        asyncio.run(main_native_floor(args))
    elif args.overload_knee:
        asyncio.run(main_overload_knee(args))
    elif args.native_client:
        main_native(args)
    else:
        asyncio.run(main_async(args))


if __name__ == "__main__":
    main()
