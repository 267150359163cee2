#!/usr/bin/env python3
"""Traffic ``point_open_loop``: reads and updates of single records on one
seeded schedule, offered OPEN LOOP at the rate the traffic file fixes,
through the repo's own client library.

The parent (the runner) draws the whole schedule from the seed — arrival
times (a Poisson process conditioned on its count, so that every seed
offers the same number of operations), kinds by the file's proportions,
keys by its distribution — and splits it over generator processes by the
owner of each key, so that one key's operations keep their order.  Each
generator launches an operation when it is due, whatever is still in
flight, and records when it was due, launched and answered.  Latency runs
from the DUE time.  What a generator refuses to launch (its cap on
operations in flight) or has not seen answered when the drain ends is
failed, never skipped.

One schedule covers the unmeasured warm-up and, without a pause, the
measured window.  This file is also the generator process
(``--worker <spec.json>``).
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import time

import numpy as np

if __name__ == "__main__":
    sys.path.insert(
        0,
        os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ),
    )

from benchmark.harness import latency  # noqa: E402
from benchmark.harness.common import child_preexec, host_merges  # noqa: E402
from benchmark.harness.docs import MAX_VERSION, Docs, zipfian  # noqa: E402

READ, UPDATE = 0, 1
KINDS = {"read": READ, "update": UPDATE}
OK, WRONG, ERROR, REFUSED, UNANSWERED = 0, 1, 2, 3, 4
STATUS_NAMES = ("ok", "wrong", "error", "refused", "unanswered")
WORKER_START_BUDGET_S = 60.0


# ----------------------------------------------------------------------
# The schedule (parent)
# ----------------------------------------------------------------------


def build_schedule(seed: int, traffic: dict, records: int, spans,
                   rate: float, stored: np.ndarray):
    """(due seconds from the start, kind, ordinal, version, base) of
    every operation of consecutive ``spans`` (seconds each: the warm-up,
    the window) at ``rate``; each span holds exactly rate x seconds
    arrivals.  ``stored[o]`` is key o's version before the schedule;
    ``version`` of an update is that plus how many updates of its key
    the schedule holds up to and including it (of a read, 0), and
    ``base`` repeats ``stored`` per operation for the generators."""
    rng = np.random.default_rng([seed, 0x5EED])
    parts, at = [], 0.0
    for span_s in spans:
        parts.append(
            at + latency.conditioned_poisson(
                rng, int(round(rate * span_s)), span_s
            )
        )
        at += span_s
    due = np.concatenate(parts)
    n = len(due)
    props = traffic["proportions"]
    unknown = [k for k, p in props.items() if p and k not in KINDS]
    if unknown:
        raise ValueError(f"this generator has no operation {unknown}")
    names = [k for k in KINDS if props.get(k)]
    p = np.array([props[k] for k in names], dtype=np.float64)
    kind = np.array([KINDS[k] for k in names], dtype=np.uint8)[
        rng.choice(len(names), size=n, p=p / p.sum())
    ]
    dist = traffic["distribution"]
    if dist["kind"] == "zipfian":
        ordinal = zipfian(rng, records, float(dist["constant"]), n)
    elif dist["kind"] == "uniform":
        ordinal = rng.integers(0, records, size=n)
    else:
        raise ValueError(f"no key distribution {dist['kind']!r}")
    version = np.zeros(n, dtype=np.int64)
    upd = np.flatnonzero(kind == UPDATE)
    order = upd[np.argsort(ordinal[upd], kind="stable")]
    keys = ordinal[order]
    first = np.r_[True, keys[1:] != keys[:-1]]
    starts = np.flatnonzero(first)
    run_id = np.cumsum(first) - 1
    base = stored[ordinal]
    version[order] = np.arange(len(order)) - starts[run_id] + 1 + base[order]
    if len(order) and version.max() > MAX_VERSION:
        raise ValueError("a key is updated more often than versions exist")
    return due, kind, ordinal, version, base


# ----------------------------------------------------------------------
# The generator process
# ----------------------------------------------------------------------


async def _worker(spec: dict) -> None:
    from dbeel_tpu.client import DbeelClient

    sched = np.load(spec["schedule"])
    due_rel, kind, ordinal, version, base = (
        sched[name]
        for name in ("due", "kind", "ordinal", "version", "base")
    )
    n = len(due_rel)
    docs = Docs(spec["seed"], spec["fields"], spec["field_bytes"])
    client = await DbeelClient.from_seed_nodes(
        [("127.0.0.1", spec["port"])],
        op_deadline_s=spec["op_deadline_s"],
        pipeline_window=spec["pipeline_window"],
    )
    await client.sync_metadata()
    col = client.collection(spec["collection"])
    # One round trip per shard opens its connection before the clock runs.
    for i in range(16):
        await col.get(docs.key(i))

    launch = np.zeros(n)
    done = np.zeros(n)
    status = np.full(n, UNANSWERED, dtype=np.uint8)
    acked: dict = {}  # ordinal -> highest version acknowledged
    sent: dict = {}  # ordinal -> highest version sent
    chain: dict = {}  # ordinal -> future of its update in flight
    in_flight = 0
    cap = spec["max_in_flight"]
    loop = asyncio.get_running_loop()
    clock = time.monotonic

    async def read(j: int, o: int) -> None:
        nonlocal in_flight
        lo = acked.get(o, int(base[j]))
        try:
            doc = await col.get(docs.key(o))
            hi = sent.get(o, int(base[j]))
            found = docs.version_of(o, doc, lo, hi)
            status[j] = OK if found is not None else WRONG
            if found is None:
                wide = docs.version_of(o, doc, max(0, lo - 64), hi + 64)
                print(f"wrong read: ordinal {o} version {wide} outside "
                      f"[{lo}, {hi}]", file=sys.stderr, flush=True)
        except Exception:
            status[j] = ERROR
        done[j] = clock()
        in_flight -= 1

    async def update(j: int, o: int, v: int) -> None:
        nonlocal in_flight
        before = chain.get(o)
        mine = chain[o] = loop.create_future()
        try:
            if before is not None:
                await before  # one key's updates go out in order
            sent[o] = v
            await col.set(docs.key(o), docs.doc(o, v))
            acked[o] = v
            status[j] = OK
        except Exception:
            status[j] = ERROR
        done[j] = clock()
        in_flight -= 1
        mine.set_result(None)
        if chain.get(o) is mine:
            del chain[o]

    print("ready", flush=True)
    go = await loop.run_in_executor(None, sys.stdin.readline)
    t0 = float(go.split()[1])
    due = due_rel + t0
    tasks = set()
    j = 0
    while j < n:
        now = clock()
        while j < n and due[j] <= now:
            if in_flight >= cap:
                status[j] = REFUSED
                launch[j] = done[j] = now
            else:
                launch[j] = now
                in_flight += 1
                o = int(ordinal[j])
                t = loop.create_task(
                    read(j, o) if kind[j] == READ
                    else update(j, o, int(version[j]))
                )
                tasks.add(t)
                t.add_done_callback(tasks.discard)
            j += 1
        if j < n:
            gap = due[j] - clock()
            # The selector's timeout has millisecond resolution: sleep
            # to within 2 ms, then yield to the loop until it is time.
            await asyncio.sleep(gap - 0.002 if gap > 0.003 else 0)
    if tasks:
        await asyncio.wait(tasks, timeout=spec["drain_s"])
    end = clock()
    for t in tasks:
        t.cancel()
    unanswered = status == UNANSWERED
    done[unanswered] = end
    client.close()
    np.savez(
        spec["out"], due=due, launch=launch, done=done, status=status,
        kind=kind, ordinal=ordinal, version=version, t0=t0,
    )


# ----------------------------------------------------------------------
# The parent's side
# ----------------------------------------------------------------------


class Generators:
    """The generator processes of one schedule."""

    def __init__(self, run, node, spans, rate=None, stored=None) -> None:
        t = run.traffic
        self.n = int(t["generators"])
        if stored is None:
            stored = np.zeros(node.records, dtype=np.int64)
        due, kind, ordinal, version, base = build_schedule(
            run.seed, t, node.records, spans,
            float(t["rate_ops_per_s"] if rate is None else rate), stored,
        )
        owner = ordinal % self.n
        self.procs, self.outs = [], []
        env = dict(os.environ)
        # A generator never needs the chip and must not touch it.
        env["JAX_PLATFORMS"] = "cpu"
        for g in range(self.n):
            mine = owner == g
            sched_path = os.path.join(run.work, f"sched{g}.npz")
            out_path = os.path.join(run.work, f"result{g}.npz")
            np.savez(
                sched_path, due=due[mine], kind=kind[mine],
                ordinal=ordinal[mine], version=version[mine],
                base=base[mine],
            )
            spec = {
                "schedule": sched_path, "out": out_path,
                "seed": run.seed, "port": node.port,
                "collection": node.collection,
                "fields": run.config["fields"],
                "field_bytes": run.config["field_bytes"],
                "op_deadline_s": float(t["op_deadline_s"]),
                "pipeline_window": int(t["pipeline_window"]),
                "max_in_flight": int(t["max_in_flight"]),
                "drain_s": float(t["drain_s"]),
            }
            spec_path = os.path.join(run.work, f"spec{g}.json")
            with open(spec_path, "w") as f:
                json.dump(spec, f)
            self.outs.append(out_path)
            self.procs.append(
                subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__),
                     "--worker", spec_path],
                    env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True, preexec_fn=child_preexec,
                )
            )

    def go(self, lead_s: float = 1.0) -> float:
        """Wait until every generator is connected, then give all the
        same start on the machine's monotonic clock."""
        try:
            for p in self.procs:
                line = p.stdout.readline()
                if line.strip() != "ready":
                    raise RuntimeError(f"a generator said {line!r}")
            t0 = time.monotonic() + lead_s
            for p in self.procs:
                p.stdin.write(f"go {t0!r}\n")
                p.stdin.flush()
            return t0
        except BaseException:
            self.kill()
            raise

    def collect(self, budget_s: float) -> dict:
        """Wait for the generators and join what they recorded."""
        try:
            deadline = time.monotonic() + budget_s
            for p in self.procs:
                rc = p.wait(timeout=max(1.0, deadline - time.monotonic()))
                if rc != 0:
                    raise RuntimeError(f"a generator exited with code {rc}")
        finally:
            self.kill()
        parts = [np.load(path) for path in self.outs]
        return {
            name: np.concatenate([p[name] for p in parts])
            for name in ("due", "launch", "done", "status", "kind",
                         "ordinal", "version")
        }

    def kill(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            for pipe in (p.stdin, p.stdout):
                if pipe is not None:
                    pipe.close()


def _sleep_until(t: float) -> None:
    while True:
        gap = t - time.monotonic()
        if gap <= 0:
            return
        time.sleep(min(gap, 0.25))


def warm(run, node) -> tuple:
    """Start the generators on one schedule that covers the warm-up and
    the window; return (them, the window's start) when the warm-up is
    over.  Set-up."""
    warm_s = float(run.traffic["warmup_s"])
    gens = Generators(run, node, (warm_s, run.seconds))
    window_t0 = gens.go() + warm_s
    _sleep_until(window_t0)
    return gens, window_t0


def summarise(ops: dict, t0: float, t1: float, drain_s: float) -> tuple:
    """(end-to-end values, facts, lines to print, outcome counts) of the
    operations DUE in [t0, t1); throughput counts correct answers that
    ARRIVED in it."""
    due, done, status, kind = (
        ops["due"], ops["done"], ops["status"], ops["kind"]
    )
    in_window = (due >= t0) & (due < t1)
    e2e, facts, lines = {}, {}, []
    answered_in = (status == OK) & (done >= t0) & (done < t1)
    e2e["ops_ok_per_s"] = float(answered_in.sum()) / (t1 - t0)
    for name, code in KINDS.items():
        sel = in_window & (kind == code)
        if not sel.any():
            continue
        # A failed operation missed any limit: it is charged the time
        # from its due moment to the end of the drain, the top of the tail.
        ms = np.where(
            status[sel] == OK,
            latency.due_latency_ms(due[sel], done[sel]),
            (t1 + drain_s - due[sel]) * 1e3,
        )
        p50, p95, p99 = (latency.percentile(ms, q) for q in (50, 95, 99))
        e2e[f"{name}_p95_ms"] = p95
        facts[f"{name}_p50_ms"] = p50
        facts[f"{name}_p99_ms"] = p99
        facts[f"{name}_samples"] = int(sel.sum())
        lines.append(
            f"{name}: {int(sel.sum())} due in the window, p50 {p50:.3f} "
            f"p95 {p95:.3f} p99 {p99:.3f} ms from the due time"
        )
    late = latency.lateness_ms(due[in_window], ops["launch"][in_window])
    facts["gen_late_p50_ms"] = latency.percentile(late, 50)
    facts["gen_late_p95_ms"] = latency.percentile(late, 95)
    facts["gen_late_max_ms"] = float(late.max())
    lines.append(
        f"generator lateness (launch - due): p50 "
        f"{facts['gen_late_p50_ms']:.3f} p95 {facts['gen_late_p95_ms']:.3f} "
        f"max {facts['gen_late_max_ms']:.3f} ms"
    )
    counts = np.bincount(status[in_window], minlength=len(STATUS_NAMES))
    lines.append(
        "outcomes of the window's operations: "
        + ", ".join(f"{n} {c}" for n, c in zip(STATUS_NAMES, counts))
    )
    facts["offered_ops_per_s"] = float(in_window.sum()) / (t1 - t0)
    return e2e, facts, lines, counts


def measure(run, node, state) -> None:
    from benchmark.harness import tracing

    gens, t0 = state
    t1 = t0 + run.seconds
    t = run.traffic
    try:
        run.stats_before = node.counters()
        if run.trace:
            trace_s = min(float(t["trace_s"]), run.seconds / 2)
            trace_dir = os.path.join(run.work, "trace")
            _sleep_until(t0 + (run.seconds - trace_s) / 2)
            node.command(f"trace_start {trace_dir}")
            _sleep_until(t0 + (run.seconds + trace_s) / 2)
            window_s = node.command("trace_stop")["window_s"]
        _sleep_until(t1)
        run.stats_after = node.counters()
        ops = gens.collect(float(t["drain_s"]) + WORKER_START_BUDGET_S)
    except BaseException:
        gens.kill()
        raise
    if run.trace:
        tracing.finish(run, trace_dir, window_s)

    e2e, facts, lines, counts = summarise(
        ops, t0, t1, float(t["drain_s"])
    )
    run.end_to_end.update(e2e)
    run.facts.update(facts)
    for line in lines:
        print(line, flush=True)
    run.attempted = int(counts.sum())
    run.failed = int(counts.sum() - counts[OK])
    if counts[WRONG]:
        run.wrong.append(
            f"{counts[WRONG]} reads in the window carried a version "
            "outside [last acknowledged, last sent]"
        )

    # ---- counters of the window -------------------------------------
    comp_a = run.stats_after["node"]["compaction"]
    host = host_merges(run.stats_before, run.stats_after)
    if any(host.values()):
        run.wrong.append(f"host-path merges in the window: {host}")
    if comp_a["platform"] != run.device["platform"]:
        run.wrong.append("the node changed platform")
    compiles = node.command("compiles")
    in_win = [s for at, s in compiles["compiles"] if t0 <= at < t1]
    run.facts["compile_s_in_window"] = float(sum(in_win))
    run.facts["tables_max"] = float(
        max(s["overload"]["signals"]["sstable_debt"]
            for s in run.stats_after["shards"])
    )
    acked_bytes = float(
        ((ops["kind"] == UPDATE) & (ops["status"] == OK)
         & (ops["due"] >= t0) & (ops["due"] < t1)).sum()
    ) * _record_bytes(node.docs)
    run.facts["acked_user_bytes"] = acked_bytes
    print(
        f"window: backend compiles inside it {in_win} s (all so far: "
        f"{len(compiles['compiles'])}, cache {compiles['cache']}); "
        f"merge paths now {json.dumps(comp_a['paths'])}",
        flush=True,
    )
    for s, shard in enumerate(run.stats_after["shards"]):
        print(
            f"shard {s}: overload.signals "
            f"{json.dumps(shard['overload']['signals'], sort_keys=True)}",
            flush=True,
        )

    # ---- read back, outside the timing ------------------------------
    _read_back(run, node, ops)


def _record_bytes(docs: Docs) -> int:
    """User bytes of one acknowledged update: the key and the record
    as they travel (msgpack)."""
    import msgpack

    return len(docs.key(0)) + len(msgpack.packb(docs.doc(0, 1)))


def _read_back(run, node, ops) -> None:
    """An acknowledged write is read back: seeded samples of keys the
    run updated and of keys it never touched, whole records against the
    model (the last update the schedule holds for the key)."""
    n_each = int(run.traffic["read_back_keys"])
    rng = np.random.default_rng([run.seed, 0xBAC])
    upd = ops["kind"] == UPDATE
    final = np.zeros(node.records, dtype=np.int64)
    np.maximum.at(final, ops["ordinal"][upd], ops["version"][upd])
    # A key with a failed update has no single right answer: such keys
    # are already counted under `failed`, and are left out here.
    unsure = np.zeros(node.records, dtype=bool)
    unsure[ops["ordinal"][upd & (ops["status"] != OK)]] = True
    touched = np.flatnonzero((final > 0) & ~unsure)
    untouched = np.flatnonzero(final == 0)
    sample = np.concatenate([
        rng.choice(touched, size=min(n_each, len(touched)), replace=False),
        rng.choice(untouched, size=min(n_each, len(untouched)),
                   replace=False),
    ])
    got = node.read_back(sample)
    bad = [
        int(i) for i, g in zip(sample, got)
        if g != node.docs.doc(int(i), int(final[i]))
    ]
    print(
        f"read-back: {len(sample)} records ({min(n_each, len(touched))} "
        f"updated in the run), {len(bad)} differ from the model",
        flush=True,
    )
    if bad:
        run.wrong.append(
            f"read-back: {len(bad)} records differ from the model, "
            f"first ordinals {bad[:5]}"
        )


if __name__ == "__main__":
    if len(sys.argv) != 3 or sys.argv[1] != "--worker":
        sys.exit("the runner starts this file as its generator process")
    with open(sys.argv[2]) as f:
        asyncio.run(_worker(json.load(f)))
