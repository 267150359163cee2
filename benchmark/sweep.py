#!/usr/bin/env python3
"""Find a served cell's knee: load the cell's deployment ONCE, then offer
its traffic at each of ``--rates`` in turn for ``--step-seconds``.

    python3 benchmark/sweep.py --workload ycsb-a.rate80 --seed 11 \\
        --rates 2000,4000,8000 --step-seconds 20

A tool for the PR that adds a cell: the knee it finds is written into the
cell's traffic file by hand, and every run then offers that fixed rate.
One JSON line per step.  A step HOLDS when the node answered what was due
(``--answered-share`` of the operations, correctly), the generators kept
their schedule (lateness p95 at most ``--late-share`` of the read p95 they
measure) and read p95 did not grow from the step's first half to its
second (by more than ``--growth``).  The knee is the highest step that
holds.  The sweep stops after the first step that answers under 90 %.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np  # noqa: E402

from benchmark.harness.common import (  # noqa: E402
    BenchFailure, Run, load_code, say,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step-seconds", type=float, default=20.0)
    ap.add_argument("--answered-share", type=float, default=0.999)
    ap.add_argument("--late-share", type=float, default=0.1)
    ap.add_argument("--growth", type=float, default=1.25)
    ap.add_argument("--generators", type=int)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    args.seconds, args.trace = args.step_seconds, 0
    rates = [float(r) for r in args.rates.split(",")]
    work = tempfile.mkdtemp(prefix="dbeel_sweep_")
    try:
        run = Run.load(args, work, time.time())
        if args.generators:
            run.traffic["generators"] = args.generators
        gen = load_code("generators", run.traffic["kind"])
        node = load_code("deploy", run.config["deploy"]).start(run)
        try:
            stored = np.zeros(node.records, dtype=np.int64)
            for rate in rates:
                row = step(run, node, gen, rate, stored, args)
                print(json.dumps(row), flush=True)
                if row["answered_share"] < 0.9:
                    break
                time.sleep(3.0)
        finally:
            node.stop()
    except BenchFailure as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return 0


def step(run, node, gen, rate, stored, args) -> dict:
    warm_s, span = 2.0, args.step_seconds
    drain_s = float(run.traffic["drain_s"])
    before = node.counters()
    gens = gen.Generators(run, node, (warm_s, span), rate=rate, stored=stored)
    t0 = gens.go() + warm_s
    ops = gens.collect(warm_s + span + drain_s + gen.WORKER_START_BUDGET_S)
    after = node.counters()
    whole, facts, _lines, counts = gen.summarise(ops, t0, t0 + span, drain_s)
    half = [
        gen.summarise(ops, a, b, drain_s)[0]
        for a, b in ((t0, t0 + span / 2), (t0 + span / 2, t0 + span))
    ]
    done = (ops["kind"] == gen.UPDATE) & (ops["status"] == gen.OK)
    np.maximum.at(stored, ops["ordinal"][done], ops["version"][done])
    answered = float(counts[gen.OK]) / max(1, int(counts.sum()))
    read_p95 = whole.get("read_p95_ms", float("nan"))
    grew = half[1].get("read_p95_ms", 0) / max(1e-9, half[0].get("read_p95_ms", 0))
    paths_a = after["node"]["compaction"]["paths"]
    paths_b = before["node"]["compaction"]["paths"]
    row = {
        "rate": rate,
        "ops_ok_per_s": whole["ops_ok_per_s"],
        "answered_share": answered,
        "outcomes": dict(zip(gen.STATUS_NAMES, (int(c) for c in counts))),
        "read_p50_ms": facts.get("read_p50_ms"),
        "read_p95_ms": read_p95,
        "read_p99_ms": facts.get("read_p99_ms"),
        "update_p50_ms": facts.get("update_p50_ms"),
        "update_p95_ms": whole.get("update_p95_ms"),
        "update_p99_ms": facts.get("update_p99_ms"),
        "read_p95_halves_ms": [h.get("read_p95_ms") for h in half],
        "gen_late_p95_ms": facts["gen_late_p95_ms"],
        "gen_late_max_ms": facts["gen_late_max_ms"],
        "merges": {k: paths_a[k] - paths_b[k] for k in paths_a
                   if paths_a[k] != paths_b[k]},
        "flushes": after["node"]["compaction"]["flush_passes"]
        - before["node"]["compaction"]["flush_passes"],
        "loop_lag_ms": [s["overload"]["signals"]["loop_lag_ms"]
                        for s in after["shards"]],
        "tables": [s["overload"]["signals"]["sstable_debt"]
                   for s in after["shards"]],
    }
    row["holds"] = bool(
        answered >= args.answered_share
        and facts["gen_late_p95_ms"] <= args.late_share * read_p95
        and grew <= args.growth
    )
    return row


if __name__ == "__main__":
    sys.exit(main())
