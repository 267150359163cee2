#!/usr/bin/env python3
"""The benchmark's command (BENCHMARK.json):

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with the contract's
keys.  ``--tiny --rehearsal`` is the CPU rehearsal the tests make; without
``--rehearsal`` a run whose JAX reports the cpu fails and prints no result.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from benchmark.harness.common import (  # noqa: E402
    BenchFailure, Run, load_code, load_json, say,
)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="the data files' tiny sizes (CPU rehearsal only)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="a cpu run is expected; no device metric is real")
    ap.add_argument("--port-block", type=int, metavar="PORT",
                    help="first port of the served node's listeners "
                    "(tests hand out their own blocks)")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced run's xplane file there")
    args = ap.parse_args(argv)
    if args.tiny and not args.rehearsal:
        ap.error("--tiny is for --rehearsal alone")
    return args


def layer_metrics(run: Run, names) -> dict:
    """Each per-layer metric through the reader its file names; a
    reader that finds nothing to read leaves the metric out."""
    out = {}
    for name in names:
        spec = load_json("layer_metrics", name)
        value = load_code("readers", spec["reader"]).read(run, spec)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def execute(run: Run) -> dict:
    deploy = load_code("deploy", run.config["deploy"])
    generator = load_code("generators", run.traffic["kind"])
    deployment = deploy.start(run)
    try:
        state = generator.warm(run, deployment)
        setup_s = run.setup_done()
        generator.measure(run, deployment, state)
        memory_peak = deployment.memory_peak_bytes()
    finally:
        deployment.stop()
    run.end_to_end["setup_s"] = setup_s
    if run.trace:
        metrics = layer_metrics(run, run.cell["per_layer"])
    else:
        metrics = {
            name: {"value": float(run.end_to_end[name]), "unit": unit}
            for name, unit in run.cell["end_to_end"].items()
        }
    device = dict(run.device, memory_peak_bytes=int(memory_peak))
    line = {
        "correct": not run.wrong,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace and run.trace_summary is not None:
        device["busy_s"] = run.trace_summary.busy_s
        device["window_s"] = run.trace_summary.window_s
        line["breakdown"] = run.trace_summary.breakdown()
    for reason in run.wrong[:10]:
        say(f"WRONG: {reason}")
    say(f"set-up {setup_s:.1f}s; facts {json.dumps(run.facts, sort_keys=True)}")
    if run.trace:
        say(f"end-to-end (traced run, not reported): "
            f"{json.dumps(run.end_to_end, sort_keys=True)}")
    return line


def main(argv=None) -> int:
    args = parse(argv)
    # Inside the checkout or under TMPDIR, never at a fixed path
    # elsewhere; removed at exit.
    work = tempfile.mkdtemp(prefix="dbeel_bench_")
    try:
        run = Run.load(args, work, T_START)
        say(f"benchmark: cell {args.workload} seed {args.seed} seconds "
            f"{args.seconds} trace {args.trace} work {work}")
        line = execute(run)
    except BenchFailure as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
