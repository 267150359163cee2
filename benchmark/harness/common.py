"""What every piece of the benchmark shares: where its files are, how it
speaks, and the one object a run's pieces hand each other."""

from __future__ import annotations

import importlib
import json
import os
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class BenchFailure(Exception):
    """The run cannot give a result: no result line, exit code 1."""


def say(msg: str) -> None:
    """A line for the reader of the run; the result is the LAST line."""
    print(msg, flush=True)


def load_json(kind: str, name: str, bench_dir: str = BENCH) -> dict:
    """``benchmark/<kind>/<name>.json``: a configuration, a traffic mix,
    a cell or a per-layer metric, found by its name."""
    path = os.path.join(bench_dir, kind, name + ".json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchFailure(f"no file {path} for {kind[:-1]} {name!r}")


def load_code(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``: a deployment, a generator or a
    reader, found by the name a data file gives it."""
    if not name.replace("_", "").isalnum():
        raise BenchFailure(f"{kind} name {name!r} is not a module name")
    try:
        return importlib.import_module(f"benchmark.{kind}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.{kind}.{name}":
            raise
        raise BenchFailure(f"no benchmark/{kind}/{name}.py")


def child_preexec() -> None:
    """In a child, before exec: SIGINT back to its default (a shell that
    started the run in the background left it ignored), and SIGKILL if
    the runner dies first, so no node or generator outlives a run."""
    import ctypes
    import signal

    signal.signal(signal.SIGINT, signal.SIG_DFL)
    ctypes.CDLL(None).prctl(1, signal.SIGKILL)  # PR_SET_PDEATHSIG


def sized(block: dict, tiny: bool) -> dict:
    """A data file's values, with its ``tiny`` overrides applied for the
    CPU rehearsal (never on the chip: run.py refuses ``--tiny`` there)."""
    out = {k: v for k, v in block.items() if k != "tiny"}
    if tiny:
        out.update(block.get("tiny", {}))
    return out


HOST_MERGE_PATHS = ("native", "columnar", "heap")


def host_merges(before: dict, after: dict) -> dict:
    """Merge outputs a host path produced between two counter
    snapshots: a cell's device merges must stay on the device."""
    a = before["node"]["compaction"]["paths"]
    b = after["node"]["compaction"]["paths"]
    return {p: b[p] - a[p] for p in HOST_MERGE_PATHS}


class Run:
    """One run of one cell: its options and data files, and what its
    pieces observed, for the readers of the per-layer metrics."""

    def __init__(self, args, cell: dict, config: dict, traffic: dict,
                 work: str, t_start: float) -> None:
        self.args = args
        self.cell = cell
        self.config = sized(config, args.tiny)
        self.traffic = sized(traffic, args.tiny)
        self.work = work
        self.t_start = t_start
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.tiny = args.tiny
        self.rehearsal = args.rehearsal
        self.keep_trace = getattr(args, "keep_trace", None)
        self.port_block = getattr(args, "port_block", None)
        # name -> number: what deployment and generator saw in the window
        self.facts: dict = {}
        # name -> value of the cell's end-to-end metrics
        self.end_to_end: dict = {}
        # get_stats-shaped counters at the window's start and end:
        # {"node": {...process-wide blocks...}, "shards": [{...}, ...]}
        self.stats_before: dict | None = None
        self.stats_after: dict | None = None
        # harness.xplane.TraceSummary of the traced part of the window
        self.trace_summary = None
        # (kernel name, operand bytes in, result bytes out) of every
        # merge launch dispatched while the trace ran
        self.launches: list = []
        self.device: dict = {}
        self.attempted = 0
        self.failed = 0
        self.wrong: list = []  # reasons `correct` is false

    @classmethod
    def load(cls, args, work: str, t_start: float) -> "Run":
        """The run of ``args.workload``, from the cell's data files."""
        cell = load_json("workloads", args.workload)
        return cls(
            args, cell, load_json("configs", cell["config"]),
            load_json("traffic", cell["traffic"]), work, t_start,
        )

    def setup_done(self) -> float:
        """Seconds from the start of the process to now: called where
        the measured window begins."""
        return time.time() - self.t_start

    def check_device(self, platform: str, kind: str, count: int) -> None:
        """No cpu number under a device metric's name, and no cell on
        fewer chips than it asks for."""
        self.device.update(platform=platform, kind=kind, count=count)
        if platform == "cpu" and not self.rehearsal:
            raise BenchFailure(
                "JAX found no accelerator (platform cpu): the benchmark "
                "measures on the chip; the CPU rehearsal is --rehearsal"
            )
        want = int(self.cell["chips"])
        if not self.rehearsal and count < want:
            raise BenchFailure(
                f"the cell asks for {want} chip(s), JAX holds {count}"
            )
