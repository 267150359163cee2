"""Shared by the rehearsal tests: run the benchmark's command as the driver
does, on the cpu at the data files' tiny sizes."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench_run(args, cache_dir, root=REPO, timeout=100):
    """(completed process, its non-empty stdout lines).  ``root`` is the
    directory whose benchmark/run.py runs; the program under test is
    always this checkout's."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(cache_dir),
        PYTHONPATH="" if root == REPO else REPO,
    )
    out = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        env=env, cwd=root, capture_output=True, text=True, timeout=timeout,
    )
    return out, [ln for ln in out.stdout.splitlines() if ln.strip()]


def result_line(out, lines):
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    line = json.loads(lines[-1])
    assert LINE_KEYS <= set(line) <= LINE_KEYS | {"breakdown"}
    assert set(line["device"]) >= {
        "platform", "kind", "count", "memory_peak_bytes",
    }
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
        assert isinstance(metric["value"], float)
    return line
