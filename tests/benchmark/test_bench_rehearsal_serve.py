"""``ycsb-a.rate80`` rehearsed on the cpu at a tiny size: one node through
node_host.py, the seeded open loop from generator processes, the version
and read-back checks, the traced middle of the window."""

import os
import sys

import pytest

from bench_rehearsal import bench_run, result_line

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import port_block  # noqa: E402  (tests/harness.py)

CELL = ["--workload", "ycsb-a.rate80", "--seed", "2147483659",
        "--seconds", "4", "--tiny", "--rehearsal"]


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def test_untraced_line_holds_latency_from_the_due_time_and_throughput(
    cache_dir,
):
    out, lines = bench_run(
        CELL + ["--trace", "0", "--port-block", str(port_block(0))],
        cache_dir,
    )
    line = result_line(out, lines)
    assert line["correct"] is True, out.stdout[-3000:]
    assert set(line["metrics"]) == {
        "read_p95_ms", "update_p95_ms", "ops_ok_per_s", "setup_s",
    }
    # The tiny mix offers 400 ops/s for 4 s, every seed the same count.
    assert line["attempted"] == 1600 and line["failed"] == 0
    assert line["metrics"]["ops_ok_per_s"]["value"] == pytest.approx(
        400.0, rel=0.02
    )
    assert 0 < line["metrics"]["read_p95_ms"]["value"] < 5000
    text = out.stdout
    for proof in (
        "generator lateness (launch - due): p50",
        "outcomes of the window's operations: ok 1600, wrong 0",
        "read-back: 400 records (200 updated in the run), 0 differ",
        "overload.signals",
        "set-up: compaction idle after",
    ):
        assert proof in text, proof


def test_traced_line_reads_the_nodes_counters_over_the_window(cache_dir):
    out, lines = bench_run(
        CELL + ["--trace", "1", "--port-block", str(port_block(1))],
        cache_dir,
    )
    line = result_line(out, lines)
    assert line["correct"] is True, out.stdout[-3000:]
    got = line["metrics"]
    # Counters and host spans are read on any platform; what only a
    # device trace gives is left out on the cpu.
    for name in ("gen_late_p95_ms", "native_share", "dp_get_work_us",
                 "dp_write_work_us", "cache_hit_share", "write_amp",
                 "tables_max", "compile_s_in_window.serve"):
        assert name in got, (name, sorted(got))
    assert "device_idle.serve" not in got
    assert "merge_kernel_s.serve" not in got
    assert got["native_share"]["value"] > 50.0
    assert line["device"]["window_s"] == pytest.approx(1.0, abs=0.5)
    assert "--trace-sample" in out.stdout  # the traced run's node flag
