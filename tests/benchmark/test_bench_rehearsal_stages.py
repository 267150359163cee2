"""The eight ``pipe_*_s.major`` metrics (benchmark/layer_metrics/), read
from ``get_stats.compaction.stages.pipeline`` by the ``stats_ratio`` reader
the benchmark already had.  ``run.py`` takes a cell's metrics from the
cell's own file, which only a ``benchmark`` PR may edit, so the rehearsal
here runs ``major-10m.merge`` in a copy of benchmark/ whose cell file has
the eight names appended: the one edit that puts them on the cell's traced
line."""

import json
import os
import re
import shutil

import pytest

from bench_rehearsal import REPO, bench_run, result_line

CELL = "major-10m.merge"
# On the calling thread, one after another: they sum to the pipeline's
# wall of a merge.
SEQUENTIAL = (
    "pipe_read_stage_s.major", "pipe_wait_device_s.major",
    "pipe_decode_s.major", "pipe_wait_writer_s.major", "pipe_tail_s.major",
)
# On the upload, writer and close threads: they overlap those.
OVERLAPPING = (
    "pipe_h2d_s.major", "pipe_gather_write_s.major", "pipe_fsync_s.major",
)
# A thread that never has to wait reads 0 there.
WAITS = ("pipe_wait_device_s.major", "pipe_wait_writer_s.major")


def _spec(name):
    with open(
        os.path.join(REPO, "benchmark", "layer_metrics", name + ".json")
    ) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = tmp_path_factory.mktemp("stages")
    copy = root / "checkout" / "benchmark"
    shutil.copytree(
        os.path.join(REPO, "benchmark"), copy,
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    cell_file = copy / "workloads" / (CELL + ".json")
    cell = json.loads(cell_file.read_text())
    had = list(cell["per_layer"])
    cell["per_layer"] = had + list(SEQUENTIAL + OVERLAPPING)
    cell_file.write_text(json.dumps(cell))
    out, lines = bench_run(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "1",
         "--trace", "1", "--tiny", "--rehearsal"],
        root / "jax_cache", root=str(root / "checkout"),
    )
    line = result_line(out, lines)
    assert line["correct"] is True and line["failed"] == 0
    (window,) = [ln for ln in lines if ln.startswith("window: ")
                 and " merges of " in ln]
    walls_sum = float(re.search(r" s, sum ([0-9.]+)s;", window).group(1))
    return had, line["metrics"], walls_sum / line["attempted"]


@pytest.mark.parametrize("name", SEQUENTIAL + OVERLAPPING)
def test_each_stage_metric_is_a_data_file_for_the_reader_that_is_there(name):
    spec = _spec(name)
    assert spec["name"] == name and spec["reader"] == "stats_ratio"
    assert (spec["unit"], spec["better"]) == ("s", "lower")
    assert spec["source"] == "program_span"
    assert spec["layer"] == "device pipeline"
    assert spec["moves"] == "merge_keys_per_s" and spec["cells"] == [CELL]
    assert spec["denominator"] == ["node.compaction.paths.pipeline"]
    for path in spec["numerator"]:
        assert path.startswith("node.compaction.stages.pipeline.")
        assert path.endswith(".s")
    assert "scale" not in spec


def test_no_stage_is_read_by_two_of_the_metrics():
    paths = [p for n in SEQUENTIAL + OVERLAPPING
             for p in _spec(n)["numerator"]]
    assert len(paths) == len(set(paths)) == 13


@pytest.mark.parametrize("name", SEQUENTIAL + OVERLAPPING)
def test_the_traced_line_holds_each_stage_metric(traced, name):
    had, metrics, _mean_wall = traced
    assert metrics[name]["unit"] == "s"
    if name in WAITS:
        assert metrics[name]["value"] >= 0.0
    else:
        assert metrics[name]["value"] > 0.0
    # What the cell reported before is still there, beside the new.
    assert {"device_merge_share.major", "merge_wall_s.major"} <= set(metrics)
    assert set(metrics) <= set(had) | set(SEQUENTIAL + OVERLAPPING)


def test_the_sequential_stages_sum_to_no_more_than_the_merges_wall(traced):
    """The five are means over the window's merges of what the calling
    thread did inside the pipeline; the wall the harness takes around
    the whole strategy call holds that and the opening and closing of
    the sstables around it.  Held against the MEAN wall (the window
    line's sum, printed to 0.01 s): ``merge_wall_s.major`` is a median,
    which one slow merge of thirty 30 ms ones moves differently.  At
    this tiny size what lies around the pipeline is a real share, so
    only a loose lower side is held; on the chip the five come within
    5 % of ``merge_wall_s.major`` (PERF.md)."""
    _had, metrics, mean_wall = traced
    total = sum(metrics[name]["value"] for name in SEQUENTIAL)
    assert 0.5 * mean_wall < total <= 1.02 * mean_wall, (total, mean_wall)
    assert metrics["merge_wall_s.major"]["value"] > 0.0
