"""``wide-64.merge`` rehearsed on the cpu at its tiny size (64 runs x 1,000
keys of variable-length records): the contract's line, untraced and traced,
with every ``.wide`` metric a cpu run can read; the vectorised run builder
against ``bench.build_runs``, whose bytes it must write; the deployment's
check of the data file's length; and the ``.wide`` data files against their
``.major`` twins."""

import json
import os
import re
import sys
import types

import numpy as np
import pytest

from bench_rehearsal import REPO, bench_run, result_line

sys.path.insert(0, REPO)

CELL = ["--workload", "wide-64.merge", "--seed", "3000000019",
        "--seconds", "1"]
# On the calling thread, one after another.
SEQUENTIAL = (
    "pipe_read_stage_s.wide", "pipe_wait_device_s.wide",
    "pipe_decode_s.wide", "pipe_wait_writer_s.wide", "pipe_tail_s.wide",
)
OVERLAPPING = (
    "pipe_h2d_s.wide", "pipe_gather_write_s.wide", "pipe_fsync_s.wide",
    "pipe_bloom_bg_s.wide",
)
SHAPE = (
    "launches_per_merge.wide", "pad_rows_share.wide", "tie_fixup_share.wide",
)
# What the device's trace alone gives: left out of a cpu line.
DEVICE_ONLY = (
    "merge_kernel_s.wide", "merge_kernel_roofline.wide", "device_idle.wide",
)
TWINS = (
    "device_merge_share", "compile_s_in_window", "merge_wall_s",
    "merge_kernel_s", "merge_kernel_roofline", "device_idle",
) + tuple(name[: -len(".wide")] for name in SEQUENTIAL + OVERLAPPING)


def _load(kind, name):
    with open(os.path.join(REPO, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def test_untraced_line_holds_the_cells_end_to_end_metrics(cache_dir):
    out, lines = bench_run(
        CELL + ["--trace", "0", "--tiny", "--rehearsal"], cache_dir
    )
    line = result_line(out, lines)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert set(line["metrics"]) == {"merge_keys_per_s", "setup_s"}
    assert line["metrics"]["merge_keys_per_s"]["unit"] == "keys/s"
    assert line["metrics"]["merge_keys_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    # The model gave an entry count AND a data-file length.
    (setup,) = [ln for ln in lines if "numpy model" in ln]
    assert re.search(r"numpy model 64000 entries, \d+ bytes", setup)


def test_traced_line_holds_every_wide_metric_a_cpu_run_can_read(cache_dir):
    out, lines = bench_run(
        CELL + ["--trace", "1", "--tiny", "--rehearsal"], cache_dir
    )
    line = result_line(out, lines)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    cell = _load("workloads", "wide-64.merge")
    assert set(metrics) == set(cell["per_layer"]) - set(DEVICE_ONLY)
    assert set(SEQUENTIAL + OVERLAPPING + SHAPE) <= set(metrics)
    for name, metric in metrics.items():
        assert metric["unit"] == _load("layer_metrics", name)["unit"]
    assert metrics["device_merge_share.wide"]["value"] == 100.0
    assert metrics["compile_s_in_window.wide"]["value"] == 0.0
    # Whole launches of (batch, 64, rows): more rows than entries.
    assert metrics["launches_per_merge.wide"]["value"] >= 1.0
    assert 0.0 < metrics["pad_rows_share.wide"]["value"] < 100.0
    assert 0.0 <= metrics["tie_fixup_share.wide"]["value"] < 100.0
    (window,) = [ln for ln in lines if ln.startswith("window: ")
                 and " merges of " in ln]
    walls_sum = float(re.search(r" s, sum ([0-9.]+)s;", window).group(1))
    caller = sum(metrics[name]["value"] for name in SEQUENTIAL)
    # As test_bench_rehearsal_stages: the harness's wall also holds the
    # opening and closing of 64 sstables around the pipeline.
    assert 0.4 * walls_sum < caller * line["attempted"] <= 1.02 * walls_sum


def test_the_builder_writes_the_bytes_bench_py_writes(tmp_path):
    import bench
    from benchmark.harness import varlen_runs

    ours, theirs = tmp_path / "ours", tmp_path / "theirs"
    ours.mkdir()
    theirs.mkdir()
    seed, total, runs = 2147483659, 64 * 150, 64
    indices, columns = varlen_runs.build_runs(
        str(ours), total, runs, seed, 16, 8, 159
    )
    bench.build_runs(
        str(theirs), total, runs, seed=seed, variable_values=True
    )
    names = sorted(os.listdir(ours))
    assert names == sorted(os.listdir(theirs)) and len(names) == 2 * runs
    for name in names:
        assert (ours / name).read_bytes() == (theirs / name).read_bytes()
    assert indices == [2 * r for r in range(runs)]
    # What the model is handed is what the files hold.
    assert sum(int(full.sum()) for _k, _ts, full in columns) == sum(
        os.path.getsize(ours / n) for n in names if n.endswith(".data")
    )
    lengths = [full - 32 for _k, _ts, full in columns]
    assert min(v.min() for v in lengths) >= 8
    assert max(v.max() for v in lengths) <= 159
    count, _nbytes = varlen_runs.model(
        *(np.concatenate(c) for c in zip(*columns))
    )
    assert count == total  # uniform 16-byte keys do not repeat


@pytest.mark.parametrize("off_by", [0, 1])
def test_a_merge_of_the_wrong_data_length_is_counted_failed(
    tmp_path, monkeypatch, off_by
):
    from benchmark.deploy import merge_job, wide_merge_job
    from dbeel_tpu.storage.entry import COMPACT_DATA_FILE_EXT, file_name

    job = wide_merge_job.WideMergeJob.__new__(wide_merge_job.WideMergeJob)
    job.run = types.SimpleNamespace(wrong=[])
    job.dir, job.model_bytes = str(tmp_path), 1000

    def fake_merge(self, strategy):
        with open(os.path.join(
            self.dir, file_name(self.OUT_INDEX, COMPACT_DATA_FILE_EXT)
        ), "wb") as f:
            f.write(b"x" * (1000 + off_by))
        return 0.5, 7

    monkeypatch.setattr(merge_job.MergeJob, "merge", fake_merge)
    assert job.merge(None) == (0.5, -1 if off_by else 7)
    assert bool(job.run.wrong) == bool(off_by)


def test_the_configuration_keeps_the_sources_shapes_and_guarantees():
    cfg, major = _load("configs", "wide-64"), _load("configs", "major-10m")
    assert cfg["guarantees"] == major["guarantees"]
    assert (cfg["strategy"], cfg["oracle_strategy"]) == ("device", "native")
    assert cfg["reduced"] == [] and cfg["architecture"] is None
    assert (cfg["runs"], cfg["key_bytes"]) == (64, 16)
    assert (cfg["value_bytes_min"], cfg["value_bytes_max"]) == (8, 159)
    assert cfg["total_keys"] == 64 * 156_250
    assert cfg["tiny"] == {"total_keys": 64_000}
    assert set(cfg["assumed"]) >= {"total_keys", "allocator"}
    assert "BASELINE.json configs[3]" in cfg["source"]
    assert len(cfg["source"]) <= 200


def test_the_allocator_policy_is_set_through_mallopt_or_the_run_fails():
    """The configuration's policy names parameters the deployment
    knows; each goes to ``mallopt`` with its number, and one that glibc
    refuses stops the run (no result line) instead of being skipped.
    The rehearsals above set the real one, in their own processes."""
    from benchmark.deploy import wide_merge_job
    from benchmark.harness.common import BenchFailure

    policy = _load("configs", "wide-64")["allocator"]
    assert policy == {"M_MMAP_THRESHOLD": 128 << 10}  # glibc's default
    calls = []

    def fake_libc(answer):
        return lambda _name: types.SimpleNamespace(
            mallopt=lambda p, v: calls.append((p, v)) or answer
        )

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(wide_merge_job.ctypes, "CDLL", fake_libc(1))
        wide_merge_job.set_allocator(policy)
        assert calls == [(-3, 128 << 10)]
        mp.setattr(wide_merge_job.ctypes, "CDLL", fake_libc(0))
        with pytest.raises(BenchFailure):
            wide_merge_job.set_allocator(policy)


@pytest.mark.parametrize("stem", TWINS)
def test_a_wide_twin_differs_from_its_major_file_in_name_and_cell(stem):
    """The fifteen twins read what the ``.major`` files read, by the
    same reader and arguments, so the two cells' numbers compare."""
    wide = _load("layer_metrics", stem + ".wide")
    major = _load("layer_metrics", stem + ".major")
    assert wide.pop("name") == stem + ".wide"
    assert major.pop("name") == stem + ".major"
    assert wide.pop("cells") == ["wide-64.merge"]
    assert major.pop("cells") == ["major-10m.merge"]
    wide.pop("what"), major.pop("what")
    assert wide == major
