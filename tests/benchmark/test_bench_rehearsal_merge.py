"""``major-10m.merge`` rehearsed on the cpu at a tiny size: the contract's
line, traced and untraced; the refusal to print a cpu number under a device
metric's name; and a cell added by data files alone."""

import hashlib
import json
import os
import shutil

import pytest

from bench_rehearsal import REPO, bench_run, result_line

CELL = ["--workload", "major-10m.merge", "--seed", "3000000019",
        "--seconds", "1"]


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
    assert line["device"]["platform"] == "cpu"
    assert "breakdown" not in line
    # The medians, walls and compile list go on earlier lines.
    assert any(ln.startswith("window: ") for ln in lines[:-1])


def test_traced_line_holds_per_layer_metrics_and_the_traced_window(cache_dir):
    out, lines = bench_run(
        CELL + ["--trace", "1", "--tiny", "--rehearsal"], cache_dir
    )
    line = result_line(out, lines)
    assert line["correct"] is True
    # On the cpu there is no device plane: the trace readers find
    # nothing and their metrics are left out, never made up.
    assert set(line["metrics"]) == {
        "device_merge_share.major", "compile_s_in_window.major",
        "merge_wall_s.major",
    }
    assert line["metrics"]["device_merge_share.major"]["value"] == 100.0
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_cpu_run_without_rehearsal_fails_and_prints_no_result(cache_dir):
    out, lines = bench_run(CELL + ["--trace", "0"], cache_dir)
    assert out.returncode != 0
    assert not any(ln.startswith("{") for ln in lines)
    assert "JAX found no accelerator" in out.stdout
    # --tiny is the rehearsal's alone: never a tiny number from the chip.
    out, lines = bench_run(CELL + ["--trace", "0", "--tiny"], cache_dir)
    assert out.returncode != 0 and not lines


def test_an_unknown_cell_fails_without_a_result(cache_dir):
    out, lines = bench_run(
        ["--workload", "no-such.cell", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearsal"], cache_dir,
    )
    assert out.returncode != 0
    assert not any(ln.startswith("{") for ln in lines)


def _digests(root):
    out = {}
    for dirpath, dirnames, files in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in files:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()
                ).hexdigest()
    return out


def test_a_cell_a_mix_and_a_layer_metric_are_added_by_files_alone(
    tmp_path, cache_dir
):
    copy = tmp_path / "checkout" / "benchmark"
    shutil.copytree(
        os.path.join(REPO, "benchmark"), copy,
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    before = _digests(copy)
    (copy / "traffic" / "merge-once-traced.json").write_text(json.dumps({
        "name": "merge-once-traced", "kind": "merge_loop",
        "traced_merges": 1,
    }))
    (copy / "layer_metrics" / "merges_in_window.json").write_text(json.dumps({
        "name": "merges_in_window", "layer": "device pipeline",
        "unit": "count", "better": "higher", "source": "program_counter",
        "moves": "merge_keys_per_s", "cells": ["major-10m.once"],
        "reader": "fact", "fact": "merges",
    }))
    (copy / "workloads" / "major-10m.once.json").write_text(json.dumps({
        "name": "major-10m.once", "config": "major-10m",
        "traffic": "merge-once-traced", "chips": 1, "why": "a test's",
        "end_to_end": {"merge_keys_per_s": "keys/s", "setup_s": "s"},
        "per_layer": ["merges_in_window", "merge_wall_s.major"],
    }))
    out, lines = bench_run(
        ["--workload", "major-10m.once", "--seed", "5", "--seconds", "1",
         "--trace", "1", "--tiny", "--rehearsal"],
        cache_dir, root=str(tmp_path / "checkout"),
    )
    line = result_line(out, lines)
    assert set(line["metrics"]) == {"merges_in_window", "merge_wall_s.major"}
    assert line["metrics"]["merges_in_window"]["value"] == line["attempted"]
    after = _digests(copy)
    assert {k: after[k] for k in before} == before  # no existing file edited
    assert len(after) == len(before) + 3
