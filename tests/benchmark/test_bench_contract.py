"""BENCHMARK.json against its contract's letter, and against the data files
under benchmark/ that the harness really reads."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, REPO)
BENCH = os.path.join(REPO, "benchmark")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def _cells_of(metric, bench):
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def test_keys_names_units_and_lines(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= bench["run_seconds"] <= 51
    names = [m["name"] for m in _metrics(bench)]
    assert len(names) == len(set(names))
    for m in _metrics(bench):
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), (m["name"], m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for entry in bench["configs"] + bench["workloads"]:
        assert NAME.match(entry["name"])
        for key in ("why", "source"):
            if key in entry:
                text = entry[key]
                assert 1 <= len(text) <= 200 and "\n" not in text
                assert "\t" not in text
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = {m["name"] for m in bench["end_to_end"]
               if w["name"] in _cells_of(m, bench)}
        layer = {m["name"] for m in bench["per_layer"]
                 if w["name"] in _cells_of(m, bench)}
        assert "setup_s" in e2e and len(e2e) >= 2 and layer


def test_each_layer_metric_moves_a_metric_every_one_of_its_cells_reports(
    bench,
):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        moved_in = set(_cells_of(e2e[m["moves"]], bench))
        assert set(_cells_of(m, bench)) <= moved_in, m["name"]
    by_layer = {}
    for m in bench["per_layer"]:
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(spellings) == 1 for spellings in by_layer.values())


def test_benchmark_json_agrees_with_the_files_the_harness_reads(bench):
    for c in bench["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        on_disk = _load("configs", c["name"])
        assert on_disk["source"] == c["source"]
        assert on_disk["reduced"] == c["reduced"]
        assert os.path.exists(
            os.path.join(BENCH, "deploy", on_disk["deploy"] + ".py")
        )
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = _load("workloads", w["name"])
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        traffic = _load("traffic", w["traffic"])
        assert os.path.exists(
            os.path.join(BENCH, "generators", traffic["kind"] + ".py")
        )
        e2e = {m["name"]: m["unit"] for m in bench["end_to_end"]
               if w["name"] in _cells_of(m, bench)}
        assert cell["end_to_end"] == e2e
        layer = [m["name"] for m in bench["per_layer"]
                 if w["name"] in _cells_of(m, bench)]
        assert sorted(cell["per_layer"]) == sorted(layer)
    for m in bench["per_layer"]:
        spec = _load("layer_metrics", m["name"])
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert sorted(spec["cells"]) == sorted(_cells_of(m, bench))
        assert os.path.exists(
            os.path.join(BENCH, "readers", spec["reader"] + ".py")
        )


def test_the_served_configuration_states_its_guarantees():
    cfg = _load("configs", "ycsb-1node")
    assert cfg["replication_factor"] == 1 and cfg["consistency"] == 1
    assert "--wal-sync" not in cfg["node_flags"]
    for phrase in ("no --wal-sync", "read back", "RF 1"):
        assert phrase in cfg["guarantees"]
    # The floor the configuration's own `reduced_why` gives: one shard's
    # whole tree passes 2 x PIPELINE_MIN_BYTES.
    assert cfg["recordcount"] >= 244_034


def test_files_under_paths_are_named_from_a_names_characters(bench):
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root in bench["paths"]:
        for dirpath, dirnames, files in os.walk(os.path.join(REPO, root)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for name in files:
                if name.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, name), REPO)
                assert allowed.match(rel), rel
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
