"""``neworder-64.merge`` rehearsed on the cpu at its tiny size (64 tables x
1,000 keys of TPC-C's NEW-ORDER stream over 2 warehouses): the contract's
line, untraced and traced, with every ``.neworder`` metric a cpu run can
read; the stream builder against a Python replay (a dict of per-district
FIFO queues); the tables' bytes and the model against a dict; and the
``.neworder`` data files against their ``.zipf`` twins."""

import collections
import json
import os
import re
import struct
import sys

import numpy as np
import pytest

from bench_rehearsal import REPO, bench_run, result_line

sys.path.insert(0, REPO)

CELL = ["--workload", "neworder-64.merge", "--seed", "3000000019",
        "--seconds", "1"]
# What the device's trace alone gives: left out of a cpu line.
DEVICE_ONLY = (
    "merge_kernel_s.neworder", "merge_kernel_roofline.neworder",
    "device_idle.neworder",
)
NEW = ("tomb_gc_s.neworder", "tombstone_share.neworder",
       "grace_kept_share.neworder")


def _load(kind, name):
    with open(os.path.join(REPO, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


TWINS = tuple(
    name[: -len(".zipf")]
    for name in _load("workloads", "zipf-64.merge")["per_layer"]
)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def _model_of(lines):
    (setup,) = [ln for ln in lines if "numpy model" in ln]
    found = re.search(
        r"drew (\d+) writes into 64 runs, 64000 keys .*, (\d+) tombstones "
        r"\(([0-9.]+) %\); cutoff (\d+); numpy model (\d+) entries "
        r"\(([0-9.]+) % dropped\), (\d+) tombstones kept \(([0-9.]+) %\), "
        r"(\d+) bytes", setup,
    )
    names = ("writes", "tombstones", "tombstone_share", "cutoff", "entries",
             "dropped", "kept", "kept_share", "nbytes")
    return {n: float(v) if "." in v else int(v)
            for n, v in zip(names, found.groups())}


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
    model = _model_of(lines)
    # A queue: most of what is read was since deleted; the newest three
    # tables' deletes lie inside the grace.
    assert model["writes"] >= 64_000 > 2 * model["entries"]
    assert 25.0 < model["tombstone_share"] < 45.0
    assert 0 < model["kept"] < model["tombstones"] // 8
    assert model["nbytes"] == 52 * (model["entries"] - model["kept"]) + (
        22 * model["kept"]
    )


def test_traced_line_holds_every_neworder_metric_a_cpu_run_can_read(cache_dir):
    out, lines = bench_run(
        CELL + ["--trace", "1", "--tiny", "--rehearsal"], cache_dir
    )
    line = result_line(out, lines)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    cell = _load("workloads", "neworder-64.merge")
    assert set(metrics) == set(cell["per_layer"]) - set(DEVICE_ONLY)
    assert set(NEW) <= set(metrics)
    for name, metric in metrics.items():
        assert metric["unit"] == _load("layer_metrics", name)["unit"]
    assert metrics["device_merge_share.neworder"]["value"] == 100.0
    assert metrics["compile_s_in_window.neworder"]["value"] == 0.0
    assert metrics["launches_per_merge.neworder"]["value"] >= 1.0
    # The program's counts of what it read, dropped and kept are the
    # model's; a row and its delete tie on the device key.
    model = _model_of(lines)
    assert metrics["tombstone_share.neworder"]["value"] == pytest.approx(
        100.0 * model["tombstones"] / 64_000
    )
    assert metrics["dedup_drop_share.neworder"]["value"] == pytest.approx(
        100.0 * (1 - model["entries"] / 64_000)
    )
    assert metrics["grace_kept_share.neworder"]["value"] == pytest.approx(
        100.0 * model["kept"] / model["tombstones"]
    )
    assert metrics["tie_fixup_share.neworder"]["value"] >= (
        2 * (metrics["tombstone_share.neworder"]["value"] - 5.0)
    )
    assert 0.0 < metrics["tomb_gc_s.neworder"]["value"] <= (
        metrics["pipe_decode_s.neworder"]["value"]
    )


def _replay(seed, warehouses, n, loaded=900, skipped=None):
    """The mix after the load, by a dict of per-district FIFO queues,
    from the draws ``transactions`` makes: [((w, d, o), is_delete)].
    ``skipped``: a list that gets a district a Delivery found empty."""
    rng = np.random.default_rng(seed)
    delivery = rng.random(n) < 4 / 49
    txn_w = rng.integers(1, warehouses + 1, size=n)
    txn_d = rng.integers(1, 11, size=n)
    queues = {
        (w, d): collections.deque(range(2101, 2101 + loaded))
        for w in range(1, warehouses + 1) for d in range(1, 11)
    }
    next_order = dict.fromkeys(queues, 2101 + loaded)
    out = []
    for i in range(n):
        w = int(txn_w[i])
        if delivery[i]:
            for d in range(1, 11):
                if queues[w, d]:
                    out.append(((w, d, queues[w, d].popleft()), True))
                elif skipped is not None:
                    skipped.append((w, d))
        else:
            d = int(txn_d[i])
            order = next_order[w, d]
            next_order[w, d] += 1
            queues[w, d].append(order)
            out.append(((w, d, order), False))
    return out


def _as_tuples(ids, delete):
    from benchmark.harness import neworder_runs

    w, d, o = neworder_runs.split_ids(ids)
    return [((int(a), int(b), int(c)), bool(t))
            for a, b, c, t in zip(w, d, o, delete)]


@pytest.mark.parametrize("warehouses,n,seed",
                         [(2, 3_000, 5), (3, 20_000, 2147483659)])
def test_the_stream_is_the_mix_a_dict_of_fifo_queues_replays(
    warehouses, n, seed
):
    from benchmark.harness import neworder_runs

    ids, delete = neworder_runs.transactions(
        np.random.default_rng(seed), warehouses, n
    )
    want = _replay(seed, warehouses, n)
    assert _as_tuples(ids, delete) == want
    # FIFO: a district's deletes take its orders lowest first, none
    # twice, and only orders that were inserted before.
    seen, last = set(), {}
    for (w, d, o), is_delete in want:
        if is_delete:
            assert o == last.get((w, d), 2100) + 1
            assert o <= 3000 or (w, d, o) in seen
            last[w, d] = o
        else:
            assert (w, d, o) not in seen
            seen.add((w, d, o))


def test_a_delivery_skips_a_district_that_has_no_undelivered_order(
    monkeypatch,
):
    """With three rows loaded a district the queues run dry: a delete
    that finds none is no write (TPC-C 2.7.4.2), and the orders that
    follow are still delivered lowest first."""
    from benchmark.harness import neworder_runs

    monkeypatch.setattr(neworder_runs, "LOADED", 3)
    ids, delete = neworder_runs.transactions(
        np.random.default_rng(7), 2, 5_000
    )
    skipped = []
    want = _replay(7, 2, 5_000, loaded=3, skipped=skipped)
    assert len(skipped) > 20
    assert _as_tuples(ids, delete) == want


def test_the_mix_is_45_inserts_for_40_deletes():
    """400,000 transactions over 20 warehouses: New-Orders 45/49 of them
    within four standard deviations (0.17 %), and 10 deletes a Delivery
    (no queue of 900 runs dry here)."""
    from benchmark.harness import neworder_runs

    n = 400_000
    ids, delete = neworder_runs.transactions(
        np.random.default_rng(2147483693), 20, n
    )
    inserts, deletes = int((~delete).sum()), int(delete.sum())
    assert abs(inserts / n - 45 / 49) < 4 * (45 / 49 * 4 / 49 / n) ** 0.5
    assert deletes == 10 * (n - inserts)
    assert inserts / deletes == pytest.approx(45 / 40, rel=0.02)
    w, d, _o = neworder_runs.split_ids(ids[~delete])
    assert np.bincount(w)[1:].min() > 0.9 * inserts / 20
    assert np.bincount(d)[1:].min() > 0.95 * inserts / 10


def test_the_tables_the_files_and_the_model_against_a_dict(tmp_path):
    """The stream cut into memtables, replayed by a Python dict that is
    flushed when it holds ``per_run`` keys; the tables on disk hold what
    the columns say, rows of 52 bytes whose key and value msgpack
    decodes to the three columns, tombstones of 22; the model's counts
    and bytes are a dict's that keeps the newest timestamp and drops a
    tombstone below the cutoff."""
    import msgpack

    from benchmark.harness import neworder_runs
    from dbeel_tpu.storage.entry import (
        DATA_FILE_EXT, INDEX_FILE_EXT, file_name,
    )

    seed, warehouses, nruns, per_run, grace = 3000000019, 2, 20, 1_300, 3
    indices, columns, writes, cutoff = neworder_runs.build_runs(
        str(tmp_path), warehouses, nruns, per_run, seed, grace
    )
    assert indices == [2 * r for r in range(nruns)]
    ids, delete = neworder_runs.stream(seed, warehouses, nruns * per_run)
    load = neworder_runs.load(warehouses)
    assert (ids[: len(load)] == load).all() and not delete[: len(load)].any()
    assert len(load) == warehouses * 10 * 900
    assert _as_tuples(ids[len(load):], delete[len(load):]) == _replay(
        seed, warehouses,
        int((nruns * per_run - len(load)) / (85 / 49) * 1.05) + 2_000,
    )
    # Table boundaries: a memtable is flushed by the write that brings
    # its per_run-th distinct key.
    memtable, at, tables = {}, 0, []
    for place, (key, is_delete) in enumerate(zip(ids.tolist(), delete)):
        memtable[key] = (place, bool(is_delete))
        if len(memtable) == per_run:
            tables.append((at, place + 1, memtable))
            memtable, at = {}, place + 1
            if len(tables) == nruns:
                break
    assert writes == tables[-1][1] >= nruns * per_run
    assert cutoff == tables[nruns - grace][0]
    # Around the end of the load a row and its delete meet in one
    # memtable, which then holds the tombstone alone.
    assert any(hi - lo > per_run for lo, hi, _m in tables)
    newest = {}
    for r, ((lo, hi, memtable), (keys, ts, tomb)) in enumerate(
        zip(tables, columns)
    ):
        assert len(keys) == len(ts) == len(tomb) == per_run
        assert (np.diff(keys) > 0).all()
        assert dict(zip(keys.tolist(), zip(ts.tolist(), tomb.tolist()))) == (
            memtable
        )
        blob = (tmp_path / file_name(2 * r, DATA_FILE_EXT)).read_bytes()
        index = (tmp_path / file_name(2 * r, INDEX_FILE_EXT)).read_bytes()
        assert len(index) == 16 * per_run
        at, table_keys = 0, []
        for i in range(per_run):
            ks, vs, stamp = struct.unpack_from("<IIq", blob, at)
            assert struct.unpack_from("<QII", index, 16 * i) == (
                at, 6, 22 + vs
            )
            w, d, o = (int(x) for x in neworder_runs.split_ids(keys[i]))
            key = blob[at + 16:at + 22]
            assert (ks, stamp) == (6, int(ts[i]))
            assert key == msgpack.packb([w, d, o])
            if tomb[i]:
                assert vs == 0
            else:
                assert vs == 30 and blob[at + 22:at + 52] == msgpack.packb(
                    {"no_o_id": o, "no_d_id": d, "no_w_id": w}
                )
            table_keys.append(key)
            if key not in newest or stamp > newest[key][0]:
                newest[key] = (stamp, bool(tomb[i]))
            at += 22 + vs
        assert at == len(blob)
        assert table_keys == sorted(set(table_keys))
    model = neworder_runs.model(
        *(np.concatenate(c) for c in zip(*columns)), cutoff
    )
    live = sum(not tomb for _ts, tomb in newest.values())
    kept = sum(tomb and ts >= cutoff for ts, tomb in newest.values())
    assert kept > 0 and live > 0
    assert model == {
        "entries_in": nruns * per_run,
        "tombstones_in": sum(int(c[2].sum()) for c in columns),
        "entries_out": live + kept,
        "rows_out": live,
        "tombstones_kept": kept,
        "bytes_out": 52 * live + 22 * kept,
    }
    # The same seed, the same bytes.
    again = tmp_path / "again"
    again.mkdir()
    neworder_runs.build_runs(
        str(again), warehouses, nruns, per_run, seed, grace
    )
    for name in os.listdir(again):
        assert (again / name).read_bytes() == (tmp_path / name).read_bytes()


def test_the_configuration_keeps_the_sources_shapes_and_guarantees():
    from benchmark.harness import neworder_runs

    cfg, zipf = _load("configs", "neworder-64"), _load("configs", "zipf-64")
    assert (cfg["strategy"], cfg["oracle_strategy"]) == ("device", "native")
    assert cfg["reduced"] == [] and cfg["architecture"] is None
    assert cfg["deploy"] == "neworder_merge_job" and cfg["chips"] == 1
    for shape in ("runs", "entries_per_run"):
        assert cfg[shape] == zipf[shape]
    # The source's shapes, which the builder's constants are.
    assert cfg["districts_per_warehouse"] == neworder_runs.DISTRICTS == 10
    assert cfg["rows_loaded_per_district"] == neworder_runs.LOADED == 900
    assert cfg["first_order_loaded"] == neworder_runs.FIRST_ORDER == 2101
    assert cfg["mix"] == {
        "new_order": neworder_runs.NEW_ORDER,
        "delivery": neworder_runs.DELIVERY,
    } == {"new_order": 45, "delivery": 4}
    assert (cfg["key_bytes"], cfg["row_bytes"], cfg["tombstone_bytes"]) == (
        neworder_runs.KEY_BYTES, neworder_runs.ROW_BYTES,
        neworder_runs.TOMBSTONE_BYTES,
    ) == (6, 52, 22)
    assert cfg["warehouses"] == 100 and cfg["grace_runs"] == 3
    assert cfg["tiny"] == {"warehouses": 2, "entries_per_run": 1_000}
    assert set(cfg["assumed"]) >= {
        "warehouses", "encodings", "shard", "entries_per_run", "grace_runs",
        "timestamps",
    }
    for phrase in ("byte-identical", "newest version",
                   "below tombstone_drop_before", "no live row is lost",
                   "cannot bring the row back"):
        assert phrase in cfg["guarantees"], phrase
    assert "keep_tombstones=False" in cfg["cutoff"]
    assert "TPC-C rev 5.11 NEW-ORDER" in cfg["source"]
    assert "2.7.4.2" in cfg["source"] and len(cfg["source"]) <= 200


@pytest.mark.parametrize("stem", TWINS)
def test_a_neworder_twin_differs_from_its_zipf_file_in_name_and_cell(stem):
    """The twenty twins read what the ``.zipf`` files read, by the same
    reader and arguments, so the two cells' numbers compare."""
    mine = _load("layer_metrics", stem + ".neworder")
    zipf = _load("layer_metrics", stem + ".zipf")
    assert mine.pop("name") == stem + ".neworder"
    assert zipf.pop("name") == stem + ".zipf"
    assert mine.pop("cells") == ["neworder-64.merge"]
    assert zipf.pop("cells") == ["zipf-64.merge"]
    assert mine == zipf


def test_the_cells_line_is_the_twins_and_the_three_new_metrics():
    cell = _load("workloads", "neworder-64.merge")
    assert cell["per_layer"] == [s + ".neworder" for s in TWINS] + list(NEW)
    assert len(TWINS) == 20
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "neworder-64", "merge", 1
    )


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_reads_get_stats_and_leaves_a_parent_out(name):
    """Each path resolves in the program's ``get_stats.compaction`` block
    (the span's once a merge has read a tombstone: the traced rehearsal
    above), and on a program without the span or the counters, as this
    PR's parent, the reader gives None and the line leaves the metric
    out."""
    import types

    from benchmark.readers import stats_ratio
    from dbeel_tpu.storage.compaction import compaction_stats

    spec = _load("layer_metrics", name)
    assert spec["reader"] == "stats_ratio" and spec["cells"] == [
        "neworder-64.merge"
    ]
    block = compaction_stats.stats()
    for path in spec["numerator"] + spec["denominator"]:
        assert path.startswith("node.compaction.")
        parts = path.split(".")[2:]
        if parts[0] == "stages":
            assert parts == ["stages", "pipeline", "tomb_gc", "s"]
            continue
        assert isinstance(stats_ratio.at(block, ".".join(parts)), int), path

    def snapshot(tombstones_in, kept, gc_s, merges, rows):
        return {"node": {"compaction": {
            "shape": {"rows_real": rows, "tombstones_in": tombstones_in,
                      "tombstones_kept": kept},
            "stages": {"pipeline": {"tomb_gc": {"s": gc_s, "n": merges}}},
            "paths": {"pipeline": merges},
        }}, "shards": []}

    run = types.SimpleNamespace(
        facts={}, stats_before=snapshot(10, 1, 0.5, 1, 100),
        stats_after=snapshot(440, 23, 0.75, 6, 1_100),
    )
    assert stats_ratio.read(run, spec) == pytest.approx({
        "tomb_gc_s.neworder": 0.05,
        "tombstone_share.neworder": 43.0,
        "grace_kept_share.neworder": 100.0 * 22 / 430,
    }[name])
    for snap in (run.stats_before, run.stats_after):
        block = snap["node"]["compaction"]
        del block["stages"]["pipeline"]["tomb_gc"]
        del block["shape"]["tombstones_in"], block["shape"]["tombstones_kept"]
    assert stats_ratio.read(run, spec) is None
