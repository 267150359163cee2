"""``zipf-64.merge`` rehearsed on the cpu at its tiny size (64 tables x 1,000
keys of one zipfian update stream over 64,000 records): the contract's line,
untraced and traced, with every ``.zipf`` metric a cpu run can read; the
run builder's draw against YCSB's distribution and against a Python dict
that replays the stream; and the ``.zipf`` data files against their
``.wide`` twins."""

import json
import os
import re
import struct
import sys

import numpy as np
import pytest

from bench_rehearsal import REPO, bench_run, result_line

sys.path.insert(0, REPO)

CELL = ["--workload", "zipf-64.merge", "--seed", "3000000019",
        "--seconds", "1"]
# What the device's trace alone gives: left out of a cpu line.
DEVICE_ONLY = (
    "merge_kernel_s.zipf", "merge_kernel_roofline.zipf", "device_idle.zipf",
)
NEW = ("dedup_drop_share.zipf", "tie_fixup_s.zipf")


def _load(kind, name):
    with open(os.path.join(REPO, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


TWINS = tuple(
    name[: -len(".wide")]
    for name in _load("workloads", "wide-64.merge")["per_layer"]
)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def _model_of(lines):
    (setup,) = [ln for ln in lines if "numpy model" in ln]
    found = re.search(
        r"drew (\d+) updates into 64 runs, 64000 keys .* numpy model "
        r"(\d+) entries \(([0-9.]+) % dropped\), (\d+) bytes", setup,
    )
    return int(found[1]), int(found[2]), float(found[3]), int(found[4])


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
    writes, entries, dropped, nbytes = _model_of(lines)
    # A merge of an update stream writes far less than it reads.
    assert writes > 64_000 > 2 * entries
    assert 55.0 < dropped < 70.0 and 40 * entries <= nbytes <= 191 * entries


def test_traced_line_holds_every_zipf_metric_a_cpu_run_can_read(cache_dir):
    out, lines = bench_run(
        CELL + ["--trace", "1", "--tiny", "--rehearsal"], cache_dir
    )
    line = result_line(out, lines)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    cell = _load("workloads", "zipf-64.merge")
    assert set(metrics) == set(cell["per_layer"]) - set(DEVICE_ONLY)
    assert set(NEW) <= set(metrics)
    for name, metric in metrics.items():
        assert metric["unit"] == _load("layer_metrics", name)["unit"]
    assert metrics["device_merge_share.zipf"]["value"] == 100.0
    assert metrics["compile_s_in_window.zipf"]["value"] == 0.0
    assert metrics["launches_per_merge.zipf"]["value"] >= 1.0
    # The program's count of what it dropped is the model's, and the
    # versions of a repeated key all tie on the device key.
    _writes, entries, dropped, _nbytes = _model_of(lines)
    drop_share = metrics["dedup_drop_share.zipf"]["value"]
    assert drop_share == pytest.approx(100.0 * (1 - entries / 64_000))
    assert drop_share == pytest.approx(dropped, abs=0.001)
    assert drop_share < metrics["tie_fixup_share.zipf"]["value"] < 100.0
    assert 0.0 < metrics["tie_fixup_s.zipf"]["value"] <= (
        metrics["pipe_decode_s.zipf"]["value"]
    )


def _stream_tables(seed, records, nruns, per_run):
    from benchmark.harness import zipf_runs

    rng = np.random.default_rng(seed)
    cdf = zipf_runs.rank_cdf(records, 0.99)
    pending = np.zeros(0, dtype=np.int64)
    tables = []
    for _ in range(nruns):
        ranks, pending = zipf_runs.next_table(rng, cdf, per_run, pending)
        tables.append(ranks)
    return tables, pending


def test_the_draw_is_ycsbs_zipfian():
    """Rank frequencies of 400,000 draws over 5,000 records against
    ``1 / i^0.99`` normalised: the ten hottest ranks' shares within 3 %
    of theirs each (three standard deviations of the coldest of them
    are 2.1 %), and Pearson's chi-square over all ranks (4,999 degrees
    of freedom: mean 4,999, standard deviation 100) under 5,400."""
    from benchmark.harness import zipf_runs

    records, draws = 5_000, 400_000
    cdf = zipf_runs.rank_cdf(records, 0.99)
    assert cdf[-1] == 1.0 and (np.diff(cdf) > 0).all()
    ranks = zipf_runs.draw_ranks(np.random.default_rng(2147483659), cdf, draws)
    assert ranks.min() == 0 and ranks.max() < records
    want = np.arange(1, records + 1, dtype=np.float64) ** -0.99
    want /= want.sum()
    got = np.bincount(ranks, minlength=records) / draws
    assert np.abs(got[:10] / want[:10] - 1.0).max() < 0.03
    chi2 = (draws * (got - want) ** 2 / want).sum()
    assert 4_600 < chi2 < 5_400


def test_a_table_holds_exactly_its_count_of_distinct_keys_newest_write_kept():
    """The stream cut into tables, replayed by a Python dict that is
    flushed when it holds ``per_run`` keys."""
    from benchmark.harness import zipf_runs

    per_run, nruns = 150, 12
    tables, pending = _stream_tables(2147483693, 2_000, nruns, per_run)
    stream = np.concatenate(tables + [pending]).tolist()
    replay, memtable, at = [], {}, 0
    for place, rank in enumerate(stream):
        memtable[rank] = place
        if len(memtable) == per_run:
            replay.append((at, place + 1, memtable))
            memtable, at = {}, place + 1
            if len(replay) == nruns:
                break
    assert len(replay) == nruns
    start = 0
    for ranks, (lo, hi, memtable) in zip(tables, replay):
        assert (start, start + len(ranks)) == (lo, hi)
        uniq, last = zipf_runs.newest_writes(ranks)
        assert len(uniq) == per_run
        assert dict(zip(uniq.tolist(), (start + last).tolist())) == memtable
        start = hi
    # Updates repeat: a table takes more writes than it keeps.
    assert all(len(ranks) > per_run for ranks in tables)


def test_the_builders_files_and_the_model_against_a_dict(tmp_path):
    """The tables on disk hold what the columns say, keys sorted and
    distinct in a table, one key a record over the whole run; and the
    model's count and bytes are a dict's that keeps the newest
    timestamp."""
    from benchmark.harness import varlen_runs, zipf_runs
    from dbeel_tpu.storage.entry import DATA_FILE_EXT, file_name

    seed, records, nruns, per_run = 3000000019, 3_000, 16, 200
    indices, columns, writes = zipf_runs.build_runs(
        str(tmp_path), records, nruns, per_run, seed, 16, 8, 159, 0.99
    )
    assert indices == [2 * r for r in range(nruns)]
    newest, seen_ts = {}, []
    for r, (keys, ts, full) in enumerate(columns):
        assert keys.shape == (per_run, 16) and len(ts) == len(full) == per_run
        blob = (tmp_path / file_name(2 * r, DATA_FILE_EXT)).read_bytes()
        assert len(blob) == int(full.sum())
        at, table_keys = 0, []
        for i in range(per_run):
            ks, vs, stamp = struct.unpack_from("<IIq", blob, at)
            assert (ks, 32 + vs, stamp) == (16, int(full[i]), int(ts[i]))
            assert 8 <= vs <= 159
            key = blob[at + 16:at + 32]
            assert key == bytes(keys[i])
            table_keys.append(key)
            if key not in newest or stamp > newest[key][0]:
                newest[key] = (stamp, int(full[i]))
            at += int(full[i])
        assert table_keys == sorted(set(table_keys))
        seen_ts.append((int(ts.min()), int(ts.max())))
    # Timestamps are places in the one stream: run r's above run r-1's.
    assert all(a[1] < b[0] for a, b in zip(seen_ts, seen_ts[1:]))
    assert seen_ts[-1][1] < writes
    count, nbytes = varlen_runs.model(
        *(np.concatenate(c) for c in zip(*columns))
    )
    assert count == len(newest) < nruns * per_run // 2
    assert nbytes == sum(full for _ts, full in newest.values())
    # The same seed, the same bytes.
    again = tmp_path / "again"
    again.mkdir()
    zipf_runs.build_runs(
        str(again), records, nruns, per_run, seed, 16, 8, 159, 0.99
    )
    for name in os.listdir(again):
        assert (again / name).read_bytes() == (tmp_path / name).read_bytes()


def test_the_configuration_keeps_the_sources_shapes_and_guarantees():
    cfg, wide = _load("configs", "zipf-64"), _load("configs", "wide-64")
    assert (cfg["strategy"], cfg["oracle_strategy"]) == ("device", "native")
    assert cfg["reduced"] == [] and cfg["architecture"] is None
    for shape in ("runs", "key_bytes", "value_bytes_min", "value_bytes_max"):
        assert cfg[shape] == wide[shape]
    assert cfg["runs"] * cfg["entries_per_run"] == wide["total_keys"]
    assert cfg["recordcount"] == 10_000_000
    assert cfg["zipfian_constant"] == 0.99
    assert cfg["tiny"] == {"recordcount": 64_000, "entries_per_run": 1_000}
    # The tiny size keeps the deployment's ratio of records to a table.
    assert cfg["recordcount"] * 1_000 == 64_000 * cfg["entries_per_run"]
    assert set(cfg["assumed"]) >= {
        "record", "recordcount", "entries_per_run", "runs", "deletes",
        "allocator",
    }
    assert "allocator" not in cfg
    assert "byte-identical" in cfg["guarantees"]
    assert "newest timestamp's version" in cfg["guarantees"]
    assert "BASELINE.json configs[3]" in cfg["source"]
    assert "workloada" in cfg["source"] and len(cfg["source"]) <= 200


@pytest.mark.parametrize("stem", TWINS)
def test_a_zipf_twin_differs_from_its_wide_file_in_name_and_cell(stem):
    """The eighteen twins read what the ``.wide`` files read, by the
    same reader and arguments, so the two cells' numbers compare."""
    zipf = _load("layer_metrics", stem + ".zipf")
    wide = _load("layer_metrics", stem + ".wide")
    assert zipf.pop("name") == stem + ".zipf"
    assert wide.pop("name") == stem + ".wide"
    assert zipf.pop("cells") == ["zipf-64.merge"]
    assert wide.pop("cells") == ["wide-64.merge"]
    assert zipf == wide


def test_the_new_metrics_read_counters_the_parent_may_lack_without_raising():
    """``dedup_drop_share.zipf`` reads ``shape.entries_out``, which this
    PR adds: on a program without it the reader gives None, and the
    line leaves the metric out."""
    import types

    from benchmark.readers import stats_share_left

    spec = _load("layer_metrics", "dedup_drop_share.zipf")
    shape = {"rows_real": 0, "entries_out": 0}
    after = {"rows_real": 1_000, "entries_out": 370}
    run = types.SimpleNamespace(
        facts={},
        stats_before={"node": {"compaction": {"shape": shape}}, "shards": []},
        stats_after={"node": {"compaction": {"shape": after}}, "shards": []},
    )
    assert stats_share_left.read(run, spec) == pytest.approx(63.0)
    del shape["entries_out"], after["entries_out"]
    assert stats_share_left.read(run, spec) is None
