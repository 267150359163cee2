"""The yardstick's arithmetic on inputs made by hand: due-time latency and
lateness, the seeded key popularity, the schedule, interval union and idle
share, the xplane reader on a trimmed recording from the chip, and the
readers of the per-layer metrics."""

import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, REPO)

from benchmark.generators import point_open_loop as pol  # noqa: E402
from benchmark.harness import latency, peaks, xplane  # noqa: E402
from benchmark.harness.docs import Docs, zipfian  # noqa: E402
from benchmark.harness import sstable_runs  # noqa: E402
from benchmark.readers import (  # noqa: E402
    fact, kernel_roofline, stats_mean, stats_ratio, trace_idle,
    trace_kernel_s,
)

FIXTURE = os.path.join(
    REPO, "benchmark", "fixtures", "major_trace_trimmed.textproto"
)


# -- open-loop arithmetic ----------------------------------------------


def test_a_stall_is_charged_to_every_operation_that_was_due_in_it():
    # 100 operations due 10 ms apart, each served in 1 ms, except that
    # the server stalls from t=0.5 s to t=0.7 s: the 20 operations due
    # in the stall are all answered at 0.701 s.
    due = np.arange(100) * 0.010
    done = due + 0.001
    stalled = (due >= 0.5) & (due < 0.7)
    done[stalled] = 0.701
    ms = latency.due_latency_ms(due, done)
    assert latency.percentile(ms, 50) == pytest.approx(1.0)
    # From the due time the stall is 20 % of the sample: p95 sees it.
    # (Timed from the launch of a closed loop it would be ONE slow op.)
    assert latency.percentile(ms, 95) == pytest.approx(151.0)
    assert latency.percentile(ms, 100) == pytest.approx(201.0)
    launch = due.copy()
    launch[10:15] += 0.004  # the generator itself ran 4 ms late, 5 times
    late = latency.lateness_ms(due, launch)
    assert latency.percentile(late, 95) == pytest.approx(0.0)
    assert latency.percentile(late, 96) == pytest.approx(4.0)
    assert late.max() == pytest.approx(4.0)


def test_percentile_is_nearest_rank():
    v = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert latency.percentile(v, 50) == 3.0
    assert latency.percentile(v, 95) == 5.0
    assert latency.percentile(v, 20) == 1.0
    with pytest.raises(ValueError):
        latency.percentile([], 50)


def test_summarise_counts_failures_at_the_top_of_the_tail():
    n = 200
    ops = {
        "due": np.arange(n) * 0.01,
        "launch": np.arange(n) * 0.01,
        "done": np.arange(n) * 0.01 + 0.002,
        "status": np.zeros(n, dtype=np.uint8),
        "kind": np.zeros(n, dtype=np.uint8),  # all reads
    }
    ops["status"][:20] = pol.REFUSED  # 10 %: p95 must be a failure
    e2e, facts, _lines, counts = pol.summarise(ops, 0.0, 2.0, 5.0)
    assert counts[pol.OK] == 180 and counts[pol.REFUSED] == 20
    assert e2e["ops_ok_per_s"] == pytest.approx(90.0)
    assert facts["read_p50_ms"] == pytest.approx(2.0)
    assert e2e["read_p95_ms"] > 5000.0  # charged to the drain's end
    assert "update_p95_ms" not in e2e


# -- traffic -------------------------------------------------------------


def test_zipfian_head_mass_and_determinism():
    n, theta = 300_000, 0.99
    a = zipfian(np.random.default_rng(7), n, theta, 400_000)
    b = zipfian(np.random.default_rng(7), n, theta, 400_000)
    assert (a == b).all() and a.min() == 0 and a.max() < n
    zetan = (1.0 / np.arange(1, n + 1) ** theta).sum()
    assert (a == 0).mean() == pytest.approx(1.0 / zetan, rel=0.03)
    assert (a == 1).mean() == pytest.approx(0.5**theta / zetan, rel=0.04)
    head = (1.0 / np.arange(1, 101) ** theta).sum() / zetan
    assert (a < 100).mean() == pytest.approx(head, rel=0.03)
    c = zipfian(np.random.default_rng(8), n, theta, 1000)
    assert (c != a[:1000]).any()


TRAFFIC = {
    "proportions": {"read": 0.5, "update": 0.5, "insert": 0, "scan": 0},
    "distribution": {"kind": "zipfian", "constant": 0.99},
}


def test_schedule_is_the_seeds_and_offers_the_same_count_to_every_seed():
    stored = np.zeros(1000, dtype=np.int64)
    stored[0] = 7  # the hottest key was at version 7 before
    one = pol.build_schedule(5, TRAFFIC, 1000, (1.0, 4.0), 500.0, stored)
    same = pol.build_schedule(5, TRAFFIC, 1000, (1.0, 4.0), 500.0, stored)
    other = pol.build_schedule(6, TRAFFIC, 1000, (1.0, 4.0), 500.0, stored)
    for x, y in zip(one, same):
        assert (x == y).all()
    due, kind, ordinal, version, base = one
    assert len(due) == len(other[0]) == 2500
    assert ((due >= 1.0) & (due < 5.0)).sum() == 2000  # the window's
    assert (np.diff(due) >= 0).all()
    assert (due != other[0]).any()
    assert 0.45 < (kind == pol.UPDATE).mean() < 0.55
    # One key's updates carry consecutive versions, in due order, on
    # from what was stored; reads carry none.
    hot = np.flatnonzero((ordinal == 0) & (kind == pol.UPDATE))
    assert len(hot) > 5
    assert version[hot].tolist() == list(range(8, 8 + len(hot)))
    assert (version[kind == pol.READ] == 0).all()
    assert (base[ordinal == 0] == 7).all() and (base[ordinal != 0] == 0).all()
    with pytest.raises(ValueError):
        bad = dict(TRAFFIC, proportions={"read": 0.5, "scan": 0.5})
        pol.build_schedule(5, bad, 1000, (1.0,), 100.0, stored)


def test_a_records_version_is_recoverable_from_field0():
    docs = Docs(3000000019)
    assert docs.key(5) == Docs(3000000019).key(5) != docs.key(6)
    assert len(docs.key(5)) == 24 and docs.key(5).startswith("user")
    doc = docs.doc(12, 3)
    assert sorted(doc) == sorted(f"field{j}" for j in range(10))
    assert all(len(v) == 100 for v in doc.values())
    assert docs.version_of(12, doc, 0, 5) == 3
    assert docs.version_of(12, doc, 4, 5) is None
    assert docs.version_of(12, None, 0, 5) is None
    assert docs.doc(12, 4) != doc != Docs(1).doc(12, 3)


def test_runs_are_sorted_and_the_model_counts_distinct_keys(tmp_path):
    indices, keys = sstable_runs.build_runs(
        str(tmp_path), 4000, 4, seed=9, key_bytes=16, value_bytes=64
    )
    assert indices == [0, 2, 4, 6] and all(len(k) == 1000 for k in keys)
    for k in keys:
        as_bytes = [bytes(row) for row in k]
        assert as_bytes == sorted(as_bytes)
    assert sstable_runs.model_entry_count(keys) == 4000
    assert sstable_runs.model_entry_count(keys + [keys[0][:10]]) == 4000
    again = sstable_runs.build_runs(
        str(tmp_path), 4000, 4, seed=9, key_bytes=16, value_bytes=64
    )[1]
    assert all((a == b).all() for a, b in zip(keys, again))


# -- trace reduction -------------------------------------------------------


def test_interval_union_gaps_and_idle_share():
    spans = [(0.0, 1.0), (0.5, 1.5), (3.0, 4.0), (3.2, 3.4)]
    assert xplane.union_seconds(spans) == pytest.approx(2.5)
    assert xplane.union_seconds([]) == 0.0
    found = xplane.gaps(spans, 0.0, 5.0)
    assert [(round(a, 6), round(b, 6)) for a, b, _i in found] == [
        (1.5, 1.5), (4.0, 1.0),
    ]
    assert found[0][2] == 1 and found[1][2] == 2  # the span before each
    assert xplane.idle_share(2.5, 5.0) == pytest.approx(50.0)


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return xplane.read_text_trace(f.read())


def test_xplane_reader_on_a_trimmed_recording_from_the_v5e(recorded):
    # Two whole merges of major-10m.merge, traced on the chip in PR 24:
    # 3 launches each of the one-word packed batch kernel, ~0.14 s each.
    assert os.path.getsize(FIXTURE) < 1 << 20
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    match = ["merge_runs_prefix32_packed_batch_kernel"]
    assert recorded.module_count(match) == 6
    assert recorded.module_seconds(match) == pytest.approx(0.886746, abs=1e-5)
    assert recorded.module_seconds(["no_such_kernel"]) == 0.0
    assert recorded.window_s == pytest.approx(4.7013, abs=1e-3)
    assert 0.0 < recorded.busy_s < recorded.window_s
    longer = xplane.read_text_trace(open(FIXTURE).read(), window_s=8.0)
    assert longer.window_s == 8.0
    down = recorded.breakdown()
    assert 1 <= len(down["device_ops"]) <= 10
    assert 1 <= len(down["idle_gaps"]) <= 10
    # The longest gap is the host-only rest of the first merge.
    name, seconds = down["idle_gaps"][0]
    assert name.startswith("after:jit_merge_runs_prefix32")
    assert seconds == pytest.approx(3.8145, abs=1e-3)


# -- readers -----------------------------------------------------------------


def _run(**kw):
    run = types.SimpleNamespace(
        facts={}, stats_before=None, stats_after=None, trace_summary=None,
        launches=[], device={"kind": "TPU v5 lite"},
    )
    run.__dict__.update(kw)
    return run


def test_readers_return_nothing_where_there_is_nothing_to_read():
    run = _run()
    assert fact.read(run, {"fact": "x"}) is None
    assert trace_idle.read(run, {}) is None
    assert trace_kernel_s.read(run, {"match": ["m"]}) is None
    assert kernel_roofline.read(run, {"match": ["m"]}) is None
    spec = {"numerator": ["cache.hits"], "denominator": ["cache.misses"]}
    assert stats_ratio.read(run, spec) is None
    assert stats_mean.read(run, {"path": "metrics.requests.get"}) is None


def _snap(hits, misses, written, count, mean):
    shard = {
        "cache": {"hits": hits, "misses": misses},
        "compaction": {"bytes_written": written},
        "metrics": {"requests": {"get": {"count": count, "mean_us": mean}}},
    }
    return {"node": shard, "shards": [shard, shard]}


def test_stats_readers_take_window_deltas():
    run = _run(
        stats_before=_snap(10, 10, 1000, 100, 50.0),
        stats_after=_snap(40, 20, 5000, 300, 40.0),
        facts={"acked_user_bytes": 2000.0},
    )
    share = stats_ratio.read(run, {
        "numerator": ["cache.hits"],
        "denominator": ["cache.hits", "cache.misses"], "scale": 100.0,
    })
    assert share == pytest.approx(75.0)  # 60 hits of 80 probes, 2 shards
    amp = stats_ratio.read(run, {
        "numerator": ["node.compaction.bytes_written"],  # read ONCE
        "denominator": ["fact:acked_user_bytes"],
    })
    assert amp == pytest.approx(2.0)
    missing = stats_ratio.read(run, {
        "numerator": ["cache.no_such"], "denominator": ["cache.hits"],
    })
    assert missing is None
    # (300 x 40 - 100 x 50) / 200 = 35 us in the window.
    mean = stats_mean.read(run, {"path": "metrics.requests.get"})
    assert mean == pytest.approx(35.0)


def test_roofline_is_bytes_over_bandwidth_over_kernel_time(recorded):
    match = ["merge_runs_prefix32_packed_batch_kernel"]
    launch = ("merge_runs_prefix32_packed_batch_kernel", 16_777_344, 2_097_152)
    run = _run(trace_summary=recorded, launches=[launch] * 6)
    got = kernel_roofline.read(run, {"match": match})
    least_s = 6 * (16_777_344 + 2_097_152) / 819e9
    assert got == pytest.approx(100.0 * least_s / 0.886746, rel=1e-4)
    assert got < 100.0
    # A launch the trace did not see: bytes and seconds out of step.
    run.launches = [launch] * 5
    assert kernel_roofline.read(run, {"match": match}) is None
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary", "hbm_bytes_per_s")
    assert trace_idle.read(run, {}) == pytest.approx(
        xplane.idle_share(recorded.busy_s, recorded.window_s)
    )
