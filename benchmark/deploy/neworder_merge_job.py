"""Deployment ``neworder_merge_job``: ``wide_merge_job`` on the tables a
collection used as a queue leaves (``harness/neworder_runs.py``: TPC-C's
NEW-ORDER table, rows inserted and deleted in FIFO order), merged as the
tree's bottom compaction merges them: tombstones dropped, but for those
the gc-grace cutoff still holds.  The cutoff is set on the strategy
before ``merge``, as ``LSMTree.compact`` sets it
(``strategy.tombstone_drop_before``), on the oracle and on the device
strategy alike.  The seam, arguments, output handling, counters and both
checks of every merge (entry count and data-file length against the
model) are ``WideMergeJob``'s, by inheritance; the set-up is written out
here once more (PERF.md, Open questions).  No allocator policy.  A
program whose pipeline declines the tree fails the run in set-up
(``pipeline_or_fail``)."""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.deploy.wide_merge_job import WideMergeJob
from benchmark.harness.common import BenchFailure, Run, say
from benchmark.harness.compiles import Compiles
from benchmark.harness import neworder_runs


class NewOrderMergeJob(WideMergeJob):
    def __init__(self, run: Run) -> None:
        from dbeel_tpu import device

        cfg = run.config
        self.run = run
        held = device.acquire()
        run.check_device(held["platform"], held["device_kind"], held["count"])
        self.compiles = Compiles()
        say(f"device: {held}; compile cache at {device.compile_cache_dir()}")

        from dbeel_tpu.ops.device_compaction import DeviceMergeStrategy
        from dbeel_tpu.storage.compaction import get_strategy

        if run.tiny:
            # As merge_job: steered here, not by an option of the
            # program, so that the tiny input still takes the pipeline.
            DeviceMergeStrategy.PIPELINE_MIN_BYTES = 1 << 20
        self.dir = os.path.join(run.work, "runs")
        os.makedirs(self.dir)
        t0 = time.perf_counter()
        self.indices, columns, writes, cutoff = neworder_runs.build_runs(
            self.dir, cfg["warehouses"], cfg["runs"], cfg["entries_per_run"],
            run.seed, cfg["grace_runs"],
        )
        t1 = time.perf_counter()
        model = neworder_runs.model(
            *(np.concatenate(column) for column in zip(*columns)), cutoff
        )
        del columns
        self.keys_in = model["entries_in"]
        self.model_entries = model["entries_out"]
        self.model_bytes = model["bytes_out"]
        t2 = time.perf_counter()
        self.oracle = get_strategy(cfg["oracle_strategy"])
        self.oracle.tombstone_drop_before = cutoff
        oracle_s, oracle_n = self.merge(self.oracle)
        self.oracle_sha = self.take_output(True)
        if oracle_n != self.model_entries:
            raise BenchFailure(
                f"the host oracle wrote {oracle_n} entries in "
                f"{self.data_bytes} bytes, the numpy model has "
                f"{self.model_entries} in {self.model_bytes}"
            )
        self.strategy = get_strategy(cfg["strategy"])
        self.strategy.tombstone_drop_before = cutoff
        self.pipeline_or_fail()
        run.facts.update(
            setup_build_s=t1 - t0, setup_model_s=t2 - t1,
            setup_oracle_s=oracle_s, writes_drawn=writes, cutoff=cutoff,
            **{"model_" + name: count for name, count in model.items()},
        )
        say(
            f"set-up: drew {writes} writes into {cfg['runs']} runs, "
            f"{self.keys_in} keys in {t1 - t0:.1f}s, "
            f"{model['tombstones_in']} tombstones ("
            f"{100.0 * model['tombstones_in'] / self.keys_in:.5f} %); "
            f"cutoff {cutoff}; numpy model {self.model_entries} entries ("
            f"{100.0 * (1 - self.model_entries / self.keys_in):.5f} % "
            f"dropped), {model['tombstones_kept']} tombstones kept ("
            f"{100.0 * model['tombstones_kept'] / max(1, model['tombstones_in']):.5f}"
            f" %), {self.model_bytes} bytes in {t2 - t1:.1f}s; "
            f"{cfg['oracle_strategy']} oracle {oracle_s:.2f}s wall "
            f"[set-up, not a metric]"
        )

    def pipeline_or_fail(self) -> None:
        """The cell measures the partitioned pipeline on this tree.  Six
        of its tables were loaded in key order and hold their entries
        in a sliver of the keyspace each; a program whose plan declines
        such a tree would send every merge of the window to the
        single-shot path (one launch over 64 x 2^18 rows, the numpy
        fix-up of ten million ties), which no stage metric of the cell
        reads: it cannot run the configuration, and the run fails here,
        soon, before any such merge is tried."""
        from dbeel_tpu.storage.sstable import SSTable

        sources = [SSTable(self.dir, i, None) for i in self.indices]
        try:
            result = self.strategy.merge_pipeline(
                sources, self.dir, self.OUT_INDEX, False, 1
            )
        finally:
            for s in sources:
                s.close()
        if result is None:
            raise BenchFailure(
                "the program's pipeline declines this tree (tables "
                "loaded in key order do not spread over the keyspace): "
                "it cannot run configuration neworder-64"
            )
        self.take_output(False)


def start(run: Run) -> NewOrderMergeJob:
    return NewOrderMergeJob(run)
