"""Deployment ``zipf_merge_job``: ``wide_merge_job`` on the tables an
update stream leaves (``harness/zipf_runs.py``): keys repeat across the
64 runs, so a merge writes far fewer entries than it reads.  The seam,
arguments, output handling, counters and both checks of every merge
(entry count and data-file length against ``varlen_runs.model``) are
``WideMergeJob``'s, by inheritance; only the set-up differs, which has
no hook for the run builder and is written out here a third time
(PERF.md, Open questions).  The process's allocator is left as glibc
ships it: the configuration names none."""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.deploy.wide_merge_job import WideMergeJob
from benchmark.harness.common import BenchFailure, Run, say
from benchmark.harness.compiles import Compiles
from benchmark.harness import varlen_runs, zipf_runs


class ZipfMergeJob(WideMergeJob):
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
        self.indices, columns, writes = zipf_runs.build_runs(
            self.dir, cfg["recordcount"], cfg["runs"], cfg["entries_per_run"],
            run.seed, cfg["key_bytes"], cfg["value_bytes_min"],
            cfg["value_bytes_max"], cfg["zipfian_constant"],
        )
        self.keys_in = sum(len(keys) for keys, _ts, _full in columns)
        t1 = time.perf_counter()
        self.model_entries, self.model_bytes = varlen_runs.model(
            *(np.concatenate(column) for column in zip(*columns))
        )
        del columns
        t2 = time.perf_counter()
        self.oracle = get_strategy(cfg["oracle_strategy"])
        oracle_s, oracle_n = self.merge(self.oracle)
        self.oracle_sha = self.take_output(True)
        if oracle_n != self.model_entries:
            raise BenchFailure(
                f"the host oracle wrote {oracle_n} entries in "
                f"{self.data_bytes} bytes, the numpy model has "
                f"{self.model_entries} in {self.model_bytes}"
            )
        self.strategy = get_strategy(cfg["strategy"])
        run.facts.update(
            setup_build_s=t1 - t0, setup_model_s=t2 - t1,
            setup_oracle_s=oracle_s, updates_drawn=writes,
            model_entries=self.model_entries,
        )
        say(
            f"set-up: drew {writes} updates into {cfg['runs']} runs, "
            f"{self.keys_in} keys in {t1 - t0:.1f}s; numpy model "
            f"{self.model_entries} entries ("
            f"{100.0 * (1 - self.model_entries / self.keys_in):.3f} % "
            f"dropped), {self.model_bytes} bytes in {t2 - t1:.1f}s; "
            f"{cfg['oracle_strategy']} oracle {oracle_s:.2f}s wall "
            f"[set-up, not a metric]"
        )


def start(run: Run) -> ZipfMergeJob:
    return ZipfMergeJob(run)
