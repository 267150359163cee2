"""Deployment ``wide_merge_job``: ``merge_job`` on runs of variable-length
records.  The same seam, arguments, output handling and counters
(``MergeJob``, by import); the run builder and the model are
``harness/varlen_runs.py``'s, and the model also gives the data file's
byte length, which every merge's output is held to.  The process's
allocator policy is the configuration's (``allocator``)."""

from __future__ import annotations

import ctypes
import os
import time

import numpy as np

from benchmark.deploy.merge_job import MergeJob
from benchmark.harness.common import BenchFailure, Run, say
from benchmark.harness.compiles import Compiles
from benchmark.harness import varlen_runs


# glibc's mallopt parameters (malloc.h).
MALLOPT = {"M_MMAP_THRESHOLD": -3}


def set_allocator(policy: dict) -> None:
    """The configuration's ``allocator`` through glibc's ``mallopt``: what
    an operator's unit file sets with ``MALLOC_MMAP_THRESHOLD_`` (the
    benchmark's command line is fixed, so the process sets it itself).
    A merge asks malloc for 64 run buffers of ~18 MB from two reader
    threads and for 8-80 MB arrays from the caller.  Left alone, glibc
    moves its mmap threshold up to 32 MB with every free, and whether
    a buffer is then memory already mapped or fresh pages depends on
    which arena a new thread lands in; on the chip's host a fresh GB
    costs ~1 s.  With the threshold named, it stays: every block over
    it is its own mapping, as ``major-10m``'s 120 MB blocks always are."""
    libc = ctypes.CDLL(None)
    for name, value in policy.items():
        if libc.mallopt(MALLOPT[name], int(value)) != 1:
            raise BenchFailure(f"mallopt refused {name} = {value}")


class WideMergeJob(MergeJob):
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
        self.indices, columns = varlen_runs.build_runs(
            self.dir, cfg["total_keys"], cfg["runs"], run.seed,
            cfg["key_bytes"], cfg["value_bytes_min"], cfg["value_bytes_max"],
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
            setup_oracle_s=oracle_s,
        )
        say(
            f"set-up: built {cfg['runs']} runs, {self.keys_in} keys in "
            f"{t1 - t0:.1f}s; numpy model {self.model_entries} entries, "
            f"{self.model_bytes} bytes in {t2 - t1:.1f}s; "
            f"{cfg['oracle_strategy']} oracle {oracle_s:.2f}s wall "
            f"[set-up, not a metric]"
        )

    def merge(self, strategy):
        """``MergeJob.merge``, and the data file's length against the
        model's, outside the wall.  A merge of the wrong length is
        given back with an entry count no model has (-1), so that the
        caller, which compares counts, counts it failed."""
        from dbeel_tpu.storage.entry import COMPACT_DATA_FILE_EXT, file_name

        wall, n = super().merge(strategy)
        self.data_bytes = os.path.getsize(os.path.join(
            self.dir, file_name(self.OUT_INDEX, COMPACT_DATA_FILE_EXT)
        ))
        if self.data_bytes != self.model_bytes:
            self.run.wrong.append(
                f"a merge's data file holds {self.data_bytes} bytes, "
                f"the model has {self.model_bytes}"
            )
            n = -1
        return wall, n


def start(run: Run) -> WideMergeJob:
    set_allocator(run.config["allocator"])
    return WideMergeJob(run)
