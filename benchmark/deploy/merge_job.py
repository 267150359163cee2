"""Deployment ``merge_job``: no server.  This process holds the chip and
calls the merge strategy at the seam the tree's compaction calls
(``CompactionStrategy.merge``, lsm_tree.py), on sorted runs made from the
seed.  The oracle is the host merge's output triplet and a numpy model of
the entry count."""

from __future__ import annotations

import os
import time

from benchmark.harness.common import BenchFailure, Run, say
from benchmark.harness.compiles import Compiles
from benchmark.harness import sstable_runs


class MergeJob:
    def __init__(self, run: Run) -> None:
        from dbeel_tpu import device

        cfg = run.config
        held = device.acquire()
        run.check_device(held["platform"], held["device_kind"], held["count"])
        self.compiles = Compiles()
        say(f"device: {held}; compile cache at {device.compile_cache_dir()}")

        from dbeel_tpu.ops.device_compaction import DeviceMergeStrategy
        from dbeel_tpu.storage.compaction import get_strategy

        if run.tiny:
            # Steered here, not by an option of the program: the tiny
            # input of the CPU rehearsal must still take the pipeline.
            DeviceMergeStrategy.PIPELINE_MIN_BYTES = 1 << 20
        self.dir = os.path.join(run.work, "runs")
        os.makedirs(self.dir)
        t0 = time.perf_counter()
        self.indices, keys = sstable_runs.build_runs(
            self.dir, cfg["total_keys"], cfg["runs"], run.seed,
            cfg["key_bytes"], cfg["value_bytes"],
        )
        self.keys_in = sum(len(k) for k in keys)
        t1 = time.perf_counter()
        self.model_entries = sstable_runs.model_entry_count(keys)
        del keys
        t2 = time.perf_counter()
        self.oracle = get_strategy(cfg["oracle_strategy"])
        oracle_s, oracle_n = self.merge(self.oracle)
        self.oracle_sha = self.take_output(True)
        if oracle_n != self.model_entries:
            raise BenchFailure(
                f"the host oracle wrote {oracle_n} entries, the numpy "
                f"model has {self.model_entries}"
            )
        self.strategy = get_strategy(cfg["strategy"])
        run.facts.update(
            setup_build_s=t1 - t0, setup_model_s=t2 - t1,
            setup_oracle_s=oracle_s,
        )
        say(
            f"set-up: built {cfg['runs']} runs, {self.keys_in} keys in "
            f"{t1 - t0:.1f}s; numpy model {self.model_entries} entries in "
            f"{t2 - t1:.1f}s; {cfg['oracle_strategy']} oracle "
            f"{oracle_s:.2f}s wall [set-up, not a metric]"
        )

    OUT_INDEX = 101

    def merge(self, strategy):
        """One whole merge, sstable files in to fsynced sstable files
        out: (wall seconds, entries written).  The output stays until
        ``take_output``."""
        from dbeel_tpu.storage.sstable import SSTable

        out_index = self.OUT_INDEX
        sources = [SSTable(self.dir, i, None) for i in self.indices]
        try:
            t0 = time.perf_counter()
            # The arguments lsm_tree.py's compaction passes, with the
            # bloom floor of chip_smoke.merge_and_hash (a bloom file is
            # written, so the triplet is whole).
            result = strategy.merge(
                sources, self.dir, out_index, None, False, 1
            )
            wall = time.perf_counter() - t0
        finally:
            for s in sources:
                s.close()
        return wall, result.entry_count

    def take_output(self, want_hash: bool):
        """Remove the last merge's output; its triplet's SHA-256 if
        asked.  Outside every timed wall."""
        return sstable_runs.hash_and_remove_output(
            self.dir, self.OUT_INDEX, want_hash
        )

    def counters(self) -> dict:
        """get_stats-shaped: the process-wide compaction block."""
        from dbeel_tpu.storage.compaction import compaction_stats

        return {"node": {"compaction": compaction_stats.stats()},
                "shards": []}

    def memory_peak_bytes(self) -> int:
        import jax

        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()
        ]
        return int(max(peaks))

    def stop(self) -> None:
        pass


def start(run: Run) -> MergeJob:
    return MergeJob(run)
