"""Compaction coalescer — many shards' merges, one device launch.

The BASELINE.json north star asks that "local_shard's compaction task
scheduler learns to coalesce per-shard compaction jobs into one TPU
launch".  Shards submit their staged merge columns here; jobs arriving
within a small window (or up to ``max_batch``) are padded to a common
(K, P) shape and dispatched as ONE ``vmap``-batched bitonic-merge kernel
call (ops/bitonic.py: merge_runs_prefix_batch_kernel).  Each shard gets
back its own permutation.

The packing itself lives in ``pack_jobs`` — the vmap-ready launch shape
ARCHITECTURE.md describes, computed independently of the device, so the
CPU backend of the tests executes the SAME batched shape (parity tested
against the ops/device_compaction.py twins).

One coalescer is shared per process (all shards of a node run on one
loop), matching the reference's one-TPU-per-host deployment picture.
"""

from __future__ import annotations

import asyncio
import logging
from typing import List, Optional, Tuple

import numpy as np

from ..ops import bitonic
from ..storage import columnar

log = logging.getLogger(__name__)


class PackedBatch:
    """One vmap-ready coalesced launch: every job's staged prefixes
    padded to the common (jobs, K, P) stack the batch kernel compiles
    for.  ``pad_frac`` measures the padding waste — the operator's
    answer to "is the window coalescing similar-shaped jobs"."""

    __slots__ = (
        "k", "p", "out_rows", "prefixes", "counts", "bases",
        "real_rows", "pad_frac",
    )

    def __init__(self, k, p, out_rows, prefixes, counts, bases,
                 real_rows, pad_frac) -> None:
        self.k = k
        self.p = p
        self.out_rows = out_rows
        self.prefixes = prefixes
        self.counts = counts
        self.bases = bases
        self.real_rows = real_rows
        self.pad_frac = pad_frac


def pack_jobs(jobs: List[Tuple]) -> PackedBatch:
    """Pack per-shard compaction jobs into ONE vmap-batched launch
    shape: K = max run count (next pow2), P = max run length (next
    pow2), every job's 8-byte key prefixes staged into a common
    (jobs, K, P) stack.  Pure host-side packing — the caller decides
    whether the batched kernel runs on the device or the CPU twin."""
    k = max(bitonic._pow2(max(1, len(rc))) for _, rc, *_ in jobs)
    p = max(
        bitonic._pow2(max(8, max(rc) if rc else 8))
        for _, rc, *_ in jobs
    )
    out_rows = 0
    staged = []
    real_rows = 0
    for cols, rc, *_ in jobs:
        prefixes, counts, bases, rows = bitonic.stage_prefixes(
            cols, rc, k=k, p=p
        )
        staged.append((prefixes, counts, bases))
        out_rows = max(out_rows, rows)
        # Actual staged rows, NOT stage_prefixes' 64Ki-bucketed
        # out_rows — pad_frac must measure real padding waste.
        real_rows += int(sum(rc))
    batch_prefixes = np.stack([s[0] for s in staged])
    batch_counts = np.stack([s[1] for s in staged])
    bases = [s[2] for s in staged]
    padded = len(jobs) * k * p
    pad_frac = round(1.0 - real_rows / padded, 4) if padded else 0.0
    return PackedBatch(
        int(k), int(p), int(out_rows), batch_prefixes, batch_counts,
        bases, real_rows, pad_frac,
    )


class CompactionCoalescer:
    def __init__(
        self, window_s: float = 0.01, max_batch: int = 16
    ) -> None:
        self.window_s = window_s
        self.max_batch = max_batch
        self._pending: List[Tuple] = []
        self._flush_task: Optional[asyncio.Task] = None
        self.launches = 0  # batched kernel launches (observability)
        self.jobs_coalesced = 0
        # Last launch's vmap shape + padding waste (observability:
        # whether the window actually coalesces, and how much of the
        # compiled (jobs, K, P) stack was real data).
        self.last_batch_jobs = 0
        self.last_batch_k = 0
        self.last_batch_p = 0
        self.last_pad_frac = 0.0

    async def submit(
        self, cols: columnar.MergeColumns, run_counts: List[int]
    ) -> np.ndarray:
        """Returns the merged permutation for this job (8B-prefix order;
        ties resolved by the caller via
        columnar.fixup_and_dedup_prefix)."""
        if len(cols) == 0:
            return np.zeros(0, np.int64)
        loop = asyncio.get_event_loop()
        fut: asyncio.Future = loop.create_future()
        self._pending.append((cols, run_counts, fut))
        if len(self._pending) >= self.max_batch:
            self._trigger()
        elif self._flush_task is None:
            self._flush_task = asyncio.ensure_future(
                self._flush_after_window()
            )
        return await fut

    def _trigger(self) -> None:
        if self._flush_task is not None:
            self._flush_task.cancel()
            self._flush_task = None
        asyncio.ensure_future(self._flush())

    async def _flush_after_window(self) -> None:
        try:
            await asyncio.sleep(self.window_s)
        except asyncio.CancelledError:
            return
        self._flush_task = None
        await self._flush()

    async def _flush(self) -> None:
        jobs, self._pending = self._pending, []
        if not jobs:
            return
        try:
            batch = pack_jobs(jobs)

            def run() -> np.ndarray:
                return np.asarray(
                    bitonic.merge_runs_prefix_batch_kernel(
                        batch.prefixes, batch.counts, batch.out_rows
                    )
                )

            packed = await asyncio.get_event_loop().run_in_executor(
                None, run
            )
            self.launches += 1
            self.jobs_coalesced += len(jobs)
            self.last_batch_jobs = len(jobs)
            self.last_batch_k = batch.k
            self.last_batch_p = batch.p
            self.last_pad_frac = batch.pad_frac

            shift = np.uint32(batch.p.bit_length() - 1)
            mask = np.uint32(batch.p - 1)
            for j, (cols, _rc, fut) in enumerate(jobs):
                n = len(cols)
                row = packed[j, :n]
                run_ids = (row >> shift).astype(np.int64)
                pos = (row & mask).astype(np.int64)
                perm = batch.bases[j][run_ids] + pos
                if not fut.done():
                    fut.set_result(perm)
        except Exception as e:
            log.exception("coalesced merge launch failed")
            for _, _, fut in jobs:
                if not fut.done():
                    fut.set_exception(e)


_default: Optional[CompactionCoalescer] = None


def default_coalescer() -> CompactionCoalescer:
    global _default
    if _default is None:
        _default = CompactionCoalescer()
    return _default


def stats() -> "dict | None":
    """Process-wide coalescer counters for ``get_stats`` (None until
    the first device merge constructs the singleton)."""
    if _default is None:
        return None
    return {
        "launches": _default.launches,
        "jobs_coalesced": _default.jobs_coalesced,
        "last_batch_jobs": _default.last_batch_jobs,
        "last_batch_k": _default.last_batch_k,
        "last_batch_p": _default.last_batch_p,
        "last_pad_frac": _default.last_pad_frac,
    }


class CoalescedDeviceMergeStrategy:
    """CompactionStrategy whose sort rides the shared coalescer.
    Exposes ``merge_async`` (the LSM tree prefers it when present) so
    concurrent shard compactions rendezvous in one launch."""

    name = "coalesced"
    # Intra-merge latency-class hook (see CompactionStrategy.throttle;
    # this class is duck-typed, not a subclass, so it needs its own).
    throttle = None
    # GC-grace cutoff (see CompactionStrategy.tombstone_drop_before) —
    # same duck-typing story: LSMTree.compact() stamps it, but a
    # directly-constructed strategy must default to "keep tombstones".
    tombstone_drop_before = None
    # Index DDL (see CompactionStrategy.index_fields), likewise.
    index_fields = None

    def __init__(
        self, coalescer: Optional[CompactionCoalescer] = None
    ) -> None:
        self.coalescer = coalescer or default_coalescer()

    def _single(self):
        """The one-merge device strategy, carrying what
        LSMTree.compact stamped on this one."""
        from ..ops.device_compaction import DeviceMergeStrategy

        s = DeviceMergeStrategy()
        s.throttle = self.throttle
        s.tombstone_drop_before = self.tombstone_drop_before
        s.index_fields = self.index_fields
        return s

    # Sync fallback (e.g. recovery paths before a loop exists).
    def merge(self, *args, **kwargs):
        return self._single().merge(*args, **kwargs)

    async def merge_async(
        self,
        sources,
        dir_path,
        output_index,
        cache,
        keep_tombstones,
        bloom_min_size,
    ):
        from ..storage.compaction import (
            compaction_stats,
            write_output_columnar,
        )

        loop = asyncio.get_event_loop()

        # Big merges: the partitioned native pipeline (off-loop) beats
        # any coalesced single-shot launch; the coalescer exists for
        # many small concurrent per-shard merges.
        result = await loop.run_in_executor(
            None,
            self._single().merge_pipeline,
            sources,
            dir_path,
            output_index,
            keep_tombstones,
            bloom_min_size,
        )
        if result is not None:
            return result

        cols = await loop.run_in_executor(
            None, columnar.load_columns, sources
        )
        run_counts = (
            np.bincount(cols.src).tolist() if len(cols) else []
        )
        perm = await self.coalescer.submit(cols, run_counts)

        def finish():
            from ..storage.compaction import drop_tombstones_mask

            p, keep = columnar.fixup_and_dedup_prefix(
                cols, perm, words=2
            )
            if not keep_tombstones:
                keep = keep & ~drop_tombstones_mask(
                    cols.is_tombstone[p],
                    cols.timestamp[p],
                    self.tombstone_drop_before,
                )
            order = p[keep]
            result = write_output_columnar(
                cols, order, dir_path, output_index, cache,
                bloom_min_size, throttle=self.throttle,
                index_fields=self.index_fields,
            )
            compaction_stats.note_path("coalesced")
            return result

        return await loop.run_in_executor(None, finish)
