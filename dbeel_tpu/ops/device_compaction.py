"""DeviceMergeStrategy — compaction with the sort+dedup on the TPU.

Drops into the CompactionStrategy seam (storage/compaction.py): the host
stages columns (storage/columnar.py), the device runs the batched
lexicographic sort + duplicate marking (ops/merge.py), and the host
finishes with the variable-length record gather and file writes.  Output
bytes are identical to the heap and columnar strategies (golden-tested).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from ..storage import columnar
from ..storage.compaction import (
    ColumnarMergeStrategy,
    compaction_stats,
)
from .bitonic import device_merge_prefix_order, device_merge_sorted_runs
from .spans import Stages


class DeviceMergeStrategy(ColumnarMergeStrategy):
    """Default device path: the transfer-minimal 8-byte-prefix bitonic
    merge (ops/bitonic.py) + host tie refinement.  Fully general — any
    prefix tie (same key, shared prefix, long keys) is re-ordered and
    dedup-confirmed on the host with full-key compares.  Keyspaces where
    many keys share one 8-byte prefix (e.g. everything under b"user:...")
    would push that refinement into interpreted Python, so past a tie
    threshold the merge re-routes to the full 16-byte-column device path
    instead of paying the cliff."""

    name = "device"

    # Above this fraction of adjacent 8-byte-prefix ties, re-sort on the
    # device with full key columns rather than fix up row-by-row on host.
    TIE_FALLBACK_FRACTION = 0.02

    # Merges below this input size stay on the single-shot path: they
    # are fast anyway and keep the page-mirroring write (small fresh
    # SSTables warm in cache when a cache is supplied).  Larger merges
    # go through the O_DIRECT native pipeline, which handles tie-heavy
    # keyspaces internally (vectorized fixup) and declines (None) only
    # when an equal-prefix group exceeds the kernel rows.
    PIPELINE_MIN_BYTES = 64 << 20

    def __init__(self, mesh=None) -> None:
        # A 1-D mesh shards the pipeline's launch batch over its
        # devices (what ``auto`` passes on a multi-chip host);
        # single-shot merges stay on one device either way.
        self.mesh = mesh

    def merge(
        self,
        sources,
        dir_path,
        output_index,
        cache,
        keep_tombstones,
        bloom_min_size,
    ):
        """Partitioned native pipeline for big merges; otherwise the
        single-shot path with per-run upload/read overlap."""
        result = self.merge_pipeline(
            sources, dir_path, output_index, keep_tombstones,
            bloom_min_size,
        )
        if result is not None:
            return result
        return self._merge_single_shot(
            sources,
            dir_path,
            output_index,
            cache,
            keep_tombstones,
            bloom_min_size,
        )

    def merge_pipeline(
        self,
        sources,
        dir_path,
        output_index,
        keep_tombstones,
        bloom_min_size,
    ):
        """The one place a merge is sent to the partitioned native
        pipeline (ops/pipeline.py: O_DIRECT reads, per-partition kernel
        launches, C++ gather + O_DIRECT streaming writes, all stages
        overlapped).  None where the merge is below the threshold, or
        the pipeline declines on the data (counted there)."""
        from .pipeline import max_partition_rows, pipeline_merge

        total = sum(getattr(s, "data_size", 0) for s in sources)
        # The single-shot kernel takes whole runs as its rows, so a
        # merge of many tiny records can ask for more rows than one
        # launch may hold however few its bytes: the pipeline cuts
        # those into partitions too.
        longest = max(
            (getattr(s, "entry_count", 0) for s in sources), default=0
        )
        if (
            total < self.PIPELINE_MIN_BYTES
            and longest <= max_partition_rows(len(sources))
        ):
            return None
        return pipeline_merge(
            sources,
            dir_path,
            output_index,
            keep_tombstones,
            bloom_min_size,
            mesh=self.mesh,
            throttle=self.throttle,
            tombstone_drop_before=self.tombstone_drop_before,
        )

    def _merge_single_shot(
        self,
        sources,
        dir_path,
        output_index,
        cache,
        keep_tombstones,
        bloom_min_size,
    ):
        """Per-run device uploads overlap the disk reads (each file
        read once), then the shared finish path."""
        from ..storage.compaction import write_output_columnar
        from .bitonic import device_merge_prefix_order_pipelined

        # Sequential stages (ops/spans.py), under
        # ``get_stats.compaction.stages.single_shot``: ``order`` is
        # read + upload + kernel + read-back, ``write`` the output
        # triplet with its bloom and sidecar.
        with Stages("single_shot", "order") as at:
            perm, pieces = device_merge_prefix_order_pipelined(sources)
            at.to("assemble")
            cols = columnar.assemble_columns(pieces)
            self._tick()
            at.to("refine")
            perm, keep = self._refine(cols, perm)
            self._tick()
            if not keep_tombstones:
                from ..storage.compaction import drop_tombstones_mask

                keep = keep & ~drop_tombstones_mask(
                    cols.is_tombstone[perm],
                    cols.timestamp[perm],
                    self.tombstone_drop_before,
                )
            at.to("write")
            result = write_output_columnar(
                cols, perm[keep], dir_path, output_index, cache,
                bloom_min_size, throttle=self.throttle,
                index_fields=self.index_fields,
            )
        compaction_stats.note_path("single_shot")
        return result

    def _refine(self, cols, perm):
        if len(cols) > 1:
            kw = cols.key_words[perm]
            ties = int(
                np.all(kw[1:, :2] == kw[:-1, :2], axis=1).sum()
            )
            if ties > max(
                1024, self.TIE_FALLBACK_FRACTION * len(cols)
            ):
                return DeviceFullMergeStrategy.sort_and_dedup(
                    self, cols
                )
        return columnar.fixup_and_dedup_prefix(cols, perm, words=2)

    def sort_and_dedup(
        self, cols: columnar.MergeColumns
    ) -> Tuple[np.ndarray, np.ndarray]:
        # Non-pipelined entry (pre-staged columns, e.g. the coalescer).
        run_counts = (
            np.bincount(cols.src).tolist() if len(cols) else []
        )
        perm = device_merge_prefix_order(cols, run_counts)
        return self._refine(cols, perm)


class DeviceFullMergeStrategy(ColumnarMergeStrategy):
    """All-columns device path: ships the full 9-column stack (16B key
    prefix, key_len, ~ts, ~src, idx) and orders everything on-device.
    More device work and ~4.5x the transfer volume of the prefix path —
    preferable when the device link is PCIe-fast and keys cluster under
    shared 8-byte prefixes."""

    name = "device_full"
    path = "device_full"

    def sort_and_dedup(
        self, cols: columnar.MergeColumns
    ) -> Tuple[np.ndarray, np.ndarray]:
        run_counts = (
            np.bincount(cols.src).tolist() if len(cols) else []
        )
        perm, same = device_merge_sorted_runs(cols, run_counts)
        # Keys longer than the 16-byte device prefix both alias (equal
        # prefix+len ≠ equal key) and mis-order (the length column is not
        # lexicographic across different-length same-prefix keys): any
        # long key means the host re-sorts prefix-tie blocks and redoes
        # the dedup mask.  No-op when all keys fit the prefix.
        if (cols.key_size > columnar.KEY_PREFIX_BYTES).any():
            perm = columnar.fixup_long_key_ties(cols, perm)
            return perm, columnar.dedup_mask(cols, perm)
        return perm, ~same
