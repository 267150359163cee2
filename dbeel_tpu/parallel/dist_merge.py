"""Distributed compaction merge over a device mesh (sample sort).

Coalesces many shards' compaction batches into ONE sharded device launch
(the BASELINE.json north star): entries are sharded over the ``shards``
mesh axis, and a classic distributed sample sort runs under ``shard_map``
with XLA collectives over ICI —

  1. local sort of each device's slice (lax.sort, 8 key operands)
  2. splitter selection: evenly-spaced local samples → ``all_gather`` →
     identical global splitters on every device
  3. bucket partition + ``all_to_all`` exchange (fixed-capacity rows,
     sentinel-padded; overflow is detected and reported so the caller can
     fall back to the single-device kernel — it never corrupts output)
  4. final local sort of the received key range + duplicate marking

Partitioning is by the first 4 key bytes (word k0); entries with equal
full keys share k0, so duplicates always land on the same device and
dedup needs no cross-device boundary pass.  Heavy first-word skew only
costs balance, never correctness (overflow triggers the fallback).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..storage import columnar
from ..ops import bitonic

_SENTINEL = jnp.uint32(0xFFFFFFFF)
_NUM_SAMPLES = 32  # per-device splitter samples

NUM_COLS = 9  # k0..k3, key_len, ~ts_hi, ~ts_lo, ~src, idx


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _local_sort(stack: jnp.ndarray):
    """Sort rows of an (M, NUM_COLS) stack by the first 8 columns via the
    bitonic network (lax.sort's multi-key TPU comparator is pathological;
    see ops/bitonic.py).  Pads to a power of two with sentinel rows that
    sort last, then slices back.  Returns (sorted, same-key flags)."""
    m = stack.shape[0]
    p = _pow2(m)
    if p != m:
        pad = jnp.full((p - m, NUM_COLS), _SENTINEL)
        stack = jnp.concatenate([stack, pad], axis=0)
    out, same = bitonic.sort_stack_kernel(stack)
    return out[:m], same[:m]


def _per_device(stack: jnp.ndarray, capacity: int, n_dev: int):
    """shard_map body. stack: (M, NUM_COLS) local slice."""
    m = stack.shape[0]
    local, _ = _local_sort(stack)  # (M, NUM_COLS), sorted

    # -- splitters: sample k0 evenly, gather everywhere ---------------
    k0 = local[:, 0]
    sample_pos = (
        jnp.arange(_NUM_SAMPLES) * m // _NUM_SAMPLES
    )
    samples = k0[sample_pos]  # (S,)
    all_samples = jax.lax.all_gather(
        samples, "shards", tiled=True
    )  # (n_dev*S,)
    all_samples = jnp.sort(all_samples)
    step = all_samples.shape[0] // n_dev
    splitters = all_samples[step - 1 :: step][: n_dev - 1]  # (n_dev-1,)

    # -- bucket + scatter into fixed-capacity send rows ---------------
    bucket = jnp.sum(
        k0[:, None] > splitters[None, :], axis=1
    )  # (M,) in [0, n_dev)
    valid = local[:, 4] != _SENTINEL  # key_len column
    counts = jnp.sum(
        (bucket[:, None] == jnp.arange(n_dev)[None, :]) & valid[:, None],
        axis=0,
    )
    starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    col = jnp.arange(m) - starts[bucket]  # local sorted => contiguous runs
    overflow = jnp.sum((col >= capacity) & valid).astype(jnp.uint32)
    send = jnp.full((n_dev, capacity, NUM_COLS), _SENTINEL)
    send = send.at[bucket, col].set(
        jnp.where(valid[:, None], local, _SENTINEL), mode="drop"
    )

    recv = jax.lax.all_to_all(
        send, "shards", split_axis=0, concat_axis=0, tiled=True
    )  # (n_dev*capacity, NUM_COLS) after tiling

    # -- final local sort over this device's key range ----------------
    flat = recv.reshape(n_dev * capacity, NUM_COLS)
    out, same = _local_sort(flat)
    return out, same, overflow[None]


@functools.partial(
    jax.jit, static_argnames=("mesh", "capacity", "n_dev")
)
def _dist_kernel(stack, mesh: Mesh, capacity: int, n_dev: int):
    body = functools.partial(
        _per_device, capacity=capacity, n_dev=n_dev
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=P("shards", None),
        out_specs=(P("shards", None), P("shards"), P("shards")),
    )(stack)


def build_stack(cols: columnar.MergeColumns, n_dev: int) -> np.ndarray:
    """(N_padded, NUM_COLS) uint32 operand stack, padded so the leading
    dim divides the mesh.

    Rows are INTERLEAVED across device blocks (block d gets original
    rows d::n_dev): inputs are concatenated sorted runs, so a contiguous
    block layout would give each device a narrow slice of the keyspace
    and funnel its whole slice into a handful of all_to_all buckets
    (~m/ceil(n_dev/n_runs) rows each), overflowing the fixed exchange
    capacity of ~2m/n_dev even with zero skew.  Interleaving makes every
    local slice a stride-sample of the global key distribution — bucket
    loads concentrate around m/n_dev and the splitter samples on each
    device see the whole keyspace.  The idx column carries original row
    identity, so downstream consumers never see the permutation."""
    n = len(cols)
    m = -(-n // n_dev)  # ceil
    m = max(m, _NUM_SAMPLES)
    p = m * n_dev
    stack = np.full((p, NUM_COLS), 0xFFFFFFFF, dtype=np.uint32)
    kw = cols.key_words
    ts_inv = ~cols.timestamp
    stack[:n, 0] = kw[:, 0]
    stack[:n, 1] = kw[:, 1]
    stack[:n, 2] = kw[:, 2]
    stack[:n, 3] = kw[:, 3]
    stack[:n, 4] = cols.key_size
    stack[:n, 5] = (ts_inv >> np.uint64(32)).astype(np.uint32)
    stack[:n, 6] = (ts_inv & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    stack[:n, 7] = ~cols.src
    stack[:n, 8] = np.arange(n, dtype=np.uint32)
    # Interleave: device block d = rows d::n_dev of the run-concatenated
    # order (sentinel padding rows disperse too; they sort last on every
    # device and are masked out of bucket counts).
    return np.ascontiguousarray(
        stack.reshape(m, n_dev, NUM_COLS)
        .transpose(1, 0, 2)
        .reshape(p, NUM_COLS)
    )


def distributed_sort_dedup(
    cols: columnar.MergeColumns,
    mesh: Mesh,
    capacity_factor: float = 2.0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Multi-device merge: returns (perm, same) like
    ops.merge.device_sort_dedup.  When bucket skew overflows the
    exchange capacity the single-device kernel orders the rows instead
    — a selection on the data, counted as ``distributed_overflow``."""
    n = len(cols)
    n_dev = mesh.devices.size
    if n == 0 or n_dev == 1:
        return _single_device_fallback(cols)
    stack = build_stack(cols, n_dev)
    m = stack.shape[0] // n_dev
    capacity = int(m * capacity_factor / n_dev) + _NUM_SAMPLES
    out, same, overflow = _dist_kernel(
        stack, mesh=mesh, capacity=capacity, n_dev=n_dev
    )
    if int(np.asarray(overflow).sum()) > 0:
        return _overflowed(cols)
    out = np.asarray(out)
    same = np.asarray(same)
    # Per-device blocks are disjoint ascending key ranges: concatenate
    # valid rows in block order.
    block = out.shape[0] // n_dev
    perms, sames = [], []
    for d in range(n_dev):
        blk = out[d * block : (d + 1) * block]
        msk = same[d * block : (d + 1) * block]
        is_real = blk[:, 8] != 0xFFFFFFFF
        perms.append(blk[is_real, 8].astype(np.int64))
        sames.append(msk[is_real])
    perm = np.concatenate(perms)
    same_np = np.concatenate(sames)
    if perm.size != n:
        # Rows went missing in the exchange without the overflow
        # counter seeing them: same decline, same count.
        return _overflowed(cols)
    return perm, same_np


def _overflowed(cols: columnar.MergeColumns):
    from ..storage.compaction import compaction_stats

    compaction_stats.note_path("distributed_overflow")
    return _single_device_fallback(cols)


def _single_device_fallback(cols: columnar.MergeColumns):
    """cols always stage sorted sstable runs, so the bitonic merge
    network serves as the single-device path."""
    run_counts = np.bincount(cols.src).tolist() if len(cols) else []
    return bitonic.device_merge_sorted_runs(cols, run_counts)


def DistributedMergeStrategy(mesh: Mesh):
    """CompactionStrategy running the sort across the whole mesh.
    Factory (rather than top-level subclass) so this module stays
    importable without dragging the storage stack in at import time."""
    from ..ops.device_compaction import DeviceMergeStrategy
    from ..storage.compaction import ColumnarMergeStrategy

    class _DistributedMergeStrategy(DeviceMergeStrategy):
        """The device strategy with its mesh, and the sample sort in
        place of the single-device launch: big merges are the parent's
        (one threshold, one call to the mesh-sharded pipeline); below
        it the columnar merge stages the whole input and
        ``sort_and_dedup`` orders it across the mesh."""

        name = "distributed"
        path = "distributed"

        def _merge_single_shot(
            self,
            sources,
            dir_path,
            output_index,
            cache,
            keep_tombstones,
            bloom_min_size,
        ):
            return ColumnarMergeStrategy.merge(
                self,
                sources,
                dir_path,
                output_index,
                cache,
                keep_tombstones,
                bloom_min_size,
            )

        def sort_and_dedup(self, cols):
            perm, same = distributed_sort_dedup(cols, self.mesh)
            # Long keys: host fixes order + dedup (see
            # DeviceFullMergeStrategy).
            if (cols.key_size > columnar.KEY_PREFIX_BYTES).any():
                perm = columnar.fixup_long_key_ties(cols, perm)
                return perm, columnar.dedup_mask(cols, perm)
            return perm, ~same

    return _DistributedMergeStrategy(mesh)
