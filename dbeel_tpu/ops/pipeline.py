"""Partitioned, fully-overlapped device compaction pipeline.

Round 1's device path ran read → stage → h2d → kernel → d2h → gather →
write strictly in sequence, so ~96% of a 10M-key major compaction was
host time with the device idle (VERDICT round 1).  Round 2 replaced the
serial host pipeline with a keyspace-partitioned software pipeline in
which every stage runs concurrently on its own partition:

  upload thread    O_DIRECT bulk reads (native C++), 8-byte-prefix
                   staging, per-partition device_put + kernel dispatch
  download thread  per-partition packed run-id d2h off the async device
                   queue
  caller thread    permutation rebuild → vectorized tie fixup → dedup →
                   tombstone filter → native C++ gather + O_DIRECT
                   streaming write

Round 3 cut the transfer volume (what that buys on a chip-local host is
not measured on the current chip):

  * Uplink (half): each partition's 8-byte prefixes are rebased to the
    partition minimum and right-shifted until the span fits 32 bits —
    an order-preserving u32 approximation, ONE word per entry instead
    of two.  Collisions under the shift become tie blocks fixed up on
    the host exactly like genuinely equal prefixes; partitions where
    the shift would collapse dense clusters (cheap host check) keep the
    exact 2-word operand.
  * Downlink (8x for K<=16): within a partition each run's survivors
    appear in increasing position order, so the kernel returns only the
    bit-packed run-id sequence (~4 bits/entry) and the host rebuilds
    positions with per-run counters.

Tie blocks (equal u32 approximations, shared 8-byte prefixes, long
keys) are re-ordered by one vectorized lexsort over padded key words —
(full key asc, newest ts, newest src), the reference merge order
(/root/reference/src/storage_engine/lsm_tree.rs:1038-1066) — so
tie-heavy keyspaces no longer abort the pipeline run.  Partitions are
keyspace ranges cut at sampled 8-byte key prefixes, so equal prefixes
(hence equal keys, hence every dedup decision) never cross a partition
boundary.  Output bytes are identical to every other strategy (golden
tests enforce it).

Memory (PR 29).  Every array of a merge that can reach 128 KiB is
leased from one process-wide block pool (ops/block_pool.py) through the
merge's ``Leases``, almost all of them up front, on the calling thread
and in one order: the index and prefix columns of all runs side by
side (each run's are slices, filled as it is read), a data buffer a
run, the tombstone column, three operand stacks, a ring of
per-partition sets, the bloom's hash pairs and bits.  A partition set
returns to its ring when both the writer and the bloom thread have
consumed its raw pointers; an operand stack when its launch's output
has been read back (JAX may read the host array until the transfer
completes); everything goes back to the pool when the merge's threads
are joined — or is dropped: all of it where one of them is wedged, a
failed merge's stacks (a launch may never have been read back).  What comes back is dirty: each stage fills what it
reads, and only logical lengths reach C.  The pool keeps what recent
merges used (a block idle for two merges is released; the free total
stays within what they leased at once), so the second and every later
merge of a process runs on pages that are already mapped.
"""

from __future__ import annotations

import ctypes
import itertools
import logging
import os
import queue
import threading
import zlib
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np

from ..storage import columnar
from ..storage.bloom import _SEED1, _SEED2, BloomFilter
from ..storage.compaction import (
    COMPACT_BLOOM_FILE_EXT,
    MergeResult,
    _write_bloom,
    compaction_stats,
)
from ..storage.entry import (
    COMPACT_DATA_FILE_EXT,
    COMPACT_INDEX_FILE_EXT,
    ENTRY_HEADER_SIZE,
    INDEX_ENTRY_SIZE,
    file_name,
)
from .block_pool import ALIGN as _ALIGN
from .block_pool import BlockPool, Leases
from .spans import Stages

log = logging.getLogger(__name__)

# Every array of a merge that can reach 128 KiB is leased from this one
# pool of the process and goes back when the merge's threads are joined
# (ops/block_pool.py; the module docstring's last paragraph).
_POOL = BlockPool(note=compaction_stats.note_pool)

SENTINEL = np.uint32(0xFFFFFFFF)
# Per-(run, partition) kernel rows: pow2-padded; partitions are split
# until every slice fits.
_MAX_P2 = 1 << 17
# Rows of one partition across its (pow2-padded) runs, K * P.  The
# merge network's temporaries grow with J * K * P, whatever K is: a v5e
# compile (16 GB of HBM) puts the exact-prefix kernel at 6.45 GB for
# (J, K, P) = (4, 8, 2^17) and for (4, 64, 2^14) alike, so two launches
# in flight fit — and refuses (4, 64, 2^17) outright at 36 GB
# (tests/test_tpu_compile.py holds both ends).
_MAX_KP = 1 << 20
# Launches dispatched and not yet read back, over EVERY merge of the
# process — what the "two in flight" above is held to.  The chip
# deployment runs each shard's merges at once in threads of one
# process, and a bound per merge would let two shards' big merges put
# four such programs on the one chip.
_LAUNCH_SLOTS = threading.BoundedSemaphore(2)
# Entries a step of the tombstone column: 64 KiB temporaries.
_TOMB_STEP = 1 << 14
# Per-partition row target used to pick the partition count.
_PAD_WASTE_LIMIT = 0.12
# A shifted-u32 partition whose within-run duplicate excess (collisions
# introduced by the shift, beyond genuine prefix ties) exceeds this
# fraction keeps the exact 2-word operand instead.
_SHIFT_DUP_LIMIT = 0.10
# Partitions per device launch: same-mode partitions are vmapped
# together so the fixed cost of a launch is paid once per batch.
_LAUNCH_BATCH = 4
# Multi-batch partitioning (>=2 launch batches for stage overlap) only
# above this many total input rows — below it the extra per-launch
# dispatch outweighs the overlap.
_MULTIBATCH_MIN_ROWS = 1 << 19
# Background fdatasync stride: flush the output's device write cache
# every this many written bytes concurrently with the write stream.
# DISABLED by default (0): on this virtio disk a concurrent fdatasync
# SERIALIZES against in-flight O_DIRECT pwrites and stalls the gather
# writer ~0.5s per flush (measured: bg-sync-on 6.0s vs off 4.85s on
# the 10M merge), while the single close-time flush costs <1s.  Set
# DBEEL_SYNC_STRIDE to a byte count on devices whose close-time cache
# flush is the bigger tail.
try:
    _SYNC_STRIDE = int(os.environ.get("DBEEL_SYNC_STRIDE", 0))
except ValueError:
    logging.getLogger(__name__).warning(
        "DBEEL_SYNC_STRIDE=%r is not an integer byte count; "
        "background sync stays disabled",
        os.environ.get("DBEEL_SYNC_STRIDE"),
    )
    _SYNC_STRIDE = 0


def _unlink_quiet(*paths: str) -> None:
    import os

    for p in paths:
        try:
            os.unlink(p)
        except OSError:
            pass


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@dataclass
class _Run:
    data: np.ndarray  # uint8 (4 KiB-aligned, leased), logical [:size]
    size: int
    offsets: np.ndarray  # u64 within-run record offsets
    key_size: np.ndarray  # u32
    full_size: np.ndarray  # u32
    prefix64: np.ndarray = field(default=None)  # (n,) u64 padded prefix


def _read_run(lib, source, buf, cols, scratch) -> _Run:
    """``buf``: the run's data buffer, 4 KiB-aligned in base and length
    (the O_DIRECT contract of dbeel_read_file); ``cols``: where its
    index columns go (its slices of ``off_cat``, ``ks_cat``,
    ``fs_cat``); ``scratch``: what the index file is read into."""
    offs, ks, fs = source.read_index_columns(out=cols, scratch=scratch)
    size = source.data_size
    if size:
        got = lib.dbeel_read_file(
            source.data_path.encode(),
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_uint64(size),
        )
        if got != size:
            raise OSError(
                f"short read {got} != {size} for {source.data_path}"
            )
    return _Run(buf, size, offs, ks, fs)


def _stage_prefixes(run: _Run, out: np.ndarray, lib=None) -> None:
    """Fill run.prefix64 = ``out`` (the run's slice of the leased
    ``pf_cat``): the zero-padded 8-byte big-endian key prefix per entry
    as one native u64 value (splitters, searchsorted, the
    per-partition rebase that feeds the device operand, the native
    decoder).  Prefers the C stager — the numpy paths held the GIL
    ~90ms per 1.25M-key run, measured as back-to-back serving stalls at
    compaction start."""
    n = run.offsets.size
    run.prefix64 = out
    if n == 0:
        return
    if lib is not None and hasattr(lib, "dbeel_stage_prefixes"):
        lib.dbeel_stage_prefixes(
            run.data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_uint64(run.size),
            run.offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            run.key_size.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            ctypes.c_uint64(n),
            ctypes.c_uint64(ENTRY_HEADER_SIZE),
            out.view(np.uint8).ctypes.data_as(
                ctypes.POINTER(ctypes.c_uint8)
            ),
        )
        # The stager writes key bytes, big-endian: one swap in place
        # here, beside the reads, makes every later use native.
        out.view(">u8").byteswap(inplace=True)
        return
    rec = int(run.full_size[0]) if run.full_size.size else 0
    uniform = (
        rec > 0
        and run.size == n * rec
        and (run.full_size == rec).all()
        and (
            run.offsets == np.arange(n, dtype=np.uint64) * np.uint64(rec)
        ).all()
        and (run.key_size >= 8).all()
    )
    if uniform:
        mat = run.data[: n * rec].reshape(n, rec)
        pref = np.ascontiguousarray(
            mat[:, ENTRY_HEADER_SIZE : ENTRY_HEADER_SIZE + 8]
        )
    else:
        lanes = np.arange(8, dtype=np.uint64)
        pos = (run.offsets + np.uint64(ENTRY_HEADER_SIZE))[:, None] + lanes
        valid = lanes < run.key_size.astype(np.uint64)[:, None]
        pos = np.minimum(pos, np.uint64(max(0, run.size - 1)))
        pref = np.where(
            valid, run.data[pos.astype(np.int64)], 0
        ).astype(np.uint8)
        pref = np.ascontiguousarray(pref)
    out[:] = pref.view(">u8").reshape(n)


def max_partition_rows(n_runs: int) -> int:
    """Largest per-run kernel rows P a merge of ``n_runs`` runs may
    launch: bounded by _MAX_P2 and, for wide merges, by K * P <=
    _MAX_KP."""
    return min(_MAX_P2, _MAX_KP // _pow2(max(1, n_runs)))


def _choose_partitions(runs: List[_Run], launch_batch: int = None):
    """Pick (splitters, per-run bounds, p2): keyspace cut points such
    that every run's slice fits the pow2 kernel rows ``p2`` with little
    padding.  ``launch_batch`` is the EFFECTIVE launch width (mesh mode
    widens it to a device multiple).  Returns None if an equal-prefix
    group exceeds the kernel (the caller then falls back)."""
    if launch_batch is None:
        launch_batch = _LAUNCH_BATCH
    max_p2 = max_partition_rows(len(runs))
    max_run = max((r.prefix64.size for r in runs), default=0)
    total_rows = sum(r.prefix64.size for r in runs)
    if max_run == 0:
        return np.zeros(0, dtype=np.uint64), None, 8
    # Prefer enough partitions to fill at least TWO launch batches:
    # the pipeline's whole point is overlapping read/upload/kernel/
    # download/write, and with every partition in one batch the stages
    # run strictly serially (measured on the 64-way config-4 shape:
    # all four writer puts + consumes landed AFTER the single
    # kernel+d2h, costing ~0.4s of unoverlapped host work on 2M keys).
    # Within the two-to-four-batch band take the smallest viable count
    # (fewest launches); below it, fall back to >=4 partitions, then
    # any.
    viable = []
    for cand in range(1, 65):
        p2c = _pow2(-(-max_run // cand))
        if (
            p2c <= max_p2
            and cand * p2c / max_run - 1.0 <= _PAD_WASTE_LIMIT
        ):
            viable.append(cand)
    # The multi-batch band only pays when there is real host work to
    # overlap: a tiny merge split into two launches just buys a second
    # dispatch.
    bands = (
        ((2 * launch_batch, 4 * launch_batch),)
        if total_rows >= _MULTIBATCH_MIN_ROWS
        else ()
    ) + ((4, 64), (1, 3))
    parts = None
    for lo, hi in bands:
        sel = [c for c in viable if lo <= c <= hi]
        if sel:
            parts = sel[0]
            break
    if parts is None:
        parts = -(-max_run // max_p2)
    p2 = _pow2(-(-max_run // parts))

    samples = np.sort(
        np.concatenate(
            [
                r.prefix64[:: max(1, r.prefix64.size // 256)]
                for r in runs
                if r.prefix64.size
            ]
        )
    )
    cut = [
        samples[(k * samples.size) // parts]
        for k in range(1, parts)
    ]
    # strictly increasing splitters (duplicates collapse partitions)
    splitters = np.array(sorted(set(cut)), dtype=np.uint64)

    def bounds_for(splits):
        return [
            np.concatenate(
                [
                    np.zeros(1, np.int64),
                    np.searchsorted(
                        r.prefix64, splits, side="right"
                    ).astype(np.int64),
                    np.array([r.prefix64.size], np.int64),
                ]
            )
            for r in runs
        ]

    bounds = bounds_for(splitters)
    # Split any partition whose largest run-slice overflows p2.  The
    # split point is a median prefix inside the overflowing slice; if
    # no strictly-interior cut exists the range is one equal-prefix
    # group — unsplittable at this kernel size.
    for _ in range(64):
        overflow = None
        for r, b in zip(runs, bounds):
            cnt = np.diff(b)
            too_big = np.flatnonzero(cnt > p2)
            if too_big.size:
                overflow = (r, b, int(too_big[0]))
                break
        if overflow is None:
            break
        r, b, p = overflow
        lo, hi = int(b[p]), int(b[p + 1])
        uniq = np.unique(r.prefix64[lo:hi])
        if uniq.size < 2:
            return None  # one equal-prefix group > kernel rows
        # side="right" cuts put entries <= splitter left, so any value
        # strictly below the slice maximum splits it into two nonempty
        # halves.
        mid = uniq[(uniq.size - 1) // 2]
        splitters = np.array(
            sorted(set(splitters.tolist()) | {int(mid)}), dtype=np.uint64
        )
        bounds = bounds_for(splitters)
    else:
        return None
    return splitters, bounds, p2


class _PipelineError(Exception):
    pass


class _PartSet:
    """One partition's arrays on their way from the decode to the
    writer and the bloom thread, leased for ``rows`` entries; each
    partition uses their heads.  ``holders``: the threads that have
    yet to consume the partition's raw pointers."""

    __slots__ = (
        "gidx", "rids32", "tieb", "keep", "mask", "sel", "src_run",
        "src_off", "ks_sel", "fs_sel", "holders",
    )

    def __init__(self, mem: Leases, rows: int) -> None:
        self.gidx = mem.array(rows, np.int64)  # decoded global indices
        self.rids32 = mem.array(rows, np.uint32)  # and their runs
        self.tieb = mem.array(rows, np.uint8)  # device-key tie flags
        self.keep = mem.array(rows, np.bool_)
        # Scratch: the tie blocks' members, then the tombstones.
        self.mask = mem.array(rows, np.bool_)
        self.sel = mem.array(rows, np.int64)  # gidx[keep]
        self.src_run = mem.array(rows, np.uint32)  # rids32[keep]
        self.src_off = mem.array(rows, np.uint64)
        self.ks_sel = mem.array(rows, np.uint32)
        self.fs_sel = mem.array(rows, np.uint32)
        self.holders = 0


def pipeline_merge(
    sources: Sequence,
    dir_path: str,
    output_index: int,
    keep_tombstones: bool,
    bloom_min_size: int,
    mesh=None,
    throttle=None,
    tombstone_drop_before: "int | None" = None,
) -> Optional[MergeResult]:
    """Run the partitioned pipeline.  Returns None only as a selection
    on the data — pathological prefix skew (one equal-prefix group
    larger than the kernel rows) — and the caller then takes the
    single-shot path.  A missing native library, a JAX that cannot
    initialise or a failed launch raise.

    ``mesh``: a 1-D jax.sharding.Mesh — keyspace partitions are
    disjoint sorted ranges, so the multi-chip form is pure data
    parallelism: the launch-batch axis is sharded over the mesh and
    every device merges its own partitions with NO cross-device
    exchange (contrast the reference's single-core heap loop,
    /root/reference/src/tasks/compaction.rs:104-137).

    Every stage boundary below is a span (ops/spans.py): seconds and
    counts under ``get_stats.compaction.stages.pipeline``, and
    ``dbeel.pipeline.*`` events in whatever profile is running.  The
    calling thread's stages — ``read_stage``, ``plan``, ``wait_device``,
    ``decode``, ``wait_writer`` (``throttle`` where a throttle is
    attached), ``bloom``, ``close_wait``, ``sidecar`` — partition the
    outer span ``merge``; the other threads' (``read_run``,
    ``operand``, ``slot_wait``, ``h2d_dispatch``, ``d2h``,
    ``gather_write``, ``fsync``, ``bloom_hash``, ``bloom_set``) and the
    nested ``stage_prefixes`` (in ``read_stage``) and ``tie_fixup`` (in
    ``decode``) overlap them and say what the caller was waiting on.
    The merge's shape (launches, partitions, rows launched and real,
    runs, tie entries) is counted under ``get_stats.compaction.shape``.

    The bloom filter is built beside the stream, not after it: a bloom
    thread hashes each partition's keys while the writer gather-writes
    them (``bloom_hash``), and sets the bits and writes and fsyncs the
    bloom file (``bloom_set``) from the moment the last partition is
    queued and the entry count known — under the writer's final join
    and the close's fdatasync.  The caller's ``bloom`` stage starts the
    close and joins that thread; ``close_wait`` is what then remains
    of the close's flush."""
    shape: dict = {}
    mem = _POOL.leases()
    threads: List[threading.Thread] = []
    try:
        with Stages("pipeline", "read_stage") as at:
            result = _pipeline_merge_impl(
                sources,
                dir_path,
                output_index,
                keep_tombstones,
                bloom_min_size,
                mesh,
                throttle,
                tombstone_drop_before,
                at=at,
                shape=shape,
                mem=mem,
                threads=threads,
            )
    finally:
        # Every way out of the merge has joined its threads; one that
        # outlived its join is wedged and may still read or write the
        # blocks (the paths that leak the native handle): those are
        # dropped, never handed to the next merge.
        mem.close(drop=any(t.is_alive() for t in threads))
    # Counted here, once, for every caller of the pipeline.
    if result is None:
        compaction_stats.note_pipeline_decline()
    else:
        compaction_stats.note_pipeline(shape)
    return result


def _plan_operand(pf_cat, run_base, bounds, p, k2, tmp):
    """Plan partition ``p``'s operand: each run's slice of the
    native-endian prefixes, and the choice between the u32
    (rebased+shifted) and the exact 2-word form.  ``tmp``: a u64
    scratch of at least the kernel rows.

    Returns (slices, counts, los, mode32, minpf, shift); ``slices`` is
    None where the partition is empty."""
    counts = np.zeros(k2, dtype=np.uint32)
    los = np.zeros(len(bounds), dtype=np.int64)
    slices = []
    minpf = None
    maxpf = None
    for ri, b in enumerate(bounds):
        lo, hi = int(b[p]), int(b[p + 1])
        los[ri] = lo
        counts[ri] = hi - lo
        base = int(run_base[ri])
        sl = pf_cat[base + lo : base + hi]
        slices.append(sl)
        if hi > lo:
            first, last = int(sl[0]), int(sl[-1])
            minpf = first if minpf is None else min(minpf, first)
            maxpf = last if maxpf is None else max(maxpf, last)
    n_p = int(counts.sum())
    if n_p == 0:
        return None, counts, los, True, 0, 0
    span = maxpf - minpf
    shift = max(0, span.bit_length() - 32)
    mode32 = True
    if shift:
        # Within-run duplicate excess introduced by the shift (beyond
        # genuine 8-byte-prefix ties): if the shift collapses dense
        # clusters, the host tie fixup would swallow the partition —
        # keep the exact operand there instead.
        d32 = 0
        d64 = 0
        for sl in slices:
            if sl.size < 2:
                continue
            v = _shifted(sl, minpf, shift, tmp)
            d32 += int(np.count_nonzero(v[1:] == v[:-1]))
            d64 += int(np.count_nonzero(sl[1:] == sl[:-1]))
        if d32 - d64 > _SHIFT_DUP_LIMIT * n_p:
            mode32 = False
    return slices, counts, los, mode32, minpf, shift


def _shifted(sl, minpf, shift, tmp):
    """(sl - minpf) >> shift in ``tmp``'s head."""
    v = tmp[: sl.size]
    np.subtract(sl, np.uint64(minpf), out=v)
    np.right_shift(v, np.uint64(shift), out=v)
    return v


def _fill_operand(dest, slices, mode32, minpf, shift, tmp):
    """Write a planned partition's sentinel-padded operand into
    ``dest``, one slot of a launch's stack — (k2, p2) u32, or
    (k2, p2, 2) for the exact form.  ``dest`` is dirty: every word of
    it is written."""
    for ri, sl in enumerate(slices):
        n = sl.size
        if n:
            if mode32:
                dest[ri, :n] = _shifted(sl, minpf, shift, tmp)
            else:
                v = tmp[:n]
                np.right_shift(sl, np.uint64(32), out=v)
                dest[ri, :n, 0] = v
                np.bitwise_and(sl, np.uint64(0xFFFFFFFF), out=v)
                dest[ri, :n, 1] = v
        dest[ri, n:] = SENTINEL
    dest[len(slices) :] = SENTINEL


def _gather_tie_arrays(runs, run_base, off_cat, ks_cat, sel, lpad):
    """Per-run vectorized gather of (padded key words, ~ts, ~src) for
    the tie-block entries ``sel`` (global indices), key matrix padded
    to ``lpad`` bytes (the caller buckets blocks by width)."""
    ri = (
        np.searchsorted(run_base, sel, side="right") - 1
    ).astype(np.int64)
    off = off_cat[sel]
    ks = ks_cat[sel]
    m = sel.size
    kwords = np.zeros((m, lpad // 8), dtype=np.uint64)
    ts = np.zeros(m, dtype=np.uint64)
    w8 = np.uint64(1) << (
        np.arange(8, dtype=np.uint64) * np.uint64(8)
    )
    for r in np.unique(ri):
        msk = ri == r
        data = runs[r].data
        o = off[msk]
        kwords[msk] = columnar.padded_key_words(
            data,
            o + np.uint64(ENTRY_HEADER_SIZE),
            ks[msk],
            pad_to=lpad,
        )
        tpos = (o + np.uint64(8))[:, None] + np.arange(
            8, dtype=np.uint64
        )
        ts[msk] = (
            data[tpos.astype(np.int64)].astype(np.uint64) @ w8
        )
    return kwords, ~ts, ~ri.astype(np.uint32)


def _gather_timestamps(runs, run_base, off_cat, sel):
    """Per-record int64-ns timestamps (as u64 bit views) for the
    GLOBAL indices ``sel`` — gathered lazily, because the pipeline
    never materializes a full timestamp column; only gc_grace needs
    them, and only for drop-candidate tombstones (a small fraction)."""
    ri = (
        np.searchsorted(run_base, sel, side="right") - 1
    ).astype(np.int64)
    off = off_cat[sel]
    ts = np.zeros(sel.size, dtype=np.uint64)
    w8 = np.uint64(1) << (
        np.arange(8, dtype=np.uint64) * np.uint64(8)
    )
    for r in np.unique(ri):
        msk = ri == r
        tpos = (off[msk] + np.uint64(8))[:, None] + np.arange(
            8, dtype=np.uint64
        )
        ts[msk] = (
            runs[r].data[tpos.astype(np.int64)].astype(np.uint64)
            @ w8
        )
    return ts


def _pipeline_merge_impl(
    sources: Sequence,
    dir_path: str,
    output_index: int,
    keep_tombstones: bool,
    bloom_min_size: int,
    mesh=None,
    throttle=None,
    tombstone_drop_before: "int | None" = None,
    *,
    at: Stages,
    shape: dict,
    mem: Leases,
    threads: List[threading.Thread],
) -> Optional[MergeResult]:
    """``shape``: filled, where the merge produces an output, with its
    ``compaction.PIPELINE_SHAPE`` counts.  ``mem``: what every large
    array is leased from; the caller closes it.  ``threads``: every
    thread started here that works in leased memory is appended, for
    the caller to see whether one is still alive."""
    from ..storage import native as native_mod

    lib = native_mod.require()
    import jax

    from .bitonic import (
        merge_runs_prefix32_packed_batch_kernel,
        merge_runs_prefix64_packed_batch_kernel,
        rid_pack_bits,
        unpack_rids,
    )

    span = at.span  # this merge's stages on its other threads

    # ---- host staging (index columns + O_DIRECT data reads) ---------
    # IO threads read ahead (O_DIRECT, GIL released inside the C
    # call) while this thread stages completed runs' prefixes.  Two
    # readers by default: queue depth 2 on the virtio disk overlaps
    # one run's tail with the next run's head (DBEEL_PIPE_READERS
    # overrides; 1 restores the round-3 serial-read prologue).
    from concurrent.futures import ThreadPoolExecutor

    n_readers = max(
        1, int(os.environ.get("DBEEL_PIPE_READERS", "2") or 2)
    )

    # Leased up front, on this thread and in one order, so that a
    # merge of the same inputs leases the same blocks: the index
    # columns and key prefixes of all runs side by side (each run's
    # are slices, filled as it is read), one data buffer a run, one
    # index-file scratch a reader.
    counts_all = np.array(
        [s.entry_count for s in sources], dtype=np.int64
    )
    run_base = np.zeros(len(sources) + 1, dtype=np.int64)
    np.cumsum(counts_all, out=run_base[1:])
    total_rows = int(run_base[-1])
    off_cat = mem.array(total_rows, np.uint64)
    ks_cat = mem.array(total_rows, np.uint32)
    fs_cat = mem.array(total_rows, np.uint32)
    pf_cat = mem.array(total_rows, np.uint64)
    bufs = [
        mem.array((s.data_size + _ALIGN - 1) & ~(_ALIGN - 1))
        for s in sources
    ]
    scratch_q: "queue.Queue" = queue.Queue()
    for _ in range(min(n_readers, len(sources))):
        scratch_q.put(
            mem.array(int(counts_all.max()) * INDEX_ENTRY_SIZE)
        )

    def read_run(i, source):
        with span("read_run", run=i):
            lo, hi = int(run_base[i]), int(run_base[i + 1])
            scratch = scratch_q.get()
            try:
                return _read_run(
                    lib,
                    source,
                    bufs[i],
                    (off_cat[lo:hi], ks_cat[lo:hi], fs_cat[lo:hi]),
                    scratch,
                )
            finally:
                scratch_q.put(scratch)

    with ThreadPoolExecutor(max_workers=n_readers) as io:
        futs = [io.submit(read_run, i, s) for i, s in enumerate(sources)]
        runs = []
        for i, f in enumerate(futs):
            r = f.result()
            with span("stage_prefixes", run=i):
                _stage_prefixes(
                    r, pf_cat[run_base[i] : run_base[i + 1]], lib
                )
            runs.append(r)
    at.to("plan")
    # Mesh mode: widen the launch batch to a device multiple and shard
    # the batch axis — each device merges its own keyspace partitions.
    # Computed BEFORE partitioning: the multi-batch preference must
    # target the EFFECTIVE launch width, or a wide mesh swallows every
    # partition into one launch and re-serializes the stages.
    launch_j = _LAUNCH_BATCH
    shard32 = shard64 = shard_counts = None
    if mesh is not None and mesh.devices.size > 1:
        from jax.sharding import NamedSharding, PartitionSpec

        n_dev = int(mesh.devices.size)
        launch_j = n_dev * max(1, _LAUNCH_BATCH // n_dev)
        axis = mesh.axis_names[0]
        shard32 = NamedSharding(mesh, PartitionSpec(axis, None, None))
        shard64 = NamedSharding(
            mesh, PartitionSpec(axis, None, None, None)
        )
        shard_counts = NamedSharding(mesh, PartitionSpec(axis, None))

    chosen = _choose_partitions(runs, launch_j)
    if chosen is None:
        return None
    _splitters, bounds, p2 = chosen
    n_parts = (bounds[0].size - 1) if bounds is not None else 0
    k2 = _pow2(max(1, len(runs)))
    pack_bits = rid_pack_bits(k2)

    tomb_cat = None
    if not keep_tombstones:
        tomb_cat = mem.array(total_rows, np.bool_)
        # In steps whose temporaries stay small enough for the heap.
        hdr = np.uint32(ENTRY_HEADER_SIZE)
        for lo in range(0, total_rows, _TOMB_STEP):
            hi = lo + _TOMB_STEP
            np.equal(
                fs_cat[lo:hi], ks_cat[lo:hi] + hdr, out=tomb_cat[lo:hi]
            )
    # Most entries of one partition over all runs: what a partition's
    # set of arrays (below) is sized for.
    max_np = (
        int(sum(np.diff(b) for b in bounds).max()) if n_parts else 0
    )
    have_decode = hasattr(lib, "dbeel_pipe_decode")

    data_path = f"{dir_path}/{file_name(output_index, COMPACT_DATA_FILE_EXT)}"
    index_path = f"{dir_path}/{file_name(output_index, COMPACT_INDEX_FILE_EXT)}"
    bloom_path = f"{dir_path}/{file_name(output_index, COMPACT_BLOOM_FILE_EXT)}"
    # Single-pass sidecar (ISSUE 15): arm the gather writer's inline
    # page-CRC accumulators so the .sums sidecar is written from the
    # bytes AS they streamed through — no post-hoc triplet re-read.
    writer_crcs = hasattr(lib, "dbeel_writer_open2")
    if writer_crcs:
        handle = lib.dbeel_writer_open2(
            data_path.encode(), index_path.encode(), 1
        )
    else:
        handle = lib.dbeel_writer_open(
            data_path.encode(), index_path.encode()
        )
    if not handle:
        # The output cannot be opened (a disk fault): the single-shot
        # writer meets the same disk and raises its errno.
        return None

    total_input = int(sum(r.size for r in runs))

    run_ptrs = (ctypes.POINTER(ctypes.c_uint8) * max(1, len(runs)))(
        *[
            r.data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
            for r in runs
        ]
    )

    # ---- pipeline threads -------------------------------------------
    # Per-partition permits, sized for two full launch batches in
    # flight (the upload thread holds up to launch_j permits while
    # assembling a batch, so the pool must exceed one batch or
    # assembly itself would deadlock).
    in_flight = threading.Semaphore(2 * launch_j)
    kernel_q: "queue.Queue" = queue.Queue()
    order_q: "queue.Queue" = queue.Queue()
    stop = threading.Event()
    # One token per _LAUNCH_SLOTS permit this merge holds: the
    # downloader returns a permit when its launch has been read back,
    # and whatever an abort leaves is returned after the joins below.
    held_slots: list = []

    def _release_slot() -> bool:
        try:
            held_slots.pop()
        except IndexError:
            return False
        _LAUNCH_SLOTS.release()
        return True

    launches = itertools.count()
    # Operand stacks: flat u32 buffers of the one-word launch's size
    # (the two-word form re-leases twice that), leased here and cycled
    # through ``stack_free``.  JAX may read a stack handed to
    # device_put until the transfer completes, so a stack is refilled
    # only after its launch's output has been read back: the downloader
    # puts it back beside the launch's permit.  Three: two launches in
    # flight (_LAUNCH_SLOTS) and the next being filled.
    stack_words = launch_j * k2 * p2
    stacks = [
        mem.array(stack_words, np.uint32)
        for _ in range(min(3, -(-n_parts // launch_j)))
    ]
    stack_free: "queue.Queue" = queue.Queue()
    for buf in stacks:
        stack_free.put(buf)
    # The upload thread's scratch for rebased prefixes.
    shift_tmp = mem.array(p2 if n_parts else 0, np.uint64)

    def _take_stack(mode32):
        """A free stack as (buffer, array in the launch's shape); None
        where the merge stopped."""
        while True:
            try:
                buf = stack_free.get(timeout=0.25)
                break
            except queue.Empty:
                if stop.is_set():
                    return None
        words = stack_words * (1 if mode32 else 2)
        if buf.size < words:
            slot = next(i for i, b in enumerate(stacks) if b is buf)
            mem.give(buf)
            buf = stacks[slot] = mem.array(words, np.uint32)
        tail = () if mode32 else (2,)
        return buf, buf[:words].reshape((launch_j, k2, p2) + tail)

    def _launch_batch(metas, taken, mode32):
        """One vmapped launch over up to ``launch_j`` same-mode
        partitions, empty-slot padded to a single compiled shape; the
        batch axis shards over the mesh when one is supplied.
        ``taken``: the stack from _take_stack, its first len(metas)
        slots filled."""
        j = launch_j
        buf, stack = taken
        # One launch's spans share ``launch`` (its ordinal in the
        # merge) and ``part`` (its first partition).
        ids = {"launch": next(launches), "part": metas[0][0]}
        with span("operand", **ids):
            stack[len(metas) :] = SENTINEL
            counts = np.zeros((j, k2), dtype=np.uint32)
            for slot, meta in enumerate(metas):
                counts[slot] = meta[1]
        with span("slot_wait", **ids):
            while not _LAUNCH_SLOTS.acquire(timeout=0.25):
                if stop.is_set():
                    return
        held_slots.append(None)
        with span("h2d_dispatch", **ids):
            sharding = shard32 if mode32 else shard64
            if sharding is not None:
                dev = jax.device_put(stack, sharding)
                cnt = jax.device_put(counts, shard_counts)
            else:
                dev = jax.device_put(stack)
                cnt = counts
            if mode32:
                out = merge_runs_prefix32_packed_batch_kernel(
                    dev, cnt, pack_bits
                )
            else:
                out = merge_runs_prefix64_packed_batch_kernel(
                    dev, cnt, pack_bits
                )
        kernel_q.put((metas, out, ids, buf))

    def upload():
        try:
            metas: list = []  # (p, counts, los, mode32, minpf, shift)
            taken = None  # the stack the pending batch is filled into
            batch_mode = True

            def flush():
                nonlocal metas, taken
                if metas:
                    _launch_batch(metas, taken, batch_mode)
                    metas, taken = [], None

            for p in range(n_parts):
                # Timed acquire + stop checks: if the downloader dies
                # it can never release permits, and this thread must
                # not park forever pinning the run buffers.
                with span("slot_wait", part=p):
                    while not in_flight.acquire(timeout=0.25):
                        if stop.is_set():
                            return
                if stop.is_set():
                    return
                with span("operand", part=p):
                    slices, counts, los, mode32, minpf, shift = (
                        _plan_operand(
                            pf_cat, run_base, bounds, p, k2, shift_tmp
                        )
                    )
                if slices is None:
                    # Keep strict partition order: launch whatever is
                    # pending first, THEN the empty marker (the
                    # downloader releases this partition's permit).
                    flush()
                    kernel_q.put(
                        ([(p, counts, los, True, 0, 0)], None, None, None)
                    )
                    continue
                if metas and mode32 != batch_mode:
                    flush()
                batch_mode = mode32
                if taken is None:
                    with span("slot_wait", part=p):
                        taken = _take_stack(mode32)
                    if taken is None:
                        return
                with span("operand", part=p):
                    _fill_operand(
                        taken[1][len(metas)],
                        slices,
                        mode32,
                        minpf,
                        shift,
                        shift_tmp,
                    )
                metas.append((p, counts, los, mode32, minpf, shift))
                if len(metas) == launch_j:
                    flush()
            flush()
            kernel_q.put(None)
        except BaseException as e:  # propagate to writer
            kernel_q.put(e)

    def download():
        try:
            while True:
                # Timed get + stop check: on a consumer-side abort no
                # sentinel may ever arrive, and this thread must not
                # park forever (it would leak and stall the joins).
                try:
                    item = kernel_q.get(timeout=0.25)
                except queue.Empty:
                    if stop.is_set():
                        return
                    continue
                if item is None:
                    order_q.put(None)
                    return
                if isinstance(item, BaseException):
                    stop.set()
                    order_q.put(item)
                    return
                metas, out, ids, buf = item
                if out is not None:
                    # The kernel's completion + the d2h of its
                    # bit-packed run-ids.
                    with span("d2h", **ids):
                        words = np.asarray(out)
                    stack_free.put(buf)
                    _release_slot()
                    for slot, meta in enumerate(metas):
                        in_flight.release()
                        order_q.put((meta, words[slot]))
                else:
                    in_flight.release()  # re-balance the empty slot
                    order_q.put((metas[0], None))
        except BaseException as e:
            stop.set()
            order_q.put(e)

    t_up = threading.Thread(target=upload, daemon=True)
    t_down = threading.Thread(target=download, daemon=True)
    threads += [t_up, t_down]
    t_up.start()
    t_down.start()

    # Writer thread: native gather-writes run off the decode thread so
    # partition p+1's permutation rebuild overlaps partition p's disk
    # write (the ctypes call releases the GIL).  A sync thread
    # periodically fdatasyncs the data file CONCURRENTLY with the
    # writes, so the device write-cache flush pipelines behind the
    # stream instead of landing as one multi-second close_sync tail.
    write_q: "queue.Queue" = queue.Queue(maxsize=4)
    writer_state = {"wrote": 0, "bytes": 0, "error": None}
    have_sync = hasattr(lib, "dbeel_writer_sync")
    will_bloom = total_input >= bloom_min_size
    # A partition's arrays, from its decode to the writer and the bloom
    # thread, are one of these sets, each sized for the largest
    # partition and dirty from its last.  As many as can be alive: the
    # one in the caller's hands, write_q's, the writer's.  A set is
    # free again when BOTH the writer and the bloom thread have
    # consumed its raw pointers.
    sets_free: "queue.Queue" = queue.Queue()
    for _ in range(min(n_parts, write_q.maxsize + 2)):
        sets_free.put(_PartSet(mem, max_np))
    sets_lock = threading.Lock()

    def _set_done(pset):
        with sets_lock:
            pset.holders -= 1
            idle = pset.holders == 0
        if idle:
            sets_free.put(pset)

    def writer():
        try:
            while True:
                try:
                    job = write_q.get(timeout=0.25)
                except queue.Empty:
                    if stop.is_set():
                        return
                    continue
                if job is None:
                    return
                sel_sz, args, nbytes, pset, p = job
                with span("gather_write", part=p):
                    rc = lib.dbeel_writer_put(handle, run_ptrs, *args)
                _set_done(pset)
                if rc != 0:
                    writer_state["error"] = _PipelineError(
                        "native gather-write failed"
                    )
                    stop.set()
                    return
                writer_state["wrote"] += sel_sz
                writer_state["bytes"] += nbytes
        except BaseException as e:
            writer_state["error"] = e
            stop.set()

    sync_done = threading.Event()

    def syncer():
        # Flush ~every _SYNC_STRIDE of new bytes; safe concurrently
        # with dbeel_writer_put (see dbeel_writer_sync).
        last = 0
        while not sync_done.wait(0.2):
            b = writer_state["bytes"]
            if b - last >= _SYNC_STRIDE:
                with span("fsync"):
                    lib.dbeel_writer_sync(handle)
                last = b

    # Bloom thread.  The filter's size follows from the FINAL entry
    # count, a key's two hashes do not: each partition's keys are
    # hashed here as it is queued for the writer (the writer job's own
    # arrays, the same run pointers), and once the caller has posted
    # the count the bits are set from the stored pairs and the bloom
    # file is written and fsynced — under the writer's last partitions
    # and the close's fdatasync, not after them.  Hashing is
    # speculative: only a merge whose INPUT passes bloom_min_size can
    # end with an output that does.  The queue is unbounded: write_q
    # paces the caller, and a partition hashes several times faster
    # than it gather-writes.
    bloom_q: "queue.Queue" = queue.Queue()
    bloom_state = {"blob": None, "error": None}

    if will_bloom:
        # Two hashes a key, and the bits of the largest filter the
        # output can need (it never has more entries than the input).
        pairs = mem.array(2 * total_rows, np.uint32)
        bits_buf = mem.array((BloomFilter.size_for(total_rows)[0] + 7) // 8)

    def bloomer(runs):
        # ``runs``: the raw pointers in run_ptrs are only as alive as
        # these buffers, whatever becomes of the caller's frame.
        try:
            u32p = ctypes.POINTER(ctypes.c_uint32)
            u64p = ctypes.POINTER(ctypes.c_uint64)
            hashed = 0
            while True:
                # Timed get + stop check, as the writer's.
                if stop.is_set():
                    return
                try:
                    item = bloom_q.get(timeout=0.25)
                except queue.Empty:
                    continue
                if not isinstance(item, tuple):
                    break
                p, src_run, src_off, ks_sel, pset = item
                with span("bloom_hash", part=p):
                    lib.dbeel_bloom_hash_gather(
                        run_ptrs,
                        src_run.ctypes.data_as(u32p),
                        src_off.ctypes.data_as(u64p),
                        ks_sel.ctypes.data_as(u32p),
                        src_run.size,
                        ENTRY_HEADER_SIZE,
                        _SEED1,
                        _SEED2,
                        pairs[2 * hashed :].ctypes.data_as(u32p),
                    )
                hashed += src_run.size
                _set_done(pset)
            # ``item`` is the output's entry count, or 0 where the
            # output ended under bloom_min_size: the hashes are dropped.
            if not item:
                return
            assert item == hashed
            with span("bloom_set"):
                num_bits, num_hashes = BloomFilter.size_for(hashed)
                bits = bits_buf[: (num_bits + 7) // 8]
                bits.fill(0)
                bloom = BloomFilter(num_bits, num_hashes, bits=bits)
                lib.dbeel_bloom_set_hashes(
                    bloom.bits.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_uint8)
                    ),
                    bloom.num_bits,
                    bloom.num_hashes,
                    pairs.ctypes.data_as(u32p),
                    hashed,
                )
                bloom_state["blob"] = _write_bloom(
                    dir_path, output_index, bloom
                )
        except BaseException as e:  # re-raised by the caller's join
            bloom_state["error"] = e

    t_write = threading.Thread(target=writer, daemon=True)
    threads.append(t_write)
    t_write.start()
    t_bloom = None
    if will_bloom:
        t_bloom = threading.Thread(
            target=bloomer,
            args=(runs,),
            name="dbeel-pipeline-bloom",
            daemon=True,
        )
        threads.append(t_bloom)
        t_bloom.start()
    t_sync = None
    if _SYNC_STRIDE <= 0:
        have_sync = False  # disabled: one flush at close only
    if have_sync:
        t_sync = threading.Thread(target=syncer, daemon=True)
        threads.append(t_sync)
        t_sync.start()

    queued = queued_bytes = tie_entries = 0
    failed = False
    try:
        expected = 0
        while True:
            # Timed get: the writer thread can fail and set ``stop``
            # without ever feeding order_q (it is not part of the
            # upload->download chain), so an untimed get could park
            # this thread forever on e.g. a full disk.
            at.to("wait_device")
            while True:
                try:
                    item = order_q.get(timeout=0.25)
                    break
                except queue.Empty:
                    if writer_state["error"] is not None:
                        raise writer_state["error"]
                    if stop.is_set():
                        raise _PipelineError("pipeline stopped")
            if item is None:
                break
            if isinstance(item, BaseException):
                raise item
            (p, counts, los, mode32, minpf, shift), packed = item
            n_p = int(counts.sum())
            pset = None
            if n_p and sets_free.empty():
                # Every set is queued or being written: the writer's
                # queue is full, one step early.
                at.to("wait_writer", part=p)
            while n_p and pset is None:
                try:
                    pset = sets_free.get(timeout=0.25)
                except queue.Empty:
                    if stop.is_set() or writer_state["error"]:
                        raise writer_state["error"] or _PipelineError(
                            "writer stopped"
                        )
            at.to("decode", part=p)
            if writer_state["error"] is not None:
                raise writer_state["error"]
            assert p == expected
            expected += 1
            if n_p == 0:
                continue
            if have_decode:
                # One C pass: unpack rids, per-run counters ->
                # permutation, device-key tie flags.  Replaces the
                # numpy unpack/bincount/argsort/cumcount chain — on a
                # 1-core host this decode was ~40% of the pipeline's
                # host CPU.
                gidx = pset.gidx[:n_p]
                rids32 = pset.rids32[:n_p]
                tieb = pset.tieb[:n_p]
                packed_c = np.ascontiguousarray(packed)
                cnts_c = np.ascontiguousarray(
                    counts[: len(runs)], dtype=np.uint32
                )
                los_c = np.ascontiguousarray(los, dtype=np.int64)
                rc = lib.dbeel_pipe_decode(
                    packed_c.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_uint32)
                    ),
                    ctypes.c_uint64(n_p),
                    ctypes.c_uint32(pack_bits),
                    ctypes.c_uint32(len(runs)),
                    cnts_c.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_uint32)
                    ),
                    los_c.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_int64)
                    ),
                    run_base.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_int64)
                    ),
                    pf_cat.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_uint64)
                    ),
                    ctypes.c_uint64(minpf),
                    ctypes.c_uint32(shift),
                    1 if mode32 else 0,
                    gidx.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_int64)
                    ),
                    rids32.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_uint32)
                    ),
                    tieb.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_uint8)
                    ),
                )
                if rc != 0:
                    raise _PipelineError(
                        "packed run-id decode mismatch"
                    )
                flags = tieb[1:].view(np.bool_)
            else:
                rids = unpack_rids(packed, pack_bits, n_p).astype(
                    np.int64
                )
                # Rebuild positions: the comparator is a total order
                # and runs are pre-sorted, so each run's entries
                # appear in increasing position order — a per-run
                # counter inverts it.  One bincount (decode check) +
                # one stable argsort (grouped cumcount), independent
                # of the run count.
                counts_dec = np.bincount(rids, minlength=len(runs))
                if counts_dec.size > len(runs) or not (
                    counts_dec == counts[: len(runs)]
                ).all():
                    raise _PipelineError(
                        "packed run-id decode mismatch"
                    )
                grouped = np.argsort(rids, kind="stable")
                group_lo = np.concatenate(
                    [[0], np.cumsum(counts_dec)[:-1]]
                )
                pos = np.empty(n_p, dtype=np.int64)
                pos[grouped] = np.arange(
                    n_p, dtype=np.int64
                ) - np.repeat(group_lo, counts_dec)
                gidx = run_base[rids] + los[rids] + pos
                rids32 = rids.astype(np.uint32)

            # Tie blocks: adjacent entries equal under the DEVICE sort
            # key (shifted u32 or exact 8B prefix) are re-ordered by
            # (full key, newest ts, newest src) — one vectorized
            # lexsort — and duplicate keys are marked for dedup.
            if not have_decode:
                pf = pf_cat[gidx]
                if mode32:
                    dv = (pf - np.uint64(minpf)) >> np.uint64(shift)
                    flags = dv[1:] == dv[:-1]
                else:
                    flags = pf[1:] == pf[:-1]
            keep = pset.keep[:n_p]
            keep.fill(True)
            with span("tie_fixup", part=p):
                positions, block_id = columnar.tie_positions_and_blocks(
                    flags, pset.mask
                )
                tie_entries += int(positions.size)
                if positions.size:
                    sel_t = gidx[positions]
                    ks_t = ks_cat[sel_t]
                    ent_w = columnar.tie_block_widths(block_id, ks_t)
                    for w in np.unique(ent_w):
                        bm = ent_w == w
                        kwords, inv_ts, inv_src = _gather_tie_arrays(
                            runs,
                            run_base,
                            off_cat,
                            ks_cat,
                            sel_t[bm],
                            int(w),
                        )
                        order, dup = columnar.tie_block_sort(
                            block_id[bm], kwords, ks_t[bm], inv_ts, inv_src
                        )
                        gidx[positions[bm]] = sel_t[bm][order]
                        # The reorder moved entries across runs: refresh
                        # the run-id column at exactly those positions.
                        rids32[positions[bm]] = (
                            np.searchsorted(
                                run_base, gidx[positions[bm]], side="right"
                            )
                            - 1
                        ).astype(np.uint32)
                        keep[positions[bm]] = ~dup

            if not keep_tombstones:
                # (mode="clip": numpy buffers ``out`` under "raise".)
                drop = np.take(
                    tomb_cat, gidx, out=pset.mask[:n_p], mode="clip"
                )
                if tombstone_drop_before and drop.any():
                    # gc_grace: tombstones younger than the cutoff
                    # survive the drop.  Timestamps are gathered only
                    # for the drop candidates.
                    cand = np.flatnonzero(drop)
                    cand_ts = _gather_timestamps(
                        runs, run_base, off_cat, gidx[cand]
                    )
                    drop[
                        cand[
                            cand_ts
                            >= np.uint64(tombstone_drop_before)
                        ]
                    ] = False
                np.logical_not(drop, out=drop)
                keep &= drop
            m = int(np.count_nonzero(keep))
            if m == 0:
                sets_free.put(pset)
                continue
            if m != n_p:
                sel = np.compress(keep, gidx, out=pset.sel[:m])
                src_run = np.compress(keep, rids32, out=pset.src_run[:m])
            else:
                sel = gidx
                src_run = np.ascontiguousarray(rids32)
            src_off = np.take(
                off_cat, sel, out=pset.src_off[:m], mode="clip"
            )
            ks_sel = np.take(ks_cat, sel, out=pset.ks_sel[:m], mode="clip")
            fs_sel = np.take(fs_cat, sel, out=pset.fs_sel[:m], mode="clip")
            args = (
                src_run.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                src_off.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                ks_sel.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                fs_sel.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                ctypes.c_uint64(m),
            )
            nbytes = int(fs_sel.sum())
            # The set stays out of ``sets_free`` exactly until the
            # writer and the bloom thread have consumed the raw
            # pointers (the number of sets caps the live jobs).
            pset.holders = 2 if t_bloom is not None else 1
            job = (m, args, nbytes, pset, p)
            queued += m
            queued_bytes += nbytes
            if t_bloom is not None:
                bloom_q.put((p, src_run, src_off, ks_sel, pset))
            at.to("wait_writer", part=p)
            while True:
                try:
                    write_q.put(job, timeout=0.25)
                    break
                except queue.Full:
                    if stop.is_set() or writer_state["error"]:
                        raise writer_state["error"] or _PipelineError(
                            "writer stopped"
                        )
            if throttle is not None:
                # Latency class: one partition is the consume quantum —
                # pay back CPU to serving between partitions.
                at.to("throttle", part=p)
                throttle.tick()
        # The last partition is queued, so the output's entry count
        # and size are known while the writer still has its queue to
        # write: the set phase starts here.
        wants_bloom = queued > 0 and queued_bytes >= bloom_min_size
        if t_bloom is not None:
            bloom_q.put(queued if wants_bloom else 0)
        at.to("wait_writer")
        write_q.put(None)
        t_write.join(timeout=600)
        if writer_state["error"] is not None:
            raise writer_state["error"]
    except BaseException:
        failed = True
        stop.set()
        t_write.join(timeout=60)
        # Joined before ``runs`` can go: it hashes through run_ptrs.
        if t_bloom is not None:
            t_bloom.join(timeout=60)
            if not t_bloom.is_alive():
                _unlink_quiet(bloom_path)
        sync_done.set()
        if t_sync is not None:
            t_sync.join(timeout=60)
        if t_write.is_alive() or (
            t_sync is not None and t_sync.is_alive()
        ):
            # A wedged writer/sync thread may still hold the native
            # handle: leak it (and the partial files) rather than
            # free memory under a live pwrite/fdatasync.
            log.error(
                "pipeline writer/sync thread wedged; leaking native "
                "writer handle for %s", data_path
            )
        else:
            lib.dbeel_writer_abort(handle)
            _unlink_quiet(data_path, index_path)
        raise
    finally:
        t_up.join(timeout=60)
        t_down.join(timeout=60)
        while _release_slot():
            pass
        if failed:
            # A launch that was never read back may still be reading
            # its stack: a failed merge's stacks are not returned.
            for buf in stacks:
                mem.forget(buf)

    sync_done.set()
    if t_sync is not None:
        t_sync.join(timeout=60)
    if t_write.is_alive() or (
        t_sync is not None and t_sync.is_alive()
    ):
        log.error(
            "pipeline writer/sync thread wedged at close; leaking "
            "native writer handle for %s", data_path
        )
        raise _PipelineError("writer thread wedged")
    # Close (final fdatasync + truncate) runs on its own thread: the
    # entry and byte counts are known from the writer's own
    # accounting, so nothing below depends on its completing.  By now
    # the bloom thread has had the count since the last partition was
    # queued (the writer's final join ago), so the caller's ``bloom``
    # stage is a join: what the set phase and the bloom file's fsync
    # have left over runs beside the close's device write-cache flush
    # (VERDICT r3 #7: that flush was ~0.5-1s of serial tail), and the
    # caller then waits in ``close_wait`` for whatever of the flush
    # remains.
    at.to("bloom")
    data_size = ctypes.c_uint64(0)
    close_ret = {"entries": -1, "crcs": None}
    # CRC handoff caps: the merged output can never exceed the sum of
    # its inputs (dedup/tombstone-drop only shrink it).
    _dcap = int(sum(r.size for r in runs)) // 4096 + 2
    _icap = int(run_base[-1]) * 16 // 4096 + 2

    def _close():
        # Inside either call: the final fdatasync + truncate.
        if writer_crcs:
            dcrc = (ctypes.c_uint32 * _dcap)()
            icrc = (ctypes.c_uint32 * _icap)()
            nd = ctypes.c_uint64(0)
            ni = ctypes.c_uint64(0)
            with span("fsync"):
                rc = lib.dbeel_writer_close2(
                    handle,
                    ctypes.byref(data_size),
                    dcrc,
                    _dcap,
                    icrc,
                    _icap,
                    ctypes.byref(nd),
                    ctypes.byref(ni),
                )
            if rc == -2:
                # Triplet closed fine; only the CRC handoff was
                # refused — the LSM's counted post-hoc sidecar
                # covers it.  Entries are known from the writer's
                # own accounting.
                close_ret["entries"] = writer_state["wrote"]
            else:
                close_ret["entries"] = rc
                if rc >= 0:
                    close_ret["crcs"] = (
                        list(dcrc[: nd.value]),
                        list(icrc[: ni.value]),
                    )
        else:
            with span("fsync"):
                close_ret["entries"] = lib.dbeel_writer_close(
                    handle, ctypes.byref(data_size)
                )

    t_close = threading.Thread(target=_close, daemon=True)
    threads.append(t_close)
    t_close.start()

    entries = writer_state["wrote"]
    assert (entries, writer_state["bytes"]) == (queued, queued_bytes)
    bloom_blob = None
    try:
        if t_bloom is not None:
            t_bloom.join(timeout=600)
            if t_bloom.is_alive():
                raise _PipelineError("bloom thread wedged")
            if bloom_state["error"] is not None:
                raise bloom_state["error"]
            bloom_blob = bloom_state["blob"]
    except BaseException:
        # The merge's contract is the whole triplet: a failed bloom
        # build (ENOSPC, MemoryError) must not leave the data/index
        # behind looking complete.  Join the async close first — never
        # unlink under a live fdatasync/truncate.
        t_close.join(timeout=600)
        if not t_close.is_alive():
            _unlink_quiet(data_path, index_path)
        if t_bloom is None or not t_bloom.is_alive():
            _unlink_quiet(bloom_path)
        raise
    wrote_bloom = bloom_blob is not None
    assert wrote_bloom == wants_bloom

    at.to("close_wait")
    t_close.join(timeout=600)
    if t_close.is_alive():
        log.error(
            "pipeline writer close wedged; leaking native writer "
            "handle for %s", data_path
        )
        raise _PipelineError("writer close wedged")
    if close_ret["entries"] < 0:
        _unlink_quiet(data_path, index_path, bloom_path)
        raise _PipelineError("native writer close failed")
    assert close_ret["entries"] == entries
    assert int(data_size.value) == writer_state["bytes"]

    at.to("sidecar")
    if close_ret["crcs"] is not None:
        # Single-pass sidecar: the per-page CRCs streamed out of the
        # gather writer; the bloom blob is still in RAM.  Written
        # under the same journaled rename as the triplet.
        from ..storage import checksums

        dcrcs, icrcs = close_ret["crcs"]
        checksums.write_crcs(
            dir_path,
            output_index,
            dcrcs,
            icrcs,
            int(data_size.value),
            zlib.crc32(bloom_blob) if bloom_blob is not None else 0,
            bloom_blob is not None,
            ext=checksums.COMPACT_SUMS_FILE_EXT,
        )

    # The upload thread is joined: ``launches`` has counted them all,
    # and every launch has the one compiled shape.
    n_launches = next(launches)
    shape.update(
        launches=n_launches,
        partitions=n_parts,
        rows_launched=n_launches * launch_j * k2 * p2,
        rows_real=int(run_base[-1]),
        runs_in=len(runs),
        tie_entries=tie_entries,
    )
    return MergeResult(int(entries), int(data_size.value), wrote_bloom)
