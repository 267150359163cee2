"""The device compaction pipeline: one merge as a few one-way boxes.

::

  sources -> inputs -> plan -> launcher -> downloader -> decode -> output -> MergeResult
             reader    caller  upload      download      caller    writer, bloom
             pool              thread      thread                  and close threads

What each box is, which thread runs it, and what crosses each arrow:

* **inputs** (``_read_inputs``; the calling thread and a pool of
  ``_READERS`` reader threads).  Every run's index columns and data are
  read (O_DIRECT, C, GIL released) while the caller stages the 8-byte
  key prefixes of the runs already in.  Out: ``_Inputs`` — the runs,
  their four columns side by side (``off_cat``, ``ks_cat``, ``fs_cat``,
  ``pf_cat``; run ``i`` is ``[run_base[i], run_base[i + 1])``) and the
  run pointers C gathers from.
* **plan** (``_make_plan``; the calling thread).  Keyspace partitions
  cut at sampled prefixes so that every run's slice of a partition
  fits the kernel's rows — equal prefixes, hence equal keys, hence
  every dedup decision never cross a cut; a run need not spread over
  the keyspace (a table loaded in key order lies in a sliver of it),
  so wherever a slice still overflows, the partition is cut further,
  evenly, until all fit — the launch width and, on a mesh, the
  shardings of the launch-batch axis (partitions are disjoint sorted
  ranges: pure data parallelism, no exchange), the tombstone column
  and its count (the column is dropped again where the merge read no
  tombstone), the output's paths.  Out: ``_Plan``, immutable, or None
  where one equal-prefix group is larger than the kernel's rows (the
  caller of the pipeline then takes the single-shot path).
* **launcher** (``_Launches._upload``; the upload thread).  Per
  partition: each run's prefixes rebased to the partition's minimum
  and right-shifted until the span fits 32 bits — an order-preserving
  ONE-word operand; where the shift would collapse dense clusters
  (``_SHIFT_DUP_LIMIT``) the exact two-word operand instead.  Up to
  ``launch_j`` same-mode partitions fill one operand stack and go to
  the device as one vmapped launch of one compiled shape.  Out, in
  partition order: (partitions, the launch's device result).
* **downloader** (``_Launches._download``; the download thread).
  Reads a launch's result back: within a partition each run's
  survivors appear in position order, so the kernel returns only the
  bit-packed run-id sequence.  Out, in partition order: (partition,
  its packed words) on ``_Launches.results``, then None.
* **decode** (``_decode``; the calling thread).  One C pass rebuilds
  the permutation and flags entries equal under the DEVICE key.  The
  device key is a prefix and the device leaves ties in (run, position)
  order, so versions of one key in several runs ALWAYS tie, as do
  shift collisions, shared 8-byte prefixes and long keys: a second C
  pass (``dbeel_pipe_resolve_ties``) sorts every tie block where its
  records lie, in the runs' buffers, by (full key asc, newest ts,
  newest src) — the reference's merge order
  (/root/reference/src/storage_engine/lsm_tree.rs:1038-1066); the
  timestamp is read from the record, never inferred from the run —
  and marks a key's older versions.  It declines no block, so nothing
  is left to numpy (``_tie_fixup_numpy``, the lexsort it replaced, is
  what the tests hold it to).  Where the merge drops tombstones and
  read any, a third C pass (``dbeel_pipe_drop_tombstones``, span
  ``tomb_gc``) decides every key's newest version that is a delete:
  dropped, unless the gc-grace cutoff still holds it — its timestamp,
  read where the record lies, is at or above
  ``tombstone_drop_before`` (``compaction.drop_tombstones_mask``'s
  rule; in a collection used as a queue that is asked of half the
  entries).  The survivors are compressed.  Spans: ``decode`` and,
  nested in it, ``tie_fixup`` and ``tomb_gc``.  Out: a ``_Job`` — run,
  offset, key size and full size per surviving entry, in the partition
  set it was given.
* **output** (``_Output``; writer, bloom and close threads).  The
  writer gather-writes each job through the native handle (O_DIRECT
  stream, page CRCs accumulated inline); the bloom thread builds the
  filter beside it (``_Output``'s docstring); the close thread runs
  the final fdatasync + truncate; the caller writes the ``.sums``
  sidecar from the CRCs the close hands back.  Out: entries, bytes,
  bloom or not.

``_pipeline_merge_impl`` wires the boxes and runs the consumer loop
(wait for the device, take a partition set, decode, queue).  Output
bytes are identical to every other strategy's (golden tests).

Failure.  One ``_Stop`` a merge: the first error of any thread sets
it, every wait of every thread honours it within ``_POLL_S``, and the
caller re-raises that first error.  What is then undone is written
once each: ``_Output.abort`` (join; never free the handle or unlink
under a live pwrite / fdatasync — a wedged writer or close leaks the
handle and its files; otherwise the whole triplet goes, so a failed
bloom takes data and index with it) and ``_Launches.close`` (join,
return the process's launch permits, forget the stacks of launches
never read back).

Memory.  Every array of a merge that can reach 128 KiB is leased from
one process-wide block pool (ops/block_pool.py) through the merge's
``Leases``, on the calling thread, before the box's threads start and
in one order — the four columns, a data buffer a run, an index scratch a
reader (inputs); the tombstone column (plan); the operand stacks and
the rebase scratch (launcher); the ring of partition sets, the bloom's
hash pairs and bits (output) — so that a merge of the same inputs
takes the same blocks (only a two-word launch re-leases its stack, on
the upload thread).  A partition set returns to its ring when both
the writer and the bloom thread have consumed its raw pointers; an
operand stack when its launch's result has been read back (JAX may
read the host array until the transfer completes).  ``pipeline_merge``
owns the ``Leases``: every block goes back when the merge's threads
are joined — or is dropped, all of them where a thread is wedged.
What comes back is dirty: each stage fills what it reads, and only
logical lengths reach C.  The native writer handle belongs to
``_Output`` from open to the start of the close thread, then to that
thread; process-wide are only the pool and ``_LAUNCH_SLOTS``.
"""

from __future__ import annotations

import ctypes
import itertools
import logging
import os
import queue
import threading
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional, Sequence

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
# Operand stacks a merge: two launches in flight (_LAUNCH_SLOTS) and
# the next being filled.
_STACKS = 3
# Reader threads of the inputs: queue depth 2 on the virtio disk
# overlaps one run's tail with the next run's head, and the calling
# thread stages one run's prefixes meanwhile.
_READERS = 2
# Jobs queued ahead of the writer.  A partition's arrays are one set of
# a ring of this many + 2: write_q's, the one in the writer's hands,
# the one in the caller's.
_WRITE_AHEAD = 4
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
# How often a waiting thread looks at its merge's stop flag: a failed
# peer may never feed the queue or return the permit it waits for.
_POLL_S = 0.25
# How long a join waits for a thread that has been told to stop, and
# for one that is finishing real work (the writer's queue, the bloom's
# set phase, the close's fdatasync).  A thread alive after its join is
# wedged (``_Output.abort``).
_JOIN_STOPPED_S = 60
_JOIN_WORK_S = 600

_u8p = ctypes.POINTER(ctypes.c_uint8)
_u32p = ctypes.POINTER(ctypes.c_uint32)
_u64p = ctypes.POINTER(ctypes.c_uint64)
_i64p = ctypes.POINTER(ctypes.c_int64)


def _unlink_quiet(*paths: str) -> None:
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
            buf.ctypes.data_as(_u8p),
            ctypes.c_uint64(size),
        )
        if got != size:
            raise OSError(
                f"short read {got} != {size} for {source.data_path}"
            )
    return _Run(buf, size, offs, ks, fs)


def _stage_prefixes(lib, run: _Run, out: np.ndarray) -> None:
    """Fill run.prefix64 = ``out`` (the run's slice of the leased
    ``pf_cat``): the zero-padded 8-byte big-endian key prefix per entry
    as one native u64 value (splitters, searchsorted, the
    per-partition rebase that feeds the device operand, the native
    decoder).  In C, GIL released: a serving shard's loop keeps running
    while a merge stages."""
    n = run.offsets.size
    run.prefix64 = out
    if n == 0:
        return
    lib.dbeel_stage_prefixes(
        run.data.ctypes.data_as(_u8p),
        ctypes.c_uint64(run.size),
        run.offsets.ctypes.data_as(_u64p),
        run.key_size.ctypes.data_as(_u32p),
        ctypes.c_uint64(n),
        ctypes.c_uint64(ENTRY_HEADER_SIZE),
        out.view(np.uint8).ctypes.data_as(_u8p),
    )
    # The stager writes key bytes, big-endian: one swap in place
    # here, beside the reads, makes every later use native.
    out.view(">u8").byteswap(inplace=True)


@dataclass(frozen=True)
class _Inputs:
    """What the inputs box hands on.  Run ``i``'s entries are
    ``[run_base[i], run_base[i + 1])`` of the four columns."""

    runs: List[_Run]
    run_base: np.ndarray  # (n_runs + 1,) int64
    off_cat: np.ndarray  # u64 within-run record offsets
    ks_cat: np.ndarray  # u32 key sizes
    fs_cat: np.ndarray  # u32 record sizes
    pf_cat: np.ndarray  # u64 native-endian 8-byte key prefixes
    run_ptrs: ctypes.Array  # each run's data, for C to gather from
    run_sizes: np.ndarray  # u64 logical bytes of each run's data
    total_rows: int
    total_bytes: int


def _read_inputs(lib, sources: Sequence, mem: Leases, span) -> _Inputs:
    """Spans ``read_run`` (a reader), ``stage_prefixes`` (the caller)."""
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
    for _ in range(min(_READERS, len(sources))):
        scratch_q.put(
            mem.array(int(counts_all.max()) * INDEX_ENTRY_SIZE)
        )

    def read(i, source):
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

    runs = []
    with ThreadPoolExecutor(
        max_workers=_READERS, thread_name_prefix="dbeel-pipeline-read"
    ) as io:
        futs = [io.submit(read, i, s) for i, s in enumerate(sources)]
        for i, f in enumerate(futs):
            r = f.result()
            with span("stage_prefixes", run=i):
                _stage_prefixes(
                    lib, r, pf_cat[run_base[i] : run_base[i + 1]]
                )
            runs.append(r)
    run_ptrs = (_u8p * max(1, len(runs)))(
        *[r.data.ctypes.data_as(_u8p) for r in runs]
    )
    run_sizes = np.array([r.size for r in runs], dtype=np.uint64)
    return _Inputs(
        runs, run_base, off_cat, ks_cat, fs_cat, pf_cat, run_ptrs,
        run_sizes, total_rows, int(run_sizes.sum()),
    )


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
    # Split every partition in which a run's slice overflows p2, until
    # none does: runs need not spread evenly over the keyspace (a table
    # loaded in key order holds all its entries in a sliver of it), so
    # the sampled cuts are only where the splitting starts.  A slice
    # that needs q kernels' rows is cut at q - 1 evenly spaced prefixes
    # inside it (its median where it overflows by less than double),
    # the slice being that of the first run that overflows there; if
    # no strictly-interior cut exists the range is one equal-prefix
    # group — unsplittable at this kernel size.
    while True:
        counts = np.stack([np.diff(b) for b in bounds])
        over = counts > p2
        crowded = np.flatnonzero(over.any(axis=0))
        if not crowded.size:
            break
        cuts = set()
        for p in crowded.tolist():
            ri = int(np.argmax(over[:, p]))
            b = bounds[ri]
            uniq = np.unique(runs[ri].prefix64[int(b[p]) : int(b[p + 1])])
            if uniq.size < 2:
                return None  # one equal-prefix group > kernel rows
            # side="right" cuts put entries <= splitter left, so any
            # value strictly below the slice maximum leaves both sides
            # nonempty.
            q = -(-int(counts[ri, p]) // p2)
            cuts.update(
                uniq[np.arange(1, q) * (uniq.size - 1) // q].tolist()
            )
        splitters = np.array(
            sorted(set(splitters.tolist()) | cuts), dtype=np.uint64
        )
        bounds = bounds_for(splitters)
    return splitters, bounds, p2


@dataclass(frozen=True)
class _Plan:
    """What the plan box decides, for every later box to read."""

    launch_j: int  # partitions a launch: its batch axis
    # Mesh mode only (else None): the batch axis of the one-word and
    # two-word operands and of their counts, sharded over the mesh.
    shard32: object
    shard64: object
    shard_counts: object
    bounds: Optional[list]  # per run, n_parts + 1 cut positions
    n_parts: int
    p2: int  # kernel rows per (run, partition)
    k2: int  # kernel runs: pow2 of the run count
    pack_bits: int  # bits a run-id in the kernel's result
    # Which entries are tombstones; None where the merge keeps them or
    # reads none, so that no partition has tombstone work.
    tomb_cat: Optional[np.ndarray]
    tombstones_in: int  # how many, where the merge may drop them
    max_np: int  # most entries of one partition over all runs
    dir_path: str
    output_index: int

    def path(self, ext: str) -> str:
        """A file of the output."""
        return f"{self.dir_path}/{file_name(self.output_index, ext)}"


def _make_plan(
    inputs: _Inputs,
    mesh,
    keep_tombstones: bool,
    dir_path: str,
    output_index: int,
    mem: Leases,
) -> Optional[_Plan]:
    from .bitonic import rid_pack_bits

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

    chosen = _choose_partitions(inputs.runs, launch_j)
    if chosen is None:
        return None
    _splitters, bounds, p2 = chosen
    n_parts = (bounds[0].size - 1) if bounds is not None else 0
    k2 = _pow2(max(1, len(inputs.runs)))

    tomb_cat = None
    tombstones_in = 0
    if not keep_tombstones:
        tomb_cat = mem.array(inputs.total_rows, np.bool_)
        # In steps whose temporaries stay small enough for the heap.
        hdr = np.uint32(ENTRY_HEADER_SIZE)
        for lo in range(0, inputs.total_rows, _TOMB_STEP):
            hi = lo + _TOMB_STEP
            np.equal(
                inputs.fs_cat[lo:hi],
                inputs.ks_cat[lo:hi] + hdr,
                out=tomb_cat[lo:hi],
            )
        tombstones_in = int(np.count_nonzero(tomb_cat))
        if not tombstones_in:
            tomb_cat = None
    return _Plan(
        launch_j=launch_j,
        shard32=shard32,
        shard64=shard64,
        shard_counts=shard_counts,
        bounds=bounds,
        n_parts=n_parts,
        p2=p2,
        k2=k2,
        pack_bits=rid_pack_bits(k2),
        tomb_cat=tomb_cat,
        tombstones_in=tombstones_in,
        max_np=(
            int(sum(np.diff(b) for b in bounds).max()) if n_parts else 0
        ),
        dir_path=dir_path,
        output_index=output_index,
    )


class _PipelineError(Exception):
    pass


class _Stopped(_PipelineError):
    """Raised in a wait of a merge that has failed elsewhere."""


class _Stop:
    """One merge's stop flag, the first error that set it, and the
    only ways its threads start and wait.  A thread that fails calls
    ``fail``; every other thread of the merge leaves its next wait
    with ``_Stopped`` and ends quietly; the calling thread re-raises
    ``error``.  ``threads``: the caller's list, for it to see whether
    one outlived the merge."""

    def __init__(self, threads: List[threading.Thread]) -> None:
        self.error: Optional[BaseException] = None
        self._set = threading.Event()
        self._lock = threading.Lock()
        self._threads = threads

    def fail(self, error: BaseException) -> None:
        with self._lock:
            if self.error is None:
                self.error = error
        self._set.set()

    def check(self) -> None:
        if self._set.is_set():
            raise _Stopped("pipeline stopped")

    def wait(self, attempt, *args):
        """``attempt(*args, timeout=)`` — a queue's ``get`` or ``put``,
        a semaphore's ``acquire`` — until it succeeds: a peer that
        failed may never feed the queue or return the permit, so no
        thread of a merge parks without looking at its stop flag."""
        while True:
            self.check()
            try:
                got = attempt(*args, timeout=_POLL_S)
            except (queue.Empty, queue.Full):
                continue
            if got is not False:
                return got

    def spawn(self, name: str, target, *args) -> threading.Thread:
        def run():
            try:
                target(*args)
            except _Stopped:
                pass
            except BaseException as e:  # the caller re-raises it
                self.fail(e)

        t = threading.Thread(
            target=run, name=f"dbeel-pipeline-{name}", daemon=True
        )
        self._threads.append(t)
        t.start()
        return t


class _PartSet:
    """One partition's arrays on their way from the decode to the
    writer and the bloom thread, leased for ``rows`` entries; each
    partition uses their heads.  ``holders``: the threads that have
    yet to consume the partition's raw pointers."""

    __slots__ = (
        "gidx", "rids32", "tieb", "keep", "sel", "src_run",
        "src_off", "ks_sel", "fs_sel", "holders",
    )

    def __init__(self, mem: Leases, rows: int) -> None:
        self.gidx = mem.array(rows, np.int64)  # decoded global indices
        self.rids32 = mem.array(rows, np.uint32)  # and their runs
        self.tieb = mem.array(rows, np.uint8)  # device-key tie flags
        self.keep = mem.array(rows, np.bool_)
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
    nested ``stage_prefixes`` (in ``read_stage``), ``tie_fixup`` and
    ``tomb_gc`` (in ``decode``) overlap them and say what the caller
    was waiting on.  The merge's shape (launches, partitions, rows
    launched and real, runs, tie entries, entries written, tombstones
    read and tombstones the grace kept) is counted under
    ``get_stats.compaction.shape``."""
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


class _Part(NamedTuple):
    """One partition as it travels from the launcher to the decode:
    ``_plan_operand``'s choices."""

    p: int
    counts: np.ndarray  # (k2,) u32 entries per run
    los: np.ndarray  # per run, where its slice starts in the run
    mode32: bool  # the one-word operand
    minpf: int
    shift: int


class _Launches:
    """The launcher and the downloader of one merge: partitions in,
    ``results`` out — (``_Part``, its packed run-ids or None where it
    is empty) in partition order, then None.

    Owns the operand stacks — flat u32 buffers of the one-word
    launch's size (the two-word form re-leases twice that), cycled
    through ``_stack_free``: JAX may read a stack handed to device_put
    until the transfer completes, so a stack is refilled only after its
    launch's result has been read back — the per-partition permits
    ``_in_flight``, and the ``_LAUNCH_SLOTS`` permits this merge
    holds, one token each in ``_held``.  ``close`` gives back whatever
    an abort left."""

    def __init__(
        self, plan: _Plan, inputs: _Inputs, mem: Leases, span, stop: _Stop
    ) -> None:
        import jax

        # Looked up at each merge: whoever wraps ops.bitonic's names
        # (the benchmark's LaunchSpy, the tests) sees every launch.
        from . import bitonic

        self._device_put = jax.device_put
        self._kernels = {
            True: bitonic.merge_runs_prefix32_packed_batch_kernel,
            False: bitonic.merge_runs_prefix64_packed_batch_kernel,
        }
        self._plan, self._inputs = plan, inputs
        self._mem, self._span, self._stop = mem, span, stop
        self.results: "queue.Queue" = queue.Queue()
        self._kernel_q: "queue.Queue" = queue.Queue()
        # Sized for two full launches: the upload thread holds up to
        # launch_j permits while it assembles one, so fewer would
        # deadlock the assembly itself.
        self._in_flight = threading.Semaphore(2 * plan.launch_j)
        self._held: list = []
        # Launches so far; every one has the one compiled shape.
        self.launched = 0
        # True once every launch has been read back.
        self._complete = False
        self._threads: List[threading.Thread] = []
        self._stack_words = plan.launch_j * plan.k2 * plan.p2
        self._stacks = [
            mem.array(self._stack_words, np.uint32)
            for _ in range(min(_STACKS, -(-plan.n_parts // plan.launch_j)))
        ]
        self._stack_free: "queue.Queue" = queue.Queue()
        for buf in self._stacks:
            self._stack_free.put(buf)
        # The upload thread's scratch for rebased prefixes.
        self._tmp = mem.array(plan.p2 if plan.n_parts else 0, np.uint64)

    def start(self) -> None:
        self._threads = [
            self._stop.spawn("upload", self._upload),
            self._stop.spawn("download", self._download),
        ]

    def _upload(self) -> None:
        plan, inputs, span = self._plan, self._inputs, self._span
        parts: List[_Part] = []  # the pending launch
        taken = None  # the stack it is filled into
        for p in range(plan.n_parts):
            with span("slot_wait", part=p):
                self._stop.wait(self._in_flight.acquire)
            with span("operand", part=p):
                slices, *choice = _plan_operand(
                    inputs.pf_cat, inputs.run_base, plan.bounds, p,
                    plan.k2, self._tmp,
                )
            part = _Part(p, *choice)
            if slices is None:
                # Keep strict partition order: launch whatever is
                # pending first, THEN the empty marker (the downloader
                # releases this partition's permit).
                parts, taken = self._launch(parts, taken)
                self._kernel_q.put(([part], None, None, None))
                continue
            if parts and part.mode32 != parts[0].mode32:
                parts, taken = self._launch(parts, taken)
            if taken is None:
                with span("slot_wait", part=p):
                    taken = self._take_stack(part.mode32)
            with span("operand", part=p):
                _fill_operand(
                    taken[1][len(parts)], slices, part.mode32,
                    part.minpf, part.shift, self._tmp,
                )
            parts.append(part)
            if len(parts) == plan.launch_j:
                parts, taken = self._launch(parts, taken)
        self._launch(parts, taken)
        self._kernel_q.put(None)

    def _take_stack(self, mode32: bool):
        """A free stack as (buffer, array in the launch's shape)."""
        plan = self._plan
        buf = self._stop.wait(self._stack_free.get)
        words = self._stack_words * (1 if mode32 else 2)
        if buf.size < words:
            slot = next(i for i, b in enumerate(self._stacks) if b is buf)
            self._mem.give(buf)
            buf = self._stacks[slot] = self._mem.array(words, np.uint32)
        tail = () if mode32 else (2,)
        return buf, buf[:words].reshape(
            (plan.launch_j, plan.k2, plan.p2) + tail
        )

    def _launch(self, parts: List[_Part], taken):
        """One vmapped launch over up to ``launch_j`` same-mode
        partitions, empty-slot padded to a single compiled shape; the
        batch axis shards over the mesh when one is supplied.
        ``taken``: the stack from _take_stack, its first len(parts)
        slots filled.  Returns the next pending launch: none."""
        if not parts:
            return [], None
        plan, span = self._plan, self._span
        mode32 = parts[0].mode32
        buf, stack = taken
        # One launch's spans share ``launch`` (its ordinal in the
        # merge) and ``part`` (its first partition).
        ids = {"launch": self.launched, "part": parts[0].p}
        self.launched += 1
        with span("operand", **ids):
            stack[len(parts) :] = SENTINEL
            counts = np.zeros((plan.launch_j, plan.k2), dtype=np.uint32)
            for slot, part in enumerate(parts):
                counts[slot] = part.counts
        with span("slot_wait", **ids):
            self._stop.wait(_LAUNCH_SLOTS.acquire)
        self._held.append(None)
        with span("h2d_dispatch", **ids):
            sharding = plan.shard32 if mode32 else plan.shard64
            if sharding is not None:
                dev = self._device_put(stack, sharding)
                cnt = self._device_put(counts, plan.shard_counts)
            else:
                dev = self._device_put(stack)
                cnt = counts
            out = self._kernels[mode32](dev, cnt, plan.pack_bits)
        self._kernel_q.put((parts, out, ids, buf))
        return [], None

    def _download(self) -> None:
        while True:
            item = self._stop.wait(self._kernel_q.get)
            if item is None:
                self._complete = True
                self.results.put(None)
                return
            parts, out, ids, buf = item
            if out is None:
                self._in_flight.release()  # re-balance the empty slot
                self.results.put((parts[0], None))
                continue
            # The kernel's completion + the d2h of its bit-packed
            # run-ids.
            with self._span("d2h", **ids):
                words = np.asarray(out)
            self._stack_free.put(buf)
            self._release_slot()
            for slot, part in enumerate(parts):
                self._in_flight.release()
                self.results.put((part, words[slot]))

    def _release_slot(self) -> bool:
        try:
            self._held.pop()
        except IndexError:
            return False
        _LAUNCH_SLOTS.release()
        return True

    def close(self) -> None:
        """When the consumer has left its loop, either way: join both
        threads, return the launch permits an abort left taken, and —
        a launch that was never read back may still be reading its
        stack — forget, not return, an incomplete merge's stacks."""
        for t in self._threads:
            t.join(timeout=_JOIN_STOPPED_S)
        while self._release_slot():
            pass
        if not self._complete:
            for buf in self._stacks:
                self._mem.forget(buf)


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


def _tie_fixup_numpy(inputs: _Inputs, gidx, rids32, tieb, keep) -> int:
    """What ``dbeel_pipe_resolve_ties`` does, in numpy, as the pipeline
    did it until ISSUE 34: the tie blocks gathered into padded key
    words and timestamps and ordered by one lexsort a key width
    (``columnar.tie_block_sort``).  Nothing on the merge path calls it
    any more (~1.2 us an entry on the caller's thread); the tests hold
    the C pass to it.  In place in ``gidx``, ``rids32`` and ``keep``;
    returns the entries in tie blocks."""
    runs, run_base = inputs.runs, inputs.run_base
    off_cat, ks_cat = inputs.off_cat, inputs.ks_cat
    keep.fill(True)
    positions, block_id = columnar.tie_positions_and_blocks(
        tieb[1:].view(np.bool_)
    )
    if positions.size:
        sel_t = gidx[positions]
        ks_t = ks_cat[sel_t]
        ent_w = columnar.tie_block_widths(block_id, ks_t)
        for w in np.unique(ent_w):
            bm = ent_w == w
            kwords, inv_ts, inv_src = _gather_tie_arrays(
                runs, run_base, off_cat, ks_cat, sel_t[bm], int(w)
            )
            order, dup = columnar.tie_block_sort(
                block_id[bm], kwords, ks_t[bm], inv_ts, inv_src
            )
            gidx[positions[bm]] = sel_t[bm][order]
            # The reorder moved entries across runs: refresh the
            # run-id column at exactly those positions.
            rids32[positions[bm]] = (
                np.searchsorted(
                    run_base, gidx[positions[bm]], side="right"
                )
                - 1
            ).astype(np.uint32)
            keep[positions[bm]] = ~dup
    return int(positions.size)


class _Job(NamedTuple):
    """What the decode hands the output: partition ``p``'s ``m``
    surviving entries in output order — run, offset in the run, key
    size and record size of each — as heads of ``pset``'s arrays."""

    p: int
    pset: _PartSet
    m: int
    nbytes: int
    src_run: np.ndarray  # u32
    src_off: np.ndarray  # u64
    ks_sel: np.ndarray  # u32
    fs_sel: np.ndarray  # u32


def _decode(
    lib,
    inputs: _Inputs,
    plan: _Plan,
    part: _Part,
    packed: np.ndarray,
    pset: _PartSet,
    span,
    tombstone_drop_before: "int | None",
):
    """One partition from the downloader's packed run-ids to the
    writer's job, in ``pset``.  Returns (job, entries the device
    order left tied, tombstones the grace kept); the job is None
    where no entry survives.  Spans ``tie_fixup`` and ``tomb_gc``,
    nested in the caller's ``decode``."""
    runs, run_base = inputs.runs, inputs.run_base
    off_cat, ks_cat = inputs.off_cat, inputs.ks_cat
    n_p = int(part.counts.sum())
    # One C pass: unpack rids, per-run counters -> permutation (the
    # comparator is a total order and runs are pre-sorted, so each
    # run's entries appear in increasing position order), device-key
    # tie flags.
    gidx = pset.gidx[:n_p]
    rids32 = pset.rids32[:n_p]
    tieb = pset.tieb[:n_p]
    rc = lib.dbeel_pipe_decode(
        np.ascontiguousarray(packed).ctypes.data_as(_u32p),
        n_p,
        plan.pack_bits,
        len(runs),
        np.ascontiguousarray(
            part.counts[: len(runs)], dtype=np.uint32
        ).ctypes.data_as(_u32p),
        np.ascontiguousarray(part.los, dtype=np.int64).ctypes.data_as(
            _i64p
        ),
        run_base.ctypes.data_as(_i64p),
        inputs.pf_cat.ctypes.data_as(_u64p),
        part.minpf,
        part.shift,
        1 if part.mode32 else 0,
        gidx.ctypes.data_as(_i64p),
        rids32.ctypes.data_as(_u32p),
        tieb.ctypes.data_as(_u8p),
    )
    if rc != 0:
        raise _PipelineError("packed run-id decode mismatch")

    # Tie blocks: adjacent entries equal under the DEVICE sort key
    # (shifted u32 or exact 8B prefix) — versions of one key above
    # all — are put in the reference's order and older versions marked,
    # in one C pass over the runs' own bytes (GIL released).
    keep = pset.keep[:n_p]
    with span("tie_fixup", part=part.p):
        ties = lib.dbeel_pipe_resolve_ties(
            n_p,
            tieb.ctypes.data_as(_u8p),
            gidx.ctypes.data_as(_i64p),
            rids32.ctypes.data_as(_u32p),
            inputs.run_ptrs,
            inputs.run_sizes.ctypes.data_as(_u64p),
            off_cat.ctypes.data_as(_u64p),
            ks_cat.ctypes.data_as(_u32p),
            ENTRY_HEADER_SIZE,
            keep.view(np.uint8).ctypes.data_as(_u8p),
        )
    if ties < 0:
        raise _PipelineError("a tied entry's key lies outside its run")

    # Tombstones: a key's newest version that is a delete is dropped,
    # unless the gc-grace cutoff still holds it (its timestamp, read
    # where the record lies, is at or above the cutoff; a cutoff of
    # None or 0 holds none: compaction.drop_tombstones_mask's rule).
    # One C pass, GIL released.
    tomb_kept = 0
    if plan.tomb_cat is not None:
        with span("tomb_gc", part=part.p):
            tomb_kept = lib.dbeel_pipe_drop_tombstones(
                n_p,
                gidx.ctypes.data_as(_i64p),
                rids32.ctypes.data_as(_u32p),
                inputs.run_ptrs,
                inputs.run_sizes.ctypes.data_as(_u64p),
                off_cat.ctypes.data_as(_u64p),
                plan.tomb_cat.view(np.uint8).ctypes.data_as(_u8p),
                0 if tombstone_drop_before else 1,
                max(0, tombstone_drop_before or 0),
                keep.view(np.uint8).ctypes.data_as(_u8p),
            )
        if tomb_kept < 0:
            raise _PipelineError(
                "a tombstone's header lies outside its run"
            )
    m = int(np.count_nonzero(keep))
    if m == 0:
        return None, ties, tomb_kept
    if m != n_p:
        sel = np.compress(keep, gidx, out=pset.sel[:m])
        src_run = np.compress(keep, rids32, out=pset.src_run[:m])
    else:
        sel = gidx
        src_run = rids32
    src_off = np.take(off_cat, sel, out=pset.src_off[:m], mode="clip")
    ks_sel = np.take(ks_cat, sel, out=pset.ks_sel[:m], mode="clip")
    fs_sel = np.take(inputs.fs_cat, sel, out=pset.fs_sel[:m], mode="clip")
    return (
        _Job(
            part.p, pset, m, int(fs_sel.sum()), src_run, src_off,
            ks_sel, fs_sel,
        ),
        ties,
        tomb_kept,
    )


class _Output:
    """The output of one merge: the native gather-writer's handle, the
    writer, bloom and close threads, the ring of partition sets.

    The bloom filter is built beside the stream, not after it.  Its
    size follows from the FINAL entry count, a key's two hashes do
    not: the bloom thread hashes each job's keys as it is queued for
    the writer (the job's own arrays, the same run pointers), and once
    ``finish`` has posted the count it sets the bits from the stored
    pairs and writes and fsyncs the bloom file — under the writer's
    last partitions and the close's fdatasync.  Hashing is
    speculative: only a merge whose INPUT passes ``bloom_min_size``
    can end with an output that does."""

    def __init__(
        self,
        lib,
        plan: _Plan,
        inputs: _Inputs,
        mem: Leases,
        span,
        stop: _Stop,
        bloom_min_size: int,
    ) -> None:
        self._lib = lib
        # ``inputs``: the raw pointers in run_ptrs are only as alive
        # as the runs' buffers, whatever becomes of the caller's frame.
        self._plan, self._inputs = plan, inputs
        self._span, self._stop = span, stop
        self._bloom_min_size = bloom_min_size
        self._write_q: "queue.Queue" = queue.Queue(maxsize=_WRITE_AHEAD)
        # Unbounded: write_q paces the caller, and a partition hashes
        # several times faster than it gather-writes.
        self._bloom_q: "queue.Queue" = queue.Queue()
        # Each set is sized for the largest partition and dirty from
        # its last; a set is free again when BOTH the writer and the
        # bloom thread have consumed its raw pointers.
        self._sets_free: "queue.Queue" = queue.Queue()
        for _ in range(min(plan.n_parts, _WRITE_AHEAD + 2)):
            self._sets_free.put(_PartSet(mem, plan.max_np))
        self._sets_lock = threading.Lock()
        self._will_bloom = inputs.total_bytes >= bloom_min_size
        if self._will_bloom:
            # Two hashes a key, and the bits of the largest filter the
            # output can need (it never has more entries than the
            # input).
            self._pairs = mem.array(2 * inputs.total_rows, np.uint32)
            self._bits = mem.array(
                (BloomFilter.size_for(inputs.total_rows)[0] + 7) // 8
            )
        self._writer = self._bloomer = self._closer = None
        self.queued = self.queued_bytes = 0  # by the caller
        self._wrote = self._wrote_bytes = 0  # by the writer
        self._bloom_blob: Optional[bytes] = None
        self._data_size = ctypes.c_uint64(0)
        self._closed_entries = -1
        self._crcs = None
        # Single-pass sidecar: the gather writer's inline page-CRC
        # accumulators are armed, so the .sums sidecar is written from
        # the bytes AS they streamed through — no triplet re-read.
        self._data_path = plan.path(COMPACT_DATA_FILE_EXT)
        self._index_path = plan.path(COMPACT_INDEX_FILE_EXT)
        # 0 where the output cannot be opened (a disk fault).
        self.handle = lib.dbeel_writer_open2(
            self._data_path.encode(), self._index_path.encode(), 1
        )

    def start(self) -> None:
        # Gather-writes run off the decode thread so partition p+1's
        # decode overlaps partition p's disk write (GIL released).
        self._writer = self._stop.spawn("writer", self._write)
        if self._will_bloom:
            self._bloomer = self._stop.spawn("bloom", self._bloom)

    # ---- the ring of partition sets ---------------------------------

    def sets_busy(self) -> bool:
        """Every set is queued or being written: the writer's queue is
        full, one step early."""
        return self._sets_free.empty()

    def take_set(self) -> _PartSet:
        return self._stop.wait(self._sets_free.get)

    def give_set(self, pset: _PartSet) -> None:
        """A set no job went out in."""
        self._sets_free.put(pset)

    def _set_done(self, pset: _PartSet) -> None:
        with self._sets_lock:
            pset.holders -= 1
            idle = pset.holders == 0
        if idle:
            self._sets_free.put(pset)

    # ---- the stream -------------------------------------------------

    def put(self, job: _Job) -> None:
        """Queue ``job`` for the bloom thread and the writer; waits
        where the writer is ``_WRITE_AHEAD`` jobs behind."""
        job.pset.holders = 2 if self._bloomer is not None else 1
        self.queued += job.m
        self.queued_bytes += job.nbytes
        if self._bloomer is not None:
            self._bloom_q.put(job)
        self._stop.wait(self._write_q.put, job)

    def _write(self) -> None:
        while True:
            job = self._stop.wait(self._write_q.get)
            if job is None:
                return
            with self._span("gather_write", part=job.p):
                rc = self._lib.dbeel_writer_put(
                    self.handle,
                    self._inputs.run_ptrs,
                    job.src_run.ctypes.data_as(_u32p),
                    job.src_off.ctypes.data_as(_u64p),
                    job.ks_sel.ctypes.data_as(_u32p),
                    job.fs_sel.ctypes.data_as(_u32p),
                    ctypes.c_uint64(job.m),
                )
            self._set_done(job.pset)
            if rc != 0:
                raise _PipelineError("native gather-write failed")
            self._wrote += job.m
            self._wrote_bytes += job.nbytes

    def _bloom(self) -> None:
        hashed = 0
        while True:
            item = self._stop.wait(self._bloom_q.get)
            if not isinstance(item, _Job):
                break
            with self._span("bloom_hash", part=item.p):
                self._lib.dbeel_bloom_hash_gather(
                    self._inputs.run_ptrs,
                    item.src_run.ctypes.data_as(_u32p),
                    item.src_off.ctypes.data_as(_u64p),
                    item.ks_sel.ctypes.data_as(_u32p),
                    item.m,
                    ENTRY_HEADER_SIZE,
                    _SEED1,
                    _SEED2,
                    self._pairs[2 * hashed :].ctypes.data_as(_u32p),
                )
            hashed += item.m
            self._set_done(item.pset)
        # ``item`` is the output's entry count, or 0 where the output
        # ended under bloom_min_size: the hashes are dropped.
        if not item:
            return
        assert item == hashed
        with self._span("bloom_set"):
            num_bits, num_hashes = BloomFilter.size_for(hashed)
            bits = self._bits[: (num_bits + 7) // 8]
            bits.fill(0)
            bloom = BloomFilter(num_bits, num_hashes, bits=bits)
            self._lib.dbeel_bloom_set_hashes(
                bloom.bits.ctypes.data_as(_u8p),
                bloom.num_bits,
                bloom.num_hashes,
                self._pairs.ctypes.data_as(_u32p),
                hashed,
            )
            self._bloom_blob = _write_bloom(
                self._plan.dir_path, self._plan.output_index, bloom
            )

    def _close(self) -> None:
        # CRC handoff caps: the merged output can never exceed the sum
        # of its inputs (dedup/tombstone-drop only shrink it).
        dcap = self._inputs.total_bytes // 4096 + 2
        icap = self._inputs.total_rows * 16 // 4096 + 2
        dcrc = (ctypes.c_uint32 * dcap)()
        icrc = (ctypes.c_uint32 * icap)()
        nd = ctypes.c_uint64(0)
        ni = ctypes.c_uint64(0)
        # Inside the call: the final fdatasync + truncate.
        with self._span("fsync"):
            rc = self._lib.dbeel_writer_close2(
                self.handle,
                ctypes.byref(self._data_size),
                dcrc,
                dcap,
                icrc,
                icap,
                ctypes.byref(nd),
                ctypes.byref(ni),
            )
        if rc == -2:
            # Triplet closed fine; only the CRC handoff was refused —
            # the LSM's counted post-hoc sidecar covers it.  Entries
            # are known from the writer's own accounting.
            rc = self._wrote
        elif rc >= 0:
            self._crcs = (list(dcrc[: nd.value]), list(icrc[: ni.value]))
        self._closed_entries = rc

    def finish(self, at: Stages):
        """Every job is queued.  Returns (entries, data bytes, whether
        a bloom was written) of the complete output; the caller's
        stages ``wait_writer``, ``bloom``, ``close_wait``, ``sidecar``."""
        # The output's entry count and size are known while the writer
        # still has its queue to write: the bloom's set phase starts
        # here.
        wants_bloom = (
            self.queued > 0 and self.queued_bytes >= self._bloom_min_size
        )
        if self._bloomer is not None:
            self._bloom_q.put(self.queued if wants_bloom else 0)
        at.to("wait_writer")
        self._stop.wait(self._write_q.put, None)
        self._join(self._writer, "writer thread")
        assert (self._wrote, self._wrote_bytes) == (
            self.queued, self.queued_bytes,
        )
        # The close runs on its own thread: nothing below depends on
        # its completing.  The bloom thread has had the count since the
        # last partition was queued, so ``bloom`` is a join — what the
        # set phase and the bloom file's fsync have left over runs
        # beside the close's device write-cache flush — and
        # ``close_wait`` is whatever of that flush remains.
        at.to("bloom")
        self._closer = self._stop.spawn("close", self._close)
        if self._bloomer is not None:
            self._join(self._bloomer, "bloom thread")
        assert (self._bloom_blob is not None) == wants_bloom
        at.to("close_wait")
        self._join(self._closer, "writer close")
        if self._closed_entries < 0:
            raise _PipelineError("native writer close failed")
        assert self._closed_entries == self._wrote
        data_size = int(self._data_size.value)
        assert data_size == self._wrote_bytes
        at.to("sidecar")
        if self._crcs is not None:
            # The per-page CRCs streamed out of the gather writer; the
            # bloom blob is still in RAM.  Written under the same
            # journaled rename as the triplet.
            from ..storage import checksums

            blob = self._bloom_blob
            checksums.write_crcs(
                self._plan.dir_path,
                self._plan.output_index,
                *self._crcs,
                data_size,
                zlib.crc32(blob) if blob is not None else 0,
                blob is not None,
                ext=checksums.COMPACT_SUMS_FILE_EXT,
            )
        return self._wrote, data_size, wants_bloom

    def _join(self, thread: threading.Thread, what: str) -> None:
        """Wait for ``thread`` to finish its work; its error, or any
        other thread's, is the merge's."""
        thread.join(timeout=_JOIN_WORK_S)
        self._stop.check()
        if thread.is_alive():
            raise _PipelineError(f"{what} wedged")

    def abort(self) -> None:
        """The merge has failed (its stop flag is set): undo the
        output.  The one place that says how.  Its contract is the
        whole triplet — a failed bloom build (ENOSPC, MemoryError)
        must not leave data and index behind looking complete — and a
        file is never unlinked, nor the handle freed, under a live
        pwrite / fdatasync / truncate: a writer or close thread that
        outlives its join is wedged, and the handle and the partial
        files are leaked to it (``pipeline_merge`` then drops the
        merge's blocks).  The bloom thread is joined before the runs'
        buffers can go: it hashes through run_ptrs."""
        self._writer.join(timeout=_JOIN_STOPPED_S)
        if self._bloomer is not None:
            self._bloomer.join(timeout=_JOIN_STOPPED_S)
        if self._closer is not None:
            self._closer.join(timeout=_JOIN_WORK_S)
        if self._bloomer is None or not self._bloomer.is_alive():
            _unlink_quiet(self._plan.path(COMPACT_BLOOM_FILE_EXT))
        for t in (self._writer, self._closer):
            if t is not None and t.is_alive():
                log.error(
                    "pipeline %s wedged; leaking native writer handle "
                    "for %s", t.name, self._data_path,
                )
                return
        if self._closer is None:
            # The handle is still ours; a close frees it itself.
            self._lib.dbeel_writer_abort(self.handle)
        _unlink_quiet(self._data_path, self._index_path)


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
    """The coordinator: wires the boxes of the module docstring and
    runs the consumer loop between the downloader and the output.
    ``shape``: filled, where the merge produces an output, with its
    ``compaction.PIPELINE_SHAPE`` counts.  ``mem``: what every large
    array is leased from; the caller closes it.  ``threads``: every
    thread started here that works in leased memory is appended, for
    the caller to see whether one is still alive."""
    from ..storage import native as native_mod

    lib = native_mod.require()
    span = at.span  # this merge's stages on its other threads
    inputs = _read_inputs(lib, sources, mem, span)
    at.to("plan")
    plan = _make_plan(
        inputs, mesh, keep_tombstones, dir_path, output_index, mem
    )
    if plan is None:
        return None
    stop = _Stop(threads)
    launches = _Launches(plan, inputs, mem, span, stop)
    output = _Output(lib, plan, inputs, mem, span, stop, bloom_min_size)
    if not output.handle:
        # The single-shot writer meets the same disk and raises its
        # errno.
        return None
    launches.start()
    output.start()
    tie_entries = tombstones_kept = 0
    try:
        while True:
            at.to("wait_device")
            item = stop.wait(launches.results.get)
            if item is None:
                break
            part, packed = item
            if packed is None:  # an empty partition
                at.to("decode", part=part.p)
                continue
            if output.sets_busy():
                at.to("wait_writer", part=part.p)
            pset = output.take_set()
            at.to("decode", part=part.p)
            job, ties, tomb_kept = _decode(
                lib, inputs, plan, part, packed, pset, span,
                tombstone_drop_before,
            )
            tie_entries += ties
            tombstones_kept += tomb_kept
            if job is None:
                output.give_set(pset)
                continue
            at.to("wait_writer", part=part.p)
            output.put(job)
            if throttle is not None:
                # Latency class: one partition is the consume quantum —
                # pay back CPU to serving between partitions.
                at.to("throttle", part=part.p)
                throttle.tick()
        entries, data_size, wrote_bloom = output.finish(at)
    except BaseException as e:
        stop.fail(e)
        output.abort()
        raise stop.error  # the first, whichever thread met it
    finally:
        launches.close()
    shape.update(
        launches=launches.launched,
        partitions=plan.n_parts,
        rows_launched=launches.launched * plan.launch_j * plan.k2 * plan.p2,
        rows_real=inputs.total_rows,
        runs_in=len(inputs.runs),
        tie_entries=tie_entries,
        entries_out=int(entries),
        tombstones_in=plan.tombstones_in,
        tombstones_kept=tombstones_kept,
    )
    return MergeResult(int(entries), int(data_size), wrote_bloom)
