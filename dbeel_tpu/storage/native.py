"""ctypes bindings for the C++ native runtime (native/).

Builds ``native/build/libdbeel_native.so`` on first use (make) and
exposes NativeMergeStrategy — the reference-grade CPU k-way heap merge
(the honest CPU baseline for BASELINE.md's ≥5x target) with native
bloom building — plus a murmur3_32 parity hook used by tests.

Everything degrades gracefully to the pure-Python/numpy implementations
when no C++ toolchain is available (get_strategy('native') then
resolves to the columnar strategy).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import Optional

import numpy as np

from .bloom import BloomFilter, _SEED1, _SEED2
from .compaction import (
    CompactionStrategy,
    MergeResult,
    _write_bloom,
)
from .entry import (
    COMPACT_DATA_FILE_EXT,
    COMPACT_INDEX_FILE_EXT,
    file_name,
)
from .file_io import PageMirroringWriter

log = logging.getLogger(__name__)

# Outputs at/above this size write through the C++ O_DIRECT streamer
# instead of the page-mirroring Python writer.
ODIRECT_MIN_BYTES = 64 << 20

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(__file__))), "native"
)
_DEFAULT_LIB_PATH = os.path.join(
    _NATIVE_DIR, "build", "libdbeel_native.so"
)
# DBEEL_NATIVE_SO selects an alternate prebuilt library — the
# sanitizer workflow loads build/libdbeel_native_asan.so (made via
# `make SANITIZE=asan`) this way.  An explicit override is loaded
# as-is: no staleness check, no rebuild (rebuilding would clobber an
# instrumented binary with a plain one mid-run).
_LIB_PATH = os.environ.get("DBEEL_NATIVE_SO") or _DEFAULT_LIB_PATH
_LIB_OVERRIDDEN = _LIB_PATH != _DEFAULT_LIB_PATH

_lib: Optional[ctypes.CDLL] = None
_tried = False

# C-side latency-class hook: the heap merge calls back into Python
# every TICK_EVERY popped entries so the BgThrottle can yield CPU to
# serving (the callback re-acquires the GIL; at this stride the cost
# is noise — ~15 calls per million entries).
TICK_FN = ctypes.CFUNCTYPE(None)
_MERGE_TICK_EVERY = 65536
# Chunk size for throttle-ticked merge IO (reads of input runs and
# O_DIRECT writes of the merged output): small enough that the
# BgThrottle can pace the virtio-queue burst against serving, large
# enough to keep near-sequential disk bandwidth.
_IO_CHUNK_BYTES = 16 << 20


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        if _lib is None and _LIB_OVERRIDDEN:
            # The override's loud-failure contract must hold for
            # EVERY caller, not just the first: with DBEEL_NATIVE_SO
            # set, all failure paths below raise, so a latched
            # (_tried, no lib) state can only mean a prior failure —
            # re-raising keeps later tests in the same process from
            # silently degrading to the Python paths.
            raise RuntimeError(
                f"DBEEL_NATIVE_SO={_LIB_PATH} failed to load "
                "earlier in this process"
            )
        return _lib
    _tried = True
    def _src_mtime() -> float:
        """Newest .cpp under native/src drives staleness."""
        src_dir = os.path.join(_NATIVE_DIR, "src")
        try:
            return max(
                os.path.getmtime(os.path.join(src_dir, f))
                for f in os.listdir(src_dir)
                if f.endswith(".cpp")
            )
        except (OSError, ValueError):
            return 0.0

    stale = (
        not _LIB_OVERRIDDEN
        and os.path.exists(_LIB_PATH)
        and os.path.getmtime(_LIB_PATH) < _src_mtime()
    )
    if not _LIB_OVERRIDDEN and (
        not os.path.exists(_LIB_PATH) or stale
    ):
        # Rebuild BEFORE the first dlopen: ctypes.CDLL caches by path,
        # so a stale library loaded once cannot be swapped in-process.
        # Serialized under an flock: with --processes every shard
        # process races through here at startup, and the lock makes
        # the others wait for one build instead of compiling N times
        # (the Makefile's atomic rename already guarantees nobody can
        # dlopen a half-written library).
        try:
            import fcntl

            os.makedirs(
                os.path.join(_NATIVE_DIR, "build"), exist_ok=True
            )
            lock_path = os.path.join(_NATIVE_DIR, "build", ".lock")
            with open(lock_path, "w") as lock_f:
                fcntl.flock(lock_f, fcntl.LOCK_EX)
                # Re-check under the lock: another process may have
                # just finished the same rebuild.
                stale = os.path.exists(_LIB_PATH) and os.path.getmtime(
                    _LIB_PATH
                ) < _src_mtime()
                if not os.path.exists(_LIB_PATH) or stale:
                    subprocess.run(
                        ["make", "-C", _NATIVE_DIR, "-B"] if stale
                        else ["make", "-C", _NATIVE_DIR],
                        check=True,
                        capture_output=True,
                        timeout=120,
                    )
        except Exception as e:
            log.info("native build unavailable: %s", e)
            if not os.path.exists(_LIB_PATH):
                return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:
        if _LIB_OVERRIDDEN:
            # An explicit DBEEL_NATIVE_SO that does not load is an
            # operator error: degrading silently would run a
            # "sanitized" suite against no native code at all (the
            # broken-.so-means-green failure tier1.sh exists to
            # prevent).
            raise RuntimeError(
                f"DBEEL_NATIVE_SO={_LIB_PATH} failed to load: {e}"
            ) from e
        log.info("native lib load failed: %s", e)
        return None
    if not hasattr(lib, "dbeel_writer_open") or not hasattr(
        lib, "dbeel_write_file"
    ):
        if _LIB_OVERRIDDEN:
            # Same loud-failure contract as the dlopen branch above:
            # an explicit override that loads but predates the ABI
            # would silently run "native" suites against pure Python.
            raise RuntimeError(
                f"DBEEL_NATIVE_SO={_LIB_PATH} loaded but lacks the "
                "pipeline ABI (dbeel_writer_open/dbeel_write_file) — "
                "stale or wrong-branch build"
            )
        # Still stale (rebuild failed / old binary pinned): degrade to
        # the pure-Python paths rather than crash on registration.
        log.warning(
            "native library at %s predates the pipeline API; "
            "falling back to host merges", _LIB_PATH
        )
        return None

    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.dbeel_murmur3_32.restype = ctypes.c_uint32
    lib.dbeel_murmur3_32.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_uint32,
    ]
    lib.dbeel_murmur3_32_batch.restype = None
    lib.dbeel_murmur3_32_batch.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_uint64,
        ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint32),
    ]
    lib.dbeel_read_file.restype = ctypes.c_int64
    lib.dbeel_read_file.argtypes = [
        ctypes.c_char_p,
        u8p,
        ctypes.c_uint64,
    ]
    lib.dbeel_write_file.restype = ctypes.c_int64
    lib.dbeel_write_file.argtypes = [
        ctypes.c_char_p,
        u8p,
        ctypes.c_uint64,
    ]
    if hasattr(lib, "dbeel_read_file_cb"):
        lib.dbeel_read_file_cb.restype = ctypes.c_int64
        lib.dbeel_read_file_cb.argtypes = [
            ctypes.c_char_p,
            u8p,
            ctypes.c_uint64,
            TICK_FN,
            ctypes.c_uint64,
        ]
        lib.dbeel_write_file_cb.restype = ctypes.c_int64
        lib.dbeel_write_file_cb.argtypes = [
            ctypes.c_char_p,
            u8p,
            ctypes.c_uint64,
            TICK_FN,
            ctypes.c_uint64,
        ]
    # The device pipeline's symbols (ops/pipeline.py), here and beside
    # the bloom's below, are bound without a probe: a library that
    # lacks one fails the load by its name.
    lib.dbeel_stage_prefixes.restype = None
    lib.dbeel_stage_prefixes.argtypes = [
        u8p,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_uint64,
        ctypes.c_uint64,
        u8p,
    ]
    lib.dbeel_pipe_decode.restype = ctypes.c_int
    lib.dbeel_pipe_decode.argtypes = [
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_uint64,
        ctypes.c_uint32,
        ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_uint64,
        ctypes.c_uint32,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint32),
        u8p,
    ]
    lib.dbeel_pipe_resolve_ties.restype = ctypes.c_int64
    lib.dbeel_pipe_resolve_ties.argtypes = [
        ctypes.c_uint64,
        u8p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(u8p),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_uint64,
        u8p,
    ]
    lib.dbeel_pipe_drop_tombstones.restype = ctypes.c_int64
    lib.dbeel_pipe_drop_tombstones.argtypes = [
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(u8p),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        u8p,
        ctypes.c_int,
        ctypes.c_uint64,
        u8p,
    ]
    lib.dbeel_writer_put.restype = ctypes.c_int64
    lib.dbeel_writer_put.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(u8p),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_uint64,
    ]
    lib.dbeel_writer_abort.restype = None
    lib.dbeel_writer_abort.argtypes = [ctypes.c_void_p]
    # Single-pass sidecar gather writer (ISSUE 15): per-page CRCs
    # accumulated as bytes are emitted, handed back at close so
    # the .sums sidecar costs zero re-reads.
    lib.dbeel_writer_open2.restype = ctypes.c_void_p
    lib.dbeel_writer_open2.argtypes = [
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_int32,
    ]
    lib.dbeel_writer_close2.restype = ctypes.c_int64
    lib.dbeel_writer_close2.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    if hasattr(lib, "dbeel_memtable_flush_write2"):
        # Single-pass native flush: triplet write + inline sidecar
        # CRCs in one GIL-free call (replaces the post-hoc
        # compute_and_write re-read of the whole freshly-written
        # triplet).
        lib.dbeel_memtable_flush_write2.restype = ctypes.c_int64
        lib.dbeel_memtable_flush_write2.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_int32),
        ]
    if hasattr(lib, "dbeel_read_files_overlapped"):
        # Overlapped O_DIRECT input loader (io_uring double-buffered;
        # serial fallback counted) — the k-way merge's input pass.
        lib.dbeel_read_files_overlapped.restype = ctypes.c_int64
        lib.dbeel_read_files_overlapped.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(u8p),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint32,
            TICK_FN,
            ctypes.c_uint64,
        ]
        lib.dbeel_read_overlap_stats.restype = None
        lib.dbeel_read_overlap_stats.argtypes = [
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
        ]
    if hasattr(lib, "dbeel_dp_handle"):
        lib.dbeel_wal_new.restype = ctypes.c_void_p
        lib.dbeel_wal_new.argtypes = [ctypes.c_int32, ctypes.c_uint64]
        lib.dbeel_wal_free.restype = None
        lib.dbeel_wal_free.argtypes = [ctypes.c_void_p]
        lib.dbeel_wal_offset.restype = ctypes.c_uint64
        lib.dbeel_wal_offset.argtypes = [ctypes.c_void_p]
        lib.dbeel_wal_append.restype = ctypes.c_uint64
        lib.dbeel_wal_append.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.c_int64,
        ]
    if hasattr(lib, "dbeel_qf_new"):
        # Quorum fan-out engine (coordinator-side replica writes +
        # ack compare in C; cluster/native_fanout.py is the loop
        # bridge).
        lib.dbeel_qf_new.restype = ctypes.c_void_p
        lib.dbeel_qf_new.argtypes = []
        lib.dbeel_qf_free.restype = None
        lib.dbeel_qf_free.argtypes = [ctypes.c_void_p]
        lib.dbeel_qf_set_stream.restype = ctypes.c_int32
        lib.dbeel_qf_set_stream.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
            ctypes.c_int32,
        ]
        lib.dbeel_qf_stream_alive.restype = ctypes.c_int32
        lib.dbeel_qf_stream_alive.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
        ]
        lib.dbeel_qf_kill_stream.restype = None
        lib.dbeel_qf_kill_stream.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
        ]
        lib.dbeel_qf_close_stream.restype = None
        lib.dbeel_qf_close_stream.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
        ]
        lib.dbeel_qf_submit.restype = ctypes.c_uint64
        lib.dbeel_qf_submit.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_uint32,
            ctypes.c_char_p,
            ctypes.c_uint32,
        ]
        lib.dbeel_qf_wants_write.restype = ctypes.c_int32
        lib.dbeel_qf_wants_write.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
        ]
        lib.dbeel_qf_on_writable.restype = ctypes.c_int32
        lib.dbeel_qf_on_writable.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
        ]
        lib.dbeel_qf_on_readable.restype = ctypes.c_int32
        lib.dbeel_qf_on_readable.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
        ]
        lib.dbeel_qf_next_event.restype = ctypes.c_int32
        lib.dbeel_qf_next_event.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.dbeel_qf_fanout_ops.restype = ctypes.c_uint64
        lib.dbeel_qf_fanout_ops.argtypes = [ctypes.c_void_p]
    if hasattr(lib, "dbeel_wal_sync_enable"):
        # Group-commit syncer (wal-sync mode): a C thread owns the
        # coalesced fdatasync, completion pings an eventfd.
        lib.dbeel_wal_sync_enable.restype = ctypes.c_int32
        lib.dbeel_wal_sync_enable.argtypes = [
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_int32,
        ]
        lib.dbeel_wal_sync_disable.restype = None
        lib.dbeel_wal_sync_disable.argtypes = [ctypes.c_void_p]
        if hasattr(lib, "dbeel_wal_sync_stop_async"):
            lib.dbeel_wal_sync_stop_async.restype = None
            lib.dbeel_wal_sync_stop_async.argtypes = [ctypes.c_void_p]
        lib.dbeel_wal_seq.restype = ctypes.c_uint64
        lib.dbeel_wal_seq.argtypes = [ctypes.c_void_p]
        lib.dbeel_wal_synced.restype = ctypes.c_uint64
        lib.dbeel_wal_synced.argtypes = [ctypes.c_void_p]
    if hasattr(lib, "dbeel_memtable_max_ts"):
        lib.dbeel_memtable_max_ts.restype = ctypes.c_int64
        lib.dbeel_memtable_max_ts.argtypes = [ctypes.c_void_p]
    if hasattr(lib, "dbeel_dp_set_watermark"):
        # Flush-watermark guard: shard-plane writes at or below it
        # punt to Python's read-guarded apply (dataplane.py).
        lib.dbeel_dp_set_watermark.restype = None
        lib.dbeel_dp_set_watermark.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.c_int64,
        ]
    if hasattr(lib, "dbeel_walsync_hub_new"):
        # Loop-driven io_uring group commit: fsyncs are SQEs on a
        # loop-owned ring, zero sync threads (wal.py _SyncHub).
        lib.dbeel_walsync_hub_new.restype = ctypes.c_void_p
        lib.dbeel_walsync_hub_new.argtypes = [ctypes.c_uint32]
        lib.dbeel_walsync_hub_free.restype = None
        lib.dbeel_walsync_hub_free.argtypes = [ctypes.c_void_p]
        lib.dbeel_walsync_hub_eventfd.restype = ctypes.c_int32
        lib.dbeel_walsync_hub_eventfd.argtypes = [ctypes.c_void_p]
        lib.dbeel_walsync_hub_reap.restype = None
        lib.dbeel_walsync_hub_reap.argtypes = [ctypes.c_void_p]
        lib.dbeel_wal_sync_attach.restype = ctypes.c_int32
        lib.dbeel_wal_sync_attach.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_uint64,
        ]
    if hasattr(lib, "dbeel_walsync_errors"):
        # Failed-fsync counter (gated separately: stale .so tolerance).
        lib.dbeel_walsync_errors.restype = ctypes.c_uint64
        lib.dbeel_walsync_errors.argtypes = []
    if hasattr(lib, "dbeel_dp_handle"):
        # (continuation of the data-plane prototypes: these must stay
        # gated on dbeel_dp_handle, NOT on the newer syncer symbols —
        # a stale .so without the syncer still runs the data plane and
        # needs every prototype declared.)
        lib.dbeel_dp_new.restype = ctypes.c_void_p
        lib.dbeel_dp_new.argtypes = []
        lib.dbeel_dp_free.restype = None
        lib.dbeel_dp_free.argtypes = [ctypes.c_void_p]
        lib.dbeel_dp_set_ownership.restype = None
        lib.dbeel_dp_set_ownership.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
            ctypes.c_uint32,
            ctypes.c_uint32,
        ]
        lib.dbeel_dp_register.restype = ctypes.c_int32
        lib.dbeel_dp_register.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_uint32,
            ctypes.c_int32,  # client_plane (0 = replica-plane only)
        ]
        if hasattr(lib, "dbeel_dp_handle_shard"):
            lib.dbeel_dp_handle_shard.restype = ctypes.c_int64
            lib.dbeel_dp_handle_shard.argtypes = [
                ctypes.c_void_p,
                ctypes.c_char_p,
                ctypes.c_uint32,
                ctypes.c_char_p,
                ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_uint32),
            ]
            lib.dbeel_dp_fast_replica_ops.restype = ctypes.c_uint64
            lib.dbeel_dp_fast_replica_ops.argtypes = [ctypes.c_void_p]
        if hasattr(lib, "dbeel_dp_handle_coord"):
            lib.dbeel_dp_handle_coord.restype = ctypes.c_int64
            lib.dbeel_dp_handle_coord.argtypes = [
                ctypes.c_void_p,
                ctypes.c_char_p,
                ctypes.c_uint32,
                ctypes.c_char_p,
                ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_uint32),
            ]
            lib.dbeel_dp_fast_coord_writes.restype = ctypes.c_uint64
            lib.dbeel_dp_fast_coord_writes.argtypes = [
                ctypes.c_void_p
            ]
            lib.dbeel_dp_fast_coord_gets.restype = ctypes.c_uint64
            lib.dbeel_dp_fast_coord_gets.argtypes = [ctypes.c_void_p]
        lib.dbeel_dp_unregister.restype = None
        lib.dbeel_dp_unregister.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_uint32,
        ]
        lib.dbeel_dp_fast_sets.restype = ctypes.c_uint64
        lib.dbeel_dp_fast_sets.argtypes = [ctypes.c_void_p]
        lib.dbeel_dp_fast_gets.restype = ctypes.c_uint64
        lib.dbeel_dp_fast_gets.argtypes = [ctypes.c_void_p]
        if hasattr(lib, "dbeel_dp_set_tables"):
            lib.dbeel_dp_set_tables.restype = ctypes.c_int32
            lib.dbeel_dp_set_tables.argtypes = [
                ctypes.c_void_p,
                ctypes.c_char_p,
                ctypes.c_uint32,
                ctypes.c_void_p,  # FastTable descriptor array
                ctypes.c_int32,
            ]
            lib.dbeel_dp_fast_table_gets.restype = ctypes.c_uint64
            lib.dbeel_dp_fast_table_gets.argtypes = [ctypes.c_void_p]
        if hasattr(lib, "dbeel_dp_set_overload"):
            # All-native serving path (ISSUE 6): multi-op frames,
            # native overload/deadline answers, CRC probe
            # verification.  Gated together: one build ships them
            # all.
            lib.dbeel_dp_set_overload.restype = None
            lib.dbeel_dp_set_overload.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int32,
            ]
            lib.dbeel_dp_set_overload_resp.restype = None
            lib.dbeel_dp_set_overload_resp.argtypes = [
                ctypes.c_void_p,
                ctypes.c_char_p,
                ctypes.c_uint32,
                ctypes.c_char_p,
                ctypes.c_uint32,
            ]
            lib.dbeel_dp_set_verify.restype = None
            lib.dbeel_dp_set_verify.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int32,
            ]
            for fn in (
                lib.dbeel_dp_fast_multi_sets,
                lib.dbeel_dp_fast_multi_gets,
                lib.dbeel_dp_native_sheds,
                lib.dbeel_dp_native_deadline_drops,
                lib.dbeel_dp_crc_failures,
            ):
                fn.restype = ctypes.c_uint64
                fn.argtypes = [ctypes.c_void_p]
            lib.dbeel_crc32_pages.restype = None
            lib.dbeel_crc32_pages.argtypes = [
                u8p,
                ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_uint32),
            ]
            lib.dbeel_odirect_fallbacks.restype = ctypes.c_uint64
            lib.dbeel_odirect_fallbacks.argtypes = []
        if hasattr(lib, "dbeel_dp_set_class_levels"):
            # QoS plane (ISSUE 14): per-class shed levels + per-class
            # native shed counters.  Gated separately — stale .so
            # tolerance (a class-blind .so keeps the scalar gate).
            lib.dbeel_dp_set_class_levels.restype = None
            lib.dbeel_dp_set_class_levels.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int32,
                ctypes.c_int32,
                ctypes.c_int32,
            ]
            lib.dbeel_dp_sheds_by_class.restype = None
            lib.dbeel_dp_sheds_by_class.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_uint64),
            ]
        if hasattr(lib, "dbeel_dp_admits_by_class"):
            # Native lane accounting (ISSUE 15 satellite): per-class
            # served-frame counters (client/coord plane + peer plane),
            # mirrored like sheds_by_class.  Gated separately — stale
            # .so tolerance.
            lib.dbeel_dp_admits_by_class.restype = None
            lib.dbeel_dp_admits_by_class.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_uint64),
            ]
        if hasattr(lib, "dbeel_dp_trace_snapshot"):
            # Tracing plane (PR 9): coarse per-verb native stage
            # counters.  Gated separately — stale .so tolerance.
            lib.dbeel_dp_set_trace.restype = None
            lib.dbeel_dp_set_trace.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int32,
            ]
            lib.dbeel_dp_trace_snapshot.restype = ctypes.c_int32
            lib.dbeel_dp_trace_snapshot.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_int32,
            ]
        lib.dbeel_dp_handle.restype = ctypes.c_int64
        lib.dbeel_dp_handle.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.c_char_p,
            ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32),
        ]
    lib.dbeel_memtable_new.restype = ctypes.c_void_p
    lib.dbeel_memtable_new.argtypes = [ctypes.c_uint32]
    lib.dbeel_memtable_free.restype = None
    lib.dbeel_memtable_free.argtypes = [ctypes.c_void_p]
    lib.dbeel_memtable_len.restype = ctypes.c_uint32
    lib.dbeel_memtable_len.argtypes = [ctypes.c_void_p]
    lib.dbeel_memtable_bytes.restype = ctypes.c_uint64
    lib.dbeel_memtable_bytes.argtypes = [ctypes.c_void_p]
    lib.dbeel_memtable_set.restype = ctypes.c_int32
    lib.dbeel_memtable_set.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint32),
    ]
    lib.dbeel_memtable_get.restype = ctypes.c_int32
    lib.dbeel_memtable_get.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.dbeel_memtable_dump_size.restype = ctypes.c_uint64
    lib.dbeel_memtable_dump_size.argtypes = [ctypes.c_void_p]
    lib.dbeel_memtable_dump.restype = ctypes.c_uint64
    lib.dbeel_memtable_dump.argtypes = [ctypes.c_void_p, u8p]
    if hasattr(lib, "dbeel_memtable_flush_write"):
        lib.dbeel_memtable_flush_write.restype = ctypes.c_int64
        lib.dbeel_memtable_flush_write.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.c_uint64,
        ]
    lib.dbeel_bloom_add_batch.restype = None
    # The device pipeline's two-phase bloom (ops/pipeline.py).
    lib.dbeel_bloom_hash_gather.restype = None
    lib.dbeel_bloom_hash_gather.argtypes = [
        ctypes.POINTER(u8p),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_uint32,
        ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint32),
    ]
    lib.dbeel_bloom_set_hashes.restype = None
    lib.dbeel_bloom_set_hashes.argtypes = [
        u8p,
        ctypes.c_uint64,
        ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_uint64,
    ]
    lib.dbeel_merge.restype = ctypes.c_int64
    lib.dbeel_merge.argtypes = [
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_uint32,
        ctypes.c_int,
        u8p,
        ctypes.POINTER(ctypes.c_uint64),
        u8p,
    ]
    if hasattr(lib, "dbeel_merge_cb"):
        lib.dbeel_merge_cb.restype = ctypes.c_int64
        lib.dbeel_merge_cb.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint32,
            ctypes.c_int,
            u8p,
            ctypes.POINTER(ctypes.c_uint64),
            u8p,
            TICK_FN,
            ctypes.c_uint64,
        ]
    if hasattr(lib, "dbeel_merge_grace_cb"):
        # gc_grace merge (tombstones younger than the int64-ns cutoff
        # survive a drop-tombstones merge).
        lib.dbeel_merge_grace_cb.restype = ctypes.c_int64
        lib.dbeel_merge_grace_cb.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint32,
            ctypes.c_int,
            ctypes.c_int64,
            u8p,
            ctypes.POINTER(ctypes.c_uint64),
            u8p,
            TICK_FN,
            ctypes.c_uint64,
        ]
    _lib = lib
    return _lib


def native_available() -> bool:
    return _load() is not None


def require() -> ctypes.CDLL:
    """The native library, built from ``native/src`` on first use
    (``native/build/`` is not committed).  Raises when it cannot be
    built or loaded: the device pipeline has no other reader, decoder
    or gather-writer, so a device backend must not start without it."""
    lib = _load()
    if lib is None:
        raise RuntimeError(
            "the native library could not be built or loaded from "
            f"{_NATIVE_DIR} (needs make and a C++17 compiler)"
        )
    return lib


def load_if_built() -> Optional[ctypes.CDLL]:
    """Return the lib only if already built — never runs make (safe to
    call from latency-sensitive / event-loop contexts).  An explicit
    DBEEL_NATIVE_SO override skips the exists-check and goes through
    _load(), which raises loudly on ANY override failure (a typo'd
    path silently degrading to Python would green-light a "sanitized"
    run that tested no native code); _load() never runs make for
    overrides, so the latency contract holds."""
    if _lib is not None:
        return _lib
    if not _LIB_OVERRIDDEN and not os.path.exists(_LIB_PATH):
        return None
    return _load()


_odirect_warned = False


def odirect_fallbacks() -> int:
    """Process-wide count of silent O_DIRECT → buffered degradations
    in the C streamers (unaligned destination buffers, filesystems
    refusing O_DIRECT).  Previously these fell back with NO signal —
    the only symptom was a mysterious throughput cliff (ISSUE 6
    satellite); now the count rides ``get_stats.durability`` and the
    first occurrence logs a warning."""
    global _odirect_warned
    lib = _lib  # never triggers a build: observability must be free
    if lib is None or not hasattr(lib, "dbeel_odirect_fallbacks"):
        return 0
    n = int(lib.dbeel_odirect_fallbacks())
    if n and not _odirect_warned:
        _odirect_warned = True
        log.warning(
            "O_DIRECT degraded to buffered I/O %d time(s) "
            "(unaligned buffer or filesystem without O_DIRECT "
            "support) — large merges/reads lose the page-cache "
            "bypass",
            n,
        )
    return n


def read_overlap_stats() -> "tuple[int, int]":
    """(uring_passes, serial_passes) of the overlapped multi-file
    input loader — how many merge input passes rode io_uring vs fell
    back to the serial chunked reader.  Free when the lib is not
    loaded (observability must never trigger a build)."""
    lib = _lib
    if lib is None or not hasattr(lib, "dbeel_read_overlap_stats"):
        return (0, 0)
    a = ctypes.c_uint64(0)
    b = ctypes.c_uint64(0)
    lib.dbeel_read_overlap_stats(ctypes.byref(a), ctypes.byref(b))
    return (int(a.value), int(b.value))


def aligned_u8_buffer(size: int) -> np.ndarray:
    """4 KiB-aligned uint8 destination of ``max(1, size)`` logical
    bytes with page-rounded capacity — what the O_DIRECT readers
    require (an unaligned buffer silently degrades to buffered IO)."""
    cap = (size + 4095) & ~4095
    raw = np.empty(cap + 4096, dtype=np.uint8)
    off = (-raw.ctypes.data) % 4096
    return raw[off : off + max(1, size)]


def page_crcs_native(lib, arr: np.ndarray, size: int) -> list:
    """Per-4KiB-page CRCs of ``arr[:size]`` via the C kernel — the
    in-RAM half of the single-pass sidecar (the merged output is
    still resident; summing it here beats re-reading the file it was
    just written to)."""
    from .entry import PAGE_SIZE

    npages = (size + PAGE_SIZE - 1) // PAGE_SIZE
    if npages == 0:
        return []
    if lib is None or not hasattr(lib, "dbeel_crc32_pages"):
        from . import checksums

        return checksums.page_crcs(memoryview(arr)[:size])
    out = np.zeros(npages, dtype=np.uint32)
    lib.dbeel_crc32_pages(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_uint64(int(size)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
    )
    return out.tolist()


def murmur3_32_native(data: bytes, seed: int = 0) -> int:
    lib = _load()
    if lib is None:
        from ..utils.murmur import murmur3_32

        return murmur3_32(data, seed)
    return lib.dbeel_murmur3_32(data, len(data), seed)


class NativeMergeStrategy(CompactionStrategy):
    """C++ k-way heap merge — reference semantics at native speed."""

    name = "native"

    def merge(
        self,
        sources,
        dir_path,
        output_index,
        cache,
        keep_tombstones,
        bloom_min_size,
    ) -> MergeResult:
        lib = _load()
        assert lib is not None

        throttle = self.throttle
        # Chunked, throttle-ticked input reads: one unbroken
        # multi-hundred-MB read saturates the virtio queue and
        # starves the serving loop (measured 40-200ms stalls at
        # compaction start); 16MB chunks with a tick between let the
        # BgThrottle pace the burst while serving is busy.
        tick_cb = (
            TICK_FN(throttle.tick) if throttle is not None else TICK_FN()
        )
        use_cb = throttle is not None and hasattr(
            lib, "dbeel_read_file_cb"
        )

        def _read_whole(path: str, size: int) -> bytes:
            if not use_cb or size < _IO_CHUNK_BYTES * 2:
                with open(path, "rb") as f:
                    data = f.read(size)
                if len(data) != size:
                    # The merge sizes its buffers from the index
                    # metadata: a truncated data file must fail here,
                    # not as an OOB read in C.
                    raise OSError(
                        f"short read {len(data)} != {size} for {path}"
                    )
                return data
            # 4KiB-aligned destination so the chunked read takes the
            # O_DIRECT path (an unaligned buffer silently falls back
            # to buffered reads).
            buf = aligned_u8_buffer(size)
            got = lib.dbeel_read_file_cb(
                path.encode(),
                buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                ctypes.c_uint64(size),
                tick_cb,
                ctypes.c_uint64(_IO_CHUNK_BYTES),
            )
            if got != size:
                raise OSError(f"short read {got} != {size} for {path}")
            return buf

        # Overlapped input pass (ISSUE 15): all data+index files ride
        # ONE io_uring with double-buffered chunk reads, so the k-way
        # merge's input load approaches device bandwidth instead of
        # paying per-file latency in sequence.  tick() still fires per
        # chunk — the BgThrottle pacing is unchanged.  Small merges
        # and stale .so keep the serial reader.
        counts = [s.entry_count for s in sources]
        datas: "list | None" = None
        indexes: "list | None" = None
        total_in = sum(
            s.data_size + s.entry_count * 16 for s in sources
        )
        if (
            hasattr(lib, "dbeel_read_files_overlapped")
            and total_in >= _IO_CHUNK_BYTES
            # Escape hatch + bench-baseline switch: serial chunked
            # reads exactly as before ISSUE 15.
            and os.environ.get("DBEEL_NO_OVERLAP_READS", "0")
            in ("", "0")
        ):
            paths = [s.data_path for s in sources] + [
                s.index_path for s in sources
            ]
            sizes = [s.data_size for s in sources] + [
                s.entry_count * 16 for s in sources
            ]
            bufs = [aligned_u8_buffer(sz) for sz in sizes]
            PathArr = ctypes.c_char_p * len(paths)
            PtrArr = ctypes.POINTER(ctypes.c_uint8) * len(paths)
            SizeArr = ctypes.c_uint64 * len(paths)
            got = lib.dbeel_read_files_overlapped(
                PathArr(*[p.encode() for p in paths]),
                PtrArr(
                    *[
                        b.ctypes.data_as(
                            ctypes.POINTER(ctypes.c_uint8)
                        )
                        for b in bufs
                    ]
                ),
                SizeArr(*sizes),
                len(paths),
                tick_cb,
                ctypes.c_uint64(_IO_CHUNK_BYTES),
            )
            if got == sum(sizes):
                datas = bufs[: len(sources)]
                indexes = bufs[len(sources) :]
            else:
                log.warning(
                    "overlapped input read failed (%d); serial "
                    "fallback",
                    got,
                )
        if datas is None or indexes is None:
            datas = [
                _read_whole(s.data_path, s.data_size)
                for s in sources
            ]
            indexes = [
                _read_whole(s.index_path, s.entry_count * 16)
                for s in sources
            ]

        total_data = sum(s.data_size for s in sources)
        total_count = sum(counts)
        out_data = np.zeros(max(1, total_data), dtype=np.uint8)
        out_index = np.zeros(max(1, total_count * 16), dtype=np.uint8)
        out_size = ctypes.c_uint64(0)

        def _as_cptr(b):
            if isinstance(b, np.ndarray):
                return ctypes.cast(
                    b.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                    ctypes.c_char_p,
                )
            return ctypes.c_char_p(b)

        DataArr = ctypes.c_char_p * len(sources)
        CountArr = ctypes.c_uint64 * len(sources)
        keep = 1 if keep_tombstones else 0
        cutoff = int(self.tombstone_drop_before or 0)
        if (
            not keep
            and cutoff > 0
            and not hasattr(lib, "dbeel_merge_grace_cb")
        ):
            # Stale .so without the grace merge: keeping ALL
            # tombstones is the conservative degradation (never
            # resurrect a delete; the space is reclaimed once the
            # library is rebuilt).
            keep = 1
            cutoff = 0
        args = (
            DataArr(*[_as_cptr(d) for d in datas]),
            DataArr(*[_as_cptr(i) for i in indexes]),
            CountArr(*counts),
            len(sources),
            keep,
            out_data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.byref(out_size),
            out_index.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        if not keep and cutoff > 0:
            n_out = lib.dbeel_merge_grace_cb(
                *args[:5],
                ctypes.c_int64(cutoff),
                *args[5:],
                tick_cb,
                _MERGE_TICK_EVERY,
            )
        elif hasattr(lib, "dbeel_merge_cb"):
            # TICK_FN() is a NULL fn pointer — same as dbeel_merge.
            n_out = lib.dbeel_merge_cb(
                *args, tick_cb, _MERGE_TICK_EVERY
            )
        else:
            n_out = lib.dbeel_merge(*args)
        data_size = out_size.value
        self._tick()

        from .entry import DATA_FILE_EXT, INDEX_FILE_EXT

        data_path = (
            f"{dir_path}/{file_name(output_index, COMPACT_DATA_FILE_EXT)}"
        )
        index_path = (
            f"{dir_path}/{file_name(output_index, COMPACT_INDEX_FILE_EXT)}"
        )
        # Large outputs: O_DIRECT native writes (no Python buffer
        # copies, no page-cache mirroring — same policy as the device
        # pipeline).  Small outputs keep the mirroring writer so fresh
        # little SSTables stay warm.  (bench.py overrides the module
        # constant to reproduce the round-1 baseline definition.)
        if data_size >= ODIRECT_MIN_BYTES:
            if use_cb and hasattr(lib, "dbeel_write_file_cb"):
                rc1 = lib.dbeel_write_file_cb(
                    data_path.encode(),
                    out_data.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_uint8)
                    ),
                    ctypes.c_uint64(int(data_size)),
                    tick_cb,
                    ctypes.c_uint64(_IO_CHUNK_BYTES),
                )
                rc2 = lib.dbeel_write_file_cb(
                    index_path.encode(),
                    out_index.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_uint8)
                    ),
                    ctypes.c_uint64(int(n_out) * 16),
                    tick_cb,
                    ctypes.c_uint64(_IO_CHUNK_BYTES),
                )
            else:
                rc1 = lib.dbeel_write_file(
                    data_path.encode(),
                    out_data.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_uint8)
                    ),
                    ctypes.c_uint64(int(data_size)),
                )
                rc2 = lib.dbeel_write_file(
                    index_path.encode(),
                    out_index.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_uint8)
                    ),
                    ctypes.c_uint64(int(n_out) * 16),
                )
            if rc1 != 0 or rc2 != 0:
                raise OSError("native O_DIRECT write failed")
        else:
            data_w = PageMirroringWriter(
                data_path,
                (DATA_FILE_EXT, output_index),
                cache,
            )
            data_w.write(out_data[:data_size].tobytes())
            data_w.close()
            index_w = PageMirroringWriter(
                index_path,
                (INDEX_FILE_EXT, output_index),
                cache,
            )
            index_w.write(out_index[: n_out * 16].tobytes())
            index_w.close()

        wrote_bloom = False
        bloom_bytes = None
        if data_size >= bloom_min_size and n_out > 0:
            rec = np.frombuffer(
                out_index[: n_out * 16].tobytes(),
                dtype=np.dtype(
                    [
                        ("offset", "<u8"),
                        ("key_size", "<u4"),
                        ("full_size", "<u4"),
                    ]
                ),
            )
            bloom = BloomFilter.with_capacity(int(n_out))
            key_offsets = (rec["offset"] + 16).astype(np.uint64)
            key_lens = rec["key_size"].astype(np.uint32)
            lib.dbeel_bloom_add_batch(
                bloom.bits.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_uint8)
                ),
                ctypes.c_uint64(bloom.num_bits),
                ctypes.c_uint32(bloom.num_hashes),
                out_data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                key_offsets.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_uint64)
                ),
                key_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                ctypes.c_uint64(n_out),
                ctypes.c_uint32(_SEED1),
                ctypes.c_uint32(_SEED2),
            )
            bloom_bytes = _write_bloom(dir_path, output_index, bloom)
            wrote_bloom = True

        # Single-pass sidecar (ISSUE 15): the merged output is still
        # resident — page-CRC it in RAM (C kernel) and write the
        # compact_sums sidecar inline under the same journaled rename,
        # instead of the post-hoc whole-triplet re-read that roughly
        # doubled compaction read amplification.
        from . import checksums

        checksums.write(
            dir_path,
            output_index,
            page_crcs_native(lib, out_data, int(data_size)),
            page_crcs_native(lib, out_index, int(n_out) * 16),
            int(data_size),
            bloom_bytes,
            ext=checksums.COMPACT_SUMS_FILE_EXT,
        )

        if self.index_fields and n_out > 0:
            # Index run (ISSUE 17): extracted from the SAME resident
            # out_data/out_index buffers the C merge just filled —
            # like the inline sidecar above, it adds zero data-file
            # reads.
            from . import secondary_index as si

            irec = np.frombuffer(
                out_index[: n_out * 16].tobytes(),
                dtype=np.dtype(
                    [
                        ("offset", "<u8"),
                        ("key_size", "<u4"),
                        ("full_size", "<u4"),
                    ]
                ),
            )
            dview = memoryview(out_data)
            offs = irec["offset"].tolist()
            kss = irec["key_size"].tolist()
            fss = irec["full_size"].tolist()
            si.emit_run(
                dir_path,
                output_index,
                self.index_fields,
                (
                    (
                        offs[i],
                        bytes(
                            dview[
                                offs[i] + 16 + kss[i] : offs[i]
                                + fss[i]
                            ]
                        ),
                    )
                    for i in range(int(n_out))
                ),
                compact=True,
            )

        from .compaction import compaction_stats

        compaction_stats.note_path("native")
        return MergeResult(int(n_out), int(data_size), wrote_bloom)
