"""The block pool of the device pipeline (ops/pipeline.py).

A big merge works in ~2 GB of host arrays: run buffers, index and
``*_cat`` columns, per-partition sets, operand stacks, bloom hashes.
Allocated anew in every merge they are fresh pages in every merge, and
on a host whose kernel charges ~1 s of system time a fresh GB the page
faults were a third of a merge's wall (PERF.md §6, PR 28 and 29).  So
the pipeline leases its blocks here and gives them back when its
threads are joined: the second and every later merge of a process runs
on pages that are already mapped.

* A block is a ``uint8`` array whose base address and capacity are
  4 KiB multiples (the O_DIRECT contract of ``dbeel_read_file``); typed
  arrays are views cut from its start.  Capacities are size classes,
  eight to an octave, and a lease takes the smallest free block from
  the request's own class up to ``WASTE_LIMIT`` above the request — so
  merges whose runs differ by a few percent hit the same blocks.
* One lock around lease and give: a block is in the free lists or with
  exactly one merge, never both, never two merges.
* A merge holds its blocks through a ``Leases``; closing it gives every
  block back, or — where a thread that may still read or write them is
  wedged — drops them: the pool forgets them and they die with their
  last reference.  A block that comes back is dirty; nothing the pool
  hands out is zeroed.
* Retention follows the traffic, no knob: a free block that no merge
  has held for ``IDLE_MERGES`` consecutive merges is released to the
  allocator, and the free total never stays above the most the recent
  merges had leased at once.  A node that ran one 10M-key compaction
  and then merges small tables gives the 2 GB back; back-to-back merges
  of one shape keep all of it.

Counted under ``get_stats.compaction.pool`` (storage/compaction.py).
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional

import numpy as np

ALIGN = 4096
# Size classes to an octave: 2**_CLASS_BITS, so a class is at most
# 1/8 above the request it rounds.
_CLASS_BITS = 3
# A lease may take a free block this much larger than the request (the
# request's own class always qualifies).
WASTE_LIMIT = 0.25
# Consecutive merges of the process that did not hold a free block
# before it is released.
IDLE_MERGES = 2


def size_class(nbytes: int) -> int:
    """Capacity a request of ``nbytes`` is rounded to: a 4 KiB multiple
    with ``_CLASS_BITS`` + 1 significant bits."""
    n = max(ALIGN, (int(nbytes) + ALIGN - 1) & ~(ALIGN - 1))
    step = max(ALIGN, 1 << (n.bit_length() - 1 - _CLASS_BITS))
    return (n + step - 1) & ~(step - 1)


class _Block:
    __slots__ = ("buf", "cap", "addr", "stamp")

    def __init__(self, cap: int) -> None:
        raw = np.empty(cap + ALIGN, dtype=np.uint8)
        off = (-raw.ctypes.data) % ALIGN
        self.buf = raw[off : off + cap]
        self.cap = cap
        self.addr = raw.ctypes.data + off
        # When last given back: (the pool's merge count, give order).
        self.stamp = (0, 0)


class BlockPool:
    """``note(retained_bytes=, leased_bytes=, **adds)`` receives every
    change of the counters (``compaction_stats.note_pool``)."""

    def __init__(self, note: Optional[Callable[..., None]] = None) -> None:
        self._lock = threading.Lock()
        self._free: Dict[int, List[_Block]] = {}  # capacity -> blocks
        self._free_bytes = 0
        self._leased_bytes = 0
        self._merges = 0  # Leases closed so far
        self._gives = 0
        # Most bytes leased at once since the last close of a Leases,
        # and in the stretch before that.
        self._peak = 0
        self._peak_before = 0
        self._note = note
        # Test hook: a byte every block is filled with as it comes
        # back, so that a reader of stale contents cannot pass.
        self.poison: Optional[int] = None

    def leases(self) -> "Leases":
        """What one merge holds; close it when its threads are joined."""
        return Leases(self)

    def _tell(self, **adds: int) -> None:
        if self._note is not None:
            self._note(
                retained_bytes=self._free_bytes,
                leased_bytes=self._leased_bytes,
                **adds,
            )

    def _lease(self, nbytes: int) -> _Block:
        need = size_class(nbytes)
        limit = max(need, int(nbytes * (1.0 + WASTE_LIMIT)))
        with self._lock:
            blk = None
            cap = need
            while cap <= limit:
                free = self._free.get(cap)
                if free:
                    blk = free.pop()
                    self._free_bytes -= cap
                    break
                cap = size_class(cap + 1)
            hit = blk is not None
            if not hit:
                blk = _Block(need)
            self._leased_bytes += blk.cap
            self._peak = max(self._peak, self._leased_bytes)
            self._tell(
                leases=1,
                hits=int(hit),
                bytes_leased=blk.cap,
                bytes_fresh=0 if hit else blk.cap,
            )
        return blk

    def _give(self, blocks: List[_Block]) -> None:
        if self.poison is not None:
            for blk in blocks:
                blk.buf.fill(self.poison)
        with self._lock:
            for blk in blocks:
                self._gives += 1
                blk.stamp = (self._merges, self._gives)
                self._free.setdefault(blk.cap, []).append(blk)
                self._free_bytes += blk.cap
                self._leased_bytes -= blk.cap
            self._tell()

    def _drop(self, blocks: List[_Block]) -> None:
        with self._lock:
            for blk in blocks:
                self._leased_bytes -= blk.cap
            self._tell()

    def _merge_ended(self) -> None:
        """The retention rule, once a merge: idle blocks go, then the
        least recently held until the free total is within what the
        recent merges had leased at once."""
        with self._lock:
            self._merges += 1
            bound = max(self._peak, self._peak_before)
            self._peak_before, self._peak = self._peak, self._leased_bytes
            kept = sorted(
                (b for free in self._free.values() for b in free),
                key=lambda b: b.stamp,
                reverse=True,
            )
            while kept and (
                self._merges - kept[-1].stamp[0] > IDLE_MERGES
                or self._free_bytes > bound
            ):
                self._free_bytes -= kept.pop().cap
            self._free = {}
            for blk in reversed(kept):
                self._free.setdefault(blk.cap, []).append(blk)
            self._tell()


def _addr(arr: np.ndarray) -> int:
    return arr.__array_interface__["data"][0]


class Leases:
    """The blocks one merge holds.  ``array`` leases (any of the
    merge's threads may), ``give`` returns one array's block early,
    ``forget`` drops one, ``close`` ends the merge: every block goes
    back, or with ``drop`` none does."""

    def __init__(self, pool: BlockPool) -> None:
        self._pool = pool
        self._lock = threading.Lock()
        self._held: Dict[int, _Block] = {}  # base address -> block

    def array(self, shape, dtype=np.uint8) -> np.ndarray:
        """An uninitialised (dirty) array of ``shape`` at the start of
        a block of its own."""
        dtype = np.dtype(dtype)
        shape = (shape,) if np.isscalar(shape) else tuple(shape)
        nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        if nbytes == 0:
            return np.empty(shape, dtype)
        blk = self._pool._lease(nbytes)
        with self._lock:
            self._held[blk.addr] = blk
        return blk.buf[:nbytes].view(dtype).reshape(shape)

    def _take(self, arr: np.ndarray) -> List[_Block]:
        with self._lock:
            blk = self._held.pop(_addr(arr), None)
        return [] if blk is None else [blk]

    def give(self, arr: np.ndarray) -> None:
        self._pool._give(self._take(arr))

    def forget(self, arr: np.ndarray) -> None:
        self._pool._drop(self._take(arr))

    def close(self, drop: bool = False) -> None:
        with self._lock:
            blocks = list(self._held.values())
            self._held.clear()
        if drop:
            self._pool._drop(blocks)
        else:
            self._pool._give(blocks)
        self._pool._merge_ended()
