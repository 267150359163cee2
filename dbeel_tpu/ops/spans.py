"""Stage spans of the device merge paths.

One pair of clock reads per stage does two things: its seconds and its
count go into ``compaction_stats`` (``get_stats.compaction.stages``,
always on), and a ``jax.profiler.TraceAnnotation`` named
``dbeel.<path>.<stage>`` is open for the same stretch — a flag check
while no profile runs, an event on the calling thread's host-plane
line, on the profiler's clock beside the device's ``XLA Modules``
events, while one does.  ``merge`` (drawn per merge) and the keyword
arguments (``part=``, ``launch=``, ``run=``) become the event's stats,
so every span of one merge shares an identifier and each names what
caused it.

The rule is ``server/trace.py::TraceCtx``'s: ``Stages`` are sequential
and partition their outer span ``dbeel.<path>.merge`` exactly (one
clock read ends a stage and starts the next); ``stage`` is for work on
another thread, or nested in a stage, that overlaps them — counted
beside the sum, never in it.
"""

from __future__ import annotations

import itertools
import time

from jax.profiler import TraceAnnotation

from ..storage.compaction import compaction_stats

# The outer span of a path: every merge's whole wall on the thread
# that called it.
OUTER = "merge"

# Process-wide: output indices repeat (a tree reuses them, the
# benchmark's merge job writes 101 every time), a merge's id does not.
_merge_ids = itertools.count(1)


class stage:
    """``with stage("pipeline", "d2h", merge=m, launch=n):`` — one
    span that may overlap any other."""

    __slots__ = ("_path", "_name", "_ann", "_t0")

    def __init__(self, path: str, name: str, **ids) -> None:
        self._path = path
        self._name = name
        self._ann = TraceAnnotation(f"dbeel.{path}.{name}", **ids)

    def begin(self, now: float) -> None:
        self._t0 = now
        self._ann.__enter__()

    def end(self, now: float) -> None:
        self._ann.__exit__(None, None, None)
        compaction_stats.note_stage(
            self._path, self._name, now - self._t0
        )

    def __enter__(self) -> "stage":
        self.begin(time.perf_counter())
        return self

    def __exit__(self, *exc) -> None:
        self.end(time.perf_counter())


class Stages:
    """The sequential stages of one merge on the thread that runs it.
    ``with Stages(path, first) as at:`` draws the merge's id, opens
    the outer span and ``first``; ``at.to(name, part=p)`` ends the open
    stage and starts ``name`` on one clock read; leaving the block — by
    return or by raise — ends whatever is open.  So the stages' seconds
    sum to the outer span's, whichever way the merge ends.
    ``at.span(name, ...)`` is a ``stage`` of the same merge for its
    other threads."""

    __slots__ = ("_path", "_ids", "_outer", "_open")

    def __init__(self, path: str, first: str) -> None:
        self._path = path
        self._ids = {"merge": next(_merge_ids)}
        self._outer = self.span(OUTER)
        self._open = self.span(first)

    def span(self, name: str, **ids) -> stage:
        return stage(self._path, name, **self._ids, **ids)

    def __enter__(self) -> "Stages":
        now = time.perf_counter()
        self._outer.begin(now)
        self._open.begin(now)
        return self

    def to(self, name: str, **ids) -> None:
        now = time.perf_counter()
        self._open.end(now)
        self._open = self.span(name, **ids)
        self._open.begin(now)

    def __exit__(self, *exc) -> None:
        now = time.perf_counter()
        self._open.end(now)
        self._outer.end(now)
