"""Open-loop arithmetic: latency from the moment an operation was DUE, so
that a stall is charged to every operation that waited behind it, and how
late the generator itself launched."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (nearest rank, no interpolation): the value
    at or below which q % of the samples lie."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if len(v) == 0:
        raise ValueError("no samples")
    rank = int(np.ceil(q / 100.0 * len(v)))
    return float(v[max(0, rank - 1)])


def due_latency_ms(due, done) -> np.ndarray:
    return (np.asarray(done) - np.asarray(due)) * 1e3


def lateness_ms(due, launch) -> np.ndarray:
    """How long after its due time each operation was launched."""
    return np.maximum(0.0, np.asarray(launch) - np.asarray(due)) * 1e3


def conditioned_poisson(rng: np.random.Generator, n: int, span_s: float):
    """Arrival times of a Poisson process on [0, span_s) given that it
    had exactly ``n`` arrivals: sorted uniforms.  Every seed offers the
    same number of operations, at other moments."""
    return np.sort(rng.random(n)) * span_s
