"""Open-loop arrivals at a fixed mean rate, the gaps of a Poisson process.

Parameters (the workload file): ``rate_rps``, ``targets_per_request`` and
``targets`` (``{"law": "zipf", "s": ...}`` or ``{"law": "uniform"}``).

Every seed gets the same set of gaps: the ``n = round(rate * seconds)``
quantiles ``-ln(1 - (i + 0.5) / n) / rate`` of the exponential
distribution, in a seeded order and scaled to end within the window.  So
seeds differ in the order of the gaps and in the targets, not in how much
arrives.  Each request also carries its own sampling seed.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

KIND = "open"


@dataclass
class Schedule:
    due: np.ndarray          # seconds after the window opens
    targets: np.ndarray      # (n, targets_per_request) vertex ids
    seeds: np.ndarray        # per-request sampling seeds


def target_ids(rng: np.random.Generator, law: dict, n_vertices: int,
               shape, popularity: np.random.Generator) -> np.ndarray:
    """Vertex ids drawn by ``law`` over a ranking of the vertices drawn
    from ``popularity``."""
    if law["law"] == "uniform":
        return rng.integers(0, n_vertices, shape)
    if law["law"] != "zipf":
        raise ValueError(f"unknown target law {law!r}")
    rank = popularity.permutation(n_vertices)
    cdf = np.cumsum(1.0 / np.arange(1, n_vertices + 1) ** float(law["s"]))
    r = np.searchsorted(cdf, rng.random(shape) * cdf[-1], side="right")
    return rank[np.minimum(r, n_vertices - 1)]


def build(spec: dict, rng: np.random.Generator, seconds: float,
          n_vertices: int, popularity: np.random.Generator) -> Schedule:
    """``popularity`` ranks the vertices for a skewed law; the set-up's
    traffic and the window's share it, so the hot set stays hot."""
    rate = float(spec["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    due = due - due[0] * 0.5                 # first arrival half a gap in
    k = int(spec["targets_per_request"])
    return Schedule(due=due,
                    targets=target_ids(rng, spec["targets"], n_vertices,
                                       (n, k), popularity),
                    seeds=rng.integers(0, 2**31 - 1, n))
