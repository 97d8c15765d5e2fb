"""Closed loop: a fixed number of callers, each sending its next request
when its last reply arrives.

Parameters (the workload file): ``callers``, ``targets_per_request`` and
``targets`` (``{"law": "permutation"}``: consecutive slices of a seeded
permutation of all vertices, so no target repeats).  Each request also
carries its own sampling seed.
"""
from __future__ import annotations

import threading

import numpy as np

KIND = "closed"


class Source:
    """Thread-safe supply of ``(targets, seed)``, request by request."""

    def __init__(self, spec: dict, rng: np.random.Generator,
                 n_vertices: int):
        if spec["targets"]["law"] != "permutation":
            raise ValueError(f"unknown target law {spec['targets']!r}")
        self.k = int(spec["targets_per_request"])
        self.order = rng.permutation(n_vertices)
        self.seeds = rng.integers(0, 2**31 - 1, n_vertices // self.k)
        self._next = 0
        self._lock = threading.Lock()

    def take(self) -> tuple[int, list[int], int]:
        """``(index, targets, seed)`` of the next request."""
        with self._lock:
            i = self._next
            self._next += 1
        j = i % len(self.seeds)
        return i, self.order[j * self.k:(j + 1) * self.k].tolist(), \
            int(self.seeds[j])


def build(spec: dict, rng: np.random.Generator, seconds: float,
          n_vertices: int, popularity: np.random.Generator) -> Source:
    """Every vertex is equally likely, so ``popularity`` goes unused."""
    return Source(spec, rng, n_vertices)
