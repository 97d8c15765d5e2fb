"""Seeded graphs at a published vertex and edge count, and their features.

The degree model is Chung-Lu: an edge joins two endpoints drawn
independently, each with probability proportional to its vertex's weight.
Weights follow a power law over the vertices' ranks,
``w(r) ~ (r + r0) ** (-1 / (gamma - 1))``, which gives degrees a power-law
tail with exponent ``gamma``.  ``r0`` is set so that the heaviest vertex's
expected degree is ``sqrt(2 E)``, the largest the model allows without
expecting more than one edge between two hubs.  Endpoints are drawn by the
inverse of the continuous rank distribution, so a draw costs O(1).

Self-loops and repeated pairs are dropped, and draws continue until exactly
``E`` distinct undirected edges exist; of the last draw's new pairs, a
random subset makes up the count.  Rank
r becomes vertex ``label[r]`` under a seeded permutation, so the hubs are
spread over the id space.  The program under test takes the edges as an
edge list; the benchmark's reference looks neighbours up in the same
distinct pairs, sorted here (``Graph.neighbors``), and never in the store.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np


def _rank_cdf(x: float, n: int, alpha: float, r0: float) -> float:
    a = 1.0 - alpha
    lo, hi = r0 ** a, (n + r0) ** a
    return ((x + r0) ** a - lo) / (hi - lo)


def rank_offset(n: int, edges: int, gamma: float) -> float:
    """``r0`` at which the top rank's expected degree is ``sqrt(2 E)``."""
    alpha = 1.0 / (gamma - 1.0)
    want = math.sqrt(2.0 * edges) / (2.0 * edges)   # top rank's share
    lo, hi = 1e-9, float(n)
    for _ in range(200):                            # share falls as r0 grows
        mid = math.sqrt(lo * hi)
        if _rank_cdf(1.0, n, alpha, mid) > want:
            lo = mid
        else:
            hi = mid
    return math.sqrt(lo * hi)


def _draw_ranks(rng, m: int, n: int, alpha: float, r0: float) -> np.ndarray:
    a = 1.0 - alpha
    lo, hi = r0 ** a, (n + r0) ** a
    y = lo + rng.random(m) * (hi - lo)
    e = 1.0 / a
    # a whole exponent (3 at gamma 2.5) multiplies instead of calling pow
    y = y ** int(round(e)) if abs(e - round(e)) < 1e-12 else y ** e
    return np.minimum((y - r0).astype(np.int64), n - 1)


@dataclass
class Graph:
    """Distinct undirected edges of a seeded power-law graph."""
    n: int
    label: np.ndarray          # rank -> vertex id
    keys: np.ndarray           # sorted distinct rank pairs lo * n + hi, lo < hi
    _by_hi: np.ndarray | None = field(default=None, repr=False)
    _rank_of: np.ndarray | None = field(default=None, repr=False)

    @property
    def num_edges(self) -> int:
        return len(self.keys)

    def edge_array(self) -> np.ndarray:
        """(E, 2) int64 vertex-id pairs, one row per undirected edge."""
        lo, hi = np.divmod(self.keys, self.n)
        return np.stack([self.label[lo], self.label[hi]], axis=1)

    def degrees(self) -> np.ndarray:
        """Degree of each vertex id (self-loops not counted)."""
        lo, hi = np.divmod(self.keys, self.n)
        by_rank = np.bincount(lo, minlength=self.n) \
            + np.bincount(hi, minlength=self.n)
        out = np.empty(self.n, np.int64)
        out[self.label] = by_rank
        return out

    def neighbors(self, vid: int) -> np.ndarray:
        """Sorted neighbour ids of ``vid``, itself included."""
        if self._by_hi is None:
            lo, hi = np.divmod(self.keys, self.n)
            self._by_hi = np.sort(hi * self.n + lo)
            self._rank_of = np.empty(self.n, np.int64)
            self._rank_of[self.label] = np.arange(self.n)
        r = int(self._rank_of[vid])
        a = self.keys[np.searchsorted(self.keys, r * self.n):
                      np.searchsorted(self.keys, (r + 1) * self.n)] % self.n
        b = self._by_hi[np.searchsorted(self._by_hi, r * self.n):
                        np.searchsorted(self._by_hi, (r + 1) * self.n)] \
            % self.n
        ids = np.concatenate([self.label[a], self.label[b], [vid]])
        return np.sort(ids)


def power_law_graph(rng: np.random.Generator, n: int, edges: int,
                    gamma: float) -> Graph:
    """Exactly ``edges`` distinct undirected edges over ``n`` vertices."""
    alpha = 1.0 / (gamma - 1.0)
    r0 = rank_offset(n, edges, gamma)
    label = rng.permutation(n).astype(np.int64)
    keys = np.empty(0, np.int64)
    while len(keys) < edges:
        need = edges - len(keys)
        m = need if not len(keys) else int(need * 1.2) + 1024
        u = _draw_ranks(rng, m, n, alpha, r0)
        v = _draw_ranks(rng, m, n, alpha, r0)
        keep = u != v
        new = np.unique(np.minimum(u[keep], v[keep]) * n
                        + np.maximum(u[keep], v[keep]))
        if len(keys):
            pos = np.minimum(np.searchsorted(keys, new), len(keys) - 1)
            new = new[keys[pos] != new]
        if len(new) > need:                  # a random subset of the last
            new = np.sort(rng.choice(new, need, replace=False))
        keys = (np.insert(keys, np.searchsorted(keys, new), new)
                if len(keys) else new)
    return Graph(n=n, label=label, keys=keys)


def features(rng: np.random.Generator, n: int, width: int) -> np.ndarray:
    """(n, width) float32 rows, uniform in [-1, 1)."""
    out = rng.random((n, width), dtype=np.float32)
    out *= 2.0
    out -= 1.0
    return out


def describe(g: Graph, h_threshold: int) -> dict:
    """Degree statistics, with each vertex's self-loop counted as the
    store counts it."""
    deg = g.degrees() + 1
    return {"vertices": g.n, "edges": g.num_edges,
            "mean_degree": float(deg.mean()), "max_degree": int(deg.max()),
            "share_above_h_threshold": float((deg > h_threshold).mean()),
            "largest_endpoint_share": float((deg.max() - 1)
                                            / (2 * g.num_edges))}
