"""The plain reference for a served GNN request, and the comparison.

A request is ``(targets, seed)``.  Its sampled neighbourhood follows the
semantics the service promises (GraphSAGE-style unique-neighbour sampling):

  * hop k starts from level k's vertex list (level 0: the targets, repeats
    kept) and visits it in order;
  * a vertex's neighbours are its distinct graph neighbours and itself,
    sorted by id.  With more than ``fanout`` of them, ``fanout`` uniforms
    are drawn from the request's stream ``np.random.default_rng(seed)`` and
    Floyd's algorithm picks that many positions without replacement;
    otherwise all are taken in order;
  * level k+1 is level k followed by every newly seen vertex in order of
    first appearance; a repeated vertex of level k answers to its last
    position.

The embedding rows are the benchmark's own feature table, the graph the
benchmark's own edges (``graphgen.Graph``), and the model the plain forward
of ``models/<model>.py``.  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np


def floyd(u: np.ndarray, m: int, k: int) -> list[int]:
    """Floyd's sampling of k of m positions from k uniforms."""
    seen: set[int] = set()
    out = []
    for j in range(k):
        t = int(u[j] * (m - k + j + 1))
        if t in seen:
            t = m - k + j
        seen.add(t)
        out.append(t)
    return out


def sample(neighbors, targets, seed: int, fanouts):
    """Levels and blocks of one request.

    ``neighbors(vid)`` gives the sorted neighbour ids including ``vid``.
    Returns ``(levels, blocks)``: ``levels[k]`` the vertex ids of level k
    and ``blocks`` the ``(nbr, mask)`` of each GNN layer, outermost first.
    """
    rng = np.random.default_rng(int(seed))
    levels = [[int(v) for v in targets]]
    hops = []
    for fanout in fanouts:
        frontier = levels[-1]
        local = {v: i for i, v in enumerate(frontier)}
        nxt = list(frontier)
        nbr = np.zeros((len(frontier), fanout), np.int64)
        mask = np.zeros((len(frontier), fanout), np.float32)
        for i, v in enumerate(frontier):
            nb = neighbors(v)
            if len(nb) > fanout:
                sel = nb[floyd(rng.random(fanout), len(nb), fanout)]
            else:
                sel = nb
            for k, w in enumerate(sel.tolist()):
                j = local.get(w)
                if j is None:
                    j = local[w] = len(nxt)
                    nxt.append(w)
                nbr[i, k] = j
                mask[i, k] = 1.0
        hops.append((nbr, mask))
        levels.append(nxt)
    return levels, hops[::-1]


def answer(model, neighbors, table, params, targets, seed, fanouts):
    """The reference's rows for one request, one per target."""
    levels, blocks = sample(neighbors, targets, seed, fanouts)
    emb = table[np.asarray(levels[-1], dtype=np.int64)]
    return model.forward(emb, blocks, params)


def rel_l2(rows, ref) -> float:
    """``|rows - ref| / |ref|`` in the Frobenius norm; inf when the shapes
    differ or a value is not finite."""
    rows = np.asarray(rows, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    if rows.shape != ref.shape or not np.isfinite(rows).all():
        return float("inf")
    scale = float(np.linalg.norm(ref))
    if not scale > 0:
        return float("inf")
    return float(np.linalg.norm(rows - ref)) / scale


def matmul_bf16x3(xp):
    """A matrix product at JAX's ``high`` precision on a TPU, written out:
    each float32 operand splits into a bfloat16 head and a bfloat16 tail,
    and the tail-by-tail product is dropped.  Written out so that it means
    the same on every backend."""
    import jax.numpy as jnp

    def split(a):
        hi = a.astype(jnp.bfloat16).astype(jnp.float32)
        lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
        return hi, lo

    def mm(a, b):
        ah, al = split(a)
        bh, bl = split(b)
        dot = lambda x, y: xp.matmul(x, y, precision="highest")  # noqa: E731
        return dot(ah, bh) + dot(ah, bl) + dot(al, bh)
    return mm
