"""GCN as the benchmark knows it: weights, the plain forward, and the
operations and bytes its layers need.

The forward follows Kipf & Welling's GCN layer as this system serves it:
``h' = relu(mean_{u in S(v)} h[u] @ W + b)``, where ``S(v)`` is the sampled
neighbour set of ``v``.  Departures from the paper, which the program makes
and the reference therefore makes too: the mean is over the sampled
neighbours (each vertex is its own neighbour through a self-loop), not the
symmetric degree normalisation; there is no separate root weight; and the
last layer keeps its ReLU.

Nothing here imports the program.  ``forward`` runs on NumPy in float64
(the reference) or on ``jax.numpy`` in float32 with a matrix product the
caller chooses (the control).
"""
from __future__ import annotations

import numpy as np

WEIGHT_DTYPE = np.float32


def init_weights(rng: np.random.Generator, widths: list[int]) -> list[dict]:
    """Glorot-uniform W and a small uniform b per layer, float32."""
    params = []
    for fi, fo in zip(widths[:-1], widths[1:]):
        s = np.sqrt(6.0 / (fi + fo))
        params.append({
            "W": rng.uniform(-s, s, (fi, fo)).astype(WEIGHT_DTYPE),
            "b": rng.uniform(-0.1, 0.1, fo).astype(WEIGHT_DTYPE)})
    return params


def service_weights(params: list[dict]) -> dict:
    """The feed names of the program's GCN DFG (``W{l}``, ``b{l}``)."""
    out = {}
    for l, p in enumerate(params):
        out[f"W{l}"], out[f"b{l}"] = p["W"], p["b"]
    return out


def forward(emb, blocks, params, *, xp=np, matmul=None):
    """``emb`` holds the rows of the deepest level; ``blocks`` are
    ``(nbr, mask)`` pairs, outermost layer first.  NumPy inputs give a
    float64 forward."""
    matmul = matmul or (lambda a, b: a @ b)
    h = emb.astype(np.float64) if xp is np else emb
    for p, (nbr, mask) in zip(params, blocks):
        w = p["W"].astype(np.float64) if xp is np else p["W"]
        b = p["b"].astype(np.float64) if xp is np else p["b"]
        g = xp.take(h, nbr, axis=0) * mask[..., None]
        agg = g.sum(axis=1) / xp.maximum(mask.sum(axis=1), 1.0)[:, None]
        h = xp.maximum(matmul(agg, w) + b, 0.0)
    return h


def layer_work(widths: list[int], rows: list[int], slots: list[int],
               itemsize: int = 4) -> list[dict]:
    """Operations and HBM bytes each layer needs, outermost first.

    ``rows[l]`` destination rows and ``slots[l]`` live neighbour slots of
    layer ``l``, summed over the requests counted.  A mean costs one add per
    live slot and feature and one scale per row and feature; the combine
    ``2 F_in F_out`` per row; bias and ReLU two per output.  Bytes are the
    gathered rows, the slot indices and mask, and the output; the weights
    are added per call by the caller (``weight_bytes``).
    """
    out = []
    for (fi, fo), d, s in zip(zip(widths[:-1], widths[1:]), rows, slots):
        flops = s * fi + d * fi + 2 * d * fi * fo + 2 * d * fo
        nbytes = s * fi * itemsize + s * 8 + d * fo * itemsize
        out.append({"flops": float(flops), "bytes": float(nbytes)})
    return out


def weight_bytes(widths: list[int], itemsize: int = 4) -> list[float]:
    """Bytes of W and b read by one call of each layer."""
    return [float((fi * fo + fo) * itemsize)
            for fi, fo in zip(widths[:-1], widths[1:])]
