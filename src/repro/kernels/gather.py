"""Row gather shared by the graph kernels (SpMM, SDDMM, AggCombine).

Every ELL/page-format kernel reads, for destination row i and slot k, the
row ``h[nbr[i, k]]`` of the sampled embedding table.  A fused super-batch
table reaches 16K rows x 420 features, far more than a VMEM block should
hold, so the table stays in HBM and its node axis is never a block axis:

  * ``table(h)`` pads the feature axis to whole 128-lane tiles and views the
    table as ``(N, 1, Fp)``: one node row is then a slice of the untiled
    leading axis, which a DMA can address (a 1-row slice of an (8, 128)-tiled
    2D array is refused by Mosaic);
  * each grid step owns ``bd`` destination rows.  Their ``(bd, K)`` index
    block sits in SMEM (``index_spec``) and drives ``bd * K`` row DMAs into a
    VMEM slab ``(K * bd, 1, Fp)``, slot-major, so slot k's rows are the
    contiguous ``slot(slab, k)``;
  * ``block_rows`` caps ``bd`` so the slab stays under ``SLAB_BYTES``
    whatever N is.

Indices must lie in ``[0, N)``: padding slots point at row 0 under a zero
mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

SLAB_BYTES = 4 << 20       # VMEM budget of one step's gathered rows
_WINDOW = 16               # destination rows whose DMAs may be in flight

TABLE_SPEC = pl.BlockSpec(memory_space=pltpu.HBM)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def table(h: jax.Array) -> jax.Array:
    """(N, F) -> (N, 1, Fp): the HBM-resident gather source."""
    n, f = h.shape
    fp = round_up(f, 128)
    return jnp.pad(h, ((0, 0), (0, fp - f))).reshape(n, 1, fp)


def block_rows(d: int, k: int, fp: int, itemsize: int, bd: int) -> int:
    """Destination rows per grid step: at most ``bd``, a multiple of 8, and
    small enough that the ``k``-slot slab fits ``SLAB_BYTES``."""
    cap = max(8, SLAB_BYTES // (k * fp * itemsize) // 8 * 8)
    return max(8, min(bd, cap, round_up(d, 8)))


def pad_rows(x: jax.Array, rows: int) -> jax.Array:
    return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def index_spec(bd: int, k: int) -> pl.BlockSpec:
    """Step i's ``(bd, k)`` int32 index block, in SMEM."""
    return pl.BlockSpec((bd, k), lambda i, *_: (i, 0),
                        memory_space=pltpu.SMEM)


def row_spec(bd: int, k: int) -> pl.BlockSpec:
    """Step i's ``(bd, k)`` block of a per-slot operand (the mask), in VMEM."""
    return pl.BlockSpec((bd, k), lambda i, *_: (i, 0))


def slab_scratch(k: int, bd: int, fp: int, dtype) -> list:
    return [pltpu.VMEM((k * bd, 1, fp), dtype), pltpu.SemaphoreType.DMA(())]


def gather_rows(h_hbm, idx_ref, slab, sem) -> None:
    """DMA ``h[idx[r, s]]`` into ``slab[s * bd + r]`` for the whole block,
    keeping at most ``_WINDOW`` rows of copies in flight."""
    bd, k = idx_ref.shape
    w = min(_WINDOW, bd)

    def copy(r, s):
        return pltpu.make_async_copy(h_hbm.at[idx_ref[r, s]],
                                     slab.at[s * bd + r], sem)

    def issue(r, carry):
        for s in range(k):
            copy(r, s).start()

        @pl.when(r >= w)
        def _retire():
            for s in range(k):
                copy(r - w, s).wait()
        return carry

    def drain(r, carry):
        for s in range(k):
            copy(r, s).wait()
        return carry

    jax.lax.fori_loop(0, bd, issue, 0)
    jax.lax.fori_loop(bd - w, bd, drain, 0)


def slot(slab, s: int, bd: int) -> jax.Array:
    """Slot ``s``'s gathered rows as a ``(bd, Fp)`` value."""
    return slab[s * bd:(s + 1) * bd].reshape(bd, slab.shape[-1])


def aggregate(slab, mask: jax.Array, mode: str) -> jax.Array:
    """Masked f32 sum over the slots (``mode="mean"``: over the live ones)."""
    bd, k = mask.shape
    acc = slot(slab, 0, bd).astype(jnp.float32) * mask[:, 0:1]
    for s in range(1, k):
        acc = acc + slot(slab, s, bd).astype(jnp.float32) * mask[:, s:s + 1]
    if mode == "mean":
        acc = acc / jnp.maximum(mask.sum(axis=1, keepdims=True), 1.0)
    return acc
