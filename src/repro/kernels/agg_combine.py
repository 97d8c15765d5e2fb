"""Fused aggregate-combine Pallas kernel — one GCN layer in one kernel.

Computes ``relu(spmm(h, nbr, mask, mode) @ w + b)`` without materialising the
aggregated features in HBM: the row gather (``gather.py``) and VPU reduce land
in a VMEM scratch slab that feeds the MXU matmul directly — the GNNHLS-style
aggregate/combine fusion on top of GraphStore's page-shaped ELL blocks.

Grid is (dst blocks, output-feature tiles) with the output dimension
innermost: the aggregation for a destination block runs once (at the first
output tile) and is reused from scratch across all output tiles, so the
expensive irregular gather is never recomputed per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .config import resolve_interpret
from .gather import (TABLE_SPEC, aggregate, block_rows, gather_rows,
                     index_spec, pad_rows, round_up, row_spec, slab_scratch,
                     table)


def _agg_combine_kernel(nbr_ref, h_hbm, mask_ref, w_ref, *refs, mode: str,
                        epilogue: bool):
    if epilogue:
        b_ref, o_ref, agg_ref, slab, sem = refs
    else:
        o_ref, agg_ref, slab, sem = refs

    @pl.when(pl.program_id(1) == 0)
    def _aggregate():
        gather_rows(h_hbm, nbr_ref, slab, sem)
        agg_ref[...] = aggregate(slab, mask_ref[...], mode)

    z = jnp.dot(agg_ref[...], w_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    if epilogue:
        z = jnp.maximum(z + b_ref[...].astype(jnp.float32), 0.0)
    o_ref[...] = z.astype(o_ref.dtype)


def agg_combine(h: jax.Array, nbr: jax.Array, mask: jax.Array,
                w: jax.Array, b: jax.Array, *, mode: str = "mean",
                bd: int = 128, bo: int = 128,
                interpret: bool | None = None) -> jax.Array:
    """h (N,F); nbr,mask (D,K); w (F,O); b (O,) -> relu(agg@w+b) (D,O)."""
    return _agg_combine(h, nbr, mask, w, b, mode=mode, bd=bd, bo=bo,
                        interpret=resolve_interpret(interpret))


def agg_combine_partial(h: jax.Array, nbr: jax.Array, mask: jax.Array,
                        w: jax.Array, *, mode: str = "mean",
                        bd: int = 128, bo: int = 128,
                        interpret: bool | None = None) -> jax.Array:
    """Slice-shaped SPMD entry point: ``agg @ w`` with NO bias/relu epilogue.

    The SPMD engine calls this per mesh slice with feature-sharded ``h``
    and row-sharded ``w``; the partial products are then ``psum``-reduced
    across the ``model`` axis and the bias+relu epilogue applied to the
    full sum (a nonlinearity cannot be applied to a partial sum).  Same
    fused Pallas kernel, epilogue compiled out.
    """
    return _agg_combine(h, nbr, mask, w, None, mode=mode, bd=bd, bo=bo,
                        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("mode", "bd", "bo", "interpret"))
def _agg_combine(h, nbr, mask, w, b, *, mode, bd, bo, interpret):
    d, k = nbr.shape
    o = w.shape[1]
    tab = table(h)
    fp = tab.shape[-1]
    bd = block_rows(d, k, fp, h.dtype.itemsize, bd)
    bo = min(bo, max(128, o))
    dp = round_up(d, bd)
    op = round_up(o, bo)
    in_specs = [index_spec(bd, k), TABLE_SPEC, row_spec(bd, k),
                pl.BlockSpec((fp, bo), lambda i, j: (0, j))]
    args = [pad_rows(nbr.astype(jnp.int32), dp), tab, pad_rows(mask, dp),
            jnp.pad(w, ((0, fp - w.shape[0]), (0, op - o)))]
    if b is not None:
        in_specs.append(pl.BlockSpec((1, bo), lambda i, j: (0, j)))
        args.append(jnp.pad(b.reshape(1, -1), ((0, 0), (0, op - o))))
    out = pl.pallas_call(
        functools.partial(_agg_combine_kernel, mode=mode,
                          epilogue=b is not None),
        grid=(dp // bd, op // bo),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bd, bo), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((dp, op), h.dtype),
        scratch_shapes=[pltpu.VMEM((bd, fp), jnp.float32)]
        + slab_scratch(k, bd, fp, h.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out[:d, :o]
