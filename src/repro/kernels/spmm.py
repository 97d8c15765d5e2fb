"""ELL/page-format SpMM Pallas kernel — the "vector processor" aggregation.

Consumes GraphStore's page-shaped blocks directly: a (D,K) padded
neighbor-index matrix + mask against the sampled embedding table h (N,F).
The table stays in HBM; each grid step DMAs the rows its ``bd`` destination
rows name into VMEM (``gather.py``) and reduces them on the VPU, so VMEM use
does not grow with the super-batch.  Grid is (dst blocks,).
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


def _spmm_kernel(nbr_ref, h_hbm, mask_ref, o_ref, slab, sem, *, mode: str):
    gather_rows(h_hbm, nbr_ref, slab, sem)
    o_ref[...] = aggregate(slab, mask_ref[...], mode).astype(o_ref.dtype)


def spmm(h: jax.Array, nbr: jax.Array, mask: jax.Array, *, mode: str = "mean",
         bd: int = 128, interpret: bool | None = None) -> jax.Array:
    return _spmm(h, nbr, mask, mode=mode, bd=bd,
                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("mode", "bd", "interpret"))
def _spmm(h: jax.Array, nbr: jax.Array, mask: jax.Array, *, mode: str,
          bd: int, interpret: bool) -> jax.Array:
    f = h.shape[1]
    d, k = nbr.shape
    tab = table(h)
    fp = tab.shape[-1]
    bd = block_rows(d, k, fp, h.dtype.itemsize, bd)
    dp = round_up(d, bd)
    out = pl.pallas_call(
        functools.partial(_spmm_kernel, mode=mode),
        grid=(dp // bd,),
        in_specs=[index_spec(bd, k), TABLE_SPEC, row_spec(bd, k)],
        out_specs=pl.BlockSpec((bd, fp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((dp, fp), h.dtype),
        scratch_shapes=slab_scratch(k, bd, fp, h.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pad_rows(nbr.astype(jnp.int32), dp), tab, pad_rows(mask, dp))
    return out[:d, :f]
