"""SDDMM Pallas kernel — per-edge elementwise products (NGCF similarity term).

out[i,k,:] = h[nbr[i,k],:] * h[i,:] * mask[i,k]   over (D,K,F).
Same HBM-table row gather as SpMM (``gather.py``).  The destination row
h[i] comes through the gather too, as an extra index column holding i.
The kernel writes a lane-dense (D, K*Fp) block that is viewed as (D,K,Fp).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .config import resolve_interpret
from .gather import (TABLE_SPEC, block_rows, gather_rows, index_spec,
                     pad_rows, round_up, row_spec, slab_scratch, slot, table)


def _sddmm_kernel(idx_ref, h_hbm, mask_ref, o_ref, slab, sem):
    gather_rows(h_hbm, idx_ref, slab, sem)
    mask = mask_ref[...]
    bd, k = mask.shape
    fp = slab.shape[-1]
    dst = slot(slab, k, bd)                    # the destination rows h[i]
    for s in range(k):
        o_ref[:, s * fp:(s + 1) * fp] = (
            slot(slab, s, bd) * dst * mask[:, s:s + 1]).astype(o_ref.dtype)


def sddmm(h: jax.Array, nbr: jax.Array, mask: jax.Array, *, bd: int = 64,
          interpret: bool | None = None) -> jax.Array:
    return _sddmm(h, nbr, mask, bd=bd, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def _sddmm(h: jax.Array, nbr: jax.Array, mask: jax.Array, *, bd: int,
           interpret: bool) -> jax.Array:
    f = h.shape[1]
    d, k = nbr.shape
    tab = table(h)
    fp = tab.shape[-1]
    bd = block_rows(d, k + 1, fp, h.dtype.itemsize, bd)
    dp = round_up(d, bd)
    idx = jnp.concatenate([nbr.astype(jnp.int32),
                           jnp.arange(d, dtype=jnp.int32)[:, None]], axis=1)
    out = pl.pallas_call(
        _sddmm_kernel,
        grid=(dp // bd,),
        in_specs=[index_spec(bd, k + 1), TABLE_SPEC, row_spec(bd, k)],
        out_specs=pl.BlockSpec((bd, k * fp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((dp, k * fp), h.dtype),
        scratch_shapes=slab_scratch(k + 1, bd, fp, h.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pad_rows(idx, dp), tab, pad_rows(mask, dp))
    return out.reshape(dp, k, fp)[:d, :, :f]
