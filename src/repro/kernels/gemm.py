"""Blocked GEMM Pallas kernel — the "systolic array" User logic on TPU.

MXU-aligned (128x128x128 default) accumulation over a 3D grid with an fp32
VMEM accumulator; K is the innermost ("arbitrary") dimension so each (i,j)
output tile is revisited across K steps — the canonical TPU matmul pipeline
(HBM -> VMEM double-buffered by pallas, MXU per tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .config import resolve_interpret


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                            b_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def gemm(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
         bk: int = 128, interpret: bool | None = None) -> jax.Array:
    """a (M,K) @ b (K,N) -> (M,N) in a's dtype (fp32 accumulate)."""
    return _gemm(a, b, bm=bm, bn=bn, bk=bk,
                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _gemm(a: jax.Array, b: jax.Array, *, bm: int, bn: int, bk: int,
          interpret: bool) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    ap = _pad_to(_pad_to(a, bm, 0), bk, 1)
    bp = _pad_to(_pad_to(b, bk, 0), bn, 1)
    mp, kp = ap.shape
    np_ = bp.shape[1]
    n_k = kp // bk
    out = pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=n_k),
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]
