"""Fused RMSNorm Pallas kernel (row blocks, full feature dim in VMEM)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .config import resolve_interpret


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps) * w_ref[...]).astype(o_ref.dtype)


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6, br: int = 256,
            interpret: bool | None = None) -> jax.Array:
    return _rmsnorm(x, w, eps=eps, br=br,
                    interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("eps", "br", "interpret"))
def _rmsnorm(x: jax.Array, w: jax.Array, *, eps: float, br: int,
             interpret: bool) -> jax.Array:
    orig_shape = x.shape
    f = orig_shape[-1]
    x2 = x.reshape(-1, f)
    b = x2.shape[0]
    br = min(br, max(8, b))
    bp = -(-b // br) * br
    xp = jnp.pad(x2, ((0, bp - b), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(bp // br,),
        in_specs=[
            pl.BlockSpec((br, f), lambda i: (i, 0)),
            pl.BlockSpec((f,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, f), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xp, w)
    return out[:b].reshape(orig_shape)
