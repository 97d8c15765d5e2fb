"""Paged decode attention — GraphStore's VID->LPN mapping as a KV page table.

This is the paper's storage technique landed in the serving hot loop: the KV
cache lives in fixed-size *pages* (the paper's 4 KB flash pages; here
``page_size`` KV slots), and a per-sequence **page table** (logical page ->
physical page, exactly the H-type VID->LPN chain flattened) tells the kernel
where each logical block of the sequence physically resides.

The page table and sequence lengths ride in **scalar-prefetch** (SMEM), so
the BlockSpec index_map itself performs the translation — the DMA engine
fetches physical page ``pt[b, p]`` while the MXU/VPU works on the previous
page: near-data gather with zero host involvement, the CSSD insight on TPU.

Grid (B, Hkv, PP): one token's attention per (batch, kv-head), online
softmax across that sequence's pages; GQA handled by grouping Hq/Hkv query
heads into the sublane dimension of a single (G, D) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .config import resolve_interpret

_LANES = 128
NEG_INF = -1e30


def _decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, ps: int, n_p: int):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(p * ps < length)                     # skip fully-past-end pages
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)       # (G, D)
        k = k_ref[0, :, 0].astype(jnp.float32)    # (ps, D)
        v = v_ref[0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = p * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < length
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_ref[:, :1] + pexp.sum(axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            pexp, v, preferred_element_type=jnp.float32)

    @pl.when(p == n_p - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     page_table: jax.Array, lengths: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """q (B,Hq,D); pages (P,ps,Hkv,D); page_table (B,PP); lengths (B,)."""
    return _decode_attention(q, k_pages, v_pages, page_table, lengths,
                             interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      page_table: jax.Array, lengths: jax.Array, *,
                      interpret: bool) -> jax.Array:
    b, hq, d = q.shape
    p_num, ps, hkv, _ = k_pages.shape
    pp = page_table.shape[1]
    g = hq // hkv
    scale = float(1.0 / (d ** 0.5))
    qg = q.reshape(b, hkv, g, d)
    # physical pages laid out (P, ps, Hkv, D) -> kernel reads (ps, 1, D) tiles
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, ps=ps, n_p=pp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, pp),
            in_specs=[
                pl.BlockSpec((1, 1, g, d),
                             lambda bi, h, p, pt, ln: (bi, h, 0, 0)),
                pl.BlockSpec((1, ps, 1, d),
                             lambda bi, h, p, pt, ln: (pt[bi, p], 0, h, 0)),
                pl.BlockSpec((1, ps, 1, d),
                             lambda bi, h, p, pt, ln: (pt[bi, p], 0, h, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda bi, h, p, pt, ln: (bi, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g, _LANES), jnp.float32),
                pltpu.VMEM((g, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, lengths, qg, k_pages, v_pages)
    return out.reshape(b, hq, d)
