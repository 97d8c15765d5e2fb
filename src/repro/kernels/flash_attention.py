"""Flash attention Pallas kernel (causal, online softmax) for train/prefill.

Grid (B*H, Tq/bq, Tk/bk); K is the innermost arbitrary dimension so each
query tile is revisited across KV tiles with running (m, l, acc) state in
VMEM scratch — the TPU analog of the paper's dense-compute path routed to
the systolic unit (QK^T and PV on the MXU, softmax on the VPU).
Causal tiles entirely above the diagonal are skipped via pl.when (compute
skip; the HLO cost model sees the saved FLOPs through the mask either way).
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, n_k: int, bq: int, bk: int,
                  kv_len: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip tiles strictly above the diagonal
    run = (not causal) or (j * bk <= i * bq + bq - 1)

    @pl.when(run)
    def _update():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_prev + p.sum(axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(j == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q (B,Hq,T,D); k,v (B,Hkv,S,D) with Hq % Hkv == 0 -> (B,Hq,T,D)."""
    return _flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def _flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool, bq: int, bk: int,
                     interpret: bool) -> jax.Array:
    b, hq, t, d = q.shape
    hkv, s_len = k.shape[1], k.shape[2]
    if hq != hkv:                                     # GQA: broadcast KV heads
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = float(1.0 / (d ** 0.5))
    bq = min(bq, max(8, t))
    bk = min(bk, max(128, s_len))
    tp = -(-t // bq) * bq
    sp = -(-s_len // bk) * bk
    qf = jnp.pad(q.reshape(b * hq, t, d), ((0, 0), (0, tp - t), (0, 0)))
    kf = jnp.pad(k.reshape(b * hq, s_len, d), ((0, 0), (0, sp - s_len), (0, 0)))
    vf = jnp.pad(v.reshape(b * hq, s_len, d), ((0, 0), (0, sp - s_len), (0, 0)))
    n_k = sp // bk
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal, n_k=n_k,
                          bq=bq, bk=bk, kv_len=s_len),
        grid=(b * hq, tp // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, tp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :t].reshape(b, hq, t, d)
