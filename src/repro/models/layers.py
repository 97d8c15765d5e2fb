"""Model substrate: ParamDef-driven parameters, sharding helpers, and the
attention / MLP / MoE building blocks shared by every architecture.

Parameters are declared as ``ParamDef`` trees; from one declaration we derive
(a) initialized arrays, (b) ShapeDtypeStruct stand-ins for the dry-run (no
allocation), and (c) PartitionSpecs for pjit — so the three can never drift.

Tensor-parallel rules (model axis ``tp`` ways):
  * attention heads sharded over "model" iff divisible, else replicated
    (GSPMD needs divisible input shardings; noted per arch in DESIGN.md);
  * KV heads likewise (GQA usually replicates KV under TP);
  * d_ff always sharded (all assigned archs are 16-divisible);
  * vocab sharded over "model" iff divisible, else the embedding is sharded
    on d_model (row-parallel logits with one psum);
  * MoE experts sharded over "model" (16 experts / 16-way TP).
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass, field, replace

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding


# ---------------------------------------------------------------- mesh state
# DP is a sentinel resolved to the data-parallel axes of the active mesh;
# DPM additionally folds in the model axis (long-context cache sharding)
DP = "__dp__"
DPM = "__dp_model__"

_ACTIVE = {"mesh": None, "dp_axes": ("data",), "tp": 1}


@contextlib.contextmanager
def use_mesh(mesh, dp_axes=("data",)):
    prev = dict(_ACTIVE)
    _ACTIVE["mesh"] = mesh
    _ACTIVE["dp_axes"] = tuple(dp_axes)
    _ACTIVE["tp"] = int(mesh.shape.get("model", 1)) if mesh is not None else 1
    try:
        yield
    finally:
        _ACTIVE.update(prev)


def active_tp() -> int:
    return _ACTIVE["tp"]


def active_dp() -> int:
    mesh = _ACTIVE["mesh"]
    if mesh is None:
        return 1
    out = 1
    for a in _ACTIVE["dp_axes"]:
        out *= int(mesh.shape.get(a, 1))
    return out


def resolve_pspec(spec) -> P:
    out = []
    for s in spec:
        if s == DP:
            out.append(_ACTIVE["dp_axes"])
        elif s == DPM:
            out.append(tuple(_ACTIVE["dp_axes"]) + ("model",))
        else:
            out.append(s)
    return P(*out)


def shard(x, *spec):
    """with_sharding_constraint that no-ops off-mesh (smoke tests)."""
    mesh = _ACTIVE["mesh"]
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, resolve_pspec(spec)))


# ----------------------------------------------------------------- ParamDef
@dataclass(frozen=True)
class ParamDef:
    shape: tuple
    pspec: tuple = ()
    init: str = "normal"          # normal | zeros | ones
    scale: float = 0.02
    dtype: str = "float32"

    def materialize(self, key):
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        return (jax.random.normal(key, self.shape, jnp.float32)
                * self.scale).astype(self.dtype)


def is_def(x):
    return isinstance(x, ParamDef)


def init_tree(defs, seed: int = 0):
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(jax.random.PRNGKey(seed), max(len(leaves), 1))
    return jax.tree.unflatten(
        treedef, [d.materialize(k) for d, k in zip(leaves, keys)])


def abstract_tree(defs):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype)),
        defs, is_leaf=is_def)


def pspec_tree(defs):
    return jax.tree.map(lambda d: resolve_pspec(d.pspec), defs, is_leaf=is_def)


def stack_defs(defs, n: int):
    """Prepend a layer-stack dimension (for lax.scan over periods)."""
    return jax.tree.map(
        lambda d: replace(d, shape=(n,) + tuple(d.shape),
                          pspec=(None,) + tuple(d.pspec)),
        defs, is_leaf=is_def)


def _div(n: int, tp: int) -> bool:
    return tp > 0 and n % tp == 0


# ------------------------------------------------------------------ norms
def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(ms + eps)) * w.astype(jnp.float32)).astype(x.dtype)


def norm_def(d):
    return ParamDef((d,), (None,), init="ones")


# ------------------------------------------------------------------- rope
def rope_tables(positions, dim: int, theta: float):
    """positions (...,) int -> (..., dim/2) cos/sin tables."""
    half = dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (..., T, H, D); cos/sin = positions-shaped + (D/2,): (T,D/2) or
    (B,T,D/2).  One head axis is inserted; leading dims broadcast."""
    half = x.shape[-1] // 2
    cos = cos[..., None, :]                        # (..., T, 1, D/2)
    sin = sin[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _act(name):
    return {"silu": jax.nn.silu, "gelu": functools.partial(jax.nn.gelu,
            approximate=True), "relu": jax.nn.relu}[name]


# ================================================================ attention
def padded_heads(h: int, kvh: int, tp: int) -> int:
    """Pad the query-head dim to the TP degree when not divisible (Megatron
    head padding): padded heads are hard-masked to zero after attention, so
    the function is exactly the published model — but attention shards
    tp-ways instead of replicating (16x compute/bytes for 24/40-head archs
    on a 16-way model axis).  GQA group mapping follows the padded layout.
    """
    if tp <= 1 or _div(h, tp):
        return h
    hp = -(-h // tp) * tp
    # keep GQA grouping valid: padded heads must divide into kv groups
    while hp % kvh != 0:
        hp += tp
    return hp


def attn_defs(cfg, tp: int):
    d, h, kvh, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                     cfg.resolved_head_dim)
    hp = padded_heads(h, kvh, tp)
    h_ax = "model" if _div(hp, tp) else None
    kv_ax = "model" if _div(kvh, tp) else None
    return {
        "wq": ParamDef((d, hp, hd), (None, h_ax, None)),
        "wk": ParamDef((d, kvh, hd), (None, kv_ax, None)),
        "wv": ParamDef((d, kvh, hd), (None, kv_ax, None)),
        "wo": ParamDef((hp, hd, d), (h_ax, None, None)),
        "ln": norm_def(d),
    }


def _head_mask(out, h_real: int, kvh: int = 1):
    """Zero the padded heads of (..., H_pad, hd) attention output.

    Padding is per KV group: real head i occupies slot
    (i // g) * g_pad + (i % g), so slot s is real iff s % g_pad < g.
    (This is also the checkpoint-import remap rule.)"""
    hp = out.shape[-2]
    if hp == h_real:
        return out
    g, gp = h_real // kvh, hp // kvh
    mask = ((jnp.arange(hp) % gp) < g).astype(out.dtype)
    return out * mask[:, None]


def _attn_mask(b, t, s, *, causal, window, q_pos0, kv_len):
    """(B, t, s) boolean visibility mask; q_pos0 scalar or (B,)."""
    if np.ndim(q_pos0) == 0:
        q_pos = jnp.broadcast_to(q_pos0 + jnp.arange(t), (b, t))
    else:
        q_pos = q_pos0[:, None] + jnp.arange(t)[None, :]
    k_pos = jnp.arange(s)
    mask = jnp.ones((b, t, s), dtype=bool)
    if causal:
        mask &= q_pos[..., None] >= k_pos
    if window and window > 0:
        mask &= (q_pos[..., None] - k_pos) < window
    if kv_len is not None:
        mask &= k_pos[None, None, :] < kv_len[:, None, None]
    return mask


_CHUNK_Q_ABOVE = 1024       # stream softmax over q chunks beyond this T
_CHUNK_Q = 512


def _sdpa_core(q, k, v, *, causal, window, q_pos0, kv_len, dtype):
    # bf16-native: QK^T and PV keep bf16 operands with f32 accumulation
    # (preferred_element_type) — no materialized f32 copies of K/V/cache.
    b, t, kvh, g, hd = q.shape
    s = k.shape[1]
    scale = 1.0 / np.sqrt(hd)
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = _attn_mask(b, t, s, causal=causal, window=window,
                      q_pos0=q_pos0, kv_len=kv_len)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(dtype or q.dtype)


def _sdpa(q, k, v, *, causal, window, q_pos0=0, kv_len=None, dtype=None):
    """q (B,T,KVH,G,hd), k/v (B,S,KVH,hd): masked attention, fp32 softmax.

    ``window > 0``: sliding-window (local) causal attention.
    ``kv_len`` (B,) masks cache positions >= length (decode).
    Long sequences stream over q chunks (scan) so the score matrix peak is
    (cq, S) not (T, S) — the flash-attention memory shape in pure jnp (the
    Pallas kernel is the TPU-native version of the same schedule).
    """
    b, t, kvh, g, hd = q.shape
    if t <= _CHUNK_Q_ABOVE or t % _CHUNK_Q != 0 or np.ndim(q_pos0) != 0:
        return _sdpa_core(q, k, v, causal=causal, window=window,
                          q_pos0=q_pos0, kv_len=kv_len, dtype=dtype)
    nq = t // _CHUNK_Q
    qc = jnp.moveaxis(q.reshape(b, nq, _CHUNK_Q, kvh, g, hd), 1, 0)
    starts = q_pos0 + jnp.arange(nq) * _CHUNK_Q

    def step(_, xs):
        qi, st = xs
        o = _sdpa_core(qi, k, v, causal=causal, window=window,
                       q_pos0=st, kv_len=kv_len, dtype=dtype)
        return None, o

    _, outs = jax.lax.scan(step, None, (qc, starts))
    return jnp.moveaxis(outs, 0, 1).reshape(b, t, kvh, g, hd)


def _sdpa_mask(q, k, v, mask, dtype=None):
    """Attention with an explicit (B, t, s) visibility mask (bf16-native)."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(dtype or q.dtype)


def attn_apply(p, x, cfg, *, kind="attn", causal=True, positions=None,
               cache=None, cache_len=None, kv_override=None, kv_len=None):
    """GQA attention.  Returns (y, new_cache).

    Modes: plain (cache=None), prefill (cache + t>1, fills from offset 0),
    decode (cache + t==1, per-sequence offsets ``cache_len`` (B,)).
    ``local`` layers keep a **ring cache** of size window (the GraphStore
    L-type insight: bound the hot set, reuse slots in place).
    kv_override: precomputed (k, v) for cross-attention (with ``kv_len``).
    """
    b, t, d = x.shape
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    h = p["wq"].shape[1]                  # padded head count (>= cfg heads)
    g = h // kvh
    h_ax = "model" if _div(h, active_tp()) else None
    window = cfg.window_size if kind == "local" else 0
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    q = jnp.einsum("btd,dhk->bthk", xn, p["wq"].astype(xn.dtype))
    q = shard(q, DP, None, h_ax, None)

    if kv_override is not None:                      # ---- cross-attention
        k, v = kv_override
        qg = q.reshape(b, t, kvh, g, hd)
        out = _sdpa(qg, k, v, causal=False, window=0, kv_len=kv_len)
        out = _head_mask(out.reshape(b, t, h, hd), cfg.num_heads, kvh)
        y = jnp.einsum("bthk,hkd->btd", out, p["wo"].astype(out.dtype))
        return x + shard(y, DP, None, None), cache

    k = jnp.einsum("btd,dhk->bthk", xn, p["wk"].astype(xn.dtype))
    v = jnp.einsum("btd,dhk->bthk", xn, p["wv"].astype(xn.dtype))
    if positions is None:
        positions = jnp.arange(t)
    cos, sin = rope_tables(positions, hd, cfg.rope_theta)
    if kind != "nope":
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    qg = q.reshape(b, t, kvh, g, hd)

    if cache is None:                                # ---- plain (train)
        out = _sdpa(qg, k, v, causal=causal, window=window)
        new_cache = None
    elif t > 1:                                      # ---- prefill
        out = _sdpa(qg, k, v, causal=causal, window=window)
        if kind == "local" and t >= cache["k"].shape[1]:
            w = cache["k"].shape[1]
            p0 = t - w
            ks = jnp.roll(k[:, -w:], shift=p0 % w, axis=1)
            vs = jnp.roll(v[:, -w:], shift=p0 % w, axis=1)
            new_cache = {"k": ks.astype(cache["k"].dtype),
                         "v": vs.astype(cache["v"].dtype)}
        else:
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), 0, axis=1),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), 0, axis=1)}
    else:                                            # ---- decode (t == 1)
        off = attn_decode_pos(cache_len, b)
        if kind == "local":
            w = cache["k"].shape[1]
            slot = off % w
            kc = _batched_update(cache["k"], k, slot)
            vc = _batched_update(cache["v"], v, slot)
            new_cache = {"k": kc, "v": vc}
            n = off + 1                               # tokens now cached
            j = jnp.arange(w)[None, :]                # ring slots
            abs_pos = j + ((n[:, None] - 1 - j) // w) * w
            q_pos = off[:, None]
            visible = (abs_pos >= 0) & (abs_pos < n[:, None]) \
                & (abs_pos <= q_pos) & (q_pos - abs_pos < w)
            out = _sdpa_mask(qg, kc, vc, visible[:, None, :])
        else:
            kc = _batched_update(cache["k"], k, off)
            vc = _batched_update(cache["v"], v, off)
            new_cache = {"k": kc, "v": vc}
            out = _sdpa(qg, kc, vc, causal=True, window=0,
                        q_pos0=off, kv_len=off + 1)
    out = _head_mask(out.reshape(b, t, h, hd), cfg.num_heads, kvh)
    y = jnp.einsum("bthk,hkd->btd", out, p["wo"].astype(out.dtype))
    return x + shard(y, DP, None, None), new_cache


def _batched_update(cache, new, offsets):
    """Per-sequence write offsets (decode with ragged lengths)."""
    def upd(c, n, o):
        return jax.lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), o, 0)
    return jax.vmap(upd)(cache, new, offsets)


def attn_decode_pos(cache_len, b):
    if np.ndim(cache_len) == 0:
        return jnp.full((b,), cache_len, jnp.int32)
    return cache_len


def attn_cache_defs(cfg, batch: int, seq: int, *, tp: int,
                    long_mode: bool = False):
    """Decode KV-cache defs.  Normal mode: batch over DP, seq over "model"
    when KV heads cannot shard (keeps big caches on-chip).  long_mode
    (batch < DP degree): batch replicated, seq over DP(+model)."""
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    kv_ax = "model" if _div(kvh, tp) else None
    if long_mode:
        pspec = (None, DP if kv_ax else DPM, kv_ax, None)
    else:
        pspec = (DP, None if kv_ax else "model", kv_ax, None)
    return {"k": ParamDef((batch, seq, kvh, hd), pspec, init="zeros",
                          dtype=cfg.dtype),
            "v": ParamDef((batch, seq, kvh, hd), pspec, init="zeros",
                          dtype=cfg.dtype)}


# ===================================================================== MLA
def mla_defs(cfg, tp: int):
    m = cfg.mla
    d = cfg.d_model
    h = padded_heads(cfg.num_heads, 1, tp)
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    h_ax = "model" if _div(h, tp) else None
    return {
        "wdq": ParamDef((d, m.q_lora_rank), (None, None)),
        "q_ln": norm_def(m.q_lora_rank),
        "wuq": ParamDef((m.q_lora_rank, h, qd), (None, h_ax, None)),
        "wdkv": ParamDef((d, m.kv_lora_rank + m.qk_rope_head_dim),
                         (None, None)),
        "kv_ln": norm_def(m.kv_lora_rank),
        "wukv": ParamDef((m.kv_lora_rank, h,
                          m.qk_nope_head_dim + m.v_head_dim),
                         (None, h_ax, None)),
        "wo": ParamDef((h, m.v_head_dim, d), (h_ax, None, None)),
        "ln": norm_def(d),
    }


def mla_apply(p, x, cfg, *, positions=None, cache=None, cache_len=None):
    """Multi-head latent attention; the cache stores the *compressed* KV
    (c_kv + shared k_rope) — MLA's serving advantage."""
    m = cfg.mla
    b, t, d = x.shape
    h = p["wuq"].shape[1]                 # padded head count
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    cq = rms_norm(jnp.einsum("btd,dr->btr", xn, p["wdq"].astype(xn.dtype)),
                  p["q_ln"], cfg.norm_eps)
    q = jnp.einsum("btr,rhk->bthk", cq, p["wuq"].astype(cq.dtype))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    dkv = jnp.einsum("btd,dr->btr", xn, p["wdkv"].astype(xn.dtype))
    ckv = rms_norm(dkv[..., : m.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
    k_rope = dkv[..., m.kv_lora_rank:]                     # (B,T,rope_d) shared
    if positions is None:
        positions = jnp.arange(t)
    cos, sin = rope_tables(positions, rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]
    if cache is not None:
        off = cache_len if cache_len is not None else 0
        if np.ndim(off) == 0:
            ckv_c = jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), off, axis=1)
            kr_c = jax.lax.dynamic_update_slice_in_dim(
                cache["krope"], k_rope.astype(cache["krope"].dtype), off, axis=1)
        else:
            ckv_c = _batched_update(cache["ckv"], ckv, off)
            kr_c = _batched_update(cache["krope"], k_rope, off)
        new_cache = {"ckv": ckv_c, "krope": kr_c}
        ckv, k_rope = ckv_c, kr_c
    else:
        new_cache = None
    kv = jnp.einsum("bsr,rhk->bshk", ckv.astype(x.dtype),
                    p["wukv"].astype(x.dtype))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    s_len = k_nope.shape[1]
    scale = 1.0 / np.sqrt(nope + rope_d)
    scores = (jnp.einsum("bthk,bshk->bhts", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthk,bsk->bhts", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    q_pos0 = 0
    kv_len = None
    if cache is not None:
        q_pos0 = cache_len if cache_len is not None else 0
        kv_len = (cache_len + t)
        if np.ndim(kv_len) == 0:
            kv_len = jnp.full((b,), kv_len, jnp.int32)
    mask = _attn_mask(b, t, s_len, causal=True, window=0,
                      q_pos0=q_pos0, kv_len=kv_len)
    scores = jnp.where(mask[:, None], scores, -1e30)
    pr = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshk->bthk", pr.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = _head_mask(out.astype(x.dtype), cfg.num_heads)
    y = jnp.einsum("bthk,hkd->btd", out, p["wo"].astype(x.dtype))
    return x + shard(y, DP, None, None), new_cache


def mla_cache_defs(cfg, batch: int, seq: int, *, tp: int,
                   long_mode: bool = False):
    m = cfg.mla
    pspec = (None, DPM, None) if long_mode else (DP, "model", None)
    return {"ckv": ParamDef((batch, seq, m.kv_lora_rank), pspec,
                            init="zeros", dtype=cfg.dtype),
            "krope": ParamDef((batch, seq, m.qk_rope_head_dim), pspec,
                              init="zeros", dtype=cfg.dtype)}


# ===================================================================== MLP
def mlp_defs(cfg, tp: int, d_ff: int = 0):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    f_ax = "model" if _div(f, tp) else None
    return {
        "w_gate": ParamDef((d, f), (None, f_ax)),
        "w_in": ParamDef((d, f), (None, f_ax)),
        "w_out": ParamDef((f, d), (f_ax, None)),
        "ln": norm_def(d),
    }


def mlp_apply(p, x, cfg):
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    a = _act(cfg.act)(jnp.einsum("btd,df->btf", xn, p["w_gate"].astype(xn.dtype)))
    u = jnp.einsum("btd,df->btf", xn, p["w_in"].astype(xn.dtype))
    hfa = "model" if _div(p["w_in"].shape[-1], active_tp()) else None
    h = shard(a * u, DP, None, hfa)
    y = jnp.einsum("btf,fd->btd", h, p["w_out"].astype(h.dtype))
    return x + shard(y, DP, None, None)


# ===================================================================== MoE
def moe_defs(cfg, tp: int):
    mc = cfg.moe
    d = cfg.d_model
    f = mc.d_ff or cfg.d_ff
    e = mc.num_experts
    e_ax = "model" if _div(e, tp) else None
    f_ax = "model" if _div(f, tp) else None
    defs = {
        "router": ParamDef((d, e), (None, None)),
        "w_gate": ParamDef((e, d, f), (e_ax, None, None)),
        "w_in": ParamDef((e, d, f), (e_ax, None, None)),
        "w_out": ParamDef((e, f, d), (e_ax, None, None)),
        "ln": norm_def(d),
    }
    if mc.num_shared:
        defs["shared"] = {
            "w_gate": ParamDef((d, mc.num_shared * f), (None, f_ax)),
            "w_in": ParamDef((d, mc.num_shared * f), (None, f_ax)),
            "w_out": ParamDef((mc.num_shared * f, d), (f_ax, None)),
        }
    return defs


def _moe_local(xl, router, wg, wi, wo, *, cfg, axes=()):
    """Per-data-shard MoE dispatch/compute/combine (runs inside shard_map;
    the model axis stays auto so the expert einsums shard E 16-ways)."""
    mc = cfg.moe
    bl, t, d = xl.shape
    nl = bl * t
    e, k = mc.num_experts, mc.top_k
    cap = max(8, int(mc.capacity_factor * nl * k / e))
    xn = xl.reshape(nl, d)
    logits = jnp.einsum("nd,de->ne", xn.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,)).at[idx.reshape(-1)].add(1.0) / (nl * k)
    aux = e * jnp.sum(me * ce)

    oh = jax.nn.one_hot(idx.reshape(nl * k), e, dtype=jnp.int32)
    ranks = jnp.cumsum(oh, axis=0) - oh
    rank = jnp.take_along_axis(ranks, idx.reshape(nl * k)[:, None],
                               axis=1)[:, 0].reshape(nl, k)
    buf = jnp.zeros((e * cap, d), xn.dtype)
    for j in range(k):
        keep = rank[:, j] < cap
        dest = jnp.where(keep, idx[:, j] * cap + rank[:, j], e * cap)
        buf = buf.at[dest].set(xn * keep[:, None].astype(xn.dtype),
                               mode="drop")
    eb = buf.reshape(e, cap, d)
    hg = _act(cfg.act)(jnp.einsum("ecd,edf->ecf", eb, wg.astype(eb.dtype)))
    hu = jnp.einsum("ecd,edf->ecf", eb, wi.astype(eb.dtype))
    ob = jnp.einsum("ecf,efd->ecd", hg * hu,
                    wo.astype(eb.dtype)).reshape(e * cap, d)
    y = jnp.zeros_like(xn)
    for j in range(k):
        keep = rank[:, j] < cap
        src = jnp.where(keep, idx[:, j] * cap + rank[:, j], 0)
        y = y + ob[src] * (gates[:, j] * keep)[:, None].astype(xn.dtype)
    if axes:
        aux = jax.lax.pmean(aux, axes)
    return y.reshape(bl, t, d), aux


def moe_apply(p, x, cfg):
    """Capacity-based top-k MoE (GShard-style, per-data-shard capacity).

    On a mesh the dispatch/compute/combine runs under shard_map over the
    data axes with "model" left auto: scatter/gather locality is by
    construction, expert weights shard E over "model" (EP), and the only
    cross-shard traffic is the minimal expert-output exchange + weight-grad
    reductions (§Perf iterations 3-4)."""
    mc = cfg.moe
    b, t, d = x.shape
    mesh = _ACTIVE["mesh"]
    dp_axes = _ACTIVE["dp_axes"]
    xn_in = rms_norm(x, p["ln"], cfg.norm_eps)
    if mesh is not None and dp_axes and b % active_dp() == 0:
        # §Perf iteration 4: shard_map over the data axes (model stays
        # auto) — dispatch/combine scatter/gathers are provably local per
        # data shard, experts still shard E over "model".  GSPMD-only
        # formulations emit (tokens, d)-sized masked all-reduces across
        # data (measured 2x34 GB/layer on phi3.5-moe).
        local = functools.partial(_moe_local, cfg=cfg, axes=dp_axes)
        dspec = P(dp_axes, None, None)
        y, aux = jax.shard_map(
            local, mesh=mesh,
            in_specs=(dspec, P(None, None), P(None, None, None),
                      P(None, None, None), P(None, None, None)),
            out_specs=(dspec, P()),
            axis_names=set(dp_axes), check_vma=False)(
            xn_in, p["router"], p["w_gate"], p["w_in"], p["w_out"])
    else:
        y, aux = _moe_local(xn_in, p["router"], p["w_gate"], p["w_in"],
                            p["w_out"], cfg=cfg)
    y = shard(y, DP, None, None)
    if mc.num_shared:
        sp = p["shared"]
        a = _act(cfg.act)(jnp.einsum("btd,df->btf",
                                     rms_norm(x, p["ln"], cfg.norm_eps),
                                     sp["w_gate"].astype(x.dtype)))
        u = jnp.einsum("btd,df->btf", rms_norm(x, p["ln"], cfg.norm_eps),
                       sp["w_in"].astype(x.dtype))
        y = y + jnp.einsum("btf,fd->btd", a * u, sp["w_out"].astype(x.dtype))
    return x + shard(y, DP, None, None), aux


# ================================================================ embedding
def embed_defs(cfg, tp: int):
    v, d = cfg.vocab_size, cfg.d_model
    if _div(v, tp):
        emb_spec = ("model", None)
    else:
        emb_spec = (None, "model")           # row-parallel logits fallback
    defs = {"tokens": ParamDef((v, d), emb_spec, scale=1.0 / np.sqrt(d)),
            "final_ln": norm_def(d)}
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((d, v),
                                (None, "model") if _div(v, tp)
                                else ("model", None))
    return defs


def embed_apply(p, tokens, cfg):
    x = jnp.take(p["tokens"].astype(jnp.dtype(cfg.dtype)), tokens, axis=0)
    if cfg.name.startswith("gemma3"):
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return shard(x, DP, None, None)


def logits_apply(p, x, cfg):
    xn = rms_norm(x, p["final_ln"], cfg.norm_eps)
    if cfg.tie_embeddings:
        w = p["tokens"].astype(xn.dtype)
        return jnp.einsum("btd,vd->btv", xn, w)
    return jnp.einsum("btd,dv->btv", xn, p["head"].astype(xn.dtype))
