"""SPMD model-parallel execution of DFG programs (engine scale-out axis 2).

The engine's cached-jit path runs the whole post-BatchPre suffix of a DFG as
one XLA program on one device.  This module lowers that same suffix through
``shard_map`` over a (data, model) device mesh instead:

  * **model axis** — embedding/hidden dims are striped: the activations'
    feature axis and every weight's contracted (row) axis are sharded, each
    mesh slice runs the bound C-kernels (Pallas or Shell jnp) at slice
    shapes, and a ``psum`` at the combine boundary rebuilds the full GEMM
    output *before* the nonlinearity — the Megatron/GShard row-parallel
    split (levanter ``sharded_gpt2.py`` / lingvo ``gshard_builder.py``);
  * **data axis** — super-batch destination rows are striped: each slice
    aggregates and transforms its own row block, with a tiled
    ``all_gather`` re-materialising the full activation at each layer
    boundary (the next hop's gather indexes into ALL previous-level rows).

The partition plan is inferred over the DFG node vocabulary (SpMM*/SDDMM/
Prefix/GEMM/BiasAdd/AggCombine/elementwise); ops outside the vocabulary
execute fully replicated, so any DFG still runs on a mesh — it just doesn't
scale.  Hidden dims that don't divide the mesh are zero-padded to
divisibility (zeros stay exact zeros through every aggregation, matmul and
relu in these models, and outputs are sliced back), so odd widths work.

Numerics: ``psum`` re-orders the contraction, so sharded == single-device
at fp32 *allclose* tolerance, not bitwise — asserted for GCN/GIN/NGCF
across mesh shapes in ``tests/test_spmd.py`` and ``benchmarks/fig28_spmd``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


ROWS_FULL, ROWS_DATA = "full", "data"
FEAT_REP, FEAT_MODEL = "rep", "model"

AGG_OPS = frozenset({"SpMM", "SpMM_Mean", "SpMM_Sum"})
_FUSED_OP = "AggCombine"


class SpmdPlanError(ValueError):
    """The DFG uses a sharded value in a way the plan cannot honor."""


@dataclass(frozen=True)
class VState:
    """Partition state of one value inside the mapped body: how its leading
    (row) axis and trailing (feature) axis relate to the mesh."""
    rows: str = ROWS_FULL       # "full" (replicated) | "data" (row-striped)
    feat: str = FEAT_REP        # "rep" | "model" (feature-striped)


_WEIGHT = VState("wrow", "wrow")      # sentinel: model-striped contracted dim


def mesh_axes(mesh) -> tuple[str | None, int, str | None, int]:
    """(data_axis, d, model_axis, m) — absent axes behave as size 1."""
    names = tuple(mesh.axis_names)
    sizes = dict(zip(names, mesh.devices.shape))
    da = "data" if "data" in names else None
    ma = "model" if "model" in names else None
    return da, sizes.get("data", 1), ma, sizes.get("model", 1)


def mesh_descriptor(mesh) -> tuple:
    """Hashable mesh identity for the engine's jit cache key."""
    return tuple(zip(mesh.axis_names, mesh.devices.shape))


def _ceil_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _agg_partial_ref(h, nbr, mask, w):
    """jnp fallback for the AggCombinePartial C-kernel (mean aggregation —
    the fusion pass only creates mean chains)."""
    g = jnp.take(h, nbr, axis=0) * mask[..., None]
    s = g.sum(axis=1) / jnp.maximum(mask.sum(axis=1), 1.0)[:, None]
    return jnp.dot(s, w, preferred_element_type=jnp.float32)


# --------------------------------------------------------------- input roles
def _classify_inputs(suffix, arr_set: set[str]) -> dict[str, str]:
    """Role per DFG input ref, from how the suffix consumes it:
    h (activations: feature-striped), idx (nbr/mask: row-striped),
    weight (contracted-dim-striped), bias (replicated, width-padded),
    gemm_x (GEMM lhs fed directly: width-padded only)."""
    roles: dict[str, str] = {}

    def mark(ref: str, role: str) -> None:
        if ref not in arr_set:
            return
        prev = roles.get(ref)
        roles[ref] = role if prev in (None, role) else "rep"   # conflict

    for n in suffix:
        if n.op in AGG_OPS or n.op == "SDDMM":
            mark(n.inputs[0], "h")
            mark(n.inputs[1], "idx")
            mark(n.inputs[2], "idx")
        elif n.op == "Prefix":
            mark(n.inputs[0], "h")
            mark(n.inputs[1], "idx")
        elif n.op == "GEMM":
            mark(n.inputs[1], "weight")
            mark(n.inputs[0], "gemm_x") if n.inputs[0] in arr_set else None
        elif n.op == _FUSED_OP:
            mark(n.inputs[0], "h")
            mark(n.inputs[1], "idx")
            mark(n.inputs[2], "idx")
            mark(n.inputs[3], "weight")
            mark(n.inputs[4], "bias")
        elif n.op == "BiasAdd":
            mark(n.inputs[1], "bias")
    return roles


def _input_padding(roles, env, arr_refs, d: int, m: int) -> dict[str, tuple]:
    """Zero-padding per input ref so every striped axis divides the mesh.

    Feature/contracted/width dims all pad with the same ``ceil(x/m)*m``
    rule, so matched dims (h cols <-> weight rows, weight cols <-> bias
    width <-> next weight's rows) stay matched; padded columns are exact
    zeros through aggregation, matmul, bias and relu, and outputs are
    sliced back to true widths.  Row-striped idx inputs pad to the data
    axis (nbr pads with index 0 — always valid — under an all-zero mask);
    activations pad their row count up to the largest padded idx row count
    so ``Prefix`` row-slices stay in bounds.
    """
    pads: dict[str, tuple] = {}
    max_dp = 0
    for r in arr_refs:
        if roles.get(r) == "idx":
            max_dp = max(max_dp, _ceil_to(env[r].shape[0], d))
    for r in arr_refs:
        v, role = env[r], roles.get(r)
        if role == "h":
            rows = max(v.shape[0], max_dp)
            p = ((0, rows - v.shape[0]),
                 (0, _ceil_to(v.shape[1], m) - v.shape[1]))
        elif role == "idx":
            p = ((0, _ceil_to(v.shape[0], d) - v.shape[0]), (0, 0))
        elif role == "weight":
            p = ((0, _ceil_to(v.shape[0], m) - v.shape[0]),
                 (0, _ceil_to(v.shape[1], m) - v.shape[1]))
        elif role == "bias":
            p = ((0, _ceil_to(v.shape[0], m) - v.shape[0]),)
        elif role == "gemm_x":
            p = ((0, 0), (0, _ceil_to(v.shape[-1], m) - v.shape[-1]))
        else:
            continue
        if any(hi for _, hi in p):
            pads[r] = p
    return pads


def _input_spec(role: str | None, rank: int, da, ma) -> P:
    if role == "h":
        return P(None, ma)
    if role == "idx":
        return P(da, None)
    if role == "weight":
        return P(ma, None)
    return P(*([None] * rank))


def _input_state(role: str | None) -> VState:
    if role == "h":
        return VState(ROWS_FULL, FEAT_MODEL)
    if role == "idx":
        return VState(ROWS_DATA, FEAT_REP)
    if role == "weight":
        return _WEIGHT
    return VState(ROWS_FULL, FEAT_REP)


# ------------------------------------------------------------- program build
def build_sharded_program(suffix, resolved, arr_refs, static_env,
                          suffix_outs, env, mesh, registry) -> Callable:
    """Lower a jit-safe DFG suffix onto ``mesh`` via shard_map.

    Returns a callable over the ``arr_refs``-ordered input arrays (same
    signature as the engine's plain ``_program``) that pads inputs to mesh
    divisibility, runs the partitioned body, and slices outputs back to the
    exact single-device shapes.
    """
    da, d, ma, m = mesh_axes(mesh)
    arr_set = set(arr_refs)
    roles = _classify_inputs(suffix, arr_set)
    pads = _input_padding(roles, env, arr_refs, d, m)

    # global PADDED shape of every value: eval_shape of the plain program
    # on padded inputs (abstract — nothing executes)
    def _plain(*vals):
        e: dict[str, Any] = dict(static_env)
        e.update(zip(arr_refs, vals))
        record = {}
        for node, (_, fn) in zip(suffix, resolved):
            args = [e[i] for i in node.inputs]
            out = fn(*args, **node.attrs) if node.attrs else fn(*args)
            if len(node.outputs) == 1:
                e[node.outputs[0]] = out
            else:
                e.update(zip(node.outputs, out))
        for r in e:
            if hasattr(e[r], "shape"):
                record[r] = e[r]
        return record

    def _struct(r, padded: bool):
        v = env[r]
        shape = list(v.shape)
        if padded:
            for ax, (_, hi) in enumerate(pads.get(r, ())):
                shape[ax] += hi
        return jax.ShapeDtypeStruct(tuple(shape), v.dtype)

    gshape = {r: s.shape for r, s in jax.eval_shape(
        _plain, *(_struct(r, True) for r in arr_refs)).items()}
    true_shapes = jax.eval_shape(
        _plain, *(_struct(r, False) for r in arr_refs))
    true_out = {r: true_shapes[r].shape for r in suffix_outs}

    states: dict[str, VState] = {r: _input_state(roles.get(r))
                                 for r in arr_refs}
    steps: list[Callable] = []

    # ---- runtime helpers (trace-time; no-ops skipped at plan time) -------
    def _gather_rows(x):
        return jax.lax.all_gather(x, da, axis=0, tiled=True)

    def _gather_feat(x):
        return jax.lax.all_gather(x, ma, axis=x.ndim - 1, tiled=True)

    def _slice_feat(x):
        w = x.shape[-1] // m
        i = jax.lax.axis_index(ma)
        return jax.lax.dynamic_slice_in_dim(x, i * w, w, axis=x.ndim - 1)

    def _slice_rows(x, loc):
        i = jax.lax.axis_index(da)
        return jax.lax.dynamic_slice_in_dim(x, i * loc, loc, axis=0)

    # ---- plan-time normalizers ------------------------------------------
    def full_rows(ref):
        """Ensure ref holds full rows inside the body (gather + store)."""
        st = states[ref]
        if st is _WEIGHT:
            raise SpmdPlanError(f"weight input {ref!r} used as activation")
        if st.rows == ROWS_DATA:
            if d > 1:
                steps.append(lambda e, r=ref: e.__setitem__(
                    r, _gather_rows(e[r])))
            states[ref] = VState(ROWS_FULL, st.feat)

    def feat_model_arg(ref):
        """Value -> this shard's feature block; returns an e->array fn."""
        st = states[ref]
        if st.feat == FEAT_MODEL or m == 1:
            return lambda e, r=ref: e[r]
        return lambda e, r=ref: _slice_feat(e[r])

    def rep_everything(ref):
        """Unknown-op fallback: gather to fully replicated."""
        st = states.get(ref)
        if st is None:
            return
        if st is _WEIGHT:
            raise SpmdPlanError(
                f"weight input {ref!r} consumed by an op outside the SPMD "
                "vocabulary — cannot replicate a contracted-dim shard")
        full_rows(ref)
        if states[ref].feat == FEAT_MODEL:
            if m > 1:
                steps.append(lambda e, r=ref: e.__setitem__(
                    r, _gather_feat(e[r])))
            states[ref] = VState(states[ref].rows, FEAT_REP)

    def assign(node, out):
        """Step helper: bind a node's output(s) into the body env."""
        if len(node.outputs) == 1:
            return [(node.outputs[0], out)]
        return list(zip(node.outputs, out))

    # ---- per-node planning ----------------------------------------------
    for node, (dev, fn) in zip(suffix, resolved):
        op, ins = node.op, node.inputs

        if op in AGG_OPS:
            h, nbr, mask = ins
            full_rows(h)
            get_h = feat_model_arg(h)
            steps.append(lambda e, n=node, f=fn, g=get_h, nb=nbr, mk=mask:
                         e.__setitem__(n.outputs[0], f(g(e), e[nb], e[mk])))
            states[node.outputs[0]] = VState(states[nbr].rows, FEAT_MODEL)

        elif op == "SDDMM":
            h, nbr, mask = ins
            full_rows(h)
            get_h = feat_model_arg(h)
            if states[nbr].rows == ROWS_DATA and d > 1:
                # the kernel pairs dst rows with h[:D]; under row striping
                # slice i's dst rows live at offset i*loc — shard-aware jnp
                def _sddmm_step(e, n=node, g=get_h, nb=nbr, mk=mask):
                    hh, nv, mv = g(e), e[nb], e[mk]
                    selfh = _slice_rows(hh, nv.shape[0])
                    out = (jnp.take(hh, nv, axis=0) * selfh[:, None, :]
                           * mv[..., None])
                    e[n.outputs[0]] = out
                steps.append(_sddmm_step)
            else:
                steps.append(lambda e, n=node, f=fn, g=get_h, nb=nbr,
                             mk=mask: e.__setitem__(
                                 n.outputs[0], f(g(e), e[nb], e[mk])))
            states[node.outputs[0]] = VState(states[nbr].rows, FEAT_MODEL)

        elif op == "Prefix":
            h, nbr = ins
            full_rows(h)
            hfeat = states[h].feat
            if states[nbr].rows == ROWS_DATA and d > 1:
                steps.append(lambda e, n=node, hr=h, nb=nbr: e.__setitem__(
                    n.outputs[0], _slice_rows(e[hr], e[nb].shape[0])))
            else:
                steps.append(lambda e, n=node, f=fn, hr=h, nb=nbr:
                             e.__setitem__(n.outputs[0], f(e[hr], e[nb])))
            states[node.outputs[0]] = VState(states[nbr].rows, hfeat)

        elif op == "GEMM" and states.get(ins[1]) is _WEIGHT:
            x, w = ins
            get_x = feat_model_arg(x)

            def _gemm_step(e, n=node, f=fn, g=get_x, wr=w):
                z = f(g(e), e[wr])
                if m > 1:
                    z = jax.lax.psum(z, ma)
                e[n.outputs[0]] = z
            steps.append(_gemm_step)
            states[node.outputs[0]] = VState(states[x].rows, FEAT_REP)

        elif op == _FUSED_OP and states.get(ins[3]) is _WEIGHT:
            h, nbr, mask, w, b = ins
            full_rows(h)
            get_h = feat_model_arg(h)
            try:
                _, pfn = registry.resolve("AggCombinePartial")
            except KeyError:
                pfn = _agg_partial_ref

            def _fused_step(e, n=node, pf=pfn, g=get_h, nb=nbr, mk=mask,
                            wr=w, br=b):
                z = pf(g(e), e[nb], e[mk], e[wr])
                if m > 1:
                    z = jax.lax.psum(z, ma)
                e[n.outputs[0]] = jnp.maximum(z + e[br], 0.0)
            steps.append(_fused_step)
            states[node.outputs[0]] = VState(states[nbr].rows, FEAT_REP)

        elif op == "BiasAdd":
            x, b = ins
            sx = states[x]
            get_b = (feat_model_arg(b) if sx.feat == FEAT_MODEL
                     else (lambda e, r=b: e[r]))
            steps.append(lambda e, n=node, f=fn, xr=x, g=get_b:
                         e.__setitem__(n.outputs[0], f(e[xr], g(e))))
            states[node.outputs[0]] = sx

        elif op in ("ReLU", "LeakyReLU", "Scale"):
            steps.append(lambda e, n=node, f=fn: e.__setitem__(
                n.outputs[0],
                f(*(e[i] for i in n.inputs), **n.attrs) if n.attrs
                else f(*(e[i] for i in n.inputs))))
            states[node.outputs[0]] = states.get(ins[0], VState())

        elif op == "DegNorm":
            steps.append(lambda e, n=node, f=fn: e.__setitem__(
                n.outputs[0], f(e[n.inputs[0]])))
            states[node.outputs[0]] = VState(
                states.get(ins[0], VState()).rows, FEAT_REP)

        elif op == "Reduce":
            x = ins[0]
            ndim = len(gshape[x])
            ax = node.attrs.get("axis", 1) % ndim
            if ax == 0:
                full_rows(x)
            if ax == ndim - 1:
                rep_everything(x)
            steps.append(lambda e, n=node, f=fn: e.__setitem__(
                n.outputs[0], f(e[n.inputs[0]], **n.attrs)))
            states[node.outputs[0]] = states[x]

        elif op in ("Add", "Mul"):
            x, y = ins
            sx = states.get(x, VState())
            sy = states.get(y, VState())
            if _WEIGHT in (sx, sy):
                raise SpmdPlanError(f"weight input consumed by {op}")
            gx, gy = gshape.get(x), gshape.get(y)
            getx = lambda e, r=x: e[r]          # noqa: E731
            gety = lambda e, r=y: e[r]          # noqa: E731
            # unify rows: row-slice the replicated side (leading dims match)
            rows = ROWS_FULL
            if sx.rows == ROWS_DATA or sy.rows == ROWS_DATA:
                rows = ROWS_DATA
                if sx.rows != ROWS_DATA and len(gx) >= 1 and d > 1:
                    getx = lambda e, r=x, lc=gx[0] // d: _slice_rows(e[r], lc)  # noqa: E731,E501
                if sy.rows != ROWS_DATA and len(gy) >= 1 and d > 1:
                    gety = lambda e, r=y, lc=gy[0] // d: _slice_rows(e[r], lc)  # noqa: E731,E501
            # unify feat: feature-slice the replicated side unless it
            # broadcasts (trailing width 1 / lower rank)
            feat = FEAT_REP
            if sx.feat == FEAT_MODEL or sy.feat == FEAT_MODEL:
                feat = FEAT_MODEL
                if sx.feat != FEAT_MODEL and gx and gx[-1] != 1 and m > 1:
                    getx = (lambda e, g0=getx: _slice_feat(g0(e)))
                if sy.feat != FEAT_MODEL and gy and gy[-1] != 1 and m > 1:
                    gety = (lambda e, g0=gety: _slice_feat(g0(e)))
            steps.append(lambda e, n=node, f=fn, g1=getx, g2=gety:
                         e.__setitem__(n.outputs[0], f(g1(e), g2(e))))
            states[node.outputs[0]] = VState(rows, feat)

        else:
            # outside the SPMD vocabulary: run fully replicated
            for i in ins:
                rep_everything(i)
            steps.append(lambda e, n=node, f=fn, a=assign: [
                e.__setitem__(r, v) for r, v in a(
                    n, f(*(e[i] for i in n.inputs), **n.attrs) if n.attrs
                    else f(*(e[i] for i in n.inputs)))])
            for o in node.outputs:
                states[o] = VState()

    # ---- output specs (shard_map reassembles striped outputs) ------------
    out_specs = []
    for r in suffix_outs:
        st, rank = states[r], len(gshape[r])
        if st is _WEIGHT:
            raise SpmdPlanError(f"DFG output {r!r} is a weight input")
        if rank < 2 and (st.rows == ROWS_DATA or st.feat == FEAT_MODEL):
            rep_everything(r)
            st = states[r]
        lead = da if st.rows == ROWS_DATA else None
        trail = ma if st.feat == FEAT_MODEL else None
        out_specs.append(
            P(*([lead] + [None] * (rank - 2) + [trail])) if rank >= 2
            else P(*([None] * rank)))

    in_specs = tuple(_input_spec(roles.get(r), len(env[r].shape), da, ma)
                     for r in arr_refs)

    def body(*vals):
        e: dict[str, Any] = dict(static_env)
        e.update(zip(arr_refs, vals))
        for step in steps:
            step(e)
        return tuple(e[r] for r in suffix_outs)

    mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=tuple(out_specs), check_vma=False)

    def program(*vals):
        padded = [jnp.pad(v, pads[r]) if r in pads else v
                  for r, v in zip(arr_refs, vals)]
        outs = mapped(*padded)
        return tuple(
            o[tuple(slice(0, s) for s in true_out[r])]
            if tuple(o.shape) != tuple(true_out[r]) else o
            for o, r in zip(outs, suffix_outs))

    return program
