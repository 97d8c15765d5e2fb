"""GraphRunner's dataflow-graph (DFG) program model — paper §4.2, Fig. 10.

Users describe a GNN (or any computation) as a DFG of abstract C-operations
via ``createIn/createOp/createOut``; ``save()`` emits the paper's markup
file: a topologically-sorted node list where each node records its sequence
number, C-operation name, input refs (``"<node>_<slot>"`` or an input name)
and output refs.  The engine deserializes the markup, resolves every
C-operation against the registry (device-priority dynamic binding) and
executes node by node — no cross-compilation, reprogrammable at run time.
"""
from __future__ import annotations

import json
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any

from .registry import KernelRegistry


@dataclass
class _Node:
    seq: int
    op: str
    inputs: list[str]
    outputs: list[str]
    attrs: dict = field(default_factory=dict)


class Ref(str):
    """A value reference inside a DFG ("Weight", "2_0", ...)."""


class DFG:
    def __init__(self):
        self._nodes: list[_Node] = []
        self._ins: list[str] = []
        self._outs: dict[str, str] = {}
        self._markup_cache: str | None = None   # memoized save() output

    # ------------------------------------------------- paper creation API
    def create_in(self, name: str) -> Ref:
        self._ins.append(name)
        self._markup_cache = None
        return Ref(name)

    def create_op(self, op: str, inputs: list[Ref], n_out: int = 1,
                  attrs: dict | None = None) -> list[Ref]:
        seq = len(self._nodes)
        outs = [f"{seq}_{i}" for i in range(n_out)]
        self._nodes.append(_Node(seq, op, [str(i) for i in inputs], outs,
                                 attrs or {}))
        self._markup_cache = None
        return [Ref(o) for o in outs]

    def create_out(self, name: str, src: Ref) -> None:
        self._outs[name] = str(src)
        self._markup_cache = None

    # ------------------------------------------------- markup (de)serialize
    def save(self) -> str:
        """Markup file (paper Fig. 10c), JSON-encoded.  Memoized: the jit
        engine keys its trace cache on this string every call, and a
        round-tripped DFG already holds its own markup."""
        if self._markup_cache is None:
            self._markup_cache = json.dumps({
                "inputs": self._ins,
                "nodes": [{"seq": n.seq, "op": n.op, "in": n.inputs,
                           "out": n.outputs, "attrs": n.attrs}
                          for n in self._nodes],
                "outputs": self._outs,
            })
        return self._markup_cache

    @classmethod
    def load(cls, markup: str) -> "DFG":
        obj = json.loads(markup)
        dfg = cls()
        dfg._ins = list(obj["inputs"])
        dfg._nodes = [_Node(n["seq"], n["op"], list(n["in"]), list(n["out"]),
                            dict(n.get("attrs", {}))) for n in obj["nodes"]]
        dfg._outs = dict(obj["outputs"])
        dfg._markup_cache = markup
        return dfg

    # ------------------------------------------------- topological order
    def topo_nodes(self) -> list[_Node]:
        """Nodes sorted so every input is produced before use (paper: the DFG
        is converted to a computational structure by topological sort)."""
        produced = set(self._ins)
        remaining = list(self._nodes)
        order: list[_Node] = []
        while remaining:
            progressed = False
            for n in list(remaining):
                if all(i in produced for i in n.inputs):
                    order.append(n)
                    produced.update(n.outputs)
                    remaining.remove(n)
                    progressed = True
            if not progressed:
                raise ValueError("DFG has a cycle or missing input: "
                                 f"{[n.op for n in remaining]}")
        return order


# GCN layer chain folded into the fused aggregate-combine C-operation:
# SpMM_Mean -> GEMM -> BiasAdd -> ReLU   =>   AggCombine(h, nbr, mask, w, b)
_FUSE_CHAIN = ("SpMM_Mean", "GEMM", "BiasAdd", "ReLU")
_FUSED_OP = "AggCombine"


def fuse_aggregate_combine(nodes: list[_Node],
                           protected: set[str]) -> list[_Node]:
    """Rewrite SpMM_Mean->GEMM->BiasAdd->ReLU chains into AggCombine nodes.

    A chain fuses only when every intermediate value has exactly one
    consumer and is not a DFG output (``protected``).  The fused node is
    placed at the ReLU's position, where all five inputs are available.
    """
    uses: dict[str, int] = {}
    consumer: dict[str, _Node] = {}
    for n in nodes:
        for i in n.inputs:
            uses[i] = uses.get(i, 0) + 1
            consumer[i] = n
    for r in protected:
        uses[r] = uses.get(r, 0) + 2        # never fuse across an output

    drop: set[int] = set()
    replace: dict[int, _Node] = {}          # seq of ReLU node -> fused node
    for n in nodes:
        if n.op != _FUSE_CHAIN[0] or n.seq in drop:
            continue
        chain = [n]
        ok = True
        for want in _FUSE_CHAIN[1:]:
            ref = chain[-1].outputs[0]
            nxt = consumer.get(ref)
            if (len(chain[-1].outputs) != 1 or uses.get(ref) != 1
                    or nxt is None or nxt.op != want or nxt.inputs[0] != ref):
                ok = False
                break
            chain.append(nxt)
        if not ok:
            continue
        spmm_n, gemm_n, bias_n, relu_n = chain
        fused = _Node(relu_n.seq, _FUSED_OP,
                      list(spmm_n.inputs) + [gemm_n.inputs[1],
                                             bias_n.inputs[1]],
                      list(relu_n.outputs), {})
        drop.update(x.seq for x in (spmm_n, gemm_n, bias_n))
        replace[relu_n.seq] = fused

    if not replace:
        return nodes
    return [replace.get(n.seq, n) for n in nodes if n.seq not in drop]


class Engine:
    """GraphRunner execution engine: dynamic binding + per-node execution.

    Two execution paths share the dynamic-binding semantics:

      * **eager** (default): resolve + dispatch node by node, with honest
        per-node timings (``self.timings``);
      * **jit** (``run(..., jit=True)``): the maximal jit-safe suffix of the
        DFG is traced *once* through the currently-bound C-kernels and
        compiled as a single XLA program, cached per (markup, registry
        version, input shapes/dtypes).  Stateful C-operations (registered
        with ``jittable=False``, e.g. the near-storage BatchPre) run eagerly
        in front of the traced suffix.  Re-programming User logic bumps the
        registry version and invalidates stale traces.

    Both paths first apply the aggregate-combine fusion pass whenever a
    fused ``AggCombine`` C-kernel is resolvable (``fuse=None`` -> auto).

    **SPMD** (``mesh=``): with a (data, model) device mesh the jit path
    lowers the traced suffix through ``shard_map`` instead of plain jit —
    hidden/embedding dims striped across the ``model`` axis, super-batch
    rows across ``data``, psum/all_gather at combine boundaries (see
    ``core/spmd.py``).  The eager prefix (BatchPre) is unchanged; the mesh
    descriptor joins the jit cache key so the same engine can serve meshed
    and un-meshed programs side by side.

    The trace cache is a bounded LRU (``jit_cache_size`` entries, default
    32): long-lived serving processes see unbounded distinct shape
    signatures from pad-group drift, and every cached entry pins a compiled
    XLA executable.  Hits/misses/evictions are exposed via
    ``cache_stats()`` and surfaced in service stats / QoS snapshots.
    """

    def __init__(self, registry: KernelRegistry, *, mesh=None,
                 jit_cache_size: int = 32):
        self.registry = registry
        self.mesh = mesh
        self.trace: list[tuple[str, str]] = []     # (op, device) per executed node
        self.timings: list[tuple[str, str, float]] = []
        if jit_cache_size < 1:
            raise ValueError(f"jit_cache_size must be >= 1, got "
                             f"{jit_cache_size}")
        self._jit_cache: OrderedDict = OrderedDict()
        self._jit_cache_size = jit_cache_size
        self._cache_hits = 0
        self._cache_misses = 0
        self._cache_evictions = 0

    def cache_stats(self) -> dict:
        """LRU jit-cache counters (entries pin compiled XLA executables)."""
        return {"size": len(self._jit_cache),
                "capacity": self._jit_cache_size,
                "hits": self._cache_hits,
                "misses": self._cache_misses,
                "evictions": self._cache_evictions}

    def run(self, dfg: DFG, feeds: dict[str, Any], *, jit: bool = False,
            fuse: bool | None = None) -> dict[str, Any]:
        env: dict[str, Any] = dict(feeds)
        missing = [i for i in dfg._ins if i not in env]
        if missing:
            raise KeyError(f"missing DFG inputs: {missing}")
        order, fuse = self._order(dfg, fuse)
        self.trace = []
        self.timings = []
        if jit:
            return self._run_jit(dfg, order, env, fuse)
        for node in order:
            self._exec_node(node, env)
        return {name: env[src] for name, src in dfg._outs.items()}

    def _order(self, dfg: DFG, fuse: bool | None) -> tuple[list[_Node], bool]:
        order = dfg.topo_nodes()
        if fuse is None:
            fuse = _FUSED_OP in self.registry.ops
        if fuse:
            order = fuse_aggregate_combine(order, set(dfg._outs.values()))
        return order, fuse

    # ------------------------------------------------------------ eager path
    def _exec_node(self, node: _Node, env: dict[str, Any]) -> None:
        import time as _time
        device, fn = self.registry.resolve(node.op)
        self.trace.append((node.op, device))
        args = [env[i] for i in node.inputs]
        t0 = _time.perf_counter()
        out = fn(*args, **node.attrs) if node.attrs else fn(*args)
        out = _block(out)
        self.timings.append((node.op, device, _time.perf_counter() - t0))
        if len(node.outputs) == 1:
            env[node.outputs[0]] = out
        else:
            for ref, val in zip(node.outputs, out):
                env[ref] = val

    # -------------------------------------------------------------- jit path
    def _run_jit(self, dfg: DFG, order: list[_Node], env: dict[str, Any],
                 fuse: bool) -> dict[str, Any]:
        import time as _time
        # eager prefix: through the last jit-unsafe (stateful) node
        cut = 0
        for idx, node in enumerate(order):
            if node.op in self.registry.unjittable:
                cut = idx + 1
        for node in order[:cut]:
            self._exec_node(node, env)
        suffix = order[cut:]
        if not suffix:
            return {name: env[src] for name, src in dfg._outs.items()}
        fn, arr_refs, suffix_outs, trace = self._program(dfg, suffix, env,
                                                         fuse)
        self.trace.extend(trace)
        t0 = _time.perf_counter()
        results = _block(fn(*(env[r] for r in arr_refs)))
        self.timings.append(("__dfg_jit__", "jit", _time.perf_counter() - t0))
        env.update(zip(suffix_outs, results))
        return {name: env[src] for name, src in dfg._outs.items()}

    def jit_program(self, dfg: DFG, feeds: dict[str, Any]):
        """The cached jitted program of a DFG with no stateful node, and the
        feed names of its array arguments in call order.  ``feeds`` may hold
        ``jax.ShapeDtypeStruct``s: ``fn.lower(*(feeds[r] for r in refs))``
        then compiles the program without running it."""
        order, fuse = self._order(dfg, None)
        if any(n.op in self.registry.unjittable for n in order):
            raise ValueError("jit_program needs a DFG without stateful ops")
        fn, arr_refs, _, _ = self._program(dfg, order, dict(feeds), fuse)
        return fn, arr_refs

    def _program(self, dfg: DFG, suffix: list[_Node], env: dict[str, Any],
                 fuse: bool):
        """(jitted fn, array arg refs, output refs, device trace) of a
        jit-safe suffix, from the LRU cache or freshly traced."""
        produced: set[str] = set()
        for n in suffix:
            produced.update(n.outputs)
        in_refs = sorted({i for n in suffix for i in n.inputs
                          if i not in produced})
        suffix_outs = [src for src in dict.fromkeys(dfg._outs.values())
                       if src in produced]
        arr_refs, sig, static_env = [], [], {}
        for r in in_refs:
            v = env[r]
            if hasattr(v, "shape") and hasattr(v, "dtype"):
                arr_refs.append(r)
                sig.append((r, tuple(v.shape), str(v.dtype)))
            else:                       # non-array feeds are trace constants
                static_env[r] = v
                sig.append((r, "static", repr(v)))
        mesh_key = None
        if self.mesh is not None:
            from .spmd import mesh_descriptor
            mesh_key = mesh_descriptor(self.mesh)
        key = (dfg.save(), self.registry.version, fuse, tuple(sig),
               tuple(suffix_outs), mesh_key)
        hit = self._jit_cache.get(key)
        if hit is not None:
            self._jit_cache.move_to_end(key)
            self._cache_hits += 1
        else:
            self._cache_misses += 1
            resolved = [self.registry.resolve(n.op) for n in suffix]
            trace = [(n.op, d) for n, (d, _) in zip(suffix, resolved)]

            import jax
            if self.mesh is not None:
                from .spmd import build_sharded_program
                _program = build_sharded_program(
                    suffix, resolved, arr_refs, static_env, suffix_outs,
                    env, self.mesh, self.registry)
            else:
                def _program(*vals):
                    e = dict(static_env)
                    e.update(zip(arr_refs, vals))
                    for node, (_, fn) in zip(suffix, resolved):
                        args = [e[i] for i in node.inputs]
                        out = (fn(*args, **node.attrs) if node.attrs
                               else fn(*args))
                        if len(node.outputs) == 1:
                            e[node.outputs[0]] = out
                        else:
                            for ref, val in zip(node.outputs, out):
                                e[ref] = val
                    return tuple(e[r] for r in suffix_outs)

            hit = (jax.jit(_program), trace)
            self._jit_cache[key] = hit
            while len(self._jit_cache) > self._jit_cache_size:
                self._jit_cache.popitem(last=False)
                self._cache_evictions += 1
        fn, trace = hit
        return fn, arr_refs, suffix_outs, trace


def _block(x):
    """Block on the array leaves of ``x`` so per-node timings are honest;
    a device error raises here."""
    import jax
    jax.block_until_ready([leaf for leaf in jax.tree.leaves(x)
                           if isinstance(leaf, jax.Array)])
    return x
