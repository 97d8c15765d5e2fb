"""Fig. 17 — inference latency decomposition into aggregation (SIMD-class
C-operations: SpMM/SDDMM/Reduce/elementwise) vs transformation (GEMM-class)
per User-logic configuration, on the 'physics' workload."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from . import common as C
from repro import spans
from repro.core import gnn
from repro.core.service import HolisticGNNService
from repro.kernels.ops import program_config
from repro.store.sampler import sample_batch

GEMM_OPS = {"engine.GEMM"}


def run(workload="physics", model="gcn"):
    edges, emb, _ = C.make_workload(workload)
    svc = HolisticGNNService(h_threshold=64, pad_to=64)
    svc.store.update_graph(edges, emb)
    b = sample_batch(svc.store, np.arange(16), [10, 10],
                     rng=np.random.default_rng(0), pad_to=64)
    params = gnn.init_params(model, [emb.shape[1], 128, 64], seed=0)
    dfg = gnn.BUILD_DFG[model](2)
    feeds = gnn.dfg_feeds(
        model, params, jnp.asarray(b.embeddings),
        [(jnp.asarray(x.nbr), jnp.asarray(x.mask)) for x in b.layers])
    lines = []
    for cfg in ("octa", "lsap", "hetero"):
        program_config(svc.xbuilder, cfg)
        # fuse=False: the decomposition needs the unfused per-op timings
        svc.engine.run(dfg, feeds, fuse=False)      # warm
        before = spans.snapshot()
        svc.engine.run(dfg, feeds, fuse=False)
        after = spans.snapshot()
        # per-op seconds of the eager nodes' ``engine.<op>`` spans
        node_s = {k: v["s"] - before.get(k, {"s": 0.0})["s"]
                  for k, v in after.items()
                  if k.startswith("engine.") and k != "engine.run"}
        gemm_t = sum(dt for op, dt in node_s.items() if op in GEMM_OPS)
        simd_t = sum(dt for op, dt in node_s.items() if op not in GEMM_OPS)
        tot = gemm_t + simd_t
        lines.append(C.csv_line(
            f"fig17.{model}.{cfg}", tot,
            f"gemm_frac={gemm_t/tot:.2f};simd_frac={simd_t/tot:.2f}"))
    return lines
