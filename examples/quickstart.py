"""Quickstart: the paper's headline flow in ~40 lines.

Build a graph -> bulk-ingest into GraphStore (near-storage) -> program the
Hetero accelerator -> run GCN inference through a DFG over RPC.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.service import HolisticGNNService, make_service_dfg
from repro.core import gnn
from repro.kernels.ops import program_config
from repro.rpc import RPCServer, RPCClient

use_compile_cache()
rng = np.random.default_rng(0)

# 1. a power-law graph + node embeddings (the "raw data on storage")
n_vertices, n_edges, feat = 1000, 8000, 64
edges = np.stack([rng.integers(0, n_vertices, n_edges),
                  rng.zipf(1.4, n_edges) % n_vertices], 1).astype(np.int64)
embeddings = rng.standard_normal((n_vertices, feat)).astype(np.float32)

# 2. the CSSD-side service, reached over RPC-over-PCIe
service = HolisticGNNService(h_threshold=32, pad_to=32)
client = RPCClient(RPCServer(service))

stats = client.call("update_graph", edge_array=edges, embeddings=embeddings)
print(f"bulk ingest: total={stats['total_s']*1e3:.1f} ms, "
      f"user-visible={stats['user_visible_s']*1e3:.1f} ms "
      f"(graph preprocessing overlapped)")

# 3. program the User logic: vector (SpMM) + systolic (GEMM) accelerators
reconfig_s = program_config(service.xbuilder, "hetero")
print(f"XBuilder reconfigured to Hetero in {reconfig_s*1e3:.2f} ms")

# 4. ship a GCN as a dataflow graph; sampling runs where the data lives
params = gnn.init_params("gcn", [feat, 32, 16], seed=1)
dfg = make_service_dfg("gcn", num_layers=2, fanouts=[10, 10])
weights = {k: v for k, v in gnn.dfg_feeds("gcn", params, None, []).items()
           if k != "H"}
out = client.call("run", dfg=dfg.save(), batch=[1, 2, 3, 4],
                  weights=weights)
print(f"inferred embeddings for 4 targets: {out['Result'][:4].shape}")
print(f"executed on devices: {sorted({d for _, d in service.engine.trace})}")
print(f"RoP traffic: {client.tx.stats.bytes_moved/1e3:.1f} KB sent, "
      f"{client.rx.stats.bytes_moved/1e3:.1f} KB received")
