"""Vectorized fast-path equivalence: batched GraphStore queries, the
NumPy sampler vs the per-vertex reference, the fused aggregate-combine
kernel, and the engine's whole-DFG jit path."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.store.blockdev import BlockDevice
from repro.store.graphstore import GraphStore
from repro.store.sampler import sample_batch, sample_batch_ref


def _store(seed=0, n=400, e=3000, h_threshold=8, feat=24):
    """Power-law graph with H/L mix; some vertices stay edge-less (isolated
    vertices have embeddings but no adjacency -> empty-neighbor path)."""
    rng = np.random.default_rng(seed)
    src = rng.zipf(1.4, e) % (n - 10)          # last 10 vids never get edges
    dst = rng.integers(0, n - 10, e)
    edges = np.stack([dst, src], axis=1).astype(np.int64)
    emb = rng.standard_normal((n, feat)).astype(np.float32)
    gs = GraphStore(BlockDevice(), h_threshold=h_threshold)
    gs.update_graph(edges, emb)
    return gs, n


@pytest.mark.parametrize("seed,h_threshold", [(0, 8), (1, 4), (2, 64)])
def test_get_neighbors_batch_matches_pointwise(seed, h_threshold):
    gs, n = _store(seed, h_threshold=h_threshold)
    vids = list(range(n)) + [n + 3, n + 17]    # incl. isolated + unknown vids
    batch = gs.get_neighbors_batch(vids)
    assert len(batch) == len(vids)
    kinds = set(gs.gmap.values())
    assert kinds == {"H", "L"}                 # both mapping types exercised
    for v, got in zip(vids, batch):
        np.testing.assert_array_equal(got, gs.get_neighbors(v), err_msg=str(v))


def test_get_neighbors_batch_after_mutations():
    """H/L boundary: batch reads stay correct across promotion and deletes."""
    gs = GraphStore(BlockDevice(), h_threshold=4)
    gs.update_graph(np.array([[0, 1], [1, 2], [2, 3]], np.int64))
    for u in range(4, 10):
        gs.add_edge(0, u)                      # promotes 0 to H-type
    gs.delete_edge(1, 2)
    assert gs.gmap[0] == "H"
    vids = list(range(12))
    for v, got in zip(vids, gs.get_neighbors_batch(vids)):
        np.testing.assert_array_equal(got, gs.get_neighbors(v), err_msg=str(v))


def test_get_neighbors_batch_multipage_h_chain():
    """Degree > H_CAP: chains spanning multiple pages, batch == pointwise,
    including after chain growth through unit-op appends."""
    n_nbrs = 2600                                  # > 2 * H_CAP (1022)
    edges = np.stack([np.zeros(n_nbrs, np.int64),
                      np.arange(1, n_nbrs + 1)], axis=1)
    gs = GraphStore(BlockDevice(), h_threshold=16)
    gs.update_graph(edges)
    assert gs.gmap[0] == "H" and len(gs.h_chain[0]) >= 3
    for u in range(n_nbrs + 1, n_nbrs + 40):       # grow the tail page
        gs.add_edge(0, u)
    got = gs.get_neighbors_batch([0, 1, 2])
    for v, g in zip([0, 1, 2], got):
        np.testing.assert_array_equal(g, gs.get_neighbors(v))


def test_get_embeds_coalesced_matches_rowwise():
    gs, n = _store(3)
    rng = np.random.default_rng(9)
    for ids in (np.arange(n), rng.permutation(n)[:137],
                np.array([0, n - 1, 1, n // 2]), np.array([5])):
        got = gs.get_embeds(ids)
        want = np.stack([gs.get_embed(int(v)) for v in ids])
        np.testing.assert_array_equal(got, want)
    assert gs.get_embeds(np.empty(0, np.int64)).shape == (0, gs.feature_dim)


def _assert_batches_equal(b1, b2):
    np.testing.assert_array_equal(b1.node_vids, b2.node_vids)
    assert b1.num_targets == b2.num_targets
    assert len(b1.layers) == len(b2.layers)
    for l1, l2 in zip(b1.layers, b2.layers):
        assert l1.num_dst == l2.num_dst
        np.testing.assert_array_equal(l1.nbr, l2.nbr)
        np.testing.assert_array_equal(l1.mask, l2.mask)
    if b1.embeddings is None:
        assert b2.embeddings is None
    else:
        np.testing.assert_array_equal(b1.embeddings, b2.embeddings)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("fanouts", [[4, 3], [10, 10], [2]])
def test_sample_batch_matches_reference(seed, fanouts):
    gs, n = _store(seed)
    targets = [3, 7, 11, n - 2]                # n-2 is isolated: self-loop path
    b_vec = sample_batch(gs, targets, fanouts,
                         rng=np.random.default_rng(seed))
    b_ref = sample_batch_ref(gs, targets, fanouts,
                             rng=np.random.default_rng(seed))
    _assert_batches_equal(b_vec, b_ref)


def test_sample_batch_matches_reference_duplicate_targets():
    """Duplicate targets: the reference maps a duplicated vid to its LAST
    frontier index (dict overwrite); the fast path must match."""
    gs, n = _store(0)
    for targets in ([5, 5, 7], [3, 3, 3]):
        b_vec = sample_batch(gs, targets, [4, 3],
                             rng=np.random.default_rng(1))
        b_ref = sample_batch_ref(gs, targets, [4, 3],
                                 rng=np.random.default_rng(1))
        _assert_batches_equal(b_vec, b_ref)


def test_sample_batch_matches_reference_padded():
    gs, n = _store(1, h_threshold=4)
    b_vec = sample_batch(gs, [1, 2, 5], [6, 6],
                         rng=np.random.default_rng(0), pad_to=32)
    b_ref = sample_batch_ref(gs, [1, 2, 5], [6, 6],
                             rng=np.random.default_rng(0), pad_to=32)
    _assert_batches_equal(b_vec, b_ref)
    assert b_vec.num_nodes % 32 == 0


def test_agg_combine_fused_kernel_matches_chain():
    from repro.kernels import agg_combine
    rng = np.random.default_rng(0)
    for (n, f, d, k, o) in [(50, 32, 10, 4, 16), (128, 220, 88, 10, 64)]:
        h = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
        nbr = jnp.asarray(rng.integers(0, n, (d, k)), jnp.int32)
        mask = jnp.asarray(rng.integers(0, 2, (d, k)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((f, o)) * 0.1, jnp.float32)
        b = jnp.asarray(rng.standard_normal(o) * 0.1, jnp.float32)
        got = agg_combine(h, nbr, mask, w, b, mode="mean")
        g = jnp.take(h, nbr, axis=0) * mask[..., None]
        agg = g.sum(1) / jnp.maximum(mask.sum(1), 1.0)[:, None]
        want = jnp.maximum(agg @ w + b[None, :], 0.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_engine_jit_path_matches_eager_and_caches():
    from repro.core.service import HolisticGNNService, make_service_dfg
    from repro.core import gnn
    rng = np.random.default_rng(3)
    edges = np.stack([rng.integers(0, 80, 400), rng.integers(0, 80, 400)],
                     axis=1).astype(np.int64)
    emb = rng.standard_normal((80, 24)).astype(np.float32)
    svc = HolisticGNNService(h_threshold=8, pad_to=16)
    svc.update_graph(edges, emb)
    for model in ("gcn", "gin", "ngcf"):
        params = gnn.init_params(model, [24, 12, 8], seed=2)
        dfg = make_service_dfg(model, 2, [4, 4])
        weights = gnn.dfg_feeds(model, params, None, [])
        weights.pop("H")
        o_eager = svc.run(dfg.save(), [1, 2], weights=weights,
                          jit=False)["Result"]
        o_jit = svc.run(dfg.save(), [1, 2], weights=weights,
                        jit=True)["Result"]
        np.testing.assert_allclose(o_eager, o_jit, rtol=1e-5, atol=1e-5)
    # one cached trace per model DFG; repeat runs hit the cache
    assert len(svc.engine._jit_cache) == 3
    svc.run(dfg.save(), [1, 2], weights=weights, jit=True)
    assert len(svc.engine._jit_cache) == 3


def test_gcn_fusion_on_hetero_bitstream():
    from repro.core.service import HolisticGNNService, make_service_dfg
    from repro.core import gnn
    from repro.kernels.ops import program_config
    rng = np.random.default_rng(4)
    edges = np.stack([rng.integers(0, 60, 300), rng.integers(0, 60, 300)],
                     axis=1).astype(np.int64)
    emb = rng.standard_normal((60, 24)).astype(np.float32)
    svc = HolisticGNNService(h_threshold=8, pad_to=16)
    svc.update_graph(edges, emb)
    params = gnn.init_params("gcn", [24, 12, 8], seed=2)
    dfg = make_service_dfg("gcn", 2, [4, 4])
    weights = gnn.dfg_feeds("gcn", params, None, [])
    weights.pop("H")
    before = svc.run(dfg.save(), [1, 2], weights=weights)["Result"]

    program_config(svc.xbuilder, "hetero")
    after = svc.run(dfg.save(), [1, 2], weights=weights)["Result"]
    # both GCN layers collapsed into the fused kernel on the vector device
    assert svc.engine.trace.count(("AggCombine", "vector")) == 2
    assert not any(op in ("SpMM_Mean", "GEMM", "BiasAdd", "ReLU")
                   for op, _ in svc.engine.trace)
    np.testing.assert_allclose(before, after, rtol=1e-4, atol=1e-4)

    # registry version bump invalidates the fused trace: unprogramming
    # falls back to the unfused shell chain with identical numerics
    svc.xbuilder.unprogram("vector")
    svc.xbuilder.unprogram("systolic")
    fallback = svc.run(dfg.save(), [1, 2], weights=weights)["Result"]
    assert all(d == "shell" for _, d in svc.engine.trace)
    np.testing.assert_allclose(before, fallback, rtol=1e-5, atol=1e-5)
