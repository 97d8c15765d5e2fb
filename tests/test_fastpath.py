"""Vectorized fast-path equivalence: batched GraphStore queries, the
NumPy sampler vs the per-vertex reference, the fused aggregate-combine
kernel, and the engine's whole-DFG jit path."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.store.blockdev import BlockDevice
from repro.store.graphstore import (H_CAP, _H_NEXT, _L_NNODES, GraphStore,
                                    _l_meta_vid)
from repro.store.sampler import _subsample, sample_batch, sample_batch_ref


def _store_edges(rng, n, e):
    src = rng.zipf(1.4, e) % (n - 10)          # last 10 vids never get edges
    dst = rng.integers(0, n - 10, e)
    return np.stack([dst, src], axis=1).astype(np.int64)


def _store(seed=0, n=400, e=3000, h_threshold=8, feat=24):
    """Power-law graph with H/L mix; some vertices stay edge-less (isolated
    vertices have embeddings but no adjacency -> empty-neighbor path)."""
    rng = np.random.default_rng(seed)
    edges = _store_edges(rng, n, e)
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
    kinds = set(gs.class_kinds()[1].tolist())
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
    assert gs.kind_of(0) == "H"
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
    assert gs.kind_of(0) == "H" and len(gs.chain_of(0)) >= 3
    for u in range(n_nbrs + 1, n_nbrs + 40):       # grow the tail page
        gs.add_edge(0, u)
    got = gs.get_neighbors_batch([0, 1, 2])
    for v, g in zip([0, 1, 2], got):
        np.testing.assert_array_equal(g, gs.get_neighbors(v))


def _hub_store(mutate=False):
    """Power-law graph plus two hubs whose chains span several pages;
    ``mutate`` then applies unit ops: L chunks relocated and L nodes
    tombstoned, an L vertex promoted to H, a chain grown by a page, an H
    and an L vertex deleted."""
    star = np.arange(400, 400 + 2 * H_CAP + 300)
    hubs = np.concatenate([np.stack([np.full(len(star), 3), star], axis=1),
                           np.stack([np.full(H_CAP + 9, 9),
                                     star[: H_CAP + 9]], axis=1)])
    gs = GraphStore(BlockDevice(), h_threshold=8)
    gs.update_graph(np.concatenate(
        [_store_edges(np.random.default_rng(5), 400, 3000), hubs]))
    n = int(star[-1]) + 1
    assert len(gs.chain_of(3)) >= 3 and len(gs.chain_of(9)) >= 2
    if mutate:
        low = [v for v in range(40, 400) if gs.kind_of(v) == "L"]
        for v in low[:12]:
            gs.add_edge(v, low[-1])          # relocates or appends chunks
        p = low[20]
        for u in range(100, 100 + 12):
            gs.add_edge(p, u)                # promotes p to H
        assert gs.kind_of(p) == "H"
        for u in range(n, n + H_CAP):        # grows hub 3 by a page
            gs.add_edge(3, u)
        gs.delete_vertex(low[30])            # tombstone in an L page
        gs.delete_vertex(9)                  # an H vertex gone
        gs.delete_edge(low[40], low[41])
        n += H_CAP
    return gs, n


def _assert_tables_match_device(gs):
    """The mapping tables agree with the pages on the device: each hub's
    pool run is the chain reached by following ``_H_NEXT`` from its head
    (and holds the hub's own self-loop, so it is the hub's chain), and
    ``_sel`` classifies as L exactly the live vids of the L pages (each
    found by the range search) and as H exactly the vids with a chain."""
    hubs = np.flatnonzero(gs._chain_n)
    for v in hubs.tolist():
        walk, lpn, held = [], int(gs._pool[gs._chain_at[v]]), set()
        while lpn >= 0:
            walk.append(lpn)
            page = gs.dev.read_page(lpn)
            held.update(page[2: 2 + int(page[0])].tolist())
            lpn = int(page[_H_NEXT])
        assert gs.chain_of(v).tolist() == walk and v in held, v
    assert int(gs._chain_n.sum()) == gs._pool_live
    assert np.all(np.diff(gs._l_keys) > 0)
    l_vids = set()
    for k, lpn in enumerate(gs._l_lpns.tolist()):
        page = gs.dev.read_page(lpn)
        on_page = [int(page[_l_meta_vid(i)])
                   for i in range(int(page[_L_NNODES]))]
        on_page = [v for v in on_page if v >= 0]          # no tombstones
        assert all(int(np.searchsorted(gs._l_keys, v)) == k
                   for v in on_page), lpn
        l_vids.update(on_page)
    assert set(np.flatnonzero(gs._sel == 1).tolist()) == l_vids
    assert np.array_equal(np.flatnonzero(gs._sel == 2), hubs)


def _select_ref(gs, vids, fanout, rng):
    """Per-vertex reference of one fanout selection (the oracle's own)."""
    parts = [_subsample(rng, int(v), gs.get_neighbors(int(v)), fanout)
             for v in vids]
    return (np.concatenate(parts + [np.empty(0, np.int32)]).astype(np.int32),
            np.array([len(x) for x in parts], dtype=np.int64))


_PLAN_CASES = ["bulk_hub_chains", "after_unit_ops", "unknown_and_dups",
               "segments"]


@pytest.mark.parametrize("case", _PLAN_CASES)
def test_array_plan_matches_reference(case):
    """The array plan's batched reads and fanout selection equal the
    per-vid references bit for bit: bulk hub chains, unit-op mutations,
    unknown and duplicate vids, and a multi-request segments/rngs
    frontier."""
    gs, n = _hub_store(mutate=case != "bulk_hub_chains")
    _assert_tables_match_device(gs)
    rng = np.random.default_rng(4)
    vids = np.concatenate([[3, 9], rng.integers(0, 400, 60)])
    if case == "unknown_and_dups":
        vids = np.concatenate([vids, [n + 5, 10**7, 3, 3, vids[4], 9]])
    for v, got in zip(vids, gs.get_neighbors_batch(vids)):
        np.testing.assert_array_equal(got, gs.get_neighbors(int(v)),
                                      err_msg=str(v))
    for fanout in (3, 10):
        if case == "segments":
            segs = [7, 0, 30, len(vids) - 37]
            seeds = [11, 12, 13, 14]
            got = gs.sample_neighbors_batch(
                vids, fanout, segments=segs,
                rngs=[np.random.default_rng(x) for x in seeds])
            bounds = np.cumsum([0] + segs)
            parts = [_select_ref(gs, vids[bounds[i]: bounds[i + 1]], fanout,
                                 np.random.default_rng(seeds[i]))
                     for i in range(len(segs))]
            want = (np.concatenate([p[0] for p in parts]),
                    np.concatenate([p[1] for p in parts]))
        else:
            got = gs.sample_neighbors_batch(vids, fanout,
                                            np.random.default_rng(fanout))
            want = _select_ref(gs, vids, fanout,
                               np.random.default_rng(fanout))
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    targets = vids[:8] if case != "unknown_and_dups" else vids[-6:]
    _assert_batches_equal(
        sample_batch(gs, targets, [10, 4], rng=np.random.default_rng(1)),
        sample_batch_ref(gs, targets, [10, 4],
                         rng=np.random.default_rng(1)))


@pytest.mark.parametrize("mutate", [False, True])
def test_sample_reads_distinct_l_pages_then_every_chain_page(mutate):
    """One fused sample reads exactly the distinct L pages of its L vids
    (sorted) and then every page of each H vid's chain (sorted, deduped),
    in one command — the page set ``pages_per_request`` counts."""
    gs, n = _hub_store(mutate)
    vids = np.concatenate([[3, 3, 9, n + 1],
                           np.random.default_rng(2).integers(0, 400, 80)])
    l_pages, h_pages = set(), set()
    for v in vids.tolist():
        if gs.kind_of(v) == "L":
            k = int(np.searchsorted(gs._l_keys, v))
            l_pages.add(int(gs._l_lpns[k]))
        elif gs.kind_of(v) == "H":
            h_pages.update(gs.chain_of(v).tolist())
    want = sorted(l_pages) + sorted(h_pages)
    seen = []
    read = gs.dev.read_pages
    gs.dev.read_pages = lambda lpns, **kw: (seen.append(list(lpns)),
                                            read(lpns, **kw))[1]
    r0 = gs.dev.stats.read_pages
    gs.sample_neighbors_batch(vids, 5, np.random.default_rng(0))
    assert seen == [want]
    assert gs.dev.stats.read_pages - r0 == len(want)


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
