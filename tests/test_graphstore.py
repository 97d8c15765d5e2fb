"""GraphStore behaviour: bulk/unit ops vs an adjacency-dict oracle,
H/L-type mapping invariants, and a hypothesis property test driving random
mutable-op sequences."""
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.store.blockdev import BlockDevice, SLOTS_PER_PAGE
from repro.store.graphstore import _H_NEXT, GraphStore, preprocess_edges


def _mk_graph(n=200, e=1200, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.zipf(1.4, e) % n
    dst = rng.integers(0, n, e)
    return np.stack([dst, src], axis=1).astype(np.int64)


def _oracle(edges, n):
    adj = {v: {v} for v in range(n)}           # self loops
    for d, s in edges:
        adj[int(d)].add(int(s))
        adj[int(s)].add(int(d))
    return adj


def test_preprocess_edges_csr():
    edges = _mk_graph()
    indptr, indices = preprocess_edges(edges)
    n = int(edges.max()) + 1
    adj = _oracle(edges, n)
    for v in range(n):
        got = set(int(x) for x in indices[indptr[v]:indptr[v + 1]])
        assert got == adj[v], v
    # sorted within rows
    for v in range(n):
        row = indices[indptr[v]:indptr[v + 1]]
        assert np.all(np.diff(row) > 0)


def test_bulk_load_matches_oracle():
    edges = _mk_graph()
    n = int(edges.max()) + 1
    gs = GraphStore(BlockDevice(), h_threshold=8)
    gs.update_graph(edges)
    adj = _oracle(edges, n)
    for v in range(n):
        assert set(int(x) for x in gs.get_neighbors(v)) == adj[v], v
    # power-law: some vertices must be H-type, most L-type
    kinds = set(gs.class_kinds()[1].tolist())
    assert kinds == {"H", "L"}


def test_bulk_overlap_timeline():
    edges = _mk_graph(500, 4000)
    emb = np.random.default_rng(0).standard_normal(
        (int(edges.max()) + 1, 64)).astype(np.float32)
    gs = GraphStore(BlockDevice(1 << 12), h_threshold=16)
    tl = gs.update_graph(edges, emb)
    # user-visible latency excludes (overlapped) graph preprocessing
    assert tl.user_visible <= tl.total
    assert tl.write_feature[1] > 0


def test_embeddings_roundtrip_and_update():
    edges = _mk_graph(100, 400)
    n = int(edges.max()) + 1
    emb = np.random.default_rng(1).standard_normal((n, 48)).astype(np.float32)
    gs = GraphStore(BlockDevice(), h_threshold=8)
    gs.update_graph(edges, emb)
    for v in (0, 1, n // 2, n - 1):
        np.testing.assert_array_equal(gs.get_embed(v), emb[v])
    new_row = np.full(48, 3.25, np.float32)
    gs.update_embed(5, new_row)
    np.testing.assert_array_equal(gs.get_embed(5), new_row)
    np.testing.assert_array_equal(gs.get_embed(4), emb[4])  # page RMW safe
    np.testing.assert_array_equal(gs.get_embed(6), emb[6])


def test_unit_ops_and_promotion():
    gs = GraphStore(BlockDevice(), h_threshold=4)
    edges = np.array([[0, 1], [1, 2], [2, 3]], np.int64)
    gs.update_graph(edges)
    # vertex addition (ascending VIDs -> appended to last L page)
    gs.add_vertex(10)
    assert set(gs.get_neighbors(10)) == {10}
    # adding many edges promotes 0 from L to H
    for u in range(4, 10):
        gs.add_edge(0, u)
    assert gs.kind_of(0) == "H"
    assert set(gs.get_neighbors(0)) == {0, 1} | set(range(4, 10))
    # delete edge both directions
    gs.delete_edge(0, 4)
    assert 4 not in gs.get_neighbors(0)
    assert 0 not in gs.get_neighbors(4)
    # delete vertex scrubs it from neighbors
    gs.delete_vertex(0)
    for u in range(1, 10):
        assert 0 not in gs.get_neighbors(u)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["add_e", "del_e", "add_v"]),
                          st.integers(0, 24), st.integers(0, 24)),
                min_size=1, max_size=60))
def test_property_random_mutations(ops):
    gs = GraphStore(BlockDevice(), h_threshold=4)
    base = np.array([[0, 1], [1, 2]], np.int64)
    gs.update_graph(base)
    adj = _oracle(base, 3)
    next_vid = 25
    for op, a, b in ops:
        if op == "add_v":
            gs.add_vertex(next_vid)
            adj[next_vid] = {next_vid}
            next_vid += 1
        elif op == "add_e":
            a2, b2 = sorted((a, b))
            gs.add_edge(b2, a2)
            for v in (a2, b2):
                adj.setdefault(v, {v}).add(v)
            adj[a2].add(b2)
            adj[b2].add(a2)
        else:
            if a in adj and b in adj[a] and a != b:
                gs.delete_edge(a, b)
                adj[a].discard(b)
                adj[b].discard(a)
    store_adj = gs.to_adjacency()
    for v, want in adj.items():
        assert store_adj.get(v, set()) == want, (v, store_adj.get(v), want)
    vids = list(range(next_vid + 2))
    for v, got in zip(vids, gs.get_neighbors_batch(vids)):
        np.testing.assert_array_equal(got, gs.get_neighbors(v))


def test_chain_pool_compacts_and_keeps_every_chain():
    """Rewritten H chains leave superseded copies in the planner's LPN
    pool; once they outweigh the live chains the pool is compacted, and
    every chain (and batched read) still matches the mapping tables."""
    gs = GraphStore(BlockDevice(), h_threshold=8)
    gs.update_graph(_mk_graph(seed=3))
    vids, kinds = gs.class_kinds()
    hubs = vids[kinds == "H"].tolist()
    pages = {v: gs.export_chain(v) for v in hubs}
    vids = list(range(210))
    before = [gs.get_neighbors(v) for v in vids]
    writes = 0
    while writes < 2 * len(gs._pool):
        for v in hubs[:3]:
            gs.import_h_chain(v, pages[v])
            writes += len(pages[v])
    assert gs._pool_top < writes
    for v in hubs:
        chain, walk, lpn = gs.chain_of(v).tolist(), [], gs.chain_of(v)[0]
        while lpn >= 0:
            walk.append(int(lpn))
            lpn = gs.dev.read_page(lpn)[_H_NEXT]
        assert chain == walk, v
        np.testing.assert_array_equal(
            np.delete(gs.export_chain(v), _H_NEXT, axis=1),
            np.delete(pages[v], _H_NEXT, axis=1))
    for v, got, want in zip(vids, gs.get_neighbors_batch(vids), before):
        np.testing.assert_array_equal(got, gs.get_neighbors(v))
        np.testing.assert_array_equal(got, want)


def test_write_amplification_unit_ops():
    """Mutable updates touch O(1) pages (the paper's WA argument)."""
    gs = GraphStore(BlockDevice(), h_threshold=64)
    gs.update_graph(_mk_graph(300, 2000))
    w0 = gs.dev.stats.written_pages
    gs.add_edge(5, 7)
    assert gs.dev.stats.written_pages - w0 <= 4
