"""PlacementMap algebra + the placement-totality property: ANY total
placement map (random owner tables included) must round-trip the
plan → fetch → build read path bit-identically to the single device,
and ingesting under a custom map must agree with resharding a default
array INTO that same map."""
import numpy as np
import pytest

from repro.store import (BlockDevice, GraphStore, ShardedGraphStore,
                         sample_batch)
from repro.store.placement import (PlacementMap, common_refine, grow_plan,
                                   heat_plan, modular, plan_moves,
                                   rows_of_class, shrink_plan)


def _graph(n=360, e=2600, feat=16, seed=0):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, e), rng.zipf(1.4, e) % n],
                     axis=1).astype(np.int64)
    emb = rng.standard_normal((n, feat)).astype(np.float32)
    return edges, emb


def _random_map(n_shards, n_classes, replication, seed):
    """A random but TOTAL placement map: every (class, role) owned, the
    replicas of each class on distinct shards."""
    rng = np.random.default_rng(seed)
    owner = np.stack([rng.choice(n_shards, size=replication, replace=False)
                      for _ in range(n_classes)]).astype(np.int64)
    return PlacementMap(n_classes, owner)


def _assert_reads_equal(ref, store, n, seed=7):
    rng = np.random.default_rng(seed)
    vids = rng.integers(0, n, 120)
    np.testing.assert_array_equal(ref.get_embeds(vids),
                                  store.get_embeds(vids))
    for a, b in zip(ref.get_neighbors_batch(vids[:40]),
                    store.get_neighbors_batch(vids[:40])):
        np.testing.assert_array_equal(a, b)
    ba = sample_batch(ref, vids[:32], [6, 6],
                      rng=np.random.default_rng(11), pad_to=32)
    bb = sample_batch(store, vids[:32], [6, 6],
                      rng=np.random.default_rng(11), pad_to=32)
    np.testing.assert_array_equal(ba.node_vids, bb.node_vids)
    np.testing.assert_array_equal(ba.embeddings, bb.embeddings)
    for la, lb in zip(ba.layers, bb.layers):
        np.testing.assert_array_equal(la.nbr, lb.nbr)
        np.testing.assert_array_equal(la.mask, lb.mask)


# --------------------------------------------------------------- map algebra
def test_modular_map_is_legacy_layout():
    m = modular(4, 2)
    assert m.is_modular(4)
    assert m.replication == 2
    for c in range(4):
        for r in range(2):
            assert int(m.owner[c, r]) == (c + r) % 4


def test_refine_preserves_ownership():
    m = _random_map(4, 4, 2, seed=1)
    f = m.refine(3)
    assert f.n_classes == 12
    for v in range(60):
        np.testing.assert_array_equal(m.owner[v % 4], f.owner[v % 12])
    # refining never plans any move
    a, b = common_refine(m, f)
    moves, drops = plan_moves(a, b)
    assert moves == [] and drops == {}


def test_rows_of_class_partitions_rows():
    for n_rows in (0, 1, 7, 64, 101):
        for C in (1, 3, 5, 8):
            assert sum(rows_of_class(n_rows, c, C)
                       for c in range(C)) == n_rows


def test_validate_rejects_bad_maps():
    with pytest.raises(ValueError):
        PlacementMap(2, np.array([[0], [5]])).validate(4)
    with pytest.raises(ValueError):
        PlacementMap(1, np.array([[1, 1]])).validate(4)
    _random_map(4, 8, 2, seed=2).validate(4)


def test_plan_moves_classifies_copy_vs_relabel():
    old = PlacementMap(2, np.array([[0, 1], [1, 2]]))
    # class 0: role 0 moves 0->2 (2 not an owner: copy); class 1:
    # roles swap 1<->2 (both already owners: relabels, no bytes)
    new = PlacementMap(2, np.array([[2, 1], [2, 1]]))
    moves, drops = plan_moves(old, new)
    kinds = {(m.cls, m.role): m.kind for m in moves}
    assert kinds[(0, 0)] == "copy"
    assert kinds[(1, 0)] == "relabel" and kinds[(1, 1)] == "relabel"
    assert drops == {0: [0]}        # shard 0 no longer holds class 0


def test_grow_plan_gives_new_shards_fair_share():
    pm = modular(4, 1)
    heat = np.array([8.0, 4.0, 2.0, 1.0])
    new = grow_plan(pm, 4, 5, heat=heat)
    assert new.n_classes % 5 == 0
    new.validate(5)
    got = len(new.classes_of(4))
    assert got == new.n_classes // 5
    # every move targets the new shard only
    a, b = common_refine(pm, new)
    moves, _ = plan_moves(a, b)
    assert moves and all(m.dst == 4 for m in moves)


def test_shrink_plan_drains_only_removed():
    pm = modular(4, 2)
    new = shrink_plan(pm, [3], 4)
    assert not (new.owner == 3).any()
    new.validate(4)
    a, b = common_refine(pm, new)
    moves, _ = plan_moves(a, b)
    assert moves and all(m.src == 3 for m in moves)


def test_heat_plan_flattens_skewed_heat():
    pm = modular(4, 1)
    heat = np.array([100.0, 1.0, 1.0, 1.0])     # one scorching class
    new = heat_plan(pm, heat, live=[0, 1, 2, 3], refine=4)
    new.validate(4)
    fine_heat = np.tile(heat / 4, 4)
    loads = np.zeros(4)
    np.add.at(loads, new.owner[:, 0], fine_heat)
    assert loads.min() / loads.max() > 0.6      # vs 0.03 before


def test_payload_roundtrip():
    m = _random_map(5, 10, 2, seed=3)
    assert PlacementMap.from_payload(m.to_payload()) == m


# ------------------------------------------------- placement totality property
@pytest.mark.parametrize("n_shards,replication,n_classes,seed", [
    (2, 1, 2, 10), (2, 1, 6, 11), (4, 1, 4, 12), (4, 1, 12, 13),
    (3, 2, 3, 14), (4, 2, 8, 15), (4, 3, 12, 16),
])
def test_any_total_map_reads_bit_identical(n_shards, replication,
                                           n_classes, seed):
    """The read path never assumes modular placement: a store ingested
    under a RANDOM total map answers plan → fetch → build reads
    bit-identically to the single device."""
    edges, emb = _graph()
    n = emb.shape[0]
    ref = GraphStore(BlockDevice(), h_threshold=16)
    ref.update_graph(edges, emb)
    pmap = _random_map(n_shards, n_classes, replication, seed)
    store = ShardedGraphStore(n_shards=n_shards, h_threshold=16,
                              replication=replication, placement=pmap)
    store.update_graph(edges, emb)
    _assert_reads_equal(ref, store, n)
    ps = store.placement_stats()
    assert ps["n_classes"] == n_classes
    assert sum(ps["classes_per_shard"]) >= n_classes


@pytest.mark.parametrize("n_shards,replication", [(3, 1), (4, 2)])
def test_ingest_under_map_agrees_with_reshard_into_map(n_shards,
                                                       replication):
    """Loading a graph directly under a custom map produces the same
    answers as loading under the default map and resharding INTO that
    map online — the two paths to a placement must agree."""
    edges, emb = _graph()
    n = emb.shape[0]
    pmap = _random_map(n_shards, 2 * n_shards, replication, seed=21)

    direct = ShardedGraphStore(n_shards=n_shards, h_threshold=16,
                               replication=replication, placement=pmap)
    moved = ShardedGraphStore(n_shards=n_shards, h_threshold=16,
                              replication=replication)
    direct.update_graph(edges, emb)
    moved.update_graph(edges, emb)
    report = moved.reshard(placement=pmap, chunk_pages=16)
    assert report["classes_moved"] > 0
    assert report["epochs"] >= 1
    # both now answer identically (and identically to one device)
    ref = GraphStore(BlockDevice(), h_threshold=16)
    ref.update_graph(edges, emb)
    _assert_reads_equal(ref, direct, n)
    _assert_reads_equal(ref, moved, n)
    a, b = common_refine(direct._routing.pmap, moved._routing.pmap)
    moves, _ = plan_moves(a, b)
    assert moves == []              # literally the same placement


def test_mutations_under_custom_map_route_correctly():
    """Unit mutations against a random map land on the mapped owners and
    stay bit-identical to the single device."""
    edges, emb = _graph(n=200, e=1200)
    n = emb.shape[0]
    ref = GraphStore(BlockDevice(), h_threshold=16)
    ref.update_graph(edges, emb)
    store = ShardedGraphStore(n_shards=3, h_threshold=16,
                              placement=_random_map(3, 6, 1, seed=5))
    store.update_graph(edges, emb)
    rng = np.random.default_rng(9)
    for _ in range(40):
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        ref.add_edge(u, v)
        store.add_edge(u, v)
    row = rng.standard_normal(emb.shape[1]).astype(np.float32)
    ref.update_embed(7, row)
    store.update_embed(7, row)
    _assert_reads_equal(ref, store, n)
