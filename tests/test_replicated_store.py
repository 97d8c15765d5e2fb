"""ShardedGraphStore with R replicas: R-way replica parity with the
single-device store, the per-R read plan, replica-spread selection balance, degraded-mode bit-identity under
every single-shard failure, write-fan-out coherence, and the
fail/rebuild/restore cycle — through the raw store and the service RPCs."""
import numpy as np
import pytest

from repro.core import gnn
from repro.core.service import HolisticGNNService, make_service_dfg
from repro.store import (BlockDevice, DeviceFailedError, GraphStore,
                         ShardedGraphStore, sample_batch)


def _graph(n=420, e=3200, feat=24, seed=0):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, e), rng.zipf(1.4, e) % n],
                     axis=1).astype(np.int64)
    emb = rng.standard_normal((n, feat)).astype(np.float32)
    return edges, emb


def _pair(n_shards, replication, *, h_threshold=16, n=420, e=3200, feat=24):
    edges, emb = _graph(n, e, feat)
    single = GraphStore(BlockDevice(), h_threshold=h_threshold)
    single.update_graph(edges, emb)
    rep = ShardedGraphStore(n_shards=n_shards, replication=replication,
                            h_threshold=h_threshold)
    rep.update_graph(edges, emb)
    return single, rep, n


def _assert_batches_equal(a, b, msg=""):
    np.testing.assert_array_equal(a.node_vids, b.node_vids, err_msg=msg)
    for la, lb in zip(a.layers, b.layers):
        np.testing.assert_array_equal(la.nbr, lb.nbr, err_msg=msg)
        np.testing.assert_array_equal(la.mask, lb.mask, err_msg=msg)
    np.testing.assert_array_equal(a.embeddings, b.embeddings, err_msg=msg)


def _assert_reads_match(single, rep, n, seed=3):
    rng = np.random.default_rng(seed)
    vids = rng.integers(0, n + 20, 70)           # includes unknown vids
    for a, b in zip(single.get_neighbors_batch(vids),
                    rep.get_neighbors_batch(vids)):
        np.testing.assert_array_equal(a, b)
    known = vids[vids < n]
    np.testing.assert_array_equal(single.get_embeds(known),
                                  rep.get_embeds(known))
    targets = rng.integers(0, n, 12)
    _assert_batches_equal(
        sample_batch(single, targets, [5, 5], rng=np.random.default_rng(9)),
        sample_batch(rep, targets, [5, 5], rng=np.random.default_rng(9)))


# ----------------------------------------------------------- healthy parity
@pytest.mark.parametrize("n_shards,replication",
                         [(3, 2), (4, 2), (4, 3), (4, 1)])
def test_replicated_bit_identical_healthy(n_shards, replication):
    single, rep, n = _pair(n_shards, replication)
    _assert_reads_match(single, rep, n)


def test_bad_replication_factor_rejected():
    with pytest.raises(ValueError):
        ShardedGraphStore(n_shards=2, replication=3)
    with pytest.raises(ValueError):
        ShardedGraphStore(n_shards=2, replication=0)


@pytest.mark.parametrize("replication", [1, 2])
def test_read_commands_follow_replication(replication):
    """One coordinator, two read plans chosen by R alone.  At R = 1 each
    owner plans its own vids: one ``fetch`` per owning shard per read,
    and neither ``plan_info`` nor a gossip ``counters`` pull — not even
    at construction.  At R >= 2 the coordinator plans from one
    ``plan_info`` per occupied class and pulls the gossip counters for
    each selection (staleness 0)."""
    single, rep, n = _pair(3, replication)

    def calls(method):
        return [ep.method_stats[method].calls
                if method in ep.method_stats else 0
                for ep in rep.endpoints]

    methods = ("fetch", "plan_info", "counters")
    before = {m: calls(m) for m in methods}
    vids = np.arange(0, n, 5)                    # every class occupied
    rep.get_neighbors_batch(vids)
    rep.get_embeds(vids)
    got = {m: [b - a for a, b in zip(before[m], calls(m))] for m in methods}
    if replication == 1:
        assert got == {"fetch": [2, 2, 2], "plan_info": [0, 0, 0],
                       "counters": [0, 0, 0]}, got
        assert calls("counters") == [0, 0, 0]
    else:
        assert got["plan_info"] == [1, 1, 1], got
        assert got["counters"] == [2, 2, 2], got
    _assert_reads_match(single, rep, n)


# ---------------------------------------------------------- degraded reads
def test_kill_each_shard_in_turn_stays_bit_identical():
    """R=2, N=3: fail every shard in turn; sample_batch / get_embeds /
    get_neighbors_batch must stay bit-identical to the healthy single
    device, and rebuild must restore full redundancy each time."""
    single, rep, n = _pair(3, 2)
    for s in range(3):
        info = rep.fail_shard(s)
        assert s not in [i for i, f in enumerate(rep.failed_shards) if not f]
        assert sorted(info["degraded_classes"]) == sorted(
            {(s - r) % 3 for r in range(2)})
        _assert_reads_match(single, rep, n, seed=10 + s)
        # reads must not touch the dead device
        with pytest.raises(DeviceFailedError):
            rep.shards[s].dev.read_page(0)
        info = rep.rebuild_shard(s)
        assert info["pages_written"] > 0
        assert not any(rep.failed_shards)
        assert rep.shards[s].dev.stats.written_pages == info["pages_written"]
        assert (rep.shards[s].stats.pages_l
                + rep.shards[s].stats.pages_h) > 0
        _assert_reads_match(single, rep, n, seed=20 + s)


def test_degraded_reads_avoid_failed_device():
    _, rep, n = _pair(4, 2)
    rep.fail_shard(1)
    reads0 = rep.shards[1].dev.stats.read_pages
    rep.get_embeds(np.arange(60))
    rep.get_neighbors_batch(np.arange(60))
    assert rep.shards[1].dev.stats.read_pages == reads0


def test_fail_validation_refuses_data_loss():
    _, rep, _ = _pair(3, 2)
    rep.fail_shard(0)
    # class c's owners are shards {c, c+1}.  With shard 0 dead, killing
    # shard 1 would lose class 0 (owners {0, 1} both dead) and killing
    # shard 2 would lose class 2 (owners {2, 0} both dead)
    with pytest.raises(DeviceFailedError):
        rep.fail_shard(1)
    with pytest.raises(DeviceFailedError):
        rep.fail_shard(2)
    rep.rebuild_shard(0)
    rep.fail_shard(1)                      # fine again after rebuild


def test_r1_cannot_fail_anything():
    _, rep, _ = _pair(3, 1)
    with pytest.raises(DeviceFailedError):
        rep.fail_shard(0)


def test_out_of_table_embedding_rows_rejected():
    """A row beyond the ingested table would land in ANOTHER role's
    stripe under the replica layout — silent cross-vertex corruption —
    so embed reads/writes bound-check the vid."""
    _, rep, n = _pair(2, 2)
    row = np.zeros(24, dtype=np.float32)
    before = rep.get_embeds(np.arange(n))
    for bad in (n, n + 7):
        with pytest.raises(KeyError):
            rep.update_embed(bad, row)
        with pytest.raises(KeyError):
            rep.get_embed(bad)
        with pytest.raises(KeyError):
            rep.get_embeds(np.array([0, bad]))
        rep.add_vertex(bad)                    # adjacency-only: fine
        with pytest.raises(KeyError):
            rep.add_vertex(bad + 100, embed=row)
    np.testing.assert_array_equal(rep.get_embeds(np.arange(n)), before)


# ------------------------------------------------------ write fan-out paths
def _mutate_both(single, rep, n, rounds=120, seed=11, feat=24):
    rng = np.random.default_rng(seed)
    for _ in range(rounds):
        op = rng.integers(0, 5)
        a, b = int(rng.integers(0, n)), int(rng.integers(0, n))
        if op == 0:
            single.add_edge(a, b), rep.add_edge(a, b)
        elif op == 1:
            single.delete_edge(a, b), rep.delete_edge(a, b)
        elif op == 2:
            v = n + int(rng.integers(0, 40))
            single.add_vertex(v), rep.add_vertex(v)
        elif op == 3:
            row = rng.standard_normal(feat).astype(np.float32)
            single.update_embed(a, row), rep.update_embed(a, row)
        else:
            single.delete_vertex(a), rep.delete_vertex(a)


def test_write_fanout_coherence_mutate_fail_read_survivor():
    """Mutations fan out to every replica: mutate, fail each shard in
    turn, and the survivors must serve the mutated state bit-identically."""
    single, rep, n = _pair(3, 2)
    _mutate_both(single, rep, n)
    assert single.to_adjacency() == rep.to_adjacency()
    for s in range(3):
        rep.fail_shard(s)
        _assert_reads_match(single, rep, n, seed=30 + s)
        rep.rebuild_shard(s)


def test_degraded_writes_then_rebuild_then_other_failure():
    """Writes while degraded land on the survivors; rebuild folds them in;
    failing ANOTHER shard afterwards forces reads through the rebuilt
    replica, which must hold the degraded-era mutations."""
    single, rep, n = _pair(3, 2)
    rep.fail_shard(0)
    _mutate_both(single, rep, n, rounds=60, seed=13)
    _assert_reads_match(single, rep, n, seed=40)
    rep.rebuild_shard(0)
    # kill shard 1: class 0 (owners {0, 1}) must now be served by the
    # REBUILT shard 0 exclusively
    rep.fail_shard(1)
    _assert_reads_match(single, rep, n, seed=41)
    rep.rebuild_shard(1)
    _assert_reads_match(single, rep, n, seed=42)


# -------------------------------------------------------- replica selection
def test_select_replicas_balances_feasible_skew():
    """A class-skewed (but feasible) weight mix must spread to near-equal
    per-shard load; repeated selections drive cumulative balance to ~1."""
    _, rep, n = _pair(4, 2, n=800, e=6000)
    rng = np.random.default_rng(0)
    for _ in range(6):
        hot = 1 + 4 * rng.integers(0, 200, 120)      # class-1 heavy
        cold = rng.integers(0, 800, 240)
        rep.get_embeds(np.concatenate([hot, cold]) % 800)
    reads = [d.stats.read_pages for d in rep.devs]
    assert min(reads) / max(reads) >= 0.9, reads


def test_selection_only_targets_live_owners():
    _, rep, n = _pair(4, 2)
    vids = np.arange(160, dtype=np.int64)
    owner = rep._select_replicas(vids)
    for v, s in zip(vids.tolist(), owner.tolist()):
        assert s in rep.replica_shards(v)
    rep.fail_shard(2)
    owner = rep._select_replicas(vids)
    assert 2 not in set(owner.tolist())


# --------------------------------------------------------- service surface
def test_service_replicated_run_and_fault_rpcs():
    edges, emb = _graph(n=600, e=5000, feat=32)
    ref = HolisticGNNService(h_threshold=16, pad_to=32)
    ref.store.update_graph(edges, emb)
    svc = HolisticGNNService(h_threshold=16, pad_to=32, n_shards=3,
                             replication=2, cache_pages=600)
    svc.store.update_graph(edges, emb)
    dfg = make_service_dfg("gcn", 2, [5, 5]).save()
    params = gnn.init_params("gcn", [32, 16, 8], seed=1)
    weights = {k: v for k, v in
               gnn.dfg_feeds("gcn", params, None, []).items() if k != "H"}
    want = ref.run(dfg, [3, 7, 11, 200], weights=weights, seed=42)["Result"]
    got = svc.run(dfg, [3, 7, 11, 200], weights=weights, seed=42)["Result"]
    np.testing.assert_array_equal(want, got)

    st = svc.stats()
    assert st["replication"] == {"r": 2, "failed_shards": []}
    assert all(not s["failed"] for s in st["shards"])

    svc.fail_shard(1)
    got = svc.run(dfg, [3, 7, 11, 200], weights=weights, seed=42)["Result"]
    np.testing.assert_array_equal(want, got)
    st = svc.stats()
    assert st["replication"]["failed_shards"] == [1]
    assert st["shards"][1]["failed"]

    info = svc.rebuild_shard(1)
    assert info["pages_written"] > 0
    st = svc.stats()
    assert st["replication"]["failed_shards"] == []
    assert st["shards"][1]["pages_l"] + st["shards"][1]["pages_h"] > 0
    got = svc.run(dfg, [3, 7, 11, 200], weights=weights, seed=42)["Result"]
    np.testing.assert_array_equal(want, got)


def test_service_fault_rpcs_need_replication():
    svc = HolisticGNNService(n_shards=2)
    with pytest.raises(RuntimeError):
        svc.fail_shard(0)
