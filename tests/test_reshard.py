"""Elastic online resharding: grow/shrink/rebalance under live reads
with bit-identity probed at every chunk boundary, write gating during
copy windows, mid-migration source failure at R=2, and the
changed-owner-pages-only byte accounting."""
import threading

import numpy as np
import pytest

from repro.store import (BlockDevice, DeviceFailedError, GraphStore,
                         LocalShardEndpoint, ShardedGraphStore,
                         sample_batch)
from repro.store.placement import modular


def _graph(n=360, e=2600, feat=16, seed=0):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, e), rng.zipf(1.4, e) % n],
                     axis=1).astype(np.int64)
    emb = rng.standard_normal((n, feat)).astype(np.float32)
    return edges, emb


def _pair(n_shards, *, replication=1, n=360, e=2600, feat=16):
    edges, emb = _graph(n, e, feat)
    ref = GraphStore(BlockDevice(), h_threshold=16)
    ref.update_graph(edges, emb)
    store = ShardedGraphStore(n_shards=n_shards, h_threshold=16,
                              replication=replication)
    store.update_graph(edges, emb)
    return ref, store, n


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


def _chunk_prober(ref, n):
    """on_progress callback asserting bit-identity at EVERY chunk
    boundary: a batched embedding read + adjacency spot checks against
    the single-device reference, issued from inside the migration."""
    probe_vids = np.arange(0, n, 7)
    ref_emb = ref.get_embeds(probe_vids)
    state = {"probes": 0, "flips": 0, "store": None}

    def cb(ev):
        st = state["store"]
        if ev.get("event") in ("chunk", "emb_chunk"):
            np.testing.assert_array_equal(st.get_embeds(probe_vids),
                                          ref_emb)
            for v in (int(probe_vids[1]), int(probe_vids[-1])):
                np.testing.assert_array_equal(st.get_neighbors(v),
                                              ref.get_neighbors(v))
            state["probes"] += 1
        elif ev.get("event") == "flip":
            state["flips"] += 1
    return cb, state


# --------------------------------------------------------------- grow/shrink
def test_grow_bit_identical_at_every_chunk_boundary():
    ref, store, n = _pair(4)
    cb, state = _chunk_prober(ref, n)
    state["store"] = store
    new_ep = LocalShardEndpoint(dev=BlockDevice(), h_threshold=16,
                                feature_dim=16)
    report = store.reshard(add=[new_ep], chunk_pages=8, on_progress=cb)
    assert state["probes"] > 0 and state["flips"] > 0
    assert store.n_shards == 5
    assert report["classes_moved"] > 0
    assert store.placement_stats()["epoch"] >= report["epochs"] > 0
    _assert_reads_equal(ref, store, n)


def test_shrink_bit_identical_at_every_chunk_boundary():
    ref, store, n = _pair(4)
    cb, state = _chunk_prober(ref, n)
    state["store"] = store
    report = store.reshard(remove=[3], chunk_pages=8, on_progress=cb)
    assert state["probes"] > 0
    assert store.n_shards == 3
    assert report["classes_moved"] > 0
    _assert_reads_equal(ref, store, n)
    # the drained endpoint is detached; survivors answer everything
    ps = store.placement_stats()
    assert not ps["resharding"] and ps["migrating_classes"] == []


def test_grow_ships_only_changed_owner_pages():
    """Byte accounting: a 4 -> 5 grow moves ~1/5 of the data, so the
    shipped bytes must be a small fraction of the resident bytes —
    never a full reload."""
    _, store, _ = _pair(4, n=500, e=4000, feat=32)
    resident = sum(int(ep.local_store.dev.stats.written_bytes)
                   for ep in store.endpoints)
    new_ep = LocalShardEndpoint(dev=BlockDevice(), h_threshold=16,
                                feature_dim=32)
    report = store.reshard(add=[new_ep], chunk_pages=16)
    assert 0 < report["bytes_shipped"] < 0.5 * resident
    assert report["bytes_shipped"] == (report["adj_bytes"]
                                       + report["emb_bytes"])


# ------------------------------------------------------------- write gating
def test_writes_during_migration_apply_exactly_once():
    """Mutations issued concurrently with the copy windows are gated per
    class and land exactly once — the final array equals serial replay
    of the same op log on one device."""
    edges, emb = _graph()
    n = emb.shape[0]
    store = ShardedGraphStore(n_shards=4, h_threshold=16)
    store.update_graph(edges, emb)
    new_ep = LocalShardEndpoint(dev=BlockDevice(), h_threshold=16,
                                feature_dim=16)

    report = {}

    def run():
        report.update(store.reshard(add=[new_ep], chunk_pages=4,
                                    pace_s=0.002))
    t = threading.Thread(target=run)
    t.start()
    rng = np.random.default_rng(3)
    log = []
    while t.is_alive():
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        store.add_edge(u, v)
        log.append(("edge", u, v))
        w = int(rng.integers(0, n))
        row = rng.standard_normal(emb.shape[1]).astype(np.float32)
        store.update_embed(w, row)
        log.append(("emb", w, row))
    t.join()
    assert report["classes_moved"] > 0 and log

    ref = GraphStore(BlockDevice(), h_threshold=16)
    ref.update_graph(edges, emb)
    for op in log:
        if op[0] == "edge":
            ref.add_edge(op[1], op[2])
        else:
            ref.update_embed(op[1], op[2])
    _assert_reads_equal(ref, store, n)


# ------------------------------------------------- mid-migration source kill
def test_source_failure_mid_migration_fails_over():
    """R=2: killing a copy source mid-stream must not abort the reshard —
    the destination re-pulls from the surviving replica and the array
    ends bit-identical (degraded), then heals by rebuild."""
    ref, store, n = _pair(3, replication=2)
    killed = {}

    def cb(ev):
        if ev.get("event") == "chunk" and not killed:
            row = store._routing.pmap.owner[int(ev["cls"])]
            srcs = [int(s) for s in row
                    if int(s) != int(ev["dst"]) and not store._failed[s]]
            if srcs:
                killed["shard"] = srcs[0]
                store.fail_shard(srcs[0])

    new_ep = LocalShardEndpoint(dev=BlockDevice(), h_threshold=16,
                                feature_dim=16)
    report = store.reshard(add=[new_ep], chunk_pages=4, on_progress=cb)
    assert "shard" in killed, "no chunk event fired before completion"
    assert report["classes_moved"] > 0
    assert store.n_shards == 4
    _assert_reads_equal(ref, store, n)           # degraded reads
    out = store.rebuild_shard(killed["shard"])
    assert out.get("rebuilt") or not store._failed[killed["shard"]]
    _assert_reads_equal(ref, store, n)           # healed reads


# ------------------------------------------------------------ heat rebalance
def test_heat_rebalance_moves_hot_classes_and_preserves_reads():
    ref, store, n = _pair(4, replication=1)
    hot = np.array([v for v in range(n) if v % 4 in (1, 2)])
    rng = np.random.default_rng(5)
    for _ in range(12):                          # accumulate skewed heat
        store.get_embeds(rng.choice(hot, 64))
    assert store.placement_stats()["heat_total"] > 0
    report = store.reshard(rebalance=True, refine=4, chunk_pages=16)
    assert report["classes_moved"] > 0
    ps = store.placement_stats()
    assert ps["n_classes"] == 16 and not ps["modular"]
    _assert_reads_equal(ref, store, n)


# ------------------------------------------------------------------ API edges
def test_reshard_mode_validation():
    _, store, _ = _pair(2, n=80, e=300)
    with pytest.raises(ValueError):
        store.reshard()
    with pytest.raises(ValueError):
        store.reshard(remove=[1], rebalance=True)
    with pytest.raises(ValueError):
        store.reshard(placement=modular(3))      # wrong shard count


def test_reshard_rejected_while_shard_failed():
    _, store, _ = _pair(3, replication=2, n=80, e=300)
    store.fail_shard(1)
    with pytest.raises(DeviceFailedError):
        store.reshard(rebalance=True)
    store.rebuild_shard(1)
    report = store.reshard(rebalance=True, refine=2)
    assert "reshard_rejected" not in report


def test_shrink_below_replication_rejected():
    _, store, _ = _pair(3, replication=2, n=80, e=300)
    with pytest.raises(ValueError):
        store.reshard(remove=[1, 2])             # 1 survivor < R=2
