"""ShardEndpoint protocol: a remote (RoP) array is bit-identical to the
in-process array — healthy, degraded, and post-rebuild — per-shard RPC
count stays O(1) per batched read, rebuild streams shard-to-shard without
coordinator-side page materialization, and the replica-selection feedback
consumes a gossiped, staleness-bounded counter snapshot."""
import numpy as np
import pytest

from repro.core import gnn
from repro.core.service import HolisticGNNService, make_service_dfg
from repro.store import (BlockDevice, DeviceFailedError, GraphStore,
                         ShardedGraphStore, make_rop_endpoints,
                         sample_batch)
from repro.store.graphstore import PLAN_FIELDS, select_from_plan


def _graph(n=400, e=3000, feat=24, seed=0):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, e), rng.zipf(1.4, e) % n],
                     axis=1).astype(np.int64)
    emb = rng.standard_normal((n, feat)).astype(np.float32)
    return edges, emb


def _single(h_threshold=16, **kw):
    edges, emb = _graph(**kw)
    single = GraphStore(BlockDevice(), h_threshold=h_threshold)
    single.update_graph(edges, emb)
    return single, edges, emb


def _remote(n_shards, replication=1, *, h_threshold=16, edges=None,
            emb=None, **store_kw):
    eps = make_rop_endpoints(n_shards, h_threshold=h_threshold)
    store = ShardedGraphStore(endpoints=eps, replication=replication,
                              h_threshold=h_threshold, **store_kw)
    if edges is not None:
        store.update_graph(edges, emb)
    return store


def _assert_reads_match(single, store, n, seed=3):
    rng = np.random.default_rng(seed)
    vids = rng.integers(0, n + 20, 70)           # includes unknown vids
    for a, b in zip(single.get_neighbors_batch(vids),
                    store.get_neighbors_batch(vids)):
        np.testing.assert_array_equal(a, b)
    known = vids[vids < n]
    np.testing.assert_array_equal(single.get_embeds(known),
                                  store.get_embeds(known))
    targets = rng.integers(0, n, 12)
    a = sample_batch(single, targets, [5, 5], rng=np.random.default_rng(9))
    b = sample_batch(store, targets, [5, 5], rng=np.random.default_rng(9))
    np.testing.assert_array_equal(a.node_vids, b.node_vids)
    for la, lb in zip(a.layers, b.layers):
        np.testing.assert_array_equal(la.nbr, lb.nbr)
        np.testing.assert_array_equal(la.mask, lb.mask)
    np.testing.assert_array_equal(a.embeddings, b.embeddings)


# ------------------------------------------------------------ array plans
def test_array_plan_crosses_wire_unchanged():
    """A shard's fetch plan is int64 arrays: it crosses the RoP wire as
    payload buffers, field for field, and selects the same sample as the
    in-process plan and the single device."""
    single, edges, emb = _single(h_threshold=4)
    store = _remote(1, edges=edges, emb=emb, h_threshold=4)
    try:
        vids = np.random.default_rng(5).integers(0, 420, 90)  # + unknown
        ep = store.endpoints[0]
        wire = ep.fetch_result(ep.fetch_submit(l_vids=vids))
        local = ep.host.service.fetch(l_vids=vids)
        np.testing.assert_array_equal(wire["block"], local["block"])
        assert set(wire["plan"]) == set(PLAN_FIELDS)
        assert len(local["plan"]["h_idx"]) and len(local["plan"]["l_idx"])
        for k in PLAN_FIELDS:
            assert wire["plan"][k].dtype == np.int64, k
            np.testing.assert_array_equal(wire["plan"][k],
                                          local["plan"][k], err_msg=k)
        want = single.sample_neighbors_batch(vids, 3,
                                             np.random.default_rng(2))
        for got in (select_from_plan(vids, wire["block"], wire["plan"], 3,
                                     np.random.default_rng(2)),
                    select_from_plan(vids, local["block"], local["plan"],
                                     3, np.random.default_rng(2))):
            np.testing.assert_array_equal(got[0], want[0])
            np.testing.assert_array_equal(got[1], want[1])
    finally:
        store.close()


# ------------------------------------------------------- remote bit-identity
@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_remote_bit_identical_healthy(n_shards):
    single, edges, emb = _single()
    store = _remote(n_shards, edges=edges, emb=emb)
    try:
        _assert_reads_match(single, store, 400)
    finally:
        store.close()


@pytest.mark.parametrize("n_shards", [2, 4])
def test_remote_replicated_degraded_and_rebuilt(n_shards):
    """R=2 remote array: healthy, degraded under every single-shard
    failure, and post-rebuild reads all bit-identical to one device."""
    single, edges, emb = _single()
    store = _remote(n_shards, replication=2, edges=edges, emb=emb)
    try:
        _assert_reads_match(single, store, 400)
        for s in range(n_shards):
            store.fail_shard(s)
            _assert_reads_match(single, store, 400, seed=10 + s)
            info = store.rebuild_shard(s)
            assert info["pages_written"] > 0
            assert not any(store.failed_shards)
            _assert_reads_match(single, store, 400, seed=20 + s)
    finally:
        store.close()


def test_remote_mutations_match_single_device_twin():
    single, edges, emb = _single()
    store = _remote(3, replication=2, edges=edges, emb=emb)
    n = 400
    try:
        rng = np.random.default_rng(11)
        for _ in range(60):
            op = rng.integers(0, 5)
            a, b = int(rng.integers(0, n)), int(rng.integers(0, n))
            if op == 0:
                single.add_edge(a, b), store.add_edge(a, b)
            elif op == 1:
                single.delete_edge(a, b), store.delete_edge(a, b)
            elif op == 2:
                v = n + int(rng.integers(0, 40))
                single.add_vertex(v), store.add_vertex(v)
            elif op == 3:
                row = rng.standard_normal(24).astype(np.float32)
                single.update_embed(a, row), store.update_embed(a, row)
            else:
                single.delete_vertex(a), store.delete_vertex(a)
        assert single.to_adjacency() == store.to_adjacency()
        _assert_reads_match(single, store, n, seed=40)
    finally:
        store.close()


def test_remote_run_bit_identical_service():
    edges, emb = _graph(n=600, e=5000, feat=32)
    ref = HolisticGNNService(h_threshold=16, pad_to=32)
    ref.store.update_graph(edges, emb)
    svc = HolisticGNNService(h_threshold=16, pad_to=32,
                             endpoints=make_rop_endpoints(2, h_threshold=16),
                             cache_pages=512)
    try:
        svc.store.update_graph(edges, emb)
        dfg = make_service_dfg("gcn", 2, [5, 5]).save()
        params = gnn.init_params("gcn", [32, 16, 8], seed=1)
        weights = {k: v for k, v in
                   gnn.dfg_feeds("gcn", params, None, []).items()
                   if k != "H"}
        want = ref.run(dfg, [3, 7, 11, 200], weights=weights,
                       seed=42)["Result"]
        got = svc.run(dfg, [3, 7, 11, 200], weights=weights,
                      seed=42)["Result"]
        np.testing.assert_array_equal(want, got)
        reqs = [{"targets": [3, 7], "seed": 1},
                {"targets": [9, 20, 31], "seed": 2}]
        for a, b in zip(ref.run_batch(dfg, reqs, weights=weights),
                        svc.run_batch(dfg, reqs, weights=weights)):
            np.testing.assert_array_equal(a["Result"], b["Result"])
    finally:
        svc.close()


# ----------------------------------------------------------- RPC accounting
def test_rpc_count_o1_per_batched_read():
    """One ``fetch`` command per shard per batched read — independent of
    how many vids (and pages) the read covers."""
    _, edges, emb = _single()
    store = _remote(2, edges=edges, emb=emb)
    try:
        def fetch_calls():
            return [ep.client.method_stats["fetch"].calls
                    if "fetch" in ep.client.method_stats else 0
                    for ep in store.endpoints]

        per_batch = []
        for b in (8, 64, 256):
            vids = np.random.default_rng(1).integers(0, 400, b)
            calls0 = fetch_calls()
            store.get_neighbors_batch(vids)
            store.get_embeds(vids % 400)
            calls1 = fetch_calls()
            per_batch.append([c1 - c0 for c0, c1 in zip(calls0, calls1)])
        # 2 batched reads -> exactly 2 fetch commands per shard, at any size
        assert all(all(c == 2 for c in row) for row in per_batch), per_batch
    finally:
        store.close()


def test_rebuild_streams_shard_to_shard():
    """The coordinator link carries plan + summary only; survivor pages
    move over the peer links straight into the replacement shard."""
    _, edges, emb = _single()
    store = _remote(3, replication=2, edges=edges, emb=emb)
    try:
        victim = 1
        store.fail_shard(victim)
        coord0 = store.endpoints[victim].channel_bytes()
        info = store.rebuild_shard(victim)
        coord_bytes = store.endpoints[victim].channel_bytes() - coord0
        page_bytes = int(info["pages_written"]) * 4096
        assert page_bytes > 0
        assert coord_bytes < 65536, coord_bytes
        assert page_bytes > 4 * coord_bytes, (coord_bytes, page_bytes)
    finally:
        store.close()


def test_failed_fetch_reaps_outstanding_handles():
    """When one shard's fetch fails mid-await (the drain path), the
    other shards' completions must still be reaped — otherwise every
    failover retry leaks full reply payloads in the RoP CQs."""
    _, edges, emb = _single()
    store = _remote(2, replication=2, edges=edges, emb=emb)
    try:
        store.endpoints[0].call("fail")      # device dies under the array
        with pytest.raises(DeviceFailedError):
            # shard 0 first: its result raises; shard 1's completion is
            # outstanding at that moment and must be drained
            store._endpoint_fetch([(0, {"emb_rows": np.arange(8)}),
                                   (1, {"emb_rows": np.arange(8)})])
        for ep in store.endpoints:
            assert not ep.client._pending, ep.client._pending
            for pair in ep.host.rop.pairs:
                assert not pair.cq, pair.cq
    finally:
        store.close()


# ------------------------------------------------------------ gossip loop
def test_gossip_staleness_bounds_counter_pulls():
    _, edges, emb = _single()
    # staleness 0: every selection refreshes the counter snapshot
    eager = ShardedGraphStore(n_shards=2, replication=2, h_threshold=16,
                              stats_staleness_s=0.0)
    eager.update_graph(edges, emb)
    p0 = eager.gossip_pulls
    for _ in range(5):
        eager.get_embeds(np.arange(40))
    assert eager.gossip_pulls - p0 >= 5
    # large staleness bound: the cached snapshot serves every selection
    lazy = ShardedGraphStore(n_shards=2, replication=2, h_threshold=16,
                             stats_staleness_s=60.0)
    lazy.update_graph(edges, emb)
    p0 = lazy.gossip_pulls
    for _ in range(5):
        lazy.get_embeds(np.arange(40))
    assert lazy.gossip_pulls - p0 == 0
    # and the stale view never changes results, only device attribution
    np.testing.assert_array_equal(eager.get_embeds(np.arange(60)),
                                  lazy.get_embeds(np.arange(60)))


# ------------------------------------------------------------ error mapping
def test_device_failure_maps_to_typed_error_across_rop():
    _, edges, emb = _single()
    store = _remote(2, replication=2, edges=edges, emb=emb)
    try:
        store.fail_shard(0)
        with pytest.raises(DeviceFailedError):
            store.endpoints[0].call("get_neighbors", vid=0)
        # reads keep working through the failover path
        assert len(store.get_neighbors(0)) >= 0
    finally:
        store.close()


# ------------------------------------------------------------- stats parity
def test_stats_report_identical_shape_local_vs_remote():
    edges, emb = _graph(n=300, e=2000, feat=24)
    local = HolisticGNNService(h_threshold=16, pad_to=32, n_shards=2,
                               cache_pages=256)
    local.store.update_graph(edges, emb)
    remote = HolisticGNNService(h_threshold=16, pad_to=32,
                                endpoints=make_rop_endpoints(
                                    2, h_threshold=16),
                                cache_pages=256)
    try:
        remote.store.update_graph(edges, emb)
        vids = np.arange(24)
        local.store.get_embeds(vids)
        remote.store.get_embeds(vids)
        a, b = local.stats(), remote.stats()
        assert set(a) == set(b)
        assert set(a["store"]) == set(b["store"])
        assert a["store"]["pages_l"] == b["store"]["pages_l"]
        assert a["store"]["pages_h"] == b["store"]["pages_h"]
        assert a["device"]["read_pages"] == b["device"]["read_pages"]
        for sa, sb in zip(a["shards"], b["shards"]):
            assert set(sa) == set(sb)
            assert sa["device"] == sb["device"]
            # both endpoint flavours report per-method RPC stats
            assert set(sa["rpc"]) == set(sb["rpc"])
            assert sa["embcache"]["hits"] == sb["embcache"]["hits"]
        assert a["embcache"] == b["embcache"]
    finally:
        remote.close()
