"""Distributed device-side ingest: chunked bulk load bit-identity with
the monolithic path (pages AND reads, across N x R), BulkTimeline phase
accounting, raw-chunk-only coordinator traffic over real RoP links, and
the mutation firehose — windowed device-side batches whose reads are
bit-identical to serial unit-mutation replay, with typed write-side
admission control."""
import threading
import time

import numpy as np
import pytest

from repro.core.service import HolisticGNNService
from repro.rpc.queues import BackpressureError
from repro.store import (BlockDevice, GraphStore, MutationFirehose,
                         ShardedGraphStore, make_rop_endpoints)
from repro.store.blockdev import DeviceFailedError


def _graph(n=400, e=3000, feat=24, seed=0):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, e), rng.zipf(1.4, e) % n],
                     axis=1).astype(np.int64)
    emb = rng.standard_normal((n, feat)).astype(np.float32)
    return edges, emb


def _mk(n_shards, replication, **kw):
    kw.setdefault("h_threshold", 16)
    return ShardedGraphStore(n_shards=n_shards, replication=replication,
                             **kw)


def _shard_devs(store):
    return [ep.service.store.dev for ep in store.endpoints] \
        if hasattr(store.endpoints[0], "service") else None


# ------------------------------------------------------- bulk bit-identity
@pytest.mark.parametrize("n_shards,replication",
                         [(1, 1), (2, 1), (2, 2), (4, 1), (4, 2)])
def test_chunked_ingest_bit_identical_pages_and_reads(n_shards, replication):
    """Chunked distributed ingest lays the SAME pages as the monolithic
    coordinator-side preprocess — device arrays compare equal — and every
    read matches."""
    edges, emb = _graph()
    n = len(emb)
    a = _mk(n_shards, replication)
    b = _mk(n_shards, replication)
    a.update_graph(edges, emb)
    tl = b.update_graph_chunked(edges, emb, chunk_edges=500,
                                emb_chunk_rows=64)
    assert a.num_vertices == b.num_vertices == n
    for s in range(n_shards):
        pa = a.endpoints[s].service.store.dev._pages
        pb = b.endpoints[s].service.store.dev._pages
        np.testing.assert_array_equal(pa, pb, err_msg=f"shard {s}")
    rng = np.random.default_rng(3)
    vids = rng.integers(0, n + 10, 80)
    for va, vb in zip(a.get_neighbors_batch(vids),
                      b.get_neighbors_batch(vids)):
        np.testing.assert_array_equal(va, vb)
    known = vids[vids < n]
    np.testing.assert_array_equal(a.get_embeds(known), b.get_embeds(known))
    assert tl.total > 0.0


def test_chunked_ingest_no_embeddings_and_already_undirected():
    edges, _ = _graph(e=1200)
    mirrored = np.concatenate([edges, edges[:, ::-1]])
    a = _mk(2, 1)
    b = _mk(2, 1)
    a.update_graph(edges)
    b.update_graph_chunked(mirrored, already_undirected=True,
                           chunk_edges=300)
    assert a.to_adjacency() == b.to_adjacency()


def test_bulk_timeline_phases_populated():
    edges, emb = _graph()
    st = _mk(2, 1)
    tl = st.update_graph_chunked(edges, emb, chunk_edges=500,
                                 emb_chunk_rows=64)
    # transfer starts the load; graph_pre (exchange + device sort) follows;
    # the commit bursts close it out; user-visible excludes the graph tail
    assert tl.transfer[0] == 0.0 and tl.transfer[1] > 0.0
    assert tl.transfer[1] <= tl.graph_pre[0] <= tl.graph_pre[1]
    assert tl.write_feature[1] > tl.write_feature[0] >= tl.graph_pre[0]
    assert tl.write_graph[1] >= tl.write_feature[0]
    assert tl.total >= tl.user_visible > 0.0
    assert st._bulk is tl


def test_chunked_ingest_over_rop_links_raw_chunks_only():
    """Over real RoP endpoints the coordinator ships only raw edge chunks
    and embedding stripes: zero preprocessed write_adjacency /
    write_embedding_table commands, yet the pages are bit-identical to a
    local monolithic load."""
    edges, emb = _graph(n=256, e=1500, feat=8)
    ref = ShardedGraphStore(n_shards=2, h_threshold=16)
    ref.update_graph(edges, emb)
    eps = make_rop_endpoints(2, h_threshold=16, feature_dim=8)
    try:
        st = ShardedGraphStore(endpoints=eps)
        st.update_graph_chunked(edges, emb, chunk_edges=400,
                                emb_chunk_rows=64)
        for s, ep in enumerate(eps):
            np.testing.assert_array_equal(
                ref.endpoints[s].service.store.dev._pages,
                ep.host.service.store.dev._pages, err_msg=f"shard {s}")
            sent = ep.method_stats
            assert "write_adjacency" not in sent
            assert "write_embedding_table" not in sent
            assert sent["ingest_edges"].calls > 0
            assert sent["ingest_commit"].calls == 1
            assert ep.channel_bytes() > 0
        vids = np.arange(0, 256, 7)
        for va, vb in zip(ref.get_neighbors_batch(vids),
                          st.get_neighbors_batch(vids)):
            np.testing.assert_array_equal(va, vb)
    finally:
        for ep in eps:
            ep.close()


def test_chunked_ingest_rejects_failed_shard_and_aborts_sessions():
    edges, emb = _graph(e=1000)
    st = _mk(3, 2)
    st.update_graph(edges, emb)
    st.fail_shard(1)
    with pytest.raises(DeviceFailedError):
        st.update_graph_chunked(edges, emb)
    # sessions on the survivors were never opened / were aborted: a fresh
    # load on a healthy twin still works
    st2 = _mk(3, 2)
    st2.update_graph_chunked(edges, emb, chunk_edges=250)
    assert st2.num_vertices == len(emb)


def test_ingest_begin_rejects_nested_session():
    st = _mk(2, 1)
    ep = st.endpoints[0]
    ep.call("ingest_begin", shard=0, n_shards=2)
    with pytest.raises(RuntimeError):
        ep.call("ingest_begin", shard=0, n_shards=2)
    ep.call("ingest_abort")


# ------------------------------------------------------------- firehose
def _mixed_ops(n, feat, count, seed=1):
    rng = np.random.default_rng(seed)
    ops = []
    for _ in range(count):
        k = int(rng.integers(0, 5))
        if k == 0:
            ops.append(("add_edge", int(rng.integers(0, n)),
                        int(rng.integers(0, n))))
        elif k == 1:
            ops.append(("delete_edge", int(rng.integers(0, n)),
                        int(rng.integers(0, n))))
        elif k == 2:
            ops.append(("update_embed", int(rng.integers(0, n)),
                        rng.standard_normal(feat).astype(np.float32)))
        elif k == 3:
            ops.append(("add_vertex", int(rng.integers(0, n)),
                        rng.standard_normal(feat).astype(np.float32)))
        else:
            ops.append(("delete_vertex", int(rng.integers(0, n))))
    return ops


@pytest.mark.parametrize("replication", [1, 2])
def test_firehose_reads_bit_identical_to_serial_replay(replication):
    """Mid-stream reads at any flush boundary match a twin store applying
    the identical ops one unit mutation at a time — including the
    delete_vertex barrier and replica fan-out accounting."""
    edges, emb = _graph(n=300, e=2000, feat=16)
    n = 300
    a = _mk(3, replication)     # serial unit-mutation replay
    b = _mk(3, replication)     # firehose windows
    a.update_graph(edges, emb)
    b.update_graph(edges, emb)
    fh = MutationFirehose(b, max_window_ops=32)
    rng = np.random.default_rng(7)
    for i, op in enumerate(_mixed_ops(n, 16, 260)):
        getattr(a, op[0])(*op[1:])
        getattr(fh, op[0])(*op[1:])
        if i % 57 == 0:
            fh.flush()
            vids = rng.integers(0, n, 32)
            for va, vb in zip(a.get_neighbors_batch(vids),
                              b.get_neighbors_batch(vids)):
                np.testing.assert_array_equal(va, vb)
            np.testing.assert_array_equal(a.get_embeds(vids),
                                          b.get_embeds(vids))
    snap = fh.close()
    assert a.to_adjacency() == b.to_adjacency()
    assert a.num_vertices == b.num_vertices
    assert a.stats.unit_updates == b.stats.unit_updates
    assert snap["applied"] == snap["submitted"] == 260
    assert snap["log_depth"] == 0
    assert snap["windows"] > 1 and snap["barriers"] > 0
    assert snap["subops"] >= snap["applied"]


def test_firehose_single_device_serial_fallback():
    edges, emb = _graph(n=200, e=1200, feat=8)
    a = GraphStore(BlockDevice(), h_threshold=16)
    b = GraphStore(BlockDevice(), h_threshold=16)
    a.update_graph(edges, emb)
    b.update_graph(edges, emb)
    fh = MutationFirehose(b, max_window_ops=16)
    for op in _mixed_ops(200, 8, 120, seed=5):
        getattr(a, op[0])(*op[1:])
        getattr(fh, op[0])(*op[1:])
    fh.close()
    assert a.to_adjacency() == b.to_adjacency()
    np.testing.assert_array_equal(a.dev._pages, b.dev._pages)


def test_firehose_sheds_typed_backpressure_when_log_full():
    edges, emb = _graph(e=500)
    st = _mk(2, 1)
    st.update_graph(edges, emb)
    fh = MutationFirehose(st, max_log_ops=4)
    for i in range(4):
        fh.add_edge(i, i + 1)
    with pytest.raises(BackpressureError) as ei:
        fh.add_edge(9, 9)
    assert ei.value.reason["source"] == "firehose_log"
    assert ei.value.reason["limit"] == 4
    assert fh.snapshot()["shed"] == 1
    fh.flush()                  # drains, admission recovers
    fh.add_edge(9, 9)
    fh.close()


def test_firehose_window_timer_applies_in_background():
    edges, emb = _graph(e=800)
    st = _mk(2, 1)
    st.update_graph(edges, emb)
    fh = st.firehose(window_s=0.01).start()
    try:
        for i in range(40):
            fh.add_edge(i % 50, (i * 7) % 50)
        deadline = time.monotonic() + 5.0
        while fh.snapshot()["applied"] < 40:
            assert time.monotonic() < deadline, fh.snapshot()
            time.sleep(0.01)
        assert fh.last_error is None
    finally:
        snap = fh.close()
    assert snap["applied"] == 40 and snap["log_depth"] == 0


def test_firehose_rejects_bad_embed_row_at_submission():
    edges, emb = _graph(e=500)
    st = _mk(2, 2)
    st.update_graph(edges, emb)
    fh = MutationFirehose(st)
    with pytest.raises(KeyError):
        fh.update_embed(len(emb) + 100,
                        np.zeros(emb.shape[1], dtype=np.float32))
    assert fh.snapshot()["submitted"] == 0     # nothing poisoned the log
    fh.close()


def test_firehose_concurrent_readers_see_consistent_windows():
    """Reads racing the window timer always observe a window boundary:
    every observed neighbor list is one the serial-replay twin passes
    through."""
    edges, emb = _graph(n=200, e=1500, feat=8)
    st = _mk(2, 1)
    st.update_graph(edges, emb)
    fh = st.firehose(window_s=0.002, max_window_ops=8).start()
    stop = threading.Event()
    errs = []

    def reader():
        rng = np.random.default_rng(11)
        while not stop.is_set():
            vids = rng.integers(0, 200, 16)
            try:
                outs = st.get_neighbors_batch(vids)
                assert len(outs) == 16
            except Exception as e:  # noqa: BLE001
                errs.append(e)
                return

    th = threading.Thread(target=reader, daemon=True)
    th.start()
    try:
        for i in range(200):
            fh.add_edge(i % 200, (i * 13) % 200)
            if i % 50 == 0:
                time.sleep(0.005)
    finally:
        snap = fh.close()
        stop.set()
        th.join(timeout=5.0)
    assert not errs
    assert snap["applied"] == 200


# ------------------------------------------------------- service plumbing
def test_service_update_graph_chunked_and_already_undirected():
    edges, emb = _graph(n=300, e=2000, feat=16)
    ref = HolisticGNNService(h_threshold=16, n_shards=2)
    ref.update_graph(edges, emb)
    svc = HolisticGNNService(h_threshold=16, n_shards=2)
    out = svc.update_graph(edges, emb, chunked=True, chunk_edges=400)
    assert out["total_s"] > 0
    assert ref.store.to_adjacency() == svc.store.to_adjacency()
    # pre-mirrored input with already_undirected=True lands identically
    mirrored = np.concatenate([edges, edges[:, ::-1]])
    svc2 = HolisticGNNService(h_threshold=16, n_shards=2)
    svc2.update_graph(mirrored, emb, already_undirected=True,
                      chunked=True, chunk_edges=400)
    assert ref.store.to_adjacency() == svc2.store.to_adjacency()
    # single-device stores fall back to the monolithic path
    solo = HolisticGNNService(h_threshold=16)
    solo.update_graph(edges, emb, chunked=True)
    assert ref.store.to_adjacency() == solo.store.to_adjacency()


def test_service_firehose_rpcs_route_unit_mutations():
    edges, emb = _graph(n=200, e=1200, feat=8)
    svc = HolisticGNNService(h_threshold=16, n_shards=2)
    svc.update_graph(edges, emb)
    ref = HolisticGNNService(h_threshold=16, n_shards=2)
    ref.update_graph(edges, emb)
    svc.open_firehose(window_s=60.0)       # timer effectively off
    with pytest.raises(RuntimeError):
        svc.open_firehose()
    ops = _mixed_ops(200, 8, 60, seed=9)
    for op in ops:
        getattr(ref, op[0])(*op[1:])
        getattr(svc, op[0])(*op[1:])
    st = svc.stats()
    assert st["firehose"]["submitted"] == 60
    out = svc.flush_firehose()
    assert out["applied_now"] + out["barriers"] >= 0
    snap = svc.close_firehose()
    assert snap["applied"] == 60
    assert svc.firehose is None
    assert ref.store.to_adjacency() == svc.store.to_adjacency()
    # after close, unit mutations hit the store directly again
    svc.add_edge(1, 2)
    ref.add_edge(1, 2)
    assert ref.store.to_adjacency() == svc.store.to_adjacency()
