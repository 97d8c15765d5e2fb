"""Autonomic array runtime: supervisor state machine (suspect/decay,
burst-drain policy, refused drains, auto-rebuild), chaos kills with NO
operator involvement (auto-detect -> auto-drain -> auto-rebuild ->
bit-identical), failover races against in-flight fetches and streaming
rebuilds, and the end-to-end flow-control path (queue-full reap+retry,
in-flight window shedding, typed backpressure reasons, reasoned
admission errors)."""
import threading
import time

import numpy as np
import pytest

from repro.core import gnn
from repro.core.service import HolisticGNNService, make_service_dfg
from repro.rpc.queues import BackpressureError, QueueFullError
from repro.serve import (AdmissionError, BatchScheduler, HealthPolicy,
                         ServingRuntime, ShardSupervisor)
from repro.store import (BlockDevice, DeviceFailedError, GraphStore,
                         ShardedGraphStore, make_local_endpoints,
                         make_rop_endpoints, sample_batch)
from repro.store.sharded import FlowControl


def _graph(n=240, e=1600, feat=12, seed=0):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, e), rng.zipf(1.4, e) % n],
                     axis=1).astype(np.int64)
    emb = rng.standard_normal((n, feat)).astype(np.float32)
    return edges, emb


def _pair(n_shards=3, replication=2, *, remote=False, flow=None,
          h_threshold=16, n=240):
    edges, emb = _graph(n)
    single = GraphStore(BlockDevice(), h_threshold=h_threshold)
    single.update_graph(edges, emb)
    eps = (make_rop_endpoints(n_shards, h_threshold=h_threshold) if remote
           else None)
    rep = ShardedGraphStore(n_shards=None if eps else n_shards,
                            endpoints=eps, replication=replication,
                            h_threshold=h_threshold, flow=flow)
    rep.update_graph(edges, emb)
    return single, rep, n


def _kill_device(rep, s):
    """Kill the shard's DEVICE directly — the chaos path: no fail_shard
    operator RPC, the array must notice on its own."""
    ep = rep.endpoints[s]
    if hasattr(ep, "local_store"):
        ep.local_store.dev.fail()
    else:
        ep.host.service.store.dev.fail()


def _ref_samples(single, n, k=6):
    out = []
    for i in range(k):
        rng = np.random.default_rng(100 + i)
        t = rng.integers(0, n, 8)
        b = sample_batch(single, t, [4, 4], rng=np.random.default_rng(i))
        out.append((t, i, b))
    return out


def _assert_batch_equal(a, b):
    np.testing.assert_array_equal(a.node_vids, b.node_vids)
    for la, lb in zip(a.layers, b.layers):
        np.testing.assert_array_equal(la.nbr, lb.nbr)
        np.testing.assert_array_equal(la.mask, lb.mask)
    np.testing.assert_array_equal(a.embeddings, b.embeddings)


def _wait_healthy(sup, rep, deadline_s=20.0):
    t_end = time.monotonic() + deadline_s
    while time.monotonic() < t_end:
        snap = sup.snapshot()
        if (snap["incidents"] and not any(rep.failed_shards)
                and all(st == "healthy" for st in snap["states"])):
            return snap
        time.sleep(0.01)
    raise AssertionError(f"array did not heal: {sup.snapshot()}")


# ------------------------------------------------------- policy state machine
def test_one_error_is_suspect_not_drain():
    _, rep, _ = _pair()
    sup = ShardSupervisor(rep, HealthPolicy(auto_rebuild=False))
    rep.health = sup                       # attach without the monitor
    sup.record_error(1, DeviceFailedError("blip"))
    assert sup.state_of(1) == "suspect"
    assert not rep.failed_shards[1]        # a single blip never drains
    assert sup.suspect_shards() == [1]
    rep.close()


def test_error_burst_drains_within_policy_window():
    _, rep, _ = _pair()
    sup = ShardSupervisor(rep, HealthPolicy(error_threshold=3, window_s=1.0,
                                            auto_rebuild=False))
    rep.health = sup
    for _ in range(2):
        sup.record_error(1, DeviceFailedError("x"))
        assert sup.state_of(1) == "suspect"
    sup.record_error(1, DeviceFailedError("x"))    # 3rd inside the window
    assert sup.state_of(1) == "failed"
    assert rep.failed_shards[1]
    snap = sup.snapshot()
    assert snap["incidents"] == 1
    inc = snap["last_incident"]
    assert inc["cause"] == "error_burst" and inc["drained"] is True
    assert inc["refused"] is None and inc["degraded_classes"]
    # further errors against a failed shard are no-ops, not new incidents
    sup.record_error(1, DeviceFailedError("x"))
    assert sup.snapshot()["incidents"] == 1
    rep.close()


def test_suspect_decays_back_to_healthy():
    _, rep, _ = _pair()
    sup = ShardSupervisor(rep, HealthPolicy(suspect_decay_s=0.05,
                                            probe_interval_s=0.01,
                                            auto_rebuild=False)).start()
    try:
        sup.record_error(0, DeviceFailedError("blip"))
        assert sup.state_of(0) == "suspect"
        t_end = time.monotonic() + 5.0
        while sup.state_of(0) != "healthy" and time.monotonic() < t_end:
            time.sleep(0.01)
        assert sup.state_of(0) == "healthy"
        assert sup.suspect_shards() == []
    finally:
        sup.stop()
        rep.close()


def test_refused_drain_is_terminal_not_a_loop():
    """Draining the LAST live replica of a class is data loss: the
    supervisor records the refusal and does NOT schedule a rebuild."""
    _, rep, _ = _pair()
    rep.fail_shard(0)                      # operator predecessor
    sup = ShardSupervisor(rep, HealthPolicy(error_threshold=2,
                                            auto_rebuild=False))
    rep.health = sup
    assert sup.state_of(0) == "failed"     # adopted at attach
    for _ in range(2):
        sup.record_error(1, DeviceFailedError("x"))
    snap = sup.snapshot()
    assert snap["states"][1] == "failed"
    assert snap["drained"][1] is False     # refused: not actually drained
    assert snap["last_incident"]["refused"] is not None
    assert not rep.failed_shards[1]        # store still serves from it
    rep.close()


def test_suspect_shard_steered_away_from():
    """Replica selection must avoid a supervisor-suspect shard while every
    class still has another live candidate."""
    _, rep, n = _pair(3, 2)
    sup = ShardSupervisor(rep, HealthPolicy(auto_rebuild=False))
    rep.health = sup
    sup.record_error(1, DeviceFailedError("blip"))
    reads0 = rep.shards[1].dev.stats.read_pages
    rng = np.random.default_rng(5)
    for _ in range(6):
        rep.get_embeds(rng.integers(0, n, 40))
    assert rep.shards[1].dev.stats.read_pages == reads0
    rep.close()


# ------------------------------------------------------------ chaos, no hands
@pytest.mark.parametrize("remote", [False, True])
def test_chaos_kill_auto_detect_drain_rebuild_bit_identical(remote):
    """Device dies with NO operator call: degraded reads stay bit-identical
    immediately, the supervisor detects + drains + rebuilds on its own,
    and post-rebuild reads are bit-identical at full redundancy."""
    single, rep, n = _pair(remote=remote)
    refs = _ref_samples(single, n)
    sup = ShardSupervisor(rep, HealthPolicy(probe_interval_s=0.005,
                                            rebuild_retry_s=0.05)).start()
    try:
        _kill_device(rep, 2)
        t, seed, ref = refs[0]             # in-flight-era degraded read
        _assert_batch_equal(ref, sample_batch(
            rep, t, [4, 4], rng=np.random.default_rng(seed)))
        snap = _wait_healthy(sup, rep)
        inc = snap["last_incident"]
        assert inc["shard"] == 2
        assert inc["cause"] in ("probe", "error_burst", "observed_drained")
        assert inc["drained"] is True and inc["refused"] is None
        assert inc["detect_s"] <= 5.0 and "restore_s" in inc
        for t, seed, ref in refs:          # full-redundancy reads
            _assert_batch_equal(ref, sample_batch(
                rep, t, [4, 4], rng=np.random.default_rng(seed)))
    finally:
        sup.stop()
        rep.close()


@pytest.mark.parametrize("remote", [False, True])
def test_kill_while_fetches_in_flight(remote):
    """Reader threads keep fetching while a device dies underneath them:
    every read stays bit-identical (failover) and the array heals."""
    single, rep, n = _pair(remote=remote)
    refs = _ref_samples(single, n, k=4)
    sup = ShardSupervisor(rep, HealthPolicy(probe_interval_s=0.005,
                                            rebuild_retry_s=0.05)).start()
    stop, errs = threading.Event(), []

    def reader(tid):
        while not stop.is_set():
            for t, seed, ref in refs:
                try:
                    _assert_batch_equal(ref, sample_batch(
                        rep, t, [4, 4], rng=np.random.default_rng(seed)))
                except Exception as e:  # noqa: BLE001 — collected
                    errs.append(f"reader{tid}: {type(e).__name__}: {e}")
                    return

    threads = [threading.Thread(target=reader, args=(i,)) for i in range(2)]
    for th in threads:
        th.start()
    try:
        time.sleep(0.05)                   # fetches in flight
        _kill_device(rep, 1)
        _wait_healthy(sup, rep)
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=30.0)
        sup.stop()
    assert not errs, errs
    rep.close()


def test_kill_bystander_during_paced_rebuild_single_fault():
    """N=4 R=2: while shard 0's paced rebuild streams, a shard that is
    neither rebuild target nor donor dies.  The error-path detection
    (record_error -> suspect steering -> burst drain) keeps reads
    bit-identical throughout — ``fail_shard`` runs under the mutate lock
    only, so the drain lands WHILE the rebuild holds the maintenance
    gate — the rebuild completes, and the second fault rebuilds cleanly
    afterwards."""
    single, rep, n = _pair(4, 2)
    refs = _ref_samples(single, n, k=3)
    rep.fail_shard(0)
    sup = ShardSupervisor(rep, HealthPolicy(auto_rebuild=False))
    rep.health = sup                       # error path only, no monitor
    out = {}

    def run_rebuild():
        out["info"] = rep.rebuild_shard(0, pacing_s=0.03)

    th = threading.Thread(target=run_rebuild)
    th.start()
    time.sleep(0.02)                       # rebuild mid-stream
    _kill_device(rep, 2)                   # classes {1, 2}: survivors live
    for t, seed, ref in refs:              # reads flow during the stream
        _assert_batch_equal(ref, sample_batch(
            rep, t, [4, 4], rng=np.random.default_rng(seed)))
    th.join(timeout=60.0)
    assert out["info"]["pages_written"] > 0
    # one error marked the shard suspect and steering kept every later
    # read off it — exactly the blip policy: no burst, no drain yet
    assert sup.state_of(2) in ("suspect", "failed")
    if not rep.failed_shards[2]:
        rep.fail_shard(2)                  # drain (monitor would, via probe)
    rep.rebuild_shard(2)
    assert not any(rep.failed_shards)
    for t, seed, ref in refs:
        _assert_batch_equal(ref, sample_batch(
            rep, t, [4, 4], rng=np.random.default_rng(seed)))
    rep.close()


def test_kill_donor_during_rebuild_double_fault_raises_cleanly():
    """N=3 R=2: the rebuild's donor dies mid-stream — that class has lost
    both replicas.  The rebuild fails with an exception (no wedge, no
    silent partial state) and reads of the lost class raise
    ``DeviceFailedError`` instead of returning wrong data."""
    _, rep, n = _pair(3, 2)
    rep.fail_shard(0)
    out = {}

    def run_rebuild():
        try:
            out["info"] = rep.rebuild_shard(0, pacing_s=0.05)
        except Exception as e:  # noqa: BLE001 — the expected double fault
            out["err"] = e

    th = threading.Thread(target=run_rebuild)
    th.start()
    time.sleep(0.02)
    _kill_device(rep, 1)                   # donor for class 0 dies
    th.join(timeout=60.0)
    assert "err" in out, f"double-fault rebuild returned {out.get('info')}"
    assert rep.failed_shards[0]            # target still marked failed
    with pytest.raises(DeviceFailedError):
        rep.get_embeds(np.arange(60))      # lost class: clean error
    rep.close()


# --------------------------------------------------------------- idempotency
def test_fault_rpcs_idempotent_status_dicts():
    _, rep, _ = _pair()
    assert rep.rebuild_shard(1) == {"shard": 1, "already_live": True}
    info = rep.fail_shard(1)
    assert info["shard"] == 1 and info["degraded_classes"]
    assert rep.fail_shard(1) == {"shard": 1, "already_failed": True}
    out = {}

    def run_rebuild():
        out["info"] = rep.rebuild_shard(1, pacing_s=0.05)

    th = threading.Thread(target=run_rebuild)
    th.start()
    time.sleep(0.02)                       # stream in progress
    assert rep.rebuild_shard(1) == {"shard": 1, "rebuild_in_progress": True}
    th.join(timeout=60.0)
    assert out["info"]["pages_written"] > 0
    assert not any(rep.failed_shards)
    rep.close()


# -------------------------------------------------------------- flow control
class _CountingEp:
    """Wrapper asserting handle hygiene: every submitted call handle must
    be consumed (result or reap) — no completions left in the CQ."""

    def __init__(self, inner):
        self._inner = inner
        self.submitted = 0
        self.consumed = 0

    def call_submit(self, method, **kw):
        h = self._inner.call_submit(method, **kw)
        self.submitted += 1
        return h

    def call_result(self, h):
        self.consumed += 1
        return self._inner.call_result(h)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _FlakyEp(_CountingEp):
    """Raises ``QueueFullError`` for the first ``fail_submits`` call
    submits, then behaves."""

    def __init__(self, inner, fail_submits):
        super().__init__(inner)
        self._fail_left = fail_submits

    def call_submit(self, method, **kw):
        if self._fail_left > 0:
            self._fail_left -= 1
            raise QueueFullError("synthetic SQ full", qid=0, depth=64)
        return super().call_submit(method, **kw)


def _flaky_store(fail_submits, retries=2):
    edges, emb = _graph()
    st = ShardedGraphStore(
        n_shards=3, h_threshold=16,
        flow=FlowControl(submit_retries=retries, backoff_base_s=1e-4,
                         backoff_max_s=1e-3))
    st.update_graph(edges, emb)
    st.endpoints = [_CountingEp(st.endpoints[0]),
                    _FlakyEp(st.endpoints[1], fail_submits),
                    _CountingEp(st.endpoints[2])]
    return st


def test_submit_round_queue_full_reaps_and_retries():
    """A QueueFullError part-way through a multi-shard round: handles
    already issued are reaped, the FULL set retries after backoff, and
    the round completes — with zero leaked completions."""
    st = _flaky_store(fail_submits=2)
    outs = st._submit_round([(s, "counters", {}) for s in range(3)])
    assert len(outs) == 3 and all("read_pages" in o for o in outs)
    assert st.backpressure_retries == 2 and st.backpressure_events == 0
    for ep in st.endpoints:
        assert ep.submitted == ep.consumed, \
            f"leaked call handles: {ep.submitted} != {ep.consumed}"
    # shard 0 was submitted on every attempt: 2 aborted + 1 good
    assert st.endpoints[0].submitted == 3
    st.close()


def test_submit_round_queue_full_exhausted_sheds_typed():
    st = _flaky_store(fail_submits=99, retries=2)
    with pytest.raises(BackpressureError) as ei:
        st._submit_round([(s, "counters", {}) for s in range(3)])
    r = ei.value.reason
    assert r["source"] == "queue_full" and r["shard"] == 1
    assert r["attempts"] == 3 and r["qid"] == 0
    assert st.backpressure_events == 1 and st.backpressure_retries == 2
    for ep in st.endpoints:
        assert ep.submitted == ep.consumed
    st.close()


def test_inflight_window_sheds_typed_backpressure():
    edges, emb = _graph()
    st = ShardedGraphStore(
        n_shards=2, h_threshold=16,
        flow=FlowControl(max_inflight_per_shard=1, window_timeout_s=0.02))
    st.update_graph(edges, emb)
    taken = st._acquire_windows([0])           # hold shard 0's only slot
    # semaphore OBJECTS come back (reshard may remap _windows mid-round)
    assert taken == [st._windows[0]]
    with pytest.raises(BackpressureError) as ei:
        st.get_embeds(np.arange(40))           # fans out onto shard 0
    r = ei.value.reason
    assert r["source"] == "inflight_window" and r["limit"] == 1
    assert st.backpressure_events == 1
    st._release_windows(taken)
    st.get_embeds(np.arange(40))               # recovers once released
    st.close()


def test_fetch_queue_full_reaps_and_recovers():
    """Same reap+retry contract on the fetch rings (fetch_submit)."""
    edges, emb = _graph()
    st = ShardedGraphStore(
        n_shards=2, h_threshold=16,
        flow=FlowControl(submit_retries=3, backoff_base_s=1e-4,
                         backoff_max_s=1e-3))
    st.update_graph(edges, emb)
    ref = st.get_embeds(np.arange(50))

    class _FlakyFetch:
        def __init__(self, inner, fail_submits):
            self._inner = inner
            self._fail_left = fail_submits

        def fetch_submit(self, **kw):
            if self._fail_left > 0:
                self._fail_left -= 1
                raise QueueFullError("synthetic SQ full", qid=1, depth=64)
            return self._inner.fetch_submit(**kw)

        def __getattr__(self, name):
            return getattr(self._inner, name)

    st.endpoints[1] = _FlakyFetch(st.endpoints[1], fail_submits=2)
    np.testing.assert_array_equal(ref, st.get_embeds(np.arange(50)))
    assert st.backpressure_retries == 2
    st.close()


# --------------------------------------------- reasoned rejections at the top
def test_admission_error_carries_reason_and_health():
    svc = HolisticGNNService(h_threshold=16)
    edges, emb = _graph()
    svc.store.update_graph(edges, emb)
    sched = BatchScheduler(svc, max_pending=1)
    sched.health_provider = lambda: {"failed_shards": [2],
                                     "states": ["healthy"] * 3}
    dfg = make_service_dfg("gcn", 2, [4, 4]).save()
    params = gnn.init_params("gcn", [12, 8, 4], seed=1)
    weights = {k: v for k, v in
               gnn.dfg_feeds("gcn", params, None, []).items() if k != "H"}
    sched.submit(dfg=dfg, batch=[1], weights=weights, on_done=lambda r: None)
    with pytest.raises(AdmissionError) as ei:
        sched.submit(dfg=dfg, batch=[2], weights=weights,
                     on_done=lambda r: None)
    r = ei.value.reason
    assert r["source"] == "admission"
    assert r["queue_depth"] == 1 and r["max_pending"] == 1
    assert r["shard_health"]["failed_shards"] == [2]
    assert sched.qos.rejected == 1
    assert sched.qos.snapshot()["last_reject_reason"]["source"] == "admission"


def test_scheduler_turns_backpressure_into_typed_completion():
    svc = HolisticGNNService(h_threshold=16)
    edges, emb = _graph()
    svc.store.update_graph(edges, emb)
    reason = {"source": "inflight_window", "shard": 1, "limit": 2}

    def run_batch(*a, **kw):
        raise BackpressureError("window full", reason=reason)

    svc.run_batch = run_batch
    sched = BatchScheduler(svc)
    dfg = make_service_dfg("gcn", 2, [4, 4]).save()
    params = gnn.init_params("gcn", [12, 8, 4], seed=1)
    weights = {k: v for k, v in
               gnn.dfg_feeds("gcn", params, None, []).items() if k != "H"}
    got = []
    sched.submit(dfg=dfg, batch=[1], weights=weights, on_done=got.append)
    sched.drain()
    assert len(got) == 1
    assert got[0]["ok"] is False and got[0]["backpressure"] is True
    assert got[0]["reason"] == reason
    assert sched.qos.backpressured == 1
    assert sched.qos.snapshot()["last_reject_reason"] == reason


def test_stats_rpc_carries_health_and_flow_blocks():
    edges, emb = _graph()
    svc = HolisticGNNService(n_shards=3, replication=2, h_threshold=16,
                             flow=FlowControl(max_inflight_per_shard=4))
    svc.store.update_graph(edges, emb)
    sup = ShardSupervisor(svc.store, HealthPolicy(auto_rebuild=False))
    svc.store.health = sup
    with ServingRuntime(svc) as rt:
        st = rt.client().call("stats", timeout=30)
    assert st["health"]["states"] == ["healthy"] * 3
    assert st["flow"]["max_inflight_per_shard"] == 4
    assert st["flow"]["backpressure_events"] == 0
    assert "backpressure" in st["qos"] and "health" in st["qos"]
    svc.close()
