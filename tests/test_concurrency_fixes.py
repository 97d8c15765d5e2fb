"""Regression tests for the concurrency defects the static analyzer
surfaced: callbacks deferred out of leaf critical sections, RPC rounds
moved off the gossip lock, counters put behind their guards, and
thread-lifecycle hygiene (names + joins)."""
import threading
import time

import numpy as np
import pytest

from repro.configs.base import ModelConfig, ShapeConfig
from repro.data.pipeline import Pipeline
from repro.serve.scheduler import QoSTelemetry
from repro.serve.supervisor import ShardSupervisor
from repro.store.blockdev import BlockDevice
from repro.store.sharded import ShardedGraphStore
from repro.train.checkpoint import Checkpointer


def _lock_free(lock) -> bool:
    """True iff ``lock`` is not currently held (probe-and-release)."""
    ok = lock.acquire(blocking=False)
    if ok:
        lock.release()
    return ok


# ------------------------------------------------------------ supervisor
class _StubStore:
    """Duck-typed seam ShardSupervisor attaches to."""

    def __init__(self, n_shards=2):
        self.n_shards = n_shards
        self.failed_shards = [False] * n_shards
        self.health = None

    def probe_shards(self):
        return [{"shard": s} for s in range(self.n_shards)]


def test_supervisor_transition_hook_runs_without_lock():
    seen = []

    def hook(s, old, new, info):
        # the defect: hooks used to fire inside _transition_locked with
        # the LEAF supervisor lock held — any hook touching a lock
        # deadlocked or inverted the order
        seen.append((s, old, new, _lock_free(sup._lock)))

    sup = ShardSupervisor(_StubStore(), on_transition=hook)
    sup.record_error(0, RuntimeError("boom"))
    assert seen == [(0, "healthy", "suspect", True)]


def test_supervisor_hook_exception_does_not_break_later_hooks():
    seen = []

    def hook(s, old, new, info):
        seen.append(s)
        raise RuntimeError("telemetry crash")

    sup = ShardSupervisor(_StubStore(n_shards=3), on_transition=hook)
    sup.record_error(0, RuntimeError("a"))
    sup.record_error(1, RuntimeError("b"))
    assert seen == [0, 1]
    assert [e["shard"] for e in sup.events] == [0, 1]


# -------------------------------------------------------------- blockdev
def test_blockdev_grow_hooks_fire_with_lock_released():
    dev = BlockDevice(num_pages=8)
    calls = []
    dev.on_grow = lambda extra: calls.append(
        ("grow", extra, _lock_free(dev._lock)))
    dev.on_write = lambda lpn0, n: calls.append(
        ("write", (lpn0, n), _lock_free(dev._lock)))
    base = dev.alloc_back(16)              # must grow: 16 > 8 pages
    assert base >= 0
    kinds = [c[0] for c in calls]
    assert "grow" in kinds and "write" in kinds
    assert all(free for _, _, free in calls), \
        "grow/write observers ran under blockdev._lock"


def test_blockdev_alloc_front_grow_hook_outside_lock():
    dev = BlockDevice(num_pages=4)
    dev.alloc_back(4)                      # embedding space eats the device
    calls = []
    dev.on_grow = lambda extra: calls.append(_lock_free(dev._lock))
    dev.alloc_front()                      # front meets back -> grow
    assert calls and all(calls)


# ------------------------------------------------------------------- qos
def test_qos_locked_mutators_reflected_in_snapshot():
    qos = QoSTelemetry()
    qos.note_rejected({"why": "queue_full"})
    qos.note_expired(2)
    qos.note_backpressured()
    qos.note_errors(3)
    qos.note_group(4)
    qos.record(0.001)
    snap = qos.snapshot()
    assert snap["rejected"] == 1
    assert snap["expired"] == 2
    assert snap["backpressured"] == 1
    assert snap["errors"] == 3
    assert snap["groups"] == 1 and snap["avg_group_size"] == 4.0
    assert snap["completed"] == 1
    assert snap["last_reject_reason"] == {"why": "queue_full"}


# ---------------------------------------------------------------- gossip
def _rep_store():
    rng = np.random.default_rng(0)
    n = 64
    edges = rng.integers(0, n, size=(256, 2), dtype=np.int64)
    emb = rng.standard_normal((n, 8)).astype(np.float32)
    st = ShardedGraphStore(n_shards=2, replication=2, h_threshold=16)
    st.update_graph(edges, emb)
    return st


def test_gossip_round_runs_outside_gossip_lock():
    st = _rep_store()
    observed = []
    orig = st._submit_round

    def spy(items):
        observed.append(_lock_free(st._gossip_lock))
        return orig(items)

    st._submit_round = spy
    pulls0 = st.gossip_pulls
    st._refresh_gossip(force=True)
    assert observed == [True], \
        "counters RPC round ran under the leaf sharded._gossip_lock"
    # published under the lock after the round
    assert st.gossip_pulls == pulls0 + 1
    assert st._gossip_reads.shape == (st.n_shards,)


def test_gossip_inflight_flag_clears_on_rpc_failure():
    st = _rep_store()
    st._submit_round = lambda items: (_ for _ in ()).throw(
        RuntimeError("net down"))
    with pytest.raises(RuntimeError):
        st._refresh_gossip(force=True)
    assert st._gossip_inflight is False      # next pull not wedged


# -------------------------------------------------------- thread hygiene
def test_pipeline_close_joins_named_prefetch_thread():
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=8,
                      num_heads=2, num_kv_heads=2, d_ff=16, vocab_size=32)
    shape = ShapeConfig(name="t", kind="train", seq_len=8, global_batch=2)
    pipe = Pipeline(cfg, shape, prefetch=1, host_index=0, host_count=1)
    assert pipe._thread.name == "pipeline-prefetch"
    pipe.next()
    pipe.close()
    assert not pipe._thread.is_alive(), \
        "close() left the prefetch worker running"


def test_checkpoint_writer_thread_is_named(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"w": np.ones((2, 2), np.float32)})
    assert ck._thread is not None and ck._thread.name == "checkpoint-writer"
    ck.wait()
    assert ck.latest_step() == 1


# ------------------------------------------------------- ingest counters
def test_firehose_snapshot_counters_consistent_after_windows():
    from repro.store.ingest import MutationFirehose
    st = _rep_store()
    fh = MutationFirehose(st)
    for v in range(8):
        fh.add_edge(1000 + v, v)
    fh.flush()
    snap = fh.snapshot()
    assert snap["windows"] >= 1
    assert snap["applied"] >= 1
    assert snap["submitted"] == 8
