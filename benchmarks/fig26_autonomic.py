"""Fig. 26 (repo extension) — autonomic array runtime under chaos.

PRs 4-5 gave the replicated CSSD array a fault PATH (drain + streaming
rebuild) driven by operator RPCs; this PR closes the LOOP.  Three phases
drive the ``ShardSupervisor`` + end-to-end flow control:

  * **chaos** — the hottest shard's DEVICE is killed mid-sweep with NO
    operator call; the supervisor must detect (probe + error mapping),
    auto-drain, and auto-rebuild while every completed batch stays
    **bit-identical** to the healthy reference (asserted).  Reported:
    wall detection latency, restore time, degraded/healed latency;
  * **paced rebuild** — serving p99 is measured degraded-without-rebuild
    (rebuild off) and again WHILE a chunk-paced rebuild streams from the
    survivors (rebuild on); pacing is asserted from the rebuild info
    (``chunks * pace_s`` is a floor on the stream time) and the
    during-rebuild p99 must stay within a bounded factor of rebuild-off;
    the unpaced stream is reported for contrast;
  * **overload** — reader threads hammer a multi-host (RoP) array sized
    to saturate (1-deep in-flight windows, shallow SQs): sustained
    overload must shed as typed ``BackpressureError`` with a reason —
    never a raw ``QueueFullError`` escape, a wedged SQ, or a wrong
    answer — and the array must serve bit-identically after the storm.

  PYTHONPATH=src:. python -m benchmarks.fig26_autonomic [--smoke]
"""
from __future__ import annotations

import threading
import time

import numpy as np

from . import common as C
from repro.rpc.queues import BackpressureError
from repro.serve import HealthPolicy, ShardSupervisor
from repro.store import (ShardedGraphStore, make_rop_endpoints,
                         sample_batch)
from repro.store.blockdev import BlockDevice
from repro.store.sharded import FlowControl

# fig23/fig24's array-scale QLC-class profile: per-page flash time
# dominant — the regime where a rebuild stream visibly contends with
# serving reads and pacing visibly helps.
PAGE_READ_US = 200.0
PAGE_WRITE_US = 250.0
CMD_LATENCY_US = 20.0

N_SHARDS = 4


def shard_devices(n: int) -> list[BlockDevice]:
    return [BlockDevice(1 << 15, simulate_latency=True,
                        page_read_us=PAGE_READ_US,
                        page_write_us=PAGE_WRITE_US,
                        command_latency_us=CMD_LATENCY_US)
            for _ in range(n)]


def _workload(n, e, feat, seed=0):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, e), rng.zipf(1.4, e) % n],
                     axis=1).astype(np.int64)
    emb = rng.standard_normal((n, feat)).astype(np.float32)
    return edges, emb


def _batches(n, batch, n_batches, seed=100):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, n, batch) for _ in range(n_batches)]


def _serve(store, targets, b, fanouts):
    return sample_batch(store, targets, list(fanouts),
                        rng=np.random.default_rng(1000 + b), pad_to=64)


def _assert_identical(want, got, ctx):
    np.testing.assert_array_equal(want.node_vids, got.node_vids, err_msg=ctx)
    np.testing.assert_array_equal(want.embeddings, got.embeddings,
                                  err_msg=ctx)
    for la, lb in zip(want.layers, got.layers):
        np.testing.assert_array_equal(la.nbr, lb.nbr, err_msg=ctx)


def _p99(lat_s: list) -> float:
    return float(np.percentile(np.array(lat_s), 99)) if lat_s else 0.0


# ------------------------------------------------------------------ phase A
def phase_chaos(smoke: bool) -> list[str]:
    n, e, feat = (8000, 60000, 32) if smoke else (40000, 300000, 64)
    batch, n_batches, fanouts = (48, 6, [8, 8]) if smoke \
        else (96, 12, [10, 10])
    edges, emb = _workload(n, e, feat)
    batches = _batches(n, batch, n_batches)
    store = ShardedGraphStore(devs=shard_devices(N_SHARDS),
                              replication=2, h_threshold=32)
    store.update_graph(edges, emb)
    ref = [_serve(store, t, b, fanouts) for b, t in enumerate(batches)]
    reads = [d.stats.read_pages for d in store.devs]
    victim = int(np.argmax(reads))

    sup = ShardSupervisor(store, HealthPolicy(
        probe_interval_s=0.01, rebuild_retry_s=0.1)).start()
    try:
        # ---- kill the device directly: no fail_shard, no operator
        t_kill = time.perf_counter()
        store.devs[victim].fail()
        t_detect = None
        lat_degraded: list[float] = []
        for b, t in enumerate(batches):
            t0 = time.perf_counter()
            out = _serve(store, t, b, fanouts)
            lat_degraded.append(time.perf_counter() - t0)
            _assert_identical(ref[b], out, f"chaos batch {b}")
            if t_detect is None and store.failed_shards[victim]:
                t_detect = time.perf_counter()
        # ---- the array must return to full redundancy on its own
        t_end = time.monotonic() + 60.0
        while time.monotonic() < t_end:
            if t_detect is None and store.failed_shards[victim]:
                t_detect = time.perf_counter()
            snap = sup.snapshot()
            if (snap["incidents"] and not any(store.failed_shards)
                    and all(s == "healthy" for s in snap["states"])):
                break
            time.sleep(0.01)
        else:
            raise AssertionError(f"array did not heal: {sup.snapshot()}")
        assert t_detect is not None and t_detect - t_kill <= 10.0
        inc = snap["last_incident"]
        assert inc["shard"] == victim and inc["drained"] is True
        assert inc["cause"] in ("probe", "error_burst", "observed_drained")
        reads1 = store.devs[victim].stats.read_pages
        lat_healed: list[float] = []
        for b, t in enumerate(batches):
            t0 = time.perf_counter()
            out = _serve(store, t, b, fanouts)
            lat_healed.append(time.perf_counter() - t0)
            _assert_identical(ref[b], out, f"healed batch {b}")
        assert store.devs[victim].stats.read_pages > reads1   # back in rotation
        return [C.csv_line(
            f"fig26.chaos.kill{victim}", t_detect - t_kill,
            f"cause={inc['cause']};restore_s={inc.get('restore_s', 0):.3f};"
            f"degraded_p99_ms={_p99(lat_degraded) * 1e3:.1f};"
            f"healed_p99_ms={_p99(lat_healed) * 1e3:.1f};"
            f"batches_identical={len(batches) * 2};operator_calls=0")]
    finally:
        sup.stop()
        store.close()


# ------------------------------------------------------------------ phase B
def phase_paced_rebuild(smoke: bool) -> list[str]:
    n, e, feat = (12000, 80000, 128) if smoke else (48000, 320000, 192)
    batch, fanouts = (48, [8, 8]) if smoke else (96, [10, 10])
    pace_s = 0.02 if smoke else 0.04
    min_off, min_on = (6, 3) if smoke else (12, 5)
    edges, emb = _workload(n, e, feat)
    store = ShardedGraphStore(devs=shard_devices(N_SHARDS),
                              replication=2, h_threshold=32)
    store.update_graph(edges, emb)
    batches = _batches(n, batch, 64)
    _serve(store, batches[0], 0, fanouts)                      # warm
    store.fail_shard(0)

    def measure(n_min, alive=None):
        lat = []
        for b, t in enumerate(batches):
            t0 = time.perf_counter()
            _serve(store, t, b, fanouts)
            lat.append(time.perf_counter() - t0)
            if len(lat) >= n_min and (alive is None or not alive()):
                break
        return lat

    lat_off = measure(min_off)                 # degraded, rebuild off
    out = {}

    def run_rebuild(pacing):
        out["info"] = store.rebuild_shard(0, pacing_s=pacing)

    th = threading.Thread(target=run_rebuild, args=(pace_s,))
    th.start()
    lat_on = measure(min_on, alive=th.is_alive)  # during the paced stream
    th.join(timeout=600.0)
    info = out["info"]
    assert info["chunks"] > 0 and info["pace_s"] == pace_s
    assert info["seconds"] >= info["chunks"] * pace_s          # pacing real
    p_off, p_on = _p99(lat_off), _p99(lat_on)
    factor = p_on / p_off if p_off else 1.0
    if not smoke:
        assert factor <= 4.0, \
            f"paced-rebuild p99 {p_on * 1e3:.1f}ms is {factor:.2f}x " \
            f"rebuild-off {p_off * 1e3:.1f}ms (> 4.0x)"
    lines = [C.csv_line(
        "fig26.rebuild.paced", p_on,
        f"rebuild_off_p99_ms={p_off * 1e3:.1f};factor={factor:.2f};"
        f"chunks={info['chunks']};pace_ms={pace_s * 1e3:.0f};"
        f"stream_s={info['seconds']:.2f};overlap_batches={len(lat_on)}")]
    # ---- unpaced contrast: same fault, pace 0
    store.fail_shard(0)
    th = threading.Thread(target=run_rebuild, args=(0.0,))
    th.start()
    lat_raw = measure(1, alive=th.is_alive)
    th.join(timeout=600.0)
    lines.append(C.csv_line(
        "fig26.rebuild.unpaced", _p99(lat_raw),
        f"stream_s={out['info']['seconds']:.2f};"
        f"overlap_batches={len(lat_raw)}"))
    store.close()
    return lines


# ------------------------------------------------------------------ phase C
def phase_overload(smoke: bool) -> list[str]:
    n, e, feat = (6000, 40000, 64) if smoke else (20000, 140000, 64)
    n_threads, per_thread = (8, 6) if smoke else (16, 10)
    edges, emb = _workload(n, e, feat)
    flow = FlowControl(max_inflight_per_shard=1, window_timeout_s=0.001,
                       submit_retries=1, backoff_base_s=1e-4,
                       backoff_max_s=5e-4)
    store = ShardedGraphStore(
        endpoints=make_rop_endpoints(3, h_threshold=32, n_queues=1,
                                     queue_depth=2),
        h_threshold=32, flow=flow)
    store.update_graph(edges, emb)
    probe = np.arange(200)
    ref = store.get_embeds(probe)

    rng = np.random.default_rng(7)
    work = [rng.integers(0, n, 4096) for _ in range(n_threads)]
    counts = {"ok": 0, "shed": 0}
    foreign: list[str] = []
    lock = threading.Lock()

    def hammer(tid):
        for _ in range(per_thread):
            try:
                store.get_embeds(work[tid])
                with lock:
                    counts["ok"] += 1
            except BackpressureError as bp:
                src = bp.reason.get("source")
                with lock:
                    counts["shed"] += 1
                if src not in ("inflight_window", "queue_full"):
                    foreign.append(f"unreasoned shed: {bp.reason}")
            except Exception as exc:  # noqa: BLE001 — must never happen
                foreign.append(f"{type(exc).__name__}: {exc}")

    t0 = time.perf_counter()
    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600.0)
    wall = time.perf_counter() - t0
    assert not foreign, foreign[:5]
    assert counts["ok"] + counts["shed"] == n_threads * per_thread
    assert counts["ok"] > 0
    assert counts["shed"] > 0, "overload storm never shed — not saturated"
    assert store.backpressure_events == counts["shed"]
    # no wedge: the array serves bit-identically after the storm
    np.testing.assert_array_equal(ref, store.get_embeds(probe),
                                  err_msg="post-storm")
    lines = [C.csv_line(
        "fig26.overload.shed", wall / (n_threads * per_thread),
        f"ok={counts['ok']};shed={counts['shed']};"
        f"retries={store.backpressure_retries};"
        f"threads={n_threads};sq_depth=2;window=1")]
    store.close()
    return lines


def run(smoke: bool = False):
    lines = []
    lines += phase_chaos(smoke)
    lines += phase_paced_rebuild(smoke)
    lines += phase_overload(smoke)
    return lines


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    for ln in run(smoke=args.smoke):
        print(ln)
