"""Fig. 29 (repo extension) — elastic online resharding of the CSSD array.

ROADMAP item 3 closes here: the array grows, shrinks, and re-places its
hottest vertex classes LIVE — batched reads keep flowing (and must stay
bit-identical to the single-device store at every migration chunk
boundary) while only the pages that change owner move shard-to-shard
over the peer links.  Three drills:

  * **grow 4 -> 5** — a fresh endpoint attaches mid-serve; the planner
    refines ``vid % 4`` to 20 classes and the new shard steals 4 of
    them, so migration must ship ~1/5 of the data set and NOT re-ship
    the rest (**byte accounting asserted**: shipped bytes within
    (5%, 35%) of the bulk-load page bytes).  Closed-loop reader threads
    hammer ``sample_batch`` throughout — **zero failed requests** and
    every result bit-identical to the single-device reference; an
    ``on_progress`` probe re-checks embedding + adjacency bit-identity
    at **every chunk boundary**;
  * **shrink 4 -> 3** — the highest shard drains out under the same
    traffic + probes (12 classes, 3 move: byte window (10%, 45%));
  * **heat rebalance, R in {1, 2}** — fig24's skewed mix (a hot
    community clustered in two residue classes) on a 4-shard array.  At
    R=1 hash placement pins the hot pages onto two shards (balance
    ~0.36, the hole fig24 leaves open); ``reshard(rebalance=True)``
    refines the map x4 and moves the hottest classes off the loaded
    shards using the measured read heat — **acceptance: R=1 min/max
    read balance >= 0.8**, results bit-identical before/after.  At R=2
    the same rebalance must coexist with replica spreading
    (bit-identity asserted; spreading already balances, the map move
    must not break it).

  PYTHONPATH=src:. python -m benchmarks.fig29_reshard [--smoke]
"""
from __future__ import annotations

import threading
import time

import numpy as np

from . import common as C
from .fig24_replicated import (HUB_CLASSES, N_SHARDS, _balance,
                               skewed_workload, target_stream)
from repro.store import GraphStore, ShardedGraphStore, sample_batch
from repro.store.blockdev import BlockDevice
from repro.store.endpoint import LocalShardEndpoint

H_THRESHOLD = 32
DEV_PAGES = 1 << 15


def _devs(n):
    return [BlockDevice(DEV_PAGES) for _ in range(n)]


def _ref_results(ref, batches, fanouts):
    return [sample_batch(ref, t, list(fanouts),
                         rng=np.random.default_rng(1000 + b), pad_to=64)
            for b, t in enumerate(batches)]


def _same(a, b) -> bool:
    if not np.array_equal(a.node_vids, b.node_vids):
        return False
    if not np.array_equal(a.embeddings, b.embeddings):
        return False
    return all(np.array_equal(la.nbr, lb.nbr) and
               np.array_equal(la.mask, lb.mask)
               for la, lb in zip(a.layers, b.layers))


def _elastic_drill(store, ref, batches, fanouts, probe_vids, *,
                   reshard_kw, n_readers=2):
    """Run ``store.reshard(**reshard_kw)`` under closed-loop traffic.

    Reader threads replay the seeded batch stream against the array and
    compare every result to the single-device reference until the
    migration finishes; an ``on_progress`` hook re-checks a probe set
    bit-identically at every adjacency/embedding chunk boundary.
    Returns (report, probes, completed, errors)."""
    ref_res = _ref_results(ref, batches, fanouts)
    ref_emb = ref.get_embeds(probe_vids)
    ref_adj = [ref.get_neighbors(int(v)) for v in probe_vids[:2]]
    stop = threading.Event()
    errors: list[str] = []
    done = [0]
    lock = threading.Lock()

    def reader(tid):
        b = tid
        while not stop.is_set():
            try:
                got = sample_batch(store, batches[b % len(batches)],
                                   list(fanouts),
                                   rng=np.random.default_rng(
                                       1000 + b % len(batches)),
                                   pad_to=64)
                if not _same(ref_res[b % len(batches)], got):
                    raise AssertionError("mid-migration batch diverged")
            except Exception as e:  # noqa: BLE001 — surfaced by the caller
                with lock:
                    errors.append(f"reader {tid}: {type(e).__name__}: {e}")
                return
            with lock:
                done[0] += 1
            b += n_readers

    probes = [0]

    def on_progress(ev):
        if ev["event"] not in ("chunk", "emb_chunk"):
            return
        if not np.array_equal(store.get_embeds(probe_vids), ref_emb):
            errors.append(f"probe at {ev}: embeddings diverged")
        for v, want in zip(probe_vids[:2], ref_adj):
            if not np.array_equal(store.get_neighbors(int(v)), want):
                errors.append(f"probe at {ev}: adjacency of {v} diverged")
        probes[0] += 1

    threads = [threading.Thread(target=reader, args=(t,))
               for t in range(n_readers)]
    for t in threads:
        t.start()
    try:
        report = store.reshard(on_progress=on_progress, **reshard_kw)
    finally:
        stop.set()
        for t in threads:
            t.join()
    if errors:
        raise AssertionError(f"{len(errors)} failures; first: {errors[0]}")
    assert probes[0] > 0, "migration produced no chunk boundaries to probe"
    assert done[0] > 0, "no closed-loop traffic completed mid-migration"
    # post-move: the full stream must still be bit-identical
    for want, t, b in zip(ref_res, batches, range(len(batches))):
        got = sample_batch(store, t, list(fanouts),
                           rng=np.random.default_rng(1000 + b), pad_to=64)
        assert _same(want, got), "post-reshard batch diverged"
    return report, probes[0], done[0], errors


def _load_bytes(store) -> int:
    return sum(d.stats.written_bytes for d in
               (ep.local_store.dev for ep in store.endpoints))


def _measure_reads(store, batches, fanouts):
    devs = [ep.local_store.dev for ep in store.endpoints]
    reads0 = [d.stats.read_pages for d in devs]
    res = _ref_results(store, batches, fanouts)
    reads = [d.stats.read_pages - r0 for d, r0 in zip(devs, reads0)]
    return reads, res


def run(smoke: bool = False):
    lines: list[str] = []
    if smoke:
        n, e, feat, n_warm = 16000, 144000, 64, 1600
        batch, n_batches, fanouts = 64, 3, [10, 10]
        chunk_pages, reps = 64, (1,)
    else:
        n, e, feat, n_warm = 48000, 432000, 128, 4800
        batch, n_batches, fanouts = 96, 4, [12, 12]
        chunk_pages, reps = 128, (1, 2)
    edges, emb, warm, cold_pool = skewed_workload(n, e, feat, n_warm)
    batches = target_stream(warm, cold_pool, batch, n_batches)
    rng = np.random.default_rng(7)
    probe_vids = rng.integers(0, n, 64).astype(np.int64)

    ref = GraphStore(BlockDevice(DEV_PAGES * N_SHARDS),
                     h_threshold=H_THRESHOLD)
    ref.update_graph(edges, emb)

    # ---------------------------------------------------- grow 4 -> 5 live
    store = ShardedGraphStore(devs=_devs(N_SHARDS), h_threshold=H_THRESHOLD)
    store.update_graph(edges, emb)
    loaded = _load_bytes(store)
    new_ep = LocalShardEndpoint(dev=BlockDevice(DEV_PAGES),
                                h_threshold=H_THRESHOLD, feature_dim=feat)
    t0 = time.perf_counter()
    rep, probes, served, _ = _elastic_drill(
        store, ref, batches, fanouts, probe_vids,
        reshard_kw=dict(add=[new_ep], chunk_pages=chunk_pages))
    grow_s = time.perf_counter() - t0
    ratio = rep["bytes_shipped"] / loaded
    # refine 4 -> 20 classes, the new shard steals 4: ~20% of the data
    # moves; anything near 100% would mean we re-shipped unmoved pages
    assert 0.05 < ratio < 0.35, \
        f"grow shipped {ratio:.2f}x of the loaded bytes (want ~0.2)"
    assert store.n_shards == N_SHARDS + 1
    lines.append(C.csv_line(
        "fig29.grow.4to5", grow_s,
        f"classes_moved={rep['classes_moved']};"
        f"bytes_shipped={rep['bytes_shipped']};byte_ratio={ratio:.3f};"
        f"chunk_probes={probes};mid_migration_batches={served};errors=0"))

    # -------------------------------------------------- shrink 4 -> 3 live
    store = ShardedGraphStore(devs=_devs(N_SHARDS), h_threshold=H_THRESHOLD)
    store.update_graph(edges, emb)
    loaded = _load_bytes(store)
    t0 = time.perf_counter()
    rep, probes, served, _ = _elastic_drill(
        store, ref, batches, fanouts, probe_vids,
        reshard_kw=dict(remove=[N_SHARDS - 1], chunk_pages=chunk_pages))
    shrink_s = time.perf_counter() - t0
    ratio = rep["bytes_shipped"] / loaded
    # refine 4 -> 12 classes, the removed shard's 3 move: ~25%
    assert 0.10 < ratio < 0.45, \
        f"shrink shipped {ratio:.2f}x of the loaded bytes (want ~0.25)"
    assert store.n_shards == N_SHARDS - 1
    lines.append(C.csv_line(
        "fig29.shrink.4to3", shrink_s,
        f"classes_moved={rep['classes_moved']};"
        f"bytes_shipped={rep['bytes_shipped']};byte_ratio={ratio:.3f};"
        f"chunk_probes={probes};mid_migration_batches={served};errors=0"))

    # ------------------------------------- heat rebalance at R in {1, 2}
    for r in reps:
        store = ShardedGraphStore(devs=_devs(N_SHARDS), replication=r,
                                  h_threshold=H_THRESHOLD)
        store.update_graph(edges, emb)
        _measure_reads(store, batches[:1], fanouts)              # warm
        reads_before, res_before = _measure_reads(store, batches, fanouts)
        bal_before = _balance(reads_before)
        t0 = time.perf_counter()
        rep, probes, served, _ = _elastic_drill(
            store, ref, batches, fanouts, probe_vids,
            reshard_kw=dict(rebalance=True, refine=4,
                            chunk_pages=chunk_pages))
        reb_s = time.perf_counter() - t0
        reads_after, res_after = _measure_reads(store, batches, fanouts)
        bal_after = _balance(reads_after)
        for want, got in zip(res_before, res_after):
            assert _same(want, got), f"R={r} rebalance changed results"
        if r == 1:
            # THE acceptance number: hash placement pins the hot
            # community onto 2 of 4 shards (~0.36); the heat-weighted
            # map must spread the hot classes themselves
            assert bal_after >= 0.8, \
                f"R=1 rebalanced balance {bal_after:.3f} < 0.8"
            assert bal_before < bal_after, (bal_before, bal_after)
        lines.append(C.csv_line(
            f"fig29.rebalance.r{r}", reb_s,
            f"balance_before={bal_before:.3f};"
            f"balance_after={bal_after:.3f};"
            f"classes_moved={rep['classes_moved']};"
            f"bytes_shipped={rep['bytes_shipped']};"
            f"chunk_probes={probes};mid_migration_batches={served}"))
    return lines


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    for ln in run(smoke=args.smoke):
        print(ln)
