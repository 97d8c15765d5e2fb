"""Fig. 24 (repo extension) — replicated CSSD array under skewed reads.

PR 3's array leaves two holes the ROADMAP calls out: a lost device loses
its partition, and hash placement concentrates hot data (fig23's balance
0.8-0.95).  This sweep drives a ``ShardedGraphStore`` over 4 simulated
devices with R ∈ {1, 2, 3} on a *skewed* read mix: a hot co-engagement
community whose vertex ids cluster in two residue classes (the
clustered-id cohort a partition-unaware ingest produces — the
adversarial-but-realistic case hash placement cannot fix), over a uniform
cold background.  Reported:

  * **batched-read latency** (``sample_batch``: per-hop adjacency
    scatter-reads + striped embedding gather).  The array's deferred
    latency is max over shards, so replica-spreading the per-page load is
    a direct wall-clock win — acceptance: R=2 cuts skewed-mix latency
    >= 1.3x vs R=1;
  * **per-shard read balance** min/max over the measured window —
    acceptance: R=2 >= 0.97 (vs hash placement's ~0.5 on this mix);
  * **degraded mode**: the hottest shard is failed mid-sweep; the same
    seeded batches must come back **bit-identical** from the survivors
    (asserted), at the reported degraded latency;
  * **rebuild**: ``rebuild_shard`` re-materialises the lost partition
    from the survivors; redundancy is verified through the per-shard page
    counters (fresh device's written pages + restored mapping tables).

  PYTHONPATH=src:. python -m benchmarks.fig24_replicated [--smoke]
"""
from __future__ import annotations

import time

import numpy as np

from . import common as C
from repro.store import ShardedGraphStore, sample_batch
from repro.store.blockdev import BlockDevice

# Same array-scale QLC-class profile as fig23: per-page flash time
# dominant — the regime where spreading pages across devices buys latency.
PAGE_READ_US = 200.0
PAGE_WRITE_US = 250.0
CMD_LATENCY_US = 20.0

N_SHARDS = 4
HUB_CLASSES = (1, 2)   # the residue classes the hot hub ids cluster into


def shard_devices(n: int) -> list[BlockDevice]:
    return [BlockDevice(1 << 15, simulate_latency=True,
                        page_read_us=PAGE_READ_US,
                        page_write_us=PAGE_WRITE_US,
                        command_latency_us=CMD_LATENCY_US)
            for _ in range(n)]


def _balance(reads: list[int]) -> float:
    lo, hi = min(reads), max(reads)
    return lo / hi if hi else 1.0


def skewed_workload(n: int, e: int, feat: int, n_warm: int, seed: int = 0):
    """Power-law serving graph with a HOT COMMUNITY whose vertex ids sit
    in two adjacent residue classes.

    The warm set (think: this week's trending items) has ids of the form
    ``N_SHARDS * k + c`` for c in ``HUB_CLASSES`` — the clustered-id
    layout a partition-unaware ingest assigns a new cohort — scattered
    across the id range, so its adjacency pages and embedding rows are
    many distinct pages that ``vid % N`` placement pins onto two of the
    four shards.  Warm vertices link mostly to each other (co-engagement
    community), so a batch seeded in the warm set STAYS hot through every
    sampling hop; a uniform cold background over the full vertex space
    supplies the scattered traffic the spread can balance against.
    """
    rng = np.random.default_rng(seed)
    per = -(-n_warm // len(HUB_CLASSES))
    ks = rng.choice(n // N_SHARDS, size=per, replace=False)
    warm = np.sort(np.concatenate(
        [N_SHARDS * ks + c for c in HUB_CLASSES])[:n_warm])
    cold_pool = np.setdiff1d(np.arange(n), warm)
    e_w = e // 2
    ww = warm[rng.integers(0, len(warm), (e_w, 2))]
    cc = cold_pool[rng.integers(0, len(cold_pool), (e - e_w, 2))]
    edges = np.concatenate([ww, cc]).astype(np.int64)
    emb = rng.standard_normal((n, feat)).astype(np.float32)
    return edges, emb, warm, cold_pool


def target_stream(warm, cold_pool, batch, n_batches, seed=100):
    """Skewed read mix: half of every batch targets the warm community
    (whose sampling hops then stay inside it), the rest is uniform cold
    traffic."""
    rng = np.random.default_rng(seed)
    n_hot = batch // 2
    out = []
    for _ in range(n_batches):
        hot = warm[rng.integers(0, len(warm), n_hot)]
        cold = cold_pool[rng.integers(0, len(cold_pool), batch - n_hot)]
        out.append(np.concatenate([hot, cold]))
    return out


def _measure(store, batches, fanouts):
    """Seeded sweep -> (mean array-IO seconds, mean wall seconds,
    per-shard read deltas, results).

    The headline latency is the store's deferred array wait (max over
    shards per fetch — the device model's own output); wall-clock is
    reported alongside but includes host scheduler oversleep noise the
    simulated array would not have.
    """
    reads0 = [d.stats.read_pages for d in store.devs]
    io0 = store.io_wait_us
    results = []
    t0 = time.perf_counter()
    for b, targets in enumerate(batches):
        results.append(sample_batch(store, targets, list(fanouts),
                                    rng=np.random.default_rng(1000 + b),
                                    pad_to=64))
    wall = (time.perf_counter() - t0) / len(batches)
    io_s = (store.io_wait_us - io0) * 1e-6 / len(batches)
    reads = [d.stats.read_pages - r0 for d, r0 in zip(store.devs, reads0)]
    return io_s, wall, reads, results


def _assert_identical(want, got, ctx):
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a.node_vids, b.node_vids, err_msg=ctx)
        np.testing.assert_array_equal(a.embeddings, b.embeddings,
                                      err_msg=ctx)
        for la, lb in zip(a.layers, b.layers):
            np.testing.assert_array_equal(la.nbr, lb.nbr, err_msg=ctx)


def run(smoke: bool = False, reps=(1, 2, 3)):
    lines: list[str] = []
    if smoke:
        reps = (1, 2)
        n, e, feat, n_warm = 80000, 720000, 256, 8000
        batch, n_batches, fanouts = 96, 4, [12, 12]
    else:
        n, e, feat, n_warm = 160000, 1440000, 256, 16000
        batch, n_batches, fanouts = 128, 10, [12, 12]
    edges, emb, warm, cold_pool = skewed_workload(n, e, feat, n_warm)
    batches = target_stream(warm, cold_pool, batch, n_batches)

    base_io = None
    healthy_ref = None
    for rep in reps:
        store = ShardedGraphStore(devs=shard_devices(N_SHARDS),
                                  replication=rep, h_threshold=32)
        store.update_graph(edges, emb)
        _measure(store, batches[:1], fanouts)            # warm
        io_s, wall, reads, results = _measure(store, batches, fanouts)
        if base_io is None:
            base_io = io_s
        if healthy_ref is None:
            healthy_ref = results
        else:
            _assert_identical(healthy_ref, results, f"healthy R={rep}")
        bal = _balance(reads)
        lines.append(C.csv_line(
            f"fig24.read.r{rep}.{N_SHARDS}shard", io_s,
            f"io_speedup={base_io / io_s:.2f}x;balance={bal:.3f};"
            f"wall_ms={wall * 1e3:.1f};"
            f"shard_reads={'/'.join(str(r) for r in reads)}"))
        if not smoke and rep == 2:
            assert bal >= 0.97, f"R=2 balance {bal:.3f} < 0.97"
            assert base_io / io_s >= 1.3, \
                f"R=2 array-IO speedup {base_io / io_s:.2f}x < 1.3x"

        if rep != 2:
            continue
        # ---- degraded mode: fail the hottest shard, results must not move
        victim = int(np.argmax(reads))
        store.fail_shard(victim)
        dio, dwall, dreads, dresults = _measure(store, batches, fanouts)
        _assert_identical(healthy_ref, dresults, "degraded R=2")
        assert dreads[victim] == 0
        live = [r for i, r in enumerate(dreads) if i != victim]
        lines.append(C.csv_line(
            f"fig24.degraded.r2.kill{victim}", dio,
            f"io_vs_healthy={dio / io_s:.2f}x;"
            f"balance_live={_balance(live):.3f}"))
        # ---- rebuild: fresh device re-materialised from survivors
        info = store.rebuild_shard(victim)
        sh = store.shards[victim]
        assert sh.dev.stats.written_pages == info["pages_written"] > 0
        assert sh.stats.pages_l + sh.stats.pages_h > 0
        assert not any(store.failed_shards)
        rio, rwall, rreads, rresults = _measure(store, batches, fanouts)
        _assert_identical(healthy_ref, rresults, "rebuilt R=2")
        assert rreads[victim] > 0                  # back in rotation
        lines.append(C.csv_line(
            f"fig24.rebuild.r2.shard{victim}", info["seconds"],
            f"vertices={info['vertices']};"
            f"pages_written={info['pages_written']};"
            f"post_rebuild_io_vs_healthy={rio / io_s:.2f}x"))
    return lines


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    for ln in run(smoke=args.smoke):
        print(ln)
