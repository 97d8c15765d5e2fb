"""Device-DRAM page cache: hit/miss accounting, LRU eviction, and — the
part that matters for mutable-graph serving — write-hook invalidation
across every mutation path (unit updates, embedding RMWs, page splits,
device growth/relocation)."""
import tracemalloc

import numpy as np
import pytest

from repro import spans
from repro.store.blockdev import PAGE_BYTES, SLOTS_PER_PAGE, BlockDevice
from repro.store.embcache import EmbeddingPageCache
from repro.store.graphstore import GraphStore


def _twin_stores(seed=0, n=300, e=2500, feat=24, h_threshold=8,
                 cache_pages=4096, num_pages=1 << 14):
    """Two identical stores; only the first gets a page cache."""
    rng = np.random.default_rng(seed)
    src = rng.zipf(1.4, e) % n
    dst = rng.integers(0, n, e)
    edges = np.stack([dst, src], axis=1).astype(np.int64)
    emb = rng.standard_normal((n, feat)).astype(np.float32)
    stores = []
    for _ in range(2):
        gs = GraphStore(BlockDevice(num_pages), h_threshold=h_threshold)
        gs.update_graph(edges, emb)
        stores.append(gs)
    cached, plain = stores
    cached.attach_cache(EmbeddingPageCache(cache_pages))
    return cached, plain, n


def test_cached_reads_match_and_hit_counters_advance():
    cached, plain, n = _twin_stores()
    rng = np.random.default_rng(1)
    vids = rng.integers(0, n, 64)
    np.testing.assert_array_equal(cached.get_embeds(vids),
                                  plain.get_embeds(vids))
    st = cached.cache.stats
    assert st.misses > 0 and st.hits == 0          # cold pass: all misses
    miss0 = st.misses
    np.testing.assert_array_equal(cached.get_embeds(vids),
                                  plain.get_embeds(vids))
    assert st.misses == miss0 and st.hits > 0      # warm pass: all hits
    assert st.bytes_from_cache > 0
    assert cached.stats.cache is st                # surfaced via store stats


def test_graph_pages_cached_and_batch_reads_match():
    cached, plain, n = _twin_stores()
    vids = list(range(n))
    for _ in range(2):                             # cold then warm
        got = cached.get_neighbors_batch(vids)
        want = plain.get_neighbors_batch(vids)
        for v, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(g, w, err_msg=str(v))
    assert cached.cache.stats.hits > 0


def test_update_embed_invalidates():
    cached, plain, n = _twin_stores()
    vids = np.arange(32)
    cached.get_embeds(vids)                        # warm the cache
    new_row = np.full(cached.feature_dim, 7.5, np.float32)
    for gs in (cached, plain):
        gs.update_embed(5, new_row)
    inv0 = cached.cache.stats.invalidations
    assert inv0 > 0                                # RMW dropped its pages
    np.testing.assert_array_equal(cached.get_embeds(vids),
                                  plain.get_embeds(vids))
    np.testing.assert_array_equal(cached.get_embed(5), new_row)


def test_mutation_sequence_stays_coherent():
    """add_edge / delete_edge / delete_vertex / add_vertex interleaved with
    cached reads: the cached store tracks the plain one exactly."""
    cached, plain, n = _twin_stores(h_threshold=4)
    rng = np.random.default_rng(2)
    vids = list(range(n))
    for step in range(30):
        a, b = int(rng.integers(0, n)), int(rng.integers(0, n))
        op = step % 4
        for gs in (cached, plain):
            if op == 0:
                gs.add_edge(a, b)
            elif op == 1:
                gs.delete_edge(a, b)
            elif op == 2:
                gs.delete_vertex(a)
            else:
                gs.add_vertex(n + step)
        got = cached.get_neighbors_batch(vids)
        want = plain.get_neighbors_batch(vids)
        for v, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(g, w,
                                          err_msg=f"step {step} vid {v}")
        np.testing.assert_array_equal(cached.get_embeds(np.arange(16)),
                                      plain.get_embeds(np.arange(16)))


def test_lru_eviction_bounded_and_correct():
    cached, plain, n = _twin_stores(cache_pages=4)
    rng = np.random.default_rng(3)
    for _ in range(6):
        vids = rng.integers(0, n, 40)
        np.testing.assert_array_equal(cached.get_embeds(vids),
                                      plain.get_embeds(vids))
    assert len(cached.cache) <= 4
    assert cached.cache.stats.evictions > 0


def test_device_grow_relocation_invalidates_everything():
    """_grow relocates the embedding span to new LPNs; stale cached pages
    must not survive it."""
    cached, plain, n = _twin_stores(num_pages=16, n=40, e=200, feat=24)
    vids = np.arange(20)
    cached.get_embeds(vids)                        # populate the cache
    pages0 = cached.dev.num_pages
    k = 0
    while cached.dev.num_pages == pages0:          # force front-alloc growth
        for gs in (cached, plain):
            gs.add_vertex(1000 + k)
        k += 1
        assert k < 20000, "device never grew"
    np.testing.assert_array_equal(cached.get_embeds(vids),
                                  plain.get_embeds(vids))


# ---------------------------------------------------------------- row path
def _merged_pages(gs, vids):
    """The merged covering page set of ``vids`` (embedding-space LPNs)."""
    d = gs.feature_dim
    lo = vids * d
    p0, p1 = lo // SLOTS_PER_PAGE, -(-(lo + d) // SLOTS_PER_PAGE)
    pages = np.unique(np.concatenate(
        [np.arange(a, b) for a, b in zip(p0, p1)]))
    return pages, lo, p0


def _page_block_gather(gs, vids):
    """``get_embeds`` by the page-block path: read the merged page set
    into one block (through the cache when one is attached) and slice
    each row out of it."""
    d = gs.feature_dim
    vids = np.asarray(vids, dtype=np.int64)
    pages, lo, p0 = _merged_pages(gs, vids)
    lpns = gs._emb_base + pages
    block = (gs.cache.read_pages(gs.dev, lpns, tag="embed")
             if gs.cache is not None
             else gs.dev.read_pages(lpns, tag="embed"))
    flat = block.reshape(-1)
    start = np.searchsorted(pages, p0) * SLOTS_PER_PAGE \
        + lo - p0 * SLOTS_PER_PAGE
    return np.stack([flat[s:s + d] for s in start]).view(np.float32)


def _cache_state(gs):
    c = gs.cache
    st = c.stats
    return {"read_pages": gs.dev.stats.read_pages,
            "read_bytes": gs.dev.stats.read_bytes,
            "hits": st.hits, "misses": st.misses,
            "evictions": st.evictions,
            "bytes_from_cache": st.bytes_from_cache,
            "bytes_from_dev": st.bytes_from_dev,
            "lpn_slot": c._lpn_slot.tolist(),
            "slot_lpn": c._slot_lpn.tolist(),
            "last_use": c._last_use.tolist(),
            "free": list(c._free)}


def _embed_bytes():
    return spans.snapshot().get("store.embed_bytes", {"n": 0})["n"]


def _rows_direct():
    return spans.snapshot().get("store.embed_rows_direct", {"n": 0})["n"]


@pytest.mark.parametrize("d", [24, 100, 1000, 1500, 8415])
def test_row_path_matches_page_block_path(d):
    """Rows gathered straight from the slab and the page array are the
    rows the page-block path slices, and the device, the cache's counters,
    its LPN->slot map and its LRU order end exactly as that path leaves
    them — cold, warm, partly warm, a scan larger than the cache, after
    RMW invalidation and after device growth (at d=1500 a row spans two
    or three pages; at d=8415, Coauthor-Physics' width, nine or ten, more
    than the whole cache holds).  Rows of a page or more are each copied
    straight into the output (``store.embed_rows_direct``); narrower rows
    never take that path."""
    cap = 8
    n = 100 * SLOTS_PER_PAGE // d + 3              # ~100 pages, last partial
    rng = np.random.default_rng(d)
    table = rng.standard_normal((n, d)).astype(np.float32)
    edges = np.array([[0, 1], [1, 2]], dtype=np.int64)
    new, old = (GraphStore(BlockDevice(64), feature_dim=d) for _ in "ab")
    for gs in (new, old):
        gs.update_graph(edges, table)
        gs.attach_cache(EmbeddingPageCache(cap))
    rows_per_page = max(1, SLOTS_PER_PAGE // d)
    if d == 8415:                     # every row spans more than the cache
        lo = np.arange(n) * d
        pages = (lo + d - 1) // SLOTS_PER_PAGE - lo // SLOTS_PER_PAGE + 1
        assert set(pages.tolist()) == {9, 10} and cap < 9

    def check(vids):
        vids = np.asarray(vids, dtype=np.int64)
        miss0 = old.cache.stats.misses
        b0, r0 = _embed_bytes(), _rows_direct()
        got = new.get_embeds(vids)
        assert _rows_direct() - r0 == \
            (len(vids) if d * 4 >= PAGE_BYTES else 0)
        want = _page_block_gather(old, vids)
        np.testing.assert_array_equal(got.view(np.int32),
                                      want.view(np.int32))
        np.testing.assert_array_equal(got.view(np.int32),
                                      table[vids].view(np.int32))
        assert _cache_state(new) == _cache_state(old)
        installed = min(old.cache.stats.misses - miss0, cap)
        assert _embed_bytes() - b0 == \
            len(vids) * d * 4 + installed * PAGE_BYTES

    few = np.arange(0, 3 * rows_per_page, 2)       # a few pages
    check(few)                                     # cold
    assert new.cache.stats.misses and not new.cache.stats.hits
    check(few[::-1])                               # warm: all hits
    assert new.cache.stats.hits
    check(np.concatenate([few, few + 4 * rows_per_page]))  # partly warm
    check(rng.permutation(n))                      # scan >> capacity
    assert new.cache.stats.evictions
    check(rng.integers(0, n, 40))
    row = rng.standard_normal(d).astype(np.float32)
    for gs in (new, old):                          # RMW invalidation
        gs.update_embed(n - 2, row)
    table[n - 2] = row
    check([n - 2, n - 1, 0])
    block = rng.standard_normal((5, d)).astype(np.float32)
    for gs in (new, old):
        gs.write_embed_rows(1, block)
    table[1:6] = block
    check(np.arange(8))
    pages0 = new.dev.num_pages
    while new.dev.num_pages == pages0:             # device growth
        for gs in (new, old):
            gs.dev.alloc_front()
    check(np.arange(0, n, 3))


@pytest.mark.parametrize("case", ["cold", "partly_warm", "warm", "uncached"])
def test_row_path_charges_latency_like_page_reads(case):
    """Deferred simulated latency of a ``get_embeds`` equals that of
    ``read_pages`` over the same missed pages (none when all hit)."""
    d, n = 100, 2000
    table = np.random.default_rng(5).standard_normal((n, d)) \
        .astype(np.float32)
    edges = np.array([[0, 1]], dtype=np.int64)
    new, old = (GraphStore(BlockDevice(64), feature_dim=d) for _ in "ab")
    for gs in (new, old):
        gs.update_graph(edges, table)
        if case != "uncached":
            gs.attach_cache(EmbeddingPageCache(64))
        gs.dev.simulate_latency = True
        gs.dev.command_latency_us = 80.0
        gs.dev.page_read_us = 30.0
    vids = np.arange(0, 400, 3)
    if case in ("partly_warm", "warm"):
        warm = vids if case == "warm" else vids[: len(vids) // 2]
        for gs in (new, old):
            with gs.dev.defer_latency():
                _page_block_gather(gs, warm)
    with new.dev.defer_latency() as got:
        new.get_embeds(vids)
    misses = old.cache.stats.misses if old.cache is not None else 0
    with old.dev.defer_latency() as want:
        _page_block_gather(old, vids)
    missed = (old.cache.stats.misses - misses if old.cache is not None
              else len(_merged_pages(old, vids)[0]))
    assert got.us == want.us
    assert got.us == (0.0 if not missed else
                      80.0 + 30.0 * missed / new.dev.channels)
    assert (got.us == 0.0) == (case == "warm")


def test_row_path_builds_no_page_block():
    """The gather's peak host allocation scales with the rows it returns,
    not with the P >> capacity pages they touch: no (P, 1024) block."""
    n, d, cap, k = 200_000, 100, 64, 5000
    table = np.random.default_rng(6).standard_normal((n, d)) \
        .astype(np.float32)
    gs = GraphStore(BlockDevice(64), feature_dim=d)
    gs.update_graph(np.array([[0, 1]], dtype=np.int64), table)
    gs.attach_cache(EmbeddingPageCache(cap))
    rng = np.random.default_rng(7)
    gs.get_embeds(rng.integers(0, n, k))           # warm every lazy path
    vids = rng.integers(0, n, k)
    n_pages = len(_merged_pages(gs, vids)[0])
    tracemalloc.start()
    try:
        got = gs.get_embeds(vids)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    np.testing.assert_array_equal(got, table[vids])
    bound = 4 * k * d * 4 + cap * PAGE_BYTES
    assert n_pages * PAGE_BYTES > 2 * bound
    assert peak < bound, (peak, bound)


def test_wide_row_path_copies_each_row_once():
    """At Coauthor-Physics' width every row spans 9-10 pages and comes
    from the device: the gather's peak host allocation is the rows it
    returns plus the pages the cache installs, with no second row-sized
    temporary."""
    n, d, cap, k = 700, 8415, 8, 300
    table = np.random.default_rng(8).standard_normal((n, d)) \
        .astype(np.float32)
    gs = GraphStore(BlockDevice(64), feature_dim=d)
    gs.update_graph(np.array([[0, 1]], dtype=np.int64), table)
    gs.attach_cache(EmbeddingPageCache(cap))
    rng = np.random.default_rng(9)
    gs.get_embeds(rng.integers(0, n, k))           # warm every lazy path
    vids = rng.integers(0, n, k)
    r0 = _rows_direct()
    tracemalloc.start()
    try:
        got = gs.get_embeds(vids)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    np.testing.assert_array_equal(got, table[vids])
    assert _rows_direct() - r0 == k
    bound = got.nbytes + cap * PAGE_BYTES + (1 << 20)
    assert 2 * got.nbytes > bound
    assert peak < bound, (peak, bound)


@pytest.mark.parametrize("d", [100, 1000, 1024, 1500, 8415])
@pytest.mark.parametrize("positions", ["given", "in_order"])
def test_device_read_rows_writes_into_out(d, positions):
    """``BlockDevice.read_rows(..., out=, at=)`` puts row ``i`` at
    ``out[at[i]]`` (``out[i]`` without ``at``), leaves every other row
    of ``out`` as it was, returns ``out``, and charges the command exactly
    as the read without ``out`` does."""
    rng = np.random.default_rng(d)
    n = 40 * SLOTS_PER_PAGE // d + 2
    table = rng.integers(-2**31, 2**31, (n, d), dtype=np.int64) \
        .astype(np.int32)
    devs = []
    for _ in "ab":
        dev = BlockDevice(64, simulate_latency=True, page_read_us=30.0,
                          command_latency_us=80.0)
        dev.write_span(0, table.reshape(-1))
        devs.append(dev)
    new, old = devs
    vids = rng.integers(0, n, 25)
    offsets = vids * d
    lpns = np.unique(np.concatenate(
        [np.arange(o // SLOTS_PER_PAGE, (o + d - 1) // SLOTS_PER_PAGE + 1)
         for o in offsets]))
    out = np.full((len(vids) + 7, d), -5, np.int32)
    at = (rng.permutation(len(out))[:len(vids)] if positions == "given"
          else None)
    r0 = _rows_direct()
    with new.defer_latency() as got_us:
        ret = new.read_rows(lpns, offsets, d, out=out, at=at)
    direct = _rows_direct() - r0
    with old.defer_latency() as want_us:
        want = old.read_rows(lpns, offsets, d)
    assert ret is out
    placed = np.arange(len(vids)) if at is None else at
    np.testing.assert_array_equal(out[placed], table[vids])
    np.testing.assert_array_equal(want, table[vids])
    kept = np.setdiff1d(np.arange(len(out)), placed)
    assert len(kept) == 7 and (out[kept] == -5).all()
    assert direct == (len(vids) if d >= SLOTS_PER_PAGE else 0)
    assert got_us.us == want_us.us == 80.0 + 30.0 * len(lpns) / 8
    assert (new.stats.read_pages, new.stats.read_bytes) == \
        (old.stats.read_pages, old.stats.read_bytes)
    ev = [(e.kind, e.lpn, e.nbytes, e.tag) for e in new.stats.events]
    assert ev == [(e.kind, e.lpn, e.nbytes, e.tag) for e in old.stats.events]
