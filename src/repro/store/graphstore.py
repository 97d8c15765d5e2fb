"""GraphStore — graph-centric archiving on a page block device (paper §4.1).

Implements the paper's dual mapping:

  * **H-type** (high-degree vertices): per-vertex chain of pages, each page
    ``[count, next_lpn, n0, n1, ...]``.  The mapping table entry is
    VID -> head LPN (we keep the whole chain's LPNs, so appends find the
    tail in O(1) and a batch fetches every chain page in one read; unit
    reads still walk the chain as in the paper).
  * **L-type** (low-degree vertices): many vertices packed in one page.
    Neighbor chunks grow from slot 0; meta grows from the page end:
    ``slot[-1]=n_nodes, slot[-2]=data_len,`` then per node *i*
    ``slot[-3-2i]=vid, slot[-4-2i]=chunk_offset``.  The L-type table key is
    the *largest* VID stored in the page (range search, paper Fig. 8).
  * **selector**: VID -> {H, L} (``GraphStore._sel``).

Embeddings are stored sequentially in the *embedding space* (top of the
device, paper Fig. 7) with no page-level mapping: the location of VID *v*'s
feature row is computed from ``v`` directly.

Bulk ingest (``update_graph``) overlaps graph preprocessing (edge array ->
undirected, self-looped, sorted adjacency) with the heavy embedding-table
write, reproducing the paper's Fig. 18 behaviour: from the user's viewpoint
the bulk latency ~= data transfer + embedding write.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np

from .. import spans
from ..concurrency import witness_lock
from .blockdev import BlockDevice, PAGE_BYTES, SLOTS_PER_PAGE, SLOT_DTYPE
from .sampler import _ramp

# H-type page layout
_H_COUNT, _H_NEXT, _H_DATA = 0, 1, 2
H_CAP = SLOTS_PER_PAGE - _H_DATA          # neighbors per H page

# L-type page layout (meta from the end)
_L_NNODES = SLOTS_PER_PAGE - 1
_L_DATALEN = SLOTS_PER_PAGE - 2

# selector codes of ``GraphStore._sel`` (0: not mapped), and back
_KIND = {"L": 1, "H": 2}
_KIND_NAME = (None, "L", "H")


def _l_meta_vid(i: int) -> int:
    return SLOTS_PER_PAGE - 3 - 2 * i


def _l_meta_off(i: int) -> int:
    return SLOTS_PER_PAGE - 4 - 2 * i


# A fetch plan locates every position of a frontier in the block of pages
# one fetch read, as int64 arrays (entries in any order; a position absent
# from both l_idx and h_idx is an unknown vid):
#   l_idx, l_row, l_start, l_end   L position: chunk block[l_row, l_start:l_end]
#   h_idx, h_len                   H position: its chain's page count
#   h_rows, h_counts               the H entries' chain pages, concatenated in
#                                  entry order: block row, neighbors on it
PLAN_FIELDS = ("l_idx", "l_row", "l_start", "l_end",
               "h_idx", "h_len", "h_rows", "h_counts")


def make_plan(**fields) -> dict:
    """A plan with the given fields and every other field empty."""
    plan = {k: np.empty(0, dtype=np.int64) for k in PLAN_FIELDS}
    plan.update(fields)
    return plan


def merge_plans(parts) -> dict:
    """One plan over a concatenated block out of ``(plan, pos, row_off)``
    parts: each part's positions map through ``pos`` and its block rows
    shift by ``row_off``.  The sharded and replicated coordinators build
    one global plan this way from their per-shard fetches."""
    cols: dict = {k: [] for k in PLAN_FIELDS}
    for plan, pos, row_off in parts:
        for k in PLAN_FIELDS:
            v = np.asarray(plan[k], dtype=np.int64)
            if k in ("l_idx", "h_idx"):
                v = pos[v]
            elif k in ("l_row", "h_rows"):
                v = v + row_off
            cols[k].append(v)
    return make_plan(**{k: np.concatenate(v) for k, v in cols.items() if v})


def _plan_lens(plan, npos: int):
    """Per-position neighbor counts, each H entry's first page in
    ``h_rows`` and neighbor total, and the exclusive running count of
    neighbors over every chain page (``cum0[j]``: neighbors before page
    ``j``)."""
    h_len = plan["h_len"]
    cum0 = np.concatenate([[0], np.cumsum(plan["h_counts"])])
    h_first = np.cumsum(h_len) - h_len
    h_tot = cum0[h_first + h_len] - cum0[h_first]
    lens = np.zeros(npos, dtype=np.int64)
    lens[plan["l_idx"]] = plan["l_end"] - plan["l_start"]
    lens[plan["h_idx"]] = h_tot
    return lens, h_first, h_tot, cum0


def _whole_rows(plan, take, out_offs, lens, h_tot):
    """``(dst, src)`` slot pairs that copy every neighbor of the positions
    in ``take`` (a mask) to the output at ``out_offs``: ``src`` indexes the
    flattened block, L chunks and H chain pages alike."""
    l_idx, h_idx = plan["l_idx"], plan["h_idx"]
    lt = take[l_idx]
    ll = lens[l_idx[lt]]
    l_src = np.repeat(plan["l_row"][lt] * SLOTS_PER_PAGE
                      + plan["l_start"][lt], ll) + _ramp(ll)
    l_dst = np.repeat(out_offs[l_idx[lt]], ll) + _ramp(ll)
    ht = take[h_idx]
    hl = h_tot[ht]
    pg = np.repeat(ht, plan["h_len"])           # chain pages of taken hubs
    cnt = plan["h_counts"][pg]
    h_src = np.repeat(plan["h_rows"][pg] * SLOTS_PER_PAGE + _H_DATA,
                      cnt) + _ramp(cnt)
    h_dst = np.repeat(out_offs[h_idx[ht]], hl) + _ramp(hl)
    return np.concatenate([l_dst, h_dst]), np.concatenate([l_src, h_src])


def neighbors_from_plan(vids_arr: np.ndarray, block, plan) -> list[np.ndarray]:
    """Materialise per-vid neighbor arrays out of a fetched plan.

    Shared back half of the batched GetNeighbors: the single-device store
    feeds it one ``_fetch_plan`` result; the sharded coordinator feeds it a
    ``merge_plans`` of N per-shard plans — either way the output equals
    ``get_neighbors`` per vid.  One flat gather, then one split.
    """
    npos = len(vids_arr)
    if not npos:
        return []
    lens, _, h_tot, _ = _plan_lens(plan, npos)
    offs = np.cumsum(lens) - lens
    flat = np.empty(int(lens.sum()), dtype=SLOT_DTYPE)
    dst, src = _whole_rows(plan, np.ones(npos, dtype=bool), offs, lens, h_tot)
    if len(src):
        flat[dst] = block.reshape(-1)[src]
    return np.split(flat, offs[1:])


def select_from_plan(vids_arr: np.ndarray, block, plan, fanout: int,
                     rng: np.random.Generator | None = None, *,
                     segments=None, rngs=None):
    """Fanout selection over a fetched plan — the back half of the fused
    near-storage sample (see ``GraphStore.sample_neighbors_batch``).

    A pure function of (plan, rng): hubs are Floyd-sampled BY INDEX against
    their chain page counts, uniform draws are consumed one ``fanout``
    block per over-full vertex in frontier order (or per-request segment
    order when ``segments``/``rngs`` are given).  The sharded coordinator
    merges N per-shard plans into one global (block, plan) and runs this
    same code, which is why an N-shard sample is bit-identical to the
    single-device sample under the same seed.  Whole-frontier array math
    throughout: no step runs per vertex or per hub.
    """
    flatb = block.reshape(-1) if block is not None else None
    npos = len(vids_arr)
    lens, h_first, h_tot, cum0 = _plan_lens(plan, npos)
    over = lens > fanout
    empty = lens == 0
    lens_sel = np.where(empty, 1, np.minimum(lens, fanout))
    out_offs = np.cumsum(lens_sel) - lens_sel
    sel = np.empty(int(lens_sel.sum()), dtype=SLOT_DTYPE)

    # degenerate rows: self-loop
    sel[out_offs[empty]] = vids_arr[empty]

    # under-full rows, L chunks and H chains alike: one flat gather
    dst, src = _whole_rows(plan, ~over & ~empty, out_offs, lens, h_tot)
    if len(src):
        sel[dst] = flatb[src]

    # over-full rows: Floyd by index, vectorized across the frontier
    # (k steps of whole-row vector math, no per-vertex python)
    n_over = int(over.sum())
    if n_over:
        if rngs is not None:
            bounds = np.concatenate([[0], np.cumsum(segments)])
            parts = [g.random(int(over[bounds[s]: bounds[s + 1]]
                                  .sum()) * fanout)
                     for s, g in enumerate(rngs)]
            u = np.concatenate(parts).reshape(-1, fanout)
        else:
            u = rng.random(n_over * fanout).reshape(-1, fanout)
        m_arr = lens[over]
        idx = np.empty((n_over, fanout), dtype=np.int64)
        for j2 in range(fanout):
            t = (u[:, j2] * (m_arr - fanout + j2 + 1)).astype(np.int64)
            if j2:
                dup = (idx[:, :j2] == t[:, None]).any(axis=1)
                t = np.where(dup, m_arr - fanout + j2, t)
            idx[:, j2] = t
        over_pos = np.nonzero(over)[0]
        # drawn index -> flat block slot.  L: chunk base + index.  Hub: the
        # index is global over every chain page once the hub's first page's
        # running count is added; one searchsorted finds (page, slot).
        base = np.zeros(npos, dtype=np.int64)
        base[plan["l_idx"]] = (plan["l_row"] * SLOTS_PER_PAGE
                               + plan["l_start"])
        ent = np.full(npos, -1, dtype=np.int64)
        ent[plan["h_idx"]] = np.arange(len(plan["h_idx"]))
        e = ent[over_pos]
        hub = e >= 0
        src = base[over_pos][:, None] + idx
        if hub.any():
            g = cum0[h_first[e[hub]]][:, None] + idx[hub]
            p = np.searchsorted(cum0[1:], g, side="right")
            src[hub] = (plan["h_rows"][p] * SLOTS_PER_PAGE + _H_DATA
                        + g - cum0[p])
        sel[(out_offs[over_pos][:, None]
             + np.arange(fanout)[None, :]).reshape(-1)] = \
            flatb[src.reshape(-1)]
    return sel, lens_sel


@dataclass
class BulkTimeline:
    """Timestamped phase spans of a bulk ingest (for Fig. 18)."""
    transfer: tuple[float, float] = (0.0, 0.0)
    graph_pre: tuple[float, float] = (0.0, 0.0)
    write_feature: tuple[float, float] = (0.0, 0.0)
    write_graph: tuple[float, float] = (0.0, 0.0)
    total: float = 0.0
    user_visible: float = 0.0     # transfer + embedding write (+ graph flush tail)


@dataclass
class GraphStoreStats:
    l_evictions: int = 0
    unit_updates: int = 0
    pages_h: int = 0
    pages_l: int = 0
    bulk: BulkTimeline = field(default_factory=BulkTimeline)
    cache: object | None = None   # CacheStats once a page cache is attached


class GraphStore:
    def __init__(self, dev: BlockDevice | None = None, *, h_threshold: int = 128,
                 feature_dim: int = 0):
        self.dev = dev or BlockDevice()
        self.h_threshold = int(h_threshold)
        # The mapping tables (device-DRAM metadata), as arrays indexed by
        # vid, so a frontier is planned with whole-array lookups:
        #   _sel[vid]          selector: 0 not mapped, 1 L, 2 H
        #   _chain_at/_n[vid]  vid's H chain in _pool (length 0 unless H):
        #                      its head is _pool[_chain_at[vid]], its tail
        #                      the run's last LPN.  The whole chain lets a
        #                      batch fetch it in one queued read instead
        #                      of one pointer-chase round per page.
        #   _pool              chain LPNs; a rewritten chain leaves its old
        #                      copy behind until a compaction
        self._sel = np.zeros(0, dtype=np.int8)
        self._chain_at = np.zeros(0, dtype=np.int64)
        self._chain_n = np.zeros(0, dtype=np.int64)
        self._pool = np.empty(0, dtype=np.int64)
        self._pool_top = 0                             # slots of _pool in use
        self._pool_live = 0                            # of which live chains
        self._l_keys = np.empty(0, dtype=np.int64)    # sorted max-vid per L page
        self._l_lpns = np.empty(0, dtype=np.int64)    # parallel LPN array
        self.feature_dim = int(feature_dim)
        self._emb_base: int | None = None              # first LPN of embedding span
        self._emb_rows = 0
        self.num_vertices = 0
        self.stats = GraphStoreStats()
        self._lock = witness_lock("graphstore._lock", threading.RLock())
        self.cache = None                              # device-DRAM page cache
        self._cache_graph = True
        # device growth relocates the embedding space to the new top; the
        # table base must shift with it (without this, a neighbor-space
        # grow AFTER bulk ingest leaves _emb_base pointing at the zeroed
        # old span and every later embedding read returns garbage)
        self.dev.on_grow = self._on_dev_grow

    def _on_dev_grow(self, extra_pages: int) -> None:
        if self._emb_base is not None:
            self._emb_base += extra_pages

    def attach_cache(self, cache, *, cache_graph_pages: bool = True) -> None:
        """Front batched page reads with a device-DRAM LRU (serving hot set).

        Invalidation rides the device's write hook, so every mutable-graph
        path (unit updates, splits, promotions, embedding RMWs, device
        growth) drops exactly the pages it dirtied.
        """
        self.cache = cache
        self._cache_graph = cache_graph_pages
        self.stats.cache = cache.stats
        self.dev.on_write = cache.invalidate

    def attach_cache_pages(self, capacity_pages: int,
                           **kw) -> None:
        """Attach a fresh device-DRAM page cache of ``capacity_pages``
        (uniform entry point with the sharded store, which splits the
        budget across its shards' devices)."""
        from .embcache import EmbeddingPageCache
        self.attach_cache(EmbeddingPageCache(capacity_pages), **kw)

    def _read_graph_pages(self, lpns) -> np.ndarray:
        with spans.span("store.read", tag="graph"):
            if self.cache is not None and self._cache_graph:
                return self.cache.read_pages(self.dev, lpns, tag="graph")
            return self.dev.read_pages(lpns, tag="graph")

    # ========================================================= mapping tables
    def _cover(self, top: int) -> None:
        """Grow the per-vid tables to cover every vid below ``top``."""
        n = len(self._sel)
        if top <= n:
            return
        pad = max(top, 2 * n) - n
        self._sel = np.concatenate([self._sel, np.zeros(pad, np.int8)])
        self._chain_at = np.concatenate([self._chain_at,
                                         np.zeros(pad, np.int64)])
        self._chain_n = np.concatenate([self._chain_n,
                                        np.zeros(pad, np.int64)])

    def _map(self, vids, kind: str | None) -> None:
        """Map ``vids`` as ``kind`` ('H' or 'L'), or unmap them (``None``),
        in the selector ``_sel``.  An H vid's chain is recorded apart
        (``_map_chain``)."""
        vids = np.asarray(vids, dtype=np.int64).reshape(-1)
        if not len(vids):
            return
        self._cover(int(vids.max()) + 1)
        self._sel[vids] = _KIND.get(kind, 0)

    def _map_chain(self, vid: int, chain) -> None:
        """Record the LPNs ``chain`` as ``vid``'s H chain: they go to the
        end of ``_pool`` and ``vid``'s table row points at them.  Once
        superseded copies outweigh the live chains, a full pool is
        compacted before it is grown."""
        chain = np.array(chain, dtype=np.int64)
        self._cover(vid + 1)
        self._pool_live += len(chain) - int(self._chain_n[vid])
        self._chain_n[vid] = 0                 # its old run is superseded
        if self._pool_top + len(chain) > len(self._pool) \
                and self._pool_top > 2 * self._pool_live:
            self._compact_pool()
        top = self._pool_top + len(chain)
        if top > len(self._pool):
            grown = np.empty(max(top, 2 * len(self._pool), 1024), np.int64)
            grown[: self._pool_top] = self._pool[: self._pool_top]
            self._pool = grown
        self._pool[self._pool_top: top] = chain
        self._chain_at[vid] = self._pool_top
        self._chain_n[vid] = len(chain)
        self._pool_top = top

    def _unmap_chain(self, vid: int) -> None:
        """Drop ``vid``'s H chain from the table."""
        if vid < len(self._chain_n) and self._chain_n[vid]:
            self._pool_live -= int(self._chain_n[vid])
            self._chain_n[vid] = 0

    def _compact_pool(self) -> None:
        """Rewrite ``_pool`` with the live chains only, in vid order,
        sized for the live chains plus any being recorded."""
        live = np.flatnonzero(self._chain_n)
        n = self._chain_n[live]
        top = int(n.sum())
        pool = np.empty(max(2 * self._pool_live, 1024), np.int64)
        pool[:top] = self._pool[np.repeat(self._chain_at[live], n)
                                + _ramp(n)]
        self._chain_at[live] = np.cumsum(n) - n
        self._pool, self._pool_top = pool, top

    def _head(self, vid: int) -> int:
        """First LPN of H vid ``vid``'s chain."""
        return int(self._pool[self._chain_at[vid]])

    def kind_of(self, vid: int) -> str | None:
        """'H' or 'L' for a vid this store maps, else ``None``."""
        v = int(vid)
        return _KIND_NAME[int(self._sel[v])] if 0 <= v < len(self._sel) \
            else None

    def chain_of(self, vid: int) -> np.ndarray:
        """The LPNs of ``vid``'s H chain, head first (empty unless H)."""
        v = int(vid)
        if not 0 <= v < len(self._chain_n):
            return np.empty(0, dtype=np.int64)
        at = int(self._chain_at[v])
        return self._pool[at: at + int(self._chain_n[v])].copy()

    def class_kinds(self, cls: int = 0, modulus: int = 1,
                    start_vid: int = 0) -> tuple[np.ndarray, np.ndarray]:
        """One locked snapshot of the mapped vids ``v >= start_vid`` with
        ``v % modulus == cls`` (every mapped vid by default), ascending,
        and each one's kind ('H' or 'L')."""
        with self._lock:
            vids = np.flatnonzero(self._sel)
            vids = vids[(vids % modulus == cls) & (vids >= start_vid)]
            return vids, np.array(_KIND_NAME[1:])[self._sel[vids] - 1]

    def export_chain(self, vid: int) -> np.ndarray:
        """The raw pages of ``vid``'s H chain, head first, read straight
        off the device in one locked step — the export half of
        ``import_h_chain``."""
        with self._lock:
            return self.dev.read_pages(self.chain_of(vid), tag="graph")

    def _kinds(self, vids: np.ndarray) -> np.ndarray:
        """Selector code of each vid (0 for a vid this store never held)."""
        sel = self._sel
        inside = (vids >= 0) & (vids < len(sel))
        out = np.zeros(len(vids), dtype=np.int8)
        out[inside] = sel[vids[inside]]
        return out

    def _chain_lens(self, vids: np.ndarray) -> np.ndarray:
        """H-chain page count of each vid (0 unless H-mapped here)."""
        out = np.zeros(len(vids), dtype=np.int64)
        h = self._kinds(vids) == _KIND["H"]
        out[h] = self._chain_n[vids[h]]
        return out

    # ================================================================= helpers
    def _classify(self, degree: int) -> str:
        return "H" if degree > self.h_threshold else "L"

    def _new_l_page(self) -> tuple[int, np.ndarray]:
        lpn = self.dev.alloc_front()
        page = np.zeros(SLOTS_PER_PAGE, dtype=SLOT_DTYPE)
        self.stats.pages_l += 1
        return lpn, page

    @staticmethod
    def _l_free_slots(page: np.ndarray) -> int:
        n, dlen = int(page[_L_NNODES]), int(page[_L_DATALEN])
        return SLOTS_PER_PAGE - 2 - 2 * n - dlen

    @staticmethod
    def _l_scan(page: np.ndarray, vid: int) -> tuple[int, int, int] | None:
        """Return (meta_index, chunk_start, chunk_len) of vid in an L page
        (vectorized: the FPGA scans page meta in hardware; a Python loop
        here would dominate every near-storage GetNeighbors)."""
        n, dlen = int(page[_L_NNODES]), int(page[_L_DATALEN])
        if n == 0:
            return None
        vid_idx = _L_NNODES - 2 - 2 * np.arange(n)      # slot of meta vid i
        vids = page[vid_idx]
        offs = page[vid_idx - 1]
        hit = np.nonzero(vids == vid)[0]
        if not len(hit):
            return None
        i = int(hit[0])
        start = int(offs[i])
        later = offs[(offs > start) & (offs <= dlen)]
        # chunk end = smallest offset beyond start (tombstones included —
        # their offsets remain valid boundaries) or the data length.
        end = int(later.min()) if len(later) else dlen
        return i, start, end - start

    def _l_lookup_page(self, vid: int) -> tuple[int, np.ndarray] | None:
        """Range search the L table (paper Fig. 8): first key >= vid."""
        k = int(np.searchsorted(self._l_keys, vid))
        if k == len(self._l_keys):
            return None
        lpn = int(self._l_lpns[k])
        return lpn, self.dev.read_page(lpn).copy()

    # ============================================================ bulk ingest
    def update_graph(self, edge_array: np.ndarray,
                     embeddings: np.ndarray | None = None,
                     *, already_undirected: bool = False) -> BulkTimeline:
        """Paper's UpdateGraph(EdgeArray, Embeddings) bulk RPC.

        Overlaps adjacency-list conversion with the (much larger) embedding
        write by running them on two threads, as GraphStore overlaps the
        conversion compute with the storage write burst.
        """
        tl = BulkTimeline()
        t0 = time.perf_counter()

        # --- "transfer": the edge array + embedding list arriving over RoP.
        # No defensive copy: preprocess_edges never mutates its input, so
        # the only allocation is the dtype conversion asarray may need —
        # peak host memory stays one edge array, not two.
        edge_array = np.asarray(edge_array, dtype=np.int64).reshape(-1, 2)
        if embeddings is not None:
            embeddings = np.ascontiguousarray(embeddings, dtype=np.float32)
        tl.transfer = (0.0, time.perf_counter() - t0)

        csr_box: dict = {}

        def graph_pre():
            s = time.perf_counter() - t0
            csr_box["csr"] = preprocess_edges(
                edge_array, already_undirected=already_undirected)
            csr_box["span"] = (s, time.perf_counter() - t0)

        def write_feature():
            s = time.perf_counter() - t0
            if embeddings is not None:
                self._write_embedding_table(embeddings)
            csr_box["wf"] = (s, time.perf_counter() - t0)

        th_g = threading.Thread(target=graph_pre)
        th_f = threading.Thread(target=write_feature)
        th_g.start(); th_f.start()
        th_f.join()
        user_visible_at = time.perf_counter() - t0     # embedding write done
        th_g.join()

        tl.graph_pre = csr_box["span"]
        tl.write_feature = csr_box.get("wf", (0.0, 0.0))

        # --- flush adjacency pages (small vs embeddings; paper Fig. 18c)
        s = time.perf_counter() - t0
        indptr, indices = csr_box["csr"]
        self._write_adjacency(indptr, indices)
        tl.write_graph = (s, time.perf_counter() - t0)

        tl.total = time.perf_counter() - t0
        tl.user_visible = max(user_visible_at, tl.transfer[1])
        self.stats.bulk = tl
        return tl

    def _write_embedding_table(self, embeddings: np.ndarray) -> None:
        n, d = embeddings.shape
        if self.feature_dim and d != self.feature_dim:
            raise ValueError(f"feature dim {d} != store dim {self.feature_dim}")
        self.feature_dim = d
        flat = embeddings.reshape(-1).view(np.int32)
        n_pages = -(-flat.size // SLOTS_PER_PAGE)
        base = self.dev.alloc_back(n_pages)
        self.dev.write_span(base, flat, tag="embed")
        self._emb_base = base
        self._emb_rows = n

    def _write_adjacency(self, indptr: np.ndarray, indices: np.ndarray) -> None:
        n = len(indptr) - 1
        degrees = np.diff(indptr)
        self.num_vertices = max(self.num_vertices, n)

        h_vids = np.nonzero(degrees > self.h_threshold)[0]
        l_vids = np.nonzero((degrees > 0) & (degrees <= self.h_threshold))[0]

        # ---- H-type: per-vertex page chains
        self._map(h_vids, "H")
        for vid in h_vids:
            nbrs = indices[indptr[vid]: indptr[vid + 1]]
            self._write_h_chain(int(vid), nbrs)

        # ---- L-type: greedy packing in ascending VID order (cumsum splits)
        keys: list[int] = []
        lpns: list[int] = []
        if len(l_vids):
            sizes = degrees[l_vids] + 2                      # data + 2 meta slots
            csum = np.concatenate([[0], np.cumsum(sizes)])
            cap = SLOTS_PER_PAGE - 2
            start = 0
            while start < len(l_vids):
                hi = np.searchsorted(csum, csum[start] + cap, side="right") - 1
                hi = max(hi, start + 1)                       # at least one node
                lpn, page = self._new_l_page()
                dlen = 0
                cnt = 0
                for vid in l_vids[start:hi]:
                    nbrs = indices[indptr[vid]: indptr[vid + 1]]
                    page[_l_meta_vid(cnt)] = vid
                    page[_l_meta_off(cnt)] = dlen
                    page[dlen: dlen + len(nbrs)] = nbrs
                    dlen += len(nbrs)
                    cnt += 1
                page[_L_NNODES] = cnt
                page[_L_DATALEN] = dlen
                self.dev.write_page(lpn, page)
                keys.append(int(l_vids[hi - 1]))
                lpns.append(lpn)
                start = hi
        self._map(l_vids, "L")
        self._l_keys = np.concatenate(
            [self._l_keys, np.asarray(keys, dtype=np.int64)])
        self._l_lpns = np.concatenate(
            [self._l_lpns, np.asarray(lpns, dtype=np.int64)])

    # ================================================================ queries
    def get_neighbors(self, vid: int) -> np.ndarray:
        """Paper GetNeighbors(VID) unit RPC."""
        with self._lock:
            kind = self.kind_of(vid)
            if kind is None:
                return np.empty(0, dtype=SLOT_DTYPE)
            if kind == "H":
                out = []
                lpn = self._head(int(vid))
                while lpn >= 0:
                    page = self.dev.read_page(lpn)
                    cnt = int(page[_H_COUNT])
                    out.append(page[_H_DATA: _H_DATA + cnt].copy())
                    lpn = int(page[_H_NEXT])
                return np.concatenate(out) if out else np.empty(0, dtype=SLOT_DTYPE)
            hit = self._l_lookup_page(vid)
            if hit is None:
                return np.empty(0, dtype=SLOT_DTYPE)
            _, page = hit
            found = self._l_scan(page, int(vid))
            if found is None:
                return np.empty(0, dtype=SLOT_DTYPE)
            _, start, ln = found
            return page[start: start + ln].copy()

    def _fetch_plan(self, vids_arr: np.ndarray):
        """Shared front half of the batched near-storage queries.

        Plans the whole request from the in-DRAM mapping tables (L range
        table + H chain lists), fetches every needed page with a single
        queued scatter-read, and locates each vid's data.  Returns
        ``(block, plan)``: the fetched pages (``None`` when none was
        needed) and the array plan over them (see ``PLAN_FIELDS``).
        """
        kind = self._kinds(vids_arr)
        h_pos = np.flatnonzero(kind == _KIND["H"])
        l_pos = np.flatnonzero(kind == _KIND["L"])
        hv = vids_arr[h_pos]
        h_len = self._chain_n[hv]
        h_lpn = self._pool[np.repeat(self._chain_at[hv], h_len)
                           + _ramp(h_len)]

        lq = vids_arr[l_pos]
        k = np.searchsorted(self._l_keys, lq)   # first key >= vid
        paged = k < len(self._l_keys)
        l_pos, lq = l_pos[paged], lq[paged]
        q_lpn = self._l_lpns[k[paged]]          # each L vid's page
        l_lpns = np.unique(q_lpn)
        h_lpns = np.unique(h_lpn)

        lpns = np.concatenate([l_lpns, h_lpns])  # ONE queued scatter-read
        if not len(lpns):
            return None, make_plan()
        block = self._read_graph_pages(lpns)

        h_rows = len(l_lpns) + np.searchsorted(h_lpns, h_lpn)
        plan = make_plan(h_idx=h_pos, h_len=h_len, h_rows=h_rows,
                         h_counts=block[h_rows, _H_COUNT].astype(np.int64))
        if len(lq):
            q_row = np.searchsorted(l_lpns, q_lpn)
            found, start, end = self._l_locate_batch(block, q_row, lq)
            plan.update(l_idx=l_pos[found], l_row=q_row[found],
                        l_start=start[found], l_end=end[found])
        return block, plan

    def get_neighbors_batch(self, vids) -> list[np.ndarray]:
        """Batched GetNeighbors — the near-storage fast path.

        One scatter-read serves the whole request (vs one page walk per
        VID): L-type vids share their owning pages' single vectorized meta
        scan, H-type chains are materialised straight from the fetched
        block — the batched-DMA behaviour of the FPGA's hardware
        GetNeighbors engine.

        Returns a list of neighbor arrays aligned with ``vids`` (empty array
        for unknown VIDs), each equal to ``get_neighbors(vid)``.
        """
        vids_arr = np.asarray(vids, dtype=np.int64).reshape(-1)
        block, plan = self.fetch_plan(vids_arr)
        return neighbors_from_plan(vids_arr, block, plan)

    def fetch_plan(self, vids_arr):
        """Locked plan fetch over vids this store holds — the *fetch* phase
        of the batched queries (one queued scatter-read).  The sharded
        coordinator calls this once per shard, concurrently; the returned
        block is a snapshot copy, so selection can run outside the lock.
        One ``store.plan`` span, its page read a ``store.read`` inside it.
        """
        with spans.span("store.plan"), self._lock:
            return self._fetch_plan(
                np.asarray(vids_arr, dtype=np.int64).reshape(-1))

    def chain_pages(self, vids: np.ndarray, pgs: np.ndarray) -> np.ndarray:
        """Explicit H-chain page reads: page ``pgs[i]`` of ``vids[i]``'s
        chain, as ONE queued (cached) scatter-read.  The replicated
        coordinator's page-granular replica spread assigns individual
        chain pages to shards; this is the device-side command that
        serves a shard's share of them."""
        with self._lock:
            vids = np.asarray(vids, dtype=np.int64)
            if np.any(pgs >= self._chain_lens(vids)):
                raise KeyError("chain page not held by this store")
            return self._read_graph_pages(
                self._pool[self._chain_at[vids] + pgs])

    def plan_info(self, vids: np.ndarray) -> dict:
        """Planning metadata for a batch of vids, no page I/O: per-vid
        H-chain page count (0 when not H-mapped here) and the L-table
        range-search index (``searchsorted`` over the page keys; -1 when
        this store has no L pages).  Lets an array coordinator plan a
        replica-spread fetch with ONE call per vertex class instead of
        reaching into the mapping tables directly."""
        with self._lock:
            chain_len = self._chain_lens(vids)
            if len(self._l_keys):
                l_page = np.searchsorted(self._l_keys, vids)
            else:
                l_page = np.full(len(vids), -1, dtype=np.int64)
            return {"chain_len": chain_len,
                    "l_page": l_page.astype(np.int64)}

    def import_h_chain(self, vid: int, pages: np.ndarray) -> None:
        """Write a page-exact H chain from raw exported page data (slot
        layout and per-page counts preserved, next pointers re-addressed)
        — the import half of replica rebuild streaming.  Replicas keep
        IDENTICAL chain page layouts, which is what lets the spread fetch
        serve page i of a chain from any live owner.  Replace-safe: any
        chain the vid already owns is freed first, so a migration redo
        after a mid-copy failure cannot leak or double-map pages."""
        with self._lock:
            if self.kind_of(vid) == "H":
                self._free_chain(vid)
            new_lpns = [self.dev.alloc_front() for _ in range(len(pages))]
            for i, lpn in enumerate(new_lpns):
                page = np.asarray(pages[i], dtype=SLOT_DTYPE).copy()
                page[_H_NEXT] = new_lpns[i + 1] if i + 1 < len(new_lpns) \
                    else -1
                self.dev.write_page(lpn, page)
            self._map_chain(vid, new_lpns)
            self._map([vid], "H")
            self.stats.pages_h += len(new_lpns)

    def import_l_vertex(self, vid: int, nbrs: np.ndarray) -> None:
        """Install a complete L-type neighbor list for ``vid`` (the
        adjacency import half of class migration).  Replace-safe: any
        prior mapping — L node or H chain — is removed first, so a redo
        after a mid-copy failure converges to the same state."""
        with self._lock:
            vid = int(vid)
            self._drop_vertex_pages(vid)
            self._l_insert(vid,
                           np.asarray(nbrs, dtype=SLOT_DTYPE).reshape(-1))
            self._map([vid], "L")
            self.num_vertices = max(self.num_vertices, vid + 1)

    def drop_class(self, cls: int, modulus: int) -> int:
        """Free every vertex whose vid ≡ ``cls`` (mod ``modulus``) — the
        source-side release after a migrated class's routing flip commits.
        Embedding stripe pages are left in place (no longer addressed;
        the next rebuild compacts them).  Returns the vertex count
        dropped."""
        with self._lock:
            vids = np.flatnonzero(self._sel)
            vids = vids[vids % modulus == cls].tolist()
            for v in vids:
                self._drop_vertex_pages(v)
            return len(vids)

    def extend_embedding_table(self, n_rows: int) -> int:
        """Grow the embedding table by ``n_rows`` zero rows and return the
        row index of the first new row (the migration import base).  The
        table is rewritten to a fresh span; the old span is abandoned
        (the simulated device reclaims it on the next rebuild)."""
        with self._lock:
            d = self.feature_dim
            if d == 0:
                raise ValueError("no feature dim set; load a table first")
            if n_rows <= 0:
                return self._emb_rows
            old_rows = self._emb_rows
            old = (self._get_embeds_locked(np.arange(old_rows,
                                                     dtype=np.int64))
                   if old_rows else np.empty((0, d), dtype=np.float32))
            grown = np.concatenate(
                [old, np.zeros((n_rows, d), dtype=np.float32)], axis=0)
            self._write_embedding_table(grown)
            return old_rows

    def write_embed_rows(self, row0: int, rows: np.ndarray) -> None:
        """Overwrite the contiguous embedding rows ``[row0, row0+len)``
        in place (page-granular RMW) — the bulk import half of embedding
        migration.  Raises ``KeyError`` if no table is loaded and
        ``IndexError`` if the range exceeds the table."""
        if self._emb_base is None:
            raise KeyError("no embedding table loaded")
        with self._lock:
            d = self.feature_dim
            rows = np.ascontiguousarray(rows, dtype=np.float32).reshape(-1, d)
            m = len(rows)
            if m == 0:
                return
            if row0 < 0 or row0 + m > self._emb_rows:
                raise IndexError(
                    f"rows [{row0}, {row0 + m}) outside table "
                    f"of {self._emb_rows}")
            lo = row0 * d
            p0 = lo // SLOTS_PER_PAGE
            within = lo - p0 * SLOTS_PER_PAGE
            n_pages = -(-(within + m * d) // SLOTS_PER_PAGE)
            flat = self.dev.read_span(self._emb_base + p0, n_pages,
                                      tag="embed").copy()
            flat[within: within + m * d] = rows.reshape(-1).view(np.int32)
            for i in range(n_pages):
                self.dev.write_page(
                    self._emb_base + p0 + i,
                    flat[i * SLOTS_PER_PAGE: (i + 1) * SLOTS_PER_PAGE],
                    tag="embed")

    def sample_neighbors_batch(self, vids, fanout: int,
                               rng: np.random.Generator | None = None, *,
                               segments=None, rngs=None):
        """Fused near-storage GetNeighbors + fanout subsampling (B-1 half).

        The decisive hub optimisation: a power-law hub with a 30K-neighbor
        chain is *sampled by index* (Floyd, O(fanout)) against the chain's
        page counts, so only the selected slots are ever touched — the full
        neighbor list is never materialised.  Uniform draws are consumed in
        vid order, one ``fanout`` block per over-full vertex, identical to
        the reference sampler's per-vertex stream.

        Multi-request mode (the serving batcher): ``vids`` may concatenate
        several requests' frontiers — ``segments`` gives the per-request row
        counts and ``rngs`` the per-request generators.  Each segment's
        draws then come from its own stream, exactly as a solo call over
        that segment would consume them, so a coalesced super-request stays
        bit-identical per request while the page fetch remains ONE queued
        scatter-read for everything.

        Returns ``(sel, lens)``: selected neighbors flattened row-major and
        per-vid selection lengths (empty/unknown vids yield a self-loop).
        """
        vids_arr = np.asarray(vids, dtype=np.int64).reshape(-1)
        block, plan = self.fetch_plan(vids_arr)
        with spans.span("store.select"):
            return select_from_plan(vids_arr, block, plan, fanout, rng,
                                    segments=segments, rngs=rngs)

    @staticmethod
    def _l_locate_batch(block, q_row, lq):
        """``_l_scan`` for a whole batch: every L vid ``lq[i]`` looked up
        in the meta of its own page, ``block[q_row[i]]``, at once.

        A vid's entry is the first meta entry that holds it; its chunk ends
        at the smallest in-meta offset beyond its start that lies within
        the data (tombstones' offsets stay valid boundaries), else at the
        data length.  Returns ``(found, start, end)``, one per query.
        """
        n = block[q_row, _L_NNODES]
        dlen = block[q_row, _L_DATALEN][:, None]
        nmax = max(int(n.max()), 1)
        # meta of nodes 0..nmax-1 read backwards: vid 0, offset 0, vid 1, ...
        meta = block[q_row, _l_meta_off(nmax - 1): _L_DATALEN][:, ::-1]
        vids, offs = meta[:, 0::2], meta[:, 1::2]
        in_meta = np.arange(nmax)[None, :] < n[:, None]
        hit = in_meta & (vids == lq[:, None])
        start = offs[np.arange(len(lq)), hit.argmax(axis=1)]
        later = in_meta & (offs > start[:, None]) & (offs <= dlen)
        end = np.where(later, offs, dlen).min(axis=1)
        return (hit.any(axis=1), start.astype(np.int64),
                end.astype(np.int64))

    def get_embed(self, vid: int) -> np.ndarray:
        """Paper GetEmbed(VID): read only the pages covering row ``vid``."""
        if self._emb_base is None:
            raise KeyError("no embedding table loaded")
        with self._lock:
            d = self.feature_dim
            lo, hi = vid * d, (vid + 1) * d
            p0, p1 = lo // SLOTS_PER_PAGE, -(-hi // SLOTS_PER_PAGE)
            flat = self.dev.read_span(self._emb_base + p0, p1 - p0, tag="embed")
            row = flat[lo - p0 * SLOTS_PER_PAGE: hi - p0 * SLOTS_PER_PAGE]
            return row.view(np.float32).copy()

    def get_embeds(self, vids: np.ndarray) -> np.ndarray:
        """Coalesced batched embedding gather.

        All rows' covering pages are merged (duplicates and overlaps
        collapsed) into one queued read command; each row is then gathered
        once, straight from the page that holds it, into the output — the
        merged pages themselves are never copied.  The sequential layout
        of the embedding space (paper Fig. 7) means adjacent VIDs share
        pages, so the merged page set is far smaller than one span per row.
        """
        if self._emb_base is None:
            raise KeyError("no embedding table loaded")
        vids = np.asarray(vids, dtype=np.int64).reshape(-1)
        if not len(vids):
            return np.empty((0, self.feature_dim), dtype=np.float32)
        with self._lock:
            return self._get_embeds_locked(vids)

    def _get_embeds_locked(self, vids: np.ndarray) -> np.ndarray:
        d = self.feature_dim
        lo = vids * d
        p0 = lo // SLOTS_PER_PAGE
        p1 = (lo + d + SLOTS_PER_PAGE - 1) // SLOTS_PER_PAGE
        span = int((p1 - p0).max())                     # pages per row (>=1)
        cand = p0[:, None] + np.arange(span)[None, :]   # (rows, span)
        pages = np.unique(cand[cand < p1[:, None]])     # merged page set
        lpns = self._emb_base + pages
        # a row's pages are consecutive LPNs, so each row is contiguous in
        # the device's flat page array: one offset locates it
        offsets = self._emb_base * SLOTS_PER_PAGE + lo
        installed = 0
        with spans.span("store.read", tag="embed"):
            if self.cache is not None:
                rows, installed = self.cache.read_rows(self.dev, lpns,
                                                       offsets, d)
            else:
                rows = self.dev.read_rows(lpns, offsets, d)
        spans.count("store.embed_bytes",
                    rows.nbytes + installed * PAGE_BYTES)
        return rows.view(np.float32)

    # ============================================================== unit ops
    def _l_collect(self, page: np.ndarray) -> list[tuple[int, np.ndarray]]:
        """All live (vid, neighbor-chunk) pairs of an L page."""
        n = int(page[_L_NNODES])
        out = []
        for i in range(n):
            vid = int(page[_l_meta_vid(i)])
            if vid < 0:
                continue                                   # tombstone
            meta = self._l_scan(page, vid)
            _, start, ln = meta
            out.append((vid, page[start: start + ln].copy()))
        return out

    @staticmethod
    def _l_build_page(nodes: list[tuple[int, np.ndarray]]) -> np.ndarray:
        page = np.zeros(SLOTS_PER_PAGE, dtype=SLOT_DTYPE)
        dlen = 0
        for i, (vid, ch) in enumerate(nodes):
            page[_l_meta_vid(i)] = vid
            page[_l_meta_off(i)] = dlen
            page[dlen: dlen + len(ch)] = ch
            dlen += len(ch)
        page[_L_NNODES] = len(nodes)
        page[_L_DATALEN] = dlen
        return page

    def _l_split_insert(self, k: int, vid: int, chunk: np.ndarray) -> None:
        """Insert (vid, chunk) into L page k; split the page if full.

        Paper adaptation: the paper evicts one neighbor set to a fresh page,
        which breaks the range-search partition under out-of-order VIDs; we
        use a range-preserving page split instead (same cost profile: one
        extra page + one table insert)."""
        lpn = int(self._l_lpns[k])
        page = self.dev.read_page(lpn).copy()
        nodes = [nc for nc in self._l_collect(page) if nc[0] != vid]
        nodes.append((vid, chunk))
        nodes.sort(key=lambda nc: nc[0])
        need = sum(len(c) + 2 for _, c in nodes) + 2
        if need <= SLOTS_PER_PAGE:
            self.dev.write_page(lpn, self._l_build_page(nodes))
            if vid > self._l_keys[k]:
                self._l_keys[k] = vid
            return
        self.stats.l_evictions += 1
        sizes = np.array([len(c) + 2 for _, c in nodes])
        csum = np.cumsum(sizes)
        half = int(np.searchsorted(csum, csum[-1] / 2)) + 1
        half = min(max(half, 1), len(nodes) - 1)
        low, high = nodes[:half], nodes[half:]
        new_lpn, _ = self._new_l_page()
        self.dev.write_page(new_lpn, self._l_build_page(low))
        self.dev.write_page(lpn, self._l_build_page(high))
        self._l_keys[k] = max(self._l_keys[k], high[-1][0])
        self._l_keys = np.insert(self._l_keys, k, low[-1][0])
        self._l_lpns = np.insert(self._l_lpns, k, new_lpn)

    def add_vertex(self, vid: int, embed: np.ndarray | None = None) -> None:
        """AddVertex: self-loop only, starts as L-type (paper), placed by
        ``_l_insert``."""
        with self._lock:
            vid = int(vid)
            if self.kind_of(vid) is not None:
                return
            self.stats.unit_updates += 1
            self._l_insert(vid, np.array([vid], dtype=SLOT_DTYPE))
            self._map([vid], "L")
            self.num_vertices = max(self.num_vertices, vid + 1)
            if embed is not None:
                self.update_embed(vid, embed)

    def _l_insert(self, vid: int, chunk: np.ndarray) -> None:
        """Place the L node ``(vid, chunk)``: ascending VIDs append to the
        last page (a fresh page once it is full); out-of-order VIDs
        split-insert into the page covering their range."""
        if len(self._l_keys) and vid <= self._l_keys[-1]:
            k = int(np.searchsorted(self._l_keys, vid))
            self._l_split_insert(k, vid, chunk)
            return
        if len(self._l_keys):
            lpn = int(self._l_lpns[-1])
            page = self.dev.read_page(lpn).copy()
            if self._l_free_slots(page) >= len(chunk) + 2:
                self._l_append_node(page, vid, chunk)
                self.dev.write_page(lpn, page)
                self._l_keys[-1] = vid
                return
        lpn, _ = self._new_l_page()
        self.dev.write_page(lpn, self._l_build_page([(vid, chunk)]))
        k = int(np.searchsorted(self._l_keys, vid))
        self._l_keys = np.insert(self._l_keys, k, vid)
        self._l_lpns = np.insert(self._l_lpns, k, lpn)

    @staticmethod
    def _l_append_node(page: np.ndarray, vid: int, chunk: np.ndarray) -> None:
        n, dlen = int(page[_L_NNODES]), int(page[_L_DATALEN])
        page[_l_meta_vid(n)] = vid
        page[_l_meta_off(n)] = dlen
        page[dlen: dlen + len(chunk)] = chunk
        page[_L_NNODES] = n + 1
        page[_L_DATALEN] = dlen + len(chunk)

    def add_edge(self, dst: int, src: int) -> None:
        """AddEdge: undirected — inserts src into N(dst) and dst into N(src)."""
        with self._lock:
            self.stats.unit_updates += 1
            for v in (dst, src):
                self.add_vertex(v)              # no-op when v is mapped
            self._insert_neighbor(int(dst), int(src))
            if dst != src:
                self._insert_neighbor(int(src), int(dst))

    def _insert_neighbor(self, vid: int, nbr: int) -> None:
        if self.kind_of(vid) == "H":
            chain = self.chain_of(vid)
            tail = int(chain[-1])
            page = self.dev.read_page(tail).copy()
            cnt = int(page[_H_COUNT])
            if cnt < H_CAP:
                page[_H_DATA + cnt] = nbr
                page[_H_COUNT] = cnt + 1
                self.dev.write_page(tail, page)
            else:
                lpn = self.dev.alloc_front()
                newp = np.zeros(SLOTS_PER_PAGE, dtype=SLOT_DTYPE)
                newp[_H_COUNT] = 1
                newp[_H_NEXT] = -1
                newp[_H_DATA] = nbr
                self.dev.write_page(lpn, newp)
                page[_H_NEXT] = lpn
                self.dev.write_page(tail, page)
                self._map_chain(vid, np.append(chain, lpn))
                self.stats.pages_h += 1
            return
        # ---- L-type
        k = int(np.searchsorted(self._l_keys, vid))
        lpn = int(self._l_lpns[k])
        page = self.dev.read_page(lpn).copy()
        meta = self._l_scan(page, vid)
        assert meta is not None, f"vid {vid} missing from L page"
        mi, start, ln = meta

        if ln + 1 > self.h_threshold:
            # degree crossed the threshold: promote to H-type
            nbrs = np.concatenate([page[start: start + ln],
                                   np.array([nbr], dtype=SLOT_DTYPE)])
            self._l_remove_node(page, lpn, vid)
            self._promote_to_h(vid, nbrs)
            return

        if self._l_free_slots(page) >= 1:
            dlen = int(page[_L_DATALEN])
            if start + ln == dlen:                       # chunk is last: append
                page[dlen] = nbr
                page[_L_DATALEN] = dlen + 1
            else:                                        # relocate chunk to end
                chunk = page[start: start + ln].copy()
                self._l_shift_left(page, start, ln)
                dlen = int(page[_L_DATALEN])
                page[dlen: dlen + ln] = chunk
                page[dlen + ln] = nbr
                page[_l_meta_off(mi)] = dlen
                page[_L_DATALEN] = dlen + ln + 1
            self.dev.write_page(lpn, page)
            return

        # no space: range-preserving split of this page (paper adaptation
        # of the neighbor-set eviction; see _l_split_insert)
        chunk = np.concatenate([page[start: start + ln],
                                np.array([nbr], dtype=SLOT_DTYPE)])
        self._l_split_insert(k, vid, chunk)

    def _promote_to_h(self, vid: int, nbrs: np.ndarray) -> None:
        self._write_h_chain(vid, nbrs)
        self._map([vid], "H")

    def _write_h_chain(self, vid: int, nbrs: np.ndarray) -> None:
        """Write a fresh H chain for ``vid`` and record its mapping."""
        chain: list[int] = []
        for c0 in range(0, len(nbrs), H_CAP):
            chunk = nbrs[c0: c0 + H_CAP]
            lpn = self.dev.alloc_front()
            page = np.zeros(SLOTS_PER_PAGE, dtype=SLOT_DTYPE)
            page[_H_COUNT] = len(chunk)
            page[_H_NEXT] = -1
            page[_H_DATA: _H_DATA + len(chunk)] = chunk
            self.dev.write_page(lpn, page)
            self.stats.pages_h += 1
            if chain:
                prev = self.dev.read_page(chain[-1]).copy()
                prev[_H_NEXT] = lpn
                self.dev.write_page(chain[-1], prev)
            chain.append(lpn)
        self._map_chain(vid, chain)

    def _l_shift_left(self, page: np.ndarray, start: int, ln: int) -> None:
        """Remove chunk [start, start+ln) from the data region, fix offsets."""
        dlen = int(page[_L_DATALEN])
        page[start: dlen - ln] = page[start + ln: dlen].copy()
        page[_L_DATALEN] = dlen - ln
        n = int(page[_L_NNODES])
        for j in range(n):
            off = int(page[_l_meta_off(j)])
            if off > start:
                page[_l_meta_off(j)] = off - ln

    def _l_remove_node(self, page: np.ndarray, lpn: int, vid: int) -> None:
        meta = self._l_scan(page, vid)
        if meta is None:
            return
        mi, start, ln = meta
        self._l_shift_left(page, start, ln)
        page[_l_meta_vid(mi)] = -1                       # tombstone (paper: reuse)
        page[_l_meta_off(mi)] = int(page[_L_DATALEN])
        self.dev.write_page(lpn, page)

    def delete_edge(self, dst: int, src: int) -> None:
        with self._lock:
            self.stats.unit_updates += 1
            self._remove_neighbor(int(dst), int(src))
            if dst != src:
                self._remove_neighbor(int(src), int(dst))

    def _remove_neighbor(self, vid: int, nbr: int) -> None:
        kind = self.kind_of(vid)
        if kind is None:
            return
        if kind == "H":
            lpn = self._head(vid)
            while lpn >= 0:
                page = self.dev.read_page(lpn).copy()
                cnt = int(page[_H_COUNT])
                data = page[_H_DATA: _H_DATA + cnt]
                hit = np.nonzero(data == nbr)[0]
                if len(hit):
                    i = int(hit[0])
                    data[i] = data[cnt - 1]
                    page[_H_COUNT] = cnt - 1
                    self.dev.write_page(lpn, page)
                    return
                lpn = int(page[_H_NEXT])
            return
        hit = self._l_lookup_page(vid)
        if hit is None:
            return
        lpn, page = hit
        meta = self._l_scan(page, vid)
        if meta is None:
            return
        mi, start, ln = meta
        data = page[start: start + ln]
        pos = np.nonzero(data == nbr)[0]
        if not len(pos):
            return
        i = int(pos[0])
        page[start + i: start + ln - 1] = page[start + i + 1: start + ln].copy()
        self._l_shift_tail_one(page, start, ln)
        self.dev.write_page(lpn, page)

    def _l_shift_tail_one(self, page: np.ndarray, start: int, ln: int) -> None:
        """Shrink chunk at ``start`` by one slot, compacting the data region."""
        dlen = int(page[_L_DATALEN])
        page[start + ln - 1: dlen - 1] = page[start + ln: dlen].copy()
        page[_L_DATALEN] = dlen - 1
        n = int(page[_L_NNODES])
        for j in range(n):
            off = int(page[_l_meta_off(j)])
            if off >= start + ln:
                page[_l_meta_off(j)] = off - 1

    def delete_vertex(self, vid: int) -> None:
        with self._lock:
            vid = int(vid)
            self.stats.unit_updates += 1
            nbrs = self.get_neighbors(vid)
            for nbr in nbrs:
                if int(nbr) != vid:
                    self._remove_neighbor(int(nbr), vid)
            self._drop_vertex_pages(vid)

    def _drop_vertex_pages(self, vid: int) -> None:
        """Release ``vid``'s own mapping + pages (not its neighbors' backlinks
        — the sharded coordinator removes those on each neighbor's owning
        shard before calling this on the owner)."""
        kind = self.kind_of(vid)
        self._map([vid], None)
        if kind == "H":
            self._free_chain(vid)
        elif kind == "L":
            hit = self._l_lookup_page(vid)
            if hit is not None:
                lpn, page = hit
                self._l_remove_node(page, lpn, vid)

    def _free_chain(self, vid: int) -> None:
        """Unmap ``vid``'s H chain and free its pages, walked from the
        head through ``_H_NEXT``."""
        lpn = self._head(vid)
        self._unmap_chain(vid)
        while lpn >= 0:
            nxt = int(self.dev.read_page(lpn)[_H_NEXT])
            self.dev.free_page(lpn)
            lpn = nxt

    def update_embed(self, vid: int, embed: np.ndarray) -> None:
        """UpdateEmbed(VID, Embed): in-place page RMW of one feature row."""
        if self._emb_base is None:
            raise KeyError("no embedding table loaded")
        with self._lock:
            d = self.feature_dim
            row = np.ascontiguousarray(embed, dtype=np.float32).reshape(-1)
            assert row.size == d
            lo = vid * d
            p0 = lo // SLOTS_PER_PAGE
            within = lo - p0 * SLOTS_PER_PAGE
            n_pages = -(-(within + d) // SLOTS_PER_PAGE)
            flat = self.dev.read_span(self._emb_base + p0, n_pages,
                                      tag="embed").copy()
            flat[within: within + d] = row.view(np.int32)
            for i in range(n_pages):
                self.dev.write_page(
                    self._emb_base + p0 + i,
                    flat[i * SLOTS_PER_PAGE: (i + 1) * SLOTS_PER_PAGE],
                    tag="embed")

    # ============================================================== export
    def to_adjacency(self) -> dict[int, set[int]]:
        """Full adjacency export (oracle/validation only — reads every page)."""
        return {vid: set(int(x) for x in self.get_neighbors(vid))
                for vid in np.flatnonzero(self._sel).tolist()}


# ---------------------------------------------------------------- preprocessing
# The paper's G-1..G-4 UpdateGraph pipeline, exposed as SHARD-LOCAL pieces
# so the distributed ingest path (store/ingest.py) can run each stage where
# the data is: the coordinator ships raw edge chunks, every shard mirrors
# and buckets its chunks device-side ([G-2]/[G-3] routing), peers exchange
# buckets, and each shard sorts + builds its partition-local CSR
# ([G-3]/[G-4]) with the exact arithmetic the monolithic path uses — which
# is what makes the chunked load bit-identical to ``preprocess_edges`` +
# ``partition_csr``.

def mirror_edges(edge_array: np.ndarray, *,
                 already_undirected: bool = False) -> np.ndarray:
    """[G-2] mirror {dst,src}->{src,dst}: directed pair list of the
    undirected edge set (no-op when the input is already symmetric)."""
    e = np.asarray(edge_array, dtype=np.int64).reshape(-1, 2)
    if already_undirected or e.size == 0:
        return e
    return np.concatenate([e, e[:, ::-1]], axis=0)


def bucket_pairs(pairs: np.ndarray, n_shards: int, *,
                 replication: int = 1, placement=None) -> list[np.ndarray]:
    """[G-3] routing: directed pairs grouped by destination shard.

    Under the default map, replica ``r`` of row ``vid`` lives on shard
    ``(vid + r) % N``, so each pair is routed to the R shards that own
    its row — shard ``s`` receives the residue classes
    ``{(s - r) % N, r < R}``, exactly the classes ``partition_csr`` keeps.
    A ``placement`` (:class:`repro.store.placement.PlacementMap`) replaces
    that rule: pairs route by ``vid % C`` through the map's owner table
    (role order preserved, so stripe layouts follow ``pairs_of``).
    """
    pairs = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
    if placement is not None:
        cls = pairs[:, 0] % placement.n_classes
        out = []
        for s in range(n_shards):
            parts = [pairs[cls == c] for c, _r in placement.pairs_of(s)]
            parts = [p for p in parts if len(p)]
            out.append(np.concatenate(parts) if parts
                       else np.empty((0, 2), dtype=np.int64))
        return out
    cls = pairs[:, 0] % n_shards
    out: list[np.ndarray] = []
    for s in range(n_shards):
        parts = [pairs[cls == (s - r) % n_shards]
                 for r in range(int(replication))]
        parts = [p for p in parts if len(p)]
        out.append(np.concatenate(parts) if parts
                   else np.empty((0, 2), dtype=np.int64))
    return out


def csr_from_pairs(pairs: np.ndarray, num_vertices: int, *,
                   n_shards: int = 1, classes=None,
                   add_self_loops: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """[G-3]+[G-4] sort stage: directed pairs -> sorted, deduped CSR in
    the GLOBAL row space (non-owned rows keep zero-degree indptr slots,
    as ``partition_csr`` leaves them).

    ``classes`` restricts the [G-4] self-loop injection to the residue
    classes this shard owns; ``None`` injects loops for every vertex (the
    single-device/global case).  The key arithmetic (``row * n + nbr`` +
    ``np.unique``) is shared with the monolithic path, so a shard sorting
    only its own bucket produces exactly the owned-row restriction of the
    globally sorted CSR.
    """
    n = int(num_vertices)
    pairs = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
    if add_self_loops and n:
        if classes is None:
            loops = np.arange(n, dtype=np.int64)
        else:
            own = [np.arange(c, n, n_shards, dtype=np.int64)
                   for c in sorted(int(c) for c in classes)]
            loops = (np.concatenate(own) if own
                     else np.empty(0, dtype=np.int64))
        pairs = np.concatenate(
            [pairs, np.stack([loops, loops], axis=1)], axis=0)
    if pairs.size == 0:
        return np.zeros(n + 1, dtype=np.int64), np.empty(0, dtype=SLOT_DTYPE)
    key = pairs[:, 0] * n + pairs[:, 1]
    key = np.unique(key)                      # sort + dedup (the "radix sort")
    src = key // n
    dst = (key % n).astype(SLOT_DTYPE)
    counts = np.bincount(src, minlength=n)
    indptr = np.concatenate([[0], np.cumsum(counts)])
    return indptr, dst


def preprocess_edges(edge_array: np.ndarray, *, already_undirected: bool = False,
                     add_self_loops: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """Paper Fig. 2 graph preprocessing: edge array -> sorted undirected CSR.

    [G-1] load edge array  [G-2] mirror {dst,src}->{src,dst}
    [G-3] merge + sort -> VID-indexed structure  [G-4] inject self-loops.
    Returns (indptr, indices) CSR over max(VID)+1 vertices.  Never mutates
    its input (every stage concatenates into fresh arrays).
    """
    e = np.asarray(edge_array, dtype=np.int64).reshape(-1, 2)
    if e.size == 0:
        return np.zeros(1, dtype=np.int64), np.empty(0, dtype=SLOT_DTYPE)
    n = int(e.max()) + 1
    return csr_from_pairs(
        mirror_edges(e, already_undirected=already_undirected), n,
        add_self_loops=add_self_loops)
