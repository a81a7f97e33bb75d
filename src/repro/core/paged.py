"""Paged KV cache layout: the second `CacheBackend` implementation.

`MixedKVCache` stores every segment (hi/lo quantized stores, bf16 staging
window) as one dense per-slot array, so slot-level `insert`/`free` in the
continuous-batching engine are row writes across every payload leaf of the
full batch cache.  `PagedKVCache` splits the layout jetstream/vLLM-style:

  * the BULKY payload — bit-packed code blocks and the bf16 staging window —
    lives in fixed-size pages drawn from per-segment page pools
    (``(n_pages, h_kv, page_size, channels)``, physical page axis leading);
  * each batch slot addresses its pages through a per-slot **page table**
    (``(b, pages_per_slot)`` int32 physical page ids), so `insert`/`free`/
    `append` touch only one slot's pages instead of rewriting the batch;
  * the SMALL quantization metadata (ZipCache's channel-separable tokenwise
    design keeps it to per-token scales + per-channel normalizers), position/
    saliency state and the per-slot counters stay dense ``(b, ...)`` arrays —
    they are bookkeeping, reported as overhead by `nbytes`.

Numerical contract: every operation is implemented so the *logical dense
view* (`dense_view`, gathering pages back into a `MixedKVCache`) evolves
bit-identically to the mixed backend under the same operation sequence —
quantization granularity is per-slot exactly as in `core/kvcache.py`, never
per-page.  That is what makes greedy engine output token-identical across
backends (tests/test_backend_conformance.py).

Beyond the protocol, `PagedKVBackend.recompress_slot(cache, slot)` folds ONE
slot's staging pages by gathering that slot into a batch=1 dense view and
recompressing at 1/batch the FLOPs of the full-batch program — removing the
`slots`x worst-case penalty of `recompress(rows=...)` under staggered
admission (ROADMAP §Serving).  Every per-token recompression op is
row-independent, so the b=1 result is bitwise the full-batch row.

Static shapes throughout: page tables are fixed-size (pages are pre-assigned
round-robin across slots at init — slot s's j-th page is physical page
``j*b + s``, deliberately non-contiguous so nothing can shortcut the table),
`slot` operands stay traced, and capacities are padded UP to whole pages —
the page-size trade-off is internal fragmentation of at most
``page_size - 1`` tokens per segment per slot, visible in `nbytes`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kvcache as kvc
from repro.core import quant
from repro.core.policy import CompressionConfig

DEFAULT_PAGE_SIZE = 64


def n_pages(capacity: int, page_size: int) -> int:
    """Pages needed for `capacity` tokens (last page may be partial)."""
    return -(-capacity // page_size) if capacity else 0


def _strided_table(b: int, npp: int) -> jnp.ndarray:
    """Round-robin page assignment: slot s's j-th page is physical j*b + s."""
    return (jnp.arange(npp, dtype=jnp.int32)[None, :] * b
            + jnp.arange(b, dtype=jnp.int32)[:, None])


# ---------------------------------------------------------------------------
# Pool <-> dense-token-axis conversion
# ---------------------------------------------------------------------------

def _paginate(dense: jnp.ndarray, page_size: int) -> jnp.ndarray:
    """(b, h, S, c) -> (b, npp, h, page, c), zero-padding the token axis."""
    b, h, s, c = dense.shape
    npp = n_pages(s, page_size)
    pad = npp * page_size - s
    x = jnp.pad(dense, ((0, 0), (0, 0), (0, pad), (0, 0)))
    x = x.reshape(b, h, npp, page_size, c)
    return jnp.swapaxes(x, 1, 2)


def _gather_dense(pages: jnp.ndarray, table: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """Pages (P, h, page, c) via table (b, npp) -> dense (b, h, capacity, c)."""
    b, npp = table.shape
    _, h, page, c = pages.shape
    g = pages[table]                      # (b, npp, h, page, c)
    g = jnp.swapaxes(g, 1, 2)             # (b, h, npp, page, c)
    return g.reshape(b, h, npp * page, c)[:, :, :capacity]


def _scatter_dense(pages: jnp.ndarray, table: jnp.ndarray, dense: jnp.ndarray,
                   rows: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Write dense (b, h, S, c) into the pool at each slot's table pages.

    `rows`: optional (b,) bool — rows where it is False write nothing (their
    table entries are redirected out of bounds and dropped)."""
    if table.shape[1] == 0:
        return pages
    tbl = table
    if rows is not None:
        tbl = jnp.where(rows[:, None], table, pages.shape[0])
    upd = _paginate(dense.astype(pages.dtype), pages.shape[2])
    return pages.at[tbl].set(upd, mode="drop")


# ---------------------------------------------------------------------------
# PagedStore
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedStore:
    """One quantized token store, paged.

    k_pages/v_pages hold the payload (packed int8 codes, or raw bf16 when
    bits >= 16) in physical pages; `table` maps (slot, logical page) ->
    physical page; `k_meta`/`v_meta` are `QuantizedTensor`s with
    ``codes=None`` — per-slot quantization parameters only (the codes live
    in the pools); pos/acc/nnz are the dense per-slot saliency state,
    identical to `TokenStore`'s.
    """

    k_pages: jnp.ndarray          # (P, h_kv, page, ck)
    v_pages: jnp.ndarray          # (P, h_kv, page, cv)
    table: jnp.ndarray            # (b, npp) int32
    k_meta: quant.QuantizedTensor
    v_meta: quant.QuantizedTensor
    pos: jnp.ndarray              # (b, S) int32, -1 = empty
    acc: jnp.ndarray              # (b, S) f32
    nnz: jnp.ndarray              # (b, S) f32
    # Free-list layout marker (static aux data, see core/alloc.py): the id
    # of the pool's SINK page — unallocated logical pages point at it, so
    # the pool holds `null_page` usable pages plus the sink at index
    # `null_page`.  None = static layout (every pool page is slot-owned).
    null_page: Optional[int] = None

    def tree_flatten(self):
        return ((self.k_pages, self.v_pages, self.table, self.k_meta,
                 self.v_meta, self.pos, self.acc, self.nnz), (self.null_page,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, null_page=aux[0])

    @property
    def capacity(self) -> int:
        return self.pos.shape[-1]

    @property
    def valid(self) -> jnp.ndarray:
        return self.pos >= 0

    def dense(self) -> kvc.TokenStore:
        """Gather pages back into the logical `TokenStore` (exact layout)."""
        k = dataclasses.replace(
            self.k_meta,
            codes=_gather_dense(self.k_pages, self.table, self._codes_cap(self.k_meta)))
        v = dataclasses.replace(
            self.v_meta,
            codes=_gather_dense(self.v_pages, self.table, self._codes_cap(self.v_meta)))
        return kvc.TokenStore(k, v, self.pos, self.acc, self.nnz)

    def _codes_cap(self, meta: quant.QuantizedTensor) -> int:
        # codes token axis == logical token axis (packing is channelwise)
        return meta.shape[-2]

    def _n_pages(self) -> int:
        """Physical pages in the pool (leading-axis count; a stacked group
        axis, if any, is folded into the per-page byte size instead)."""
        return int(self.k_pages.shape[-4])

    def _page_nbytes(self, pages: jnp.ndarray) -> int:
        """Bytes of ONE physical page (times the stacked group axis)."""
        n = self._n_pages()
        return int(pages.size // n * pages.dtype.itemsize) if n else 0

    def _live_pages(self) -> int:
        """Pages referenced by some slot's table row.  Static layout: every
        pool page is slot-owned.  Free-list layout: host-side table scan —
        unreferenced pages (and the sink) are free-pool overhead."""
        if self.null_page is None:
            return self._n_pages()
        ids = np.unique(np.asarray(self.table))
        return int((ids < self.null_page).sum())

    def nbytes_packed(self) -> int:
        """Live payload pages + quantization parameters (page-granular:
        includes the zero padding of each slot's partial last page; the
        free-list layout's unallocated pages are NOT payload — they are
        reported by `nbytes_free_pool` and count as pool overhead)."""
        live = self._live_pages()
        n = live * (self._page_nbytes(self.k_pages)
                    + self._page_nbytes(self.v_pages))
        for meta in (self.k_meta, self.v_meta):
            for t in (meta.scale, meta.zero, meta.channel_scale):
                if t is not None:
                    n += t.size * t.dtype.itemsize
        return int(n)

    def nbytes_free_pool(self) -> int:
        """Bytes of free-pool pages: pool pages not referenced by any slot
        (plus the sink page).  0 for the static layout."""
        free = self._n_pages() - self._live_pages()
        return int(free * (self._page_nbytes(self.k_pages)
                           + self._page_nbytes(self.v_pages)))


def _store_from_token_store(ts: kvc.TokenStore, page_size: int,
                            table: jnp.ndarray) -> PagedStore:
    """Distribute a dense `TokenStore`'s payload into pages (pure layout)."""
    b, npp = table.shape
    pools = []
    for qt in (ts.k, ts.v):
        paged = _paginate(qt.codes, page_size)          # (b, npp, h, page, c)
        pool = paged.reshape(b * npp, *paged.shape[2:]) if npp else \
            jnp.zeros((0, *paged.shape[2:]), paged.dtype)
        # place each slot's pages at its table-assigned physical ids
        pool = jnp.zeros_like(pool).at[table].set(paged) if npp else pool
        pools.append(pool)
    return PagedStore(
        k_pages=pools[0], v_pages=pools[1], table=table,
        k_meta=dataclasses.replace(ts.k, codes=None),
        v_meta=dataclasses.replace(ts.v, codes=None),
        pos=ts.pos, acc=ts.acc, nnz=ts.nnz)


# ---------------------------------------------------------------------------
# PagedKVCache
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """Paged mixed-precision KV cache.  Field names mirror `MixedKVCache`
    (hi/lo stores, win_* state, length, win_fill) so the metadata-only
    operations in `core/kvcache.py` — `update_probe_state`, `free_slot`,
    `window_is_full` — apply to it unchanged via duck typing."""

    hi: PagedStore
    lo: PagedStore
    win_k_pages: jnp.ndarray      # (P_w, h_kv, page, d) bf16 staging pages
    win_v_pages: jnp.ndarray
    win_table: jnp.ndarray        # (b, npp_w) int32
    win_pos: jnp.ndarray          # (b, W) int32, -1 empty
    win_acc: jnp.ndarray          # (b, W) f32
    win_nnz: jnp.ndarray          # (b, W) f32
    length: jnp.ndarray           # (b,) int32
    win_fill: jnp.ndarray         # (b,) int32
    # sink-page id of the staging-window pool (see PagedStore.null_page);
    # None = static layout
    win_null_page: Optional[int] = None

    def tree_flatten(self):
        return ((self.hi, self.lo, self.win_k_pages, self.win_v_pages,
                 self.win_table, self.win_pos, self.win_acc, self.win_nnz,
                 self.length, self.win_fill), (self.win_null_page,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, win_null_page=aux[0])

    @property
    def page_size(self) -> int:
        return self.win_k_pages.shape[2]

    @property
    def window(self) -> int:
        return self.win_pos.shape[-1]

    @property
    def capacity(self) -> int:
        return self.hi.capacity + self.lo.capacity + self.window

    def dense_view(self) -> kvc.MixedKVCache:
        """Gather all pages into the equivalent `MixedKVCache` (bit-exact
        logical contents; used for attention/recompression math)."""
        w = self.window
        k_win = _gather_dense(self.win_k_pages, self.win_table, w)
        v_win = _gather_dense(self.win_v_pages, self.win_table, w)
        return kvc.MixedKVCache(
            hi=self.hi.dense(), lo=self.lo.dense(), k_win=k_win, v_win=v_win,
            win_pos=self.win_pos, win_acc=self.win_acc, win_nnz=self.win_nnz,
            length=self.length, win_fill=self.win_fill)

    def _win_pages_total(self) -> int:
        return int(self.win_k_pages.shape[-4])

    def _win_live_pages(self) -> int:
        if self.win_null_page is None:
            return self._win_pages_total()
        ids = np.unique(np.asarray(self.win_table))
        return int((ids < self.win_null_page).sum())

    def _win_page_nbytes(self) -> int:
        n = self._win_pages_total()
        if not n:
            return 0
        return int(sum(t.size // n * t.dtype.itemsize
                       for t in (self.win_k_pages, self.win_v_pages)))

    def nbytes_packed(self) -> int:
        n = self.hi.nbytes_packed() + self.lo.nbytes_packed()
        n += self._win_live_pages() * self._win_page_nbytes()
        return int(n)

    def nbytes_free_pool(self) -> int:
        """Bytes of unallocated (free-list + sink) pages across the three
        pools — provisioned pool capacity not currently holding any slot's
        payload.  0 for the static layout, where every page is slot-owned."""
        n = self.hi.nbytes_free_pool() + self.lo.nbytes_free_pool()
        n += (self._win_pages_total() - self._win_live_pages()) \
            * self._win_page_nbytes()
        return int(n)

    def nbytes_total(self) -> int:
        return int(sum(l.size * l.dtype.itemsize
                       for l in jax.tree_util.tree_leaves(self)))

    def nbytes_overhead(self) -> int:
        """Page tables + positions/saliency/counters + free-pool pages."""
        return self.nbytes_total() - self.nbytes_packed()


def from_mixed(mx: kvc.MixedKVCache, page_size: int = DEFAULT_PAGE_SIZE,
               tables: Optional[Tuple[jnp.ndarray, ...]] = None) -> PagedKVCache:
    """Pure layout conversion: page the payload, keep metadata dense.

    `tables`: optional (hi, lo, win) page tables to place pages at (defaults
    to the strided round-robin assignment)."""
    b = mx.length.shape[0]
    if tables is None:
        tables = tuple(_strided_table(b, n_pages(c, page_size))
                       for c in (mx.hi.capacity, mx.lo.capacity, mx.window))
    t_hi, t_lo, t_w = tables
    hi = _store_from_token_store(mx.hi, page_size, t_hi)
    lo = _store_from_token_store(mx.lo, page_size, t_lo)
    npp_w = t_w.shape[1]
    win_pools = []
    for dense in (mx.k_win, mx.v_win):
        paged = _paginate(dense, page_size)
        pool = jnp.zeros((b * npp_w, *paged.shape[2:]), dense.dtype)
        win_pools.append(pool.at[t_w].set(paged) if npp_w else pool)
    return PagedKVCache(
        hi=hi, lo=lo, win_k_pages=win_pools[0], win_v_pages=win_pools[1],
        win_table=t_w, win_pos=mx.win_pos, win_acc=mx.win_acc,
        win_nnz=mx.win_nnz, length=mx.length, win_fill=mx.win_fill)


# ---------------------------------------------------------------------------
# Free-list layout (elastic pools; allocation lives in core/alloc.py)
# ---------------------------------------------------------------------------

def freelist_pool_pages(b: int, npp: int, fraction: float) -> int:
    """Usable pool pages for a segment under `pool_fraction`: the given
    fraction of the static worst case (`b * npp`), never below one full
    request's worth (`npp` — a lone max-length request must always fit)."""
    if npp == 0:
        return 0
    return max(int(np.ceil(b * npp * fraction)), npp)


def from_mixed_freelist(mx: kvc.MixedKVCache, page_size: int,
                        pool_pages: Tuple[int, int, int]) -> PagedKVCache:
    """EMPTY free-list cache shaped like `mx` (which must be an
    `init_cache` result — all-zero payload, no valid tokens).

    Pools hold `pool_pages[i]` usable pages plus one SINK page; every table
    entry starts at the sink id (`null_page`).  Pages are granted to slots
    host-side by `alloc.FreeListAllocator` between jitted steps — reads of
    unallocated logical pages land on the sink (finite garbage that no
    consumer lets influence live rows: attention masks invalid positions
    to exact-zero weights, recompression zeroes invalid payload), writes
    to NULL entries are absorbed by the sink."""
    base = from_mixed(mx, page_size)
    b = int(mx.length.shape[0])
    p_hi, p_lo, p_w = pool_pages

    def seg(store: PagedStore, usable: int) -> PagedStore:
        npp = store.table.shape[1]
        if npp == 0:
            return store
        return dataclasses.replace(
            store,
            k_pages=jnp.zeros((usable + 1, *store.k_pages.shape[1:]),
                              store.k_pages.dtype),
            v_pages=jnp.zeros((usable + 1, *store.v_pages.shape[1:]),
                              store.v_pages.dtype),
            table=jnp.full((b, npp), usable, jnp.int32),
            null_page=usable)

    out = dataclasses.replace(base, hi=seg(base.hi, p_hi),
                              lo=seg(base.lo, p_lo))
    npp_w = base.win_table.shape[1]
    if npp_w == 0:
        return out
    return dataclasses.replace(
        out,
        win_k_pages=jnp.zeros((p_w + 1, *base.win_k_pages.shape[1:]),
                              base.win_k_pages.dtype),
        win_v_pages=jnp.zeros((p_w + 1, *base.win_v_pages.shape[1:]),
                              base.win_v_pages.dtype),
        win_table=jnp.full((b, npp_w), p_w, jnp.int32),
        win_null_page=p_w)


def with_tables(cache: PagedKVCache, t_hi: np.ndarray, t_lo: np.ndarray,
                t_win: np.ndarray) -> PagedKVCache:
    """Install allocator-produced (slots, npp) page tables onto a cache
    element, broadcasting over a stacked leading group axis if present.
    Values-only: shapes and dtypes are unchanged, so jitted programs that
    close over this cache's avals never retrace.

    Accepts host OR device tables.  Callers installing onto many elements
    should upload each table once (`jnp.asarray`) and pass the device
    array — the broadcast then happens device-side instead of shipping a
    full broadcast-shaped host copy per element per table."""
    def put(cur: jnp.ndarray, new) -> jnp.ndarray:
        if cur.shape[-1] == 0:
            return cur
        return jnp.broadcast_to(
            jnp.asarray(new, jnp.int32),  # sync: ok(no-op for device tables; one small upload when handed a host table)
            cur.shape)

    return dataclasses.replace(
        cache,
        hi=dataclasses.replace(cache.hi, table=put(cache.hi.table, t_hi)),
        lo=dataclasses.replace(cache.lo, table=put(cache.lo.table, t_lo)),
        win_table=put(cache.win_table, t_win))


# ---------------------------------------------------------------------------
# Ops (decode append, slot insert, recompress write-back)
# ---------------------------------------------------------------------------

def append_token(cache: PagedKVCache, k_t: jnp.ndarray, v_t: jnp.ndarray,
                 active: Optional[jnp.ndarray] = None) -> PagedKVCache:
    """Append one decoded token per slot into its CURRENT staging page.

    Bookkeeping is identical to `kvcache.append_token`; the payload write
    resolves (slot, win_fill) -> (physical page, in-page offset) through the
    page table and touches exactly one page per active slot."""
    b = cache.win_pos.shape[0]
    page = cache.page_size
    w = cache.window
    bidx = jnp.arange(b)
    fill = cache.win_fill
    inc = jnp.ones((b,), jnp.int32)
    if active is not None:
        act = active.astype(jnp.bool_)
        fill = jnp.where(act, fill, w)    # out-of-bounds -> dropped write
        inc = act.astype(jnp.int32)
    j = jnp.minimum(fill // page, jnp.maximum(cache.win_table.shape[1] - 1, 0))
    off = fill % page
    phys = jnp.take_along_axis(cache.win_table, j[:, None], axis=1)[:, 0]
    phys = jnp.where(fill < w, phys, cache.win_k_pages.shape[0])  # OOB drop
    win_k = cache.win_k_pages.at[phys, :, off].set(
        k_t.astype(cache.win_k_pages.dtype), mode="drop")
    win_v = cache.win_v_pages.at[phys, :, off].set(
        v_t.astype(cache.win_v_pages.dtype), mode="drop")
    win_pos = cache.win_pos.at[bidx, fill].set(cache.length, mode="drop")
    return dataclasses.replace(
        cache, win_k_pages=win_k, win_v_pages=win_v, win_pos=win_pos,
        length=cache.length + inc, win_fill=cache.win_fill + inc)


def _strip_store(s: PagedStore) -> PagedStore:
    """A store's dense per-slot metadata only (pools + table removed)."""
    return dataclasses.replace(s, k_pages=None, v_pages=None, table=None)


def _meta_only(cache: PagedKVCache) -> PagedKVCache:
    """Strip pools + tables: the dense per-slot metadata subtree (same
    structure for a b=1 slice and the full batch, so row updates pair up)."""
    return dataclasses.replace(
        cache, hi=_strip_store(cache.hi), lo=_strip_store(cache.lo),
        win_k_pages=None, win_v_pages=None, win_table=None)


def _with_payload_of(meta: PagedKVCache, src: PagedKVCache) -> PagedKVCache:
    """Re-attach `src`'s pools and tables onto a metadata-only tree."""
    def attach(m, s):
        return dataclasses.replace(m, k_pages=s.k_pages, v_pages=s.v_pages,
                                   table=s.table)
    return dataclasses.replace(
        meta, hi=attach(meta.hi, src.hi), lo=attach(meta.lo, src.lo),
        win_k_pages=src.win_k_pages, win_v_pages=src.win_v_pages,
        win_table=src.win_table)


def _slot_pages(pages: jnp.ndarray, table: jnp.ndarray, slot) -> jnp.ndarray:
    """One slot's pages in logical order: (npp, h, page, c). Traced `slot`."""
    row = jax.lax.dynamic_slice_in_dim(table, slot, 1, axis=0)[0]  # (npp,)
    return pages[row]


def insert_slot(dst: PagedKVCache, src: PagedKVCache, slot,
                batch_axis: int = 0) -> PagedKVCache:
    """Write a 1-request cache `src` into batch slot `slot` of `dst`.

    Payload: src's logical pages are scattered onto the physical pages the
    slot owns in dst's table (npp pages per segment — nothing else in the
    pools is touched).  Metadata: plain row writes.  batch_axis=1 handles
    group-stacked caches (leaves (G, ...)) by vmapping over the stack."""
    if batch_axis == 1:
        return jax.vmap(lambda d, s: insert_slot(d, s, slot))(dst, src)

    def scatter_seg(d_pages, d_table, s_pages, s_table):
        if d_table.shape[1] == 0:
            return d_pages
        logical = s_pages[s_table[0]]                 # (npp, h, page, c)
        row = jax.lax.dynamic_slice_in_dim(d_table, slot, 1, axis=0)[0]
        return d_pages.at[row].set(logical.astype(d_pages.dtype))

    meta = kvc.tree_update_rows(_meta_only(dst), _meta_only(src), slot, axis=0)
    out = _with_payload_of(meta, dst)
    hi = dataclasses.replace(
        out.hi,
        k_pages=scatter_seg(dst.hi.k_pages, dst.hi.table, src.hi.k_pages, src.hi.table),
        v_pages=scatter_seg(dst.hi.v_pages, dst.hi.table, src.hi.v_pages, src.hi.table))
    lo = dataclasses.replace(
        out.lo,
        k_pages=scatter_seg(dst.lo.k_pages, dst.lo.table, src.lo.k_pages, src.lo.table),
        v_pages=scatter_seg(dst.lo.v_pages, dst.lo.table, src.lo.v_pages, src.lo.table))
    return dataclasses.replace(
        out, hi=hi, lo=lo,
        win_k_pages=scatter_seg(dst.win_k_pages, dst.win_table,
                                src.win_k_pages, src.win_table),
        win_v_pages=scatter_seg(dst.win_v_pages, dst.win_table,
                                src.win_v_pages, src.win_table))


def extract_slot(cache: PagedKVCache, slot, batch_axis: int = 0):
    """One slot's complete device state: payload pages in LOGICAL order per
    segment plus its dense metadata rows — the device half of swap-out
    (`core/swap.py` owns the host mirrors).

    Payload is gathered through the slot's page table with `_slot_pages`, so
    each segment yields (npp, h, page, c) regardless of which physical pages
    the slot holds.  Table entries past the granted prefix are NULL (sink id)
    and gather sink garbage — harmless, because validity is pos-driven and
    `restore_slot` scatters those logical pages back into the sink.  Keeping
    the full npp extent (instead of the valid prefix) keeps shapes static so
    ONE warm program serves every occupancy.

    Returns a dict pytree (`hi_k/hi_v/lo_k/lo_v/win_k/win_v` page stacks and
    a `meta` leaf list) rather than a PagedKVCache: the b=1 metadata rows and
    the logical page stacks don't form a valid cache (no pools/tables), and a
    flat list sidesteps the QuantizedTensor aux-shape mismatch exactly like
    `kvcache.tree_update_rows`.  batch_axis=1 vmaps over a stacked leading
    group axis (5-d pools)."""
    if batch_axis == 1:
        return jax.vmap(lambda c: extract_slot(c, slot))(cache)

    def gather_seg(pages, table):
        if table.shape[1] == 0:
            return pages[:0]
        return _slot_pages(pages, table, slot)

    return {
        "hi_k": gather_seg(cache.hi.k_pages, cache.hi.table),
        "hi_v": gather_seg(cache.hi.v_pages, cache.hi.table),
        "lo_k": gather_seg(cache.lo.k_pages, cache.lo.table),
        "lo_v": gather_seg(cache.lo.v_pages, cache.lo.table),
        "win_k": gather_seg(cache.win_k_pages, cache.win_table),
        "win_v": gather_seg(cache.win_v_pages, cache.win_table),
        "meta": [jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=0)
                 for x in jax.tree_util.tree_leaves(_meta_only(cache))],
    }


def restore_slot(cache: PagedKVCache, payload, slot,
                 batch_axis: int = 0) -> PagedKVCache:
    """Inverse of `extract_slot`: scatter a swapped-out slot's payload onto
    the physical pages its NEW table row grants and rewrite its metadata
    rows.  The allocator re-granted `pages_for(occ)` pages host-side before
    this runs, so every live logical page lands on a real physical page;
    logical pages past the grant hit NULL entries and are absorbed by the
    sink (don't-care, validity is pos-driven).  Bitwise: pages and metadata
    return exactly the bytes `extract_slot` captured."""
    if batch_axis == 1:
        return jax.vmap(lambda c, p: restore_slot(c, p, slot))(cache, payload)

    def scatter_seg(pages, table, logical):
        if table.shape[1] == 0:
            return pages
        row = jax.lax.dynamic_slice_in_dim(table, slot, 1, axis=0)[0]
        return pages.at[row].set(logical.astype(pages.dtype))

    meta_leaves, treedef = jax.tree_util.tree_flatten(_meta_only(cache))
    new_meta = [jax.lax.dynamic_update_slice_in_dim(d, s.astype(d.dtype),
                                                    slot, axis=0)
                for d, s in zip(meta_leaves, payload["meta"])]
    out = _with_payload_of(jax.tree_util.tree_unflatten(treedef, new_meta),
                           cache)
    hi = dataclasses.replace(
        out.hi,
        k_pages=scatter_seg(cache.hi.k_pages, cache.hi.table, payload["hi_k"]),
        v_pages=scatter_seg(cache.hi.v_pages, cache.hi.table, payload["hi_v"]))
    lo = dataclasses.replace(
        out.lo,
        k_pages=scatter_seg(cache.lo.k_pages, cache.lo.table, payload["lo_k"]),
        v_pages=scatter_seg(cache.lo.v_pages, cache.lo.table, payload["lo_v"]))
    return dataclasses.replace(
        out, hi=hi, lo=lo,
        win_k_pages=scatter_seg(cache.win_k_pages, cache.win_table,
                                payload["win_k"]),
        win_v_pages=scatter_seg(cache.win_v_pages, cache.win_table,
                                payload["win_v"]))


def free_slot(cache: PagedKVCache, slot, batch_axis: int = 0) -> PagedKVCache:
    """Retire a slot: invalidate its dense metadata rows.  Pages are left
    stale (validity is pos-driven, exactly as in the mixed layout).  With
    the static round-robin assignment the slot keeps its pages; under the
    free-list layout the engine-level allocator (core/alloc.py) returns
    them to the free list and NULLs the slot's table rows host-side — this
    jitted program only touches metadata either way."""
    return kvc.free_slot(cache, slot, batch_axis=batch_axis)


def copy_pages(cache: PagedKVCache,
               moves: Dict[str, Tuple[jnp.ndarray, jnp.ndarray]]
               ) -> PagedKVCache:
    """Copy physical pages inside each pool: pages `src[i]` -> `dst[i]` per
    segment ("hi"/"lo"/"win").  The copy-on-write half of shared-prefix
    dedup (core/alloc.py `privatize`): the allocator repoints a slot's
    table at fresh pages host-side, and this program materializes their
    payload device-side before any fold reads through the new table.

    `moves` carries fixed-length int32 id vectors — the engine pads unused
    entries with the segment's SINK id, so sink->sink self-copies absorb
    the padding and the program never retraces on the number of real moves.
    Tables and metadata are untouched (pure pool payload permutation); a
    stacked leading group axis (5-d pools) is broadcast over."""
    def cp(pages, mv):
        src, dst = mv
        if pages.shape[-4] == 0:
            return pages
        if pages.ndim == 5:                    # (G, P, h, page, c)
            return pages.at[:, dst].set(pages[:, src])
        return pages.at[dst].set(pages[src])

    hi = dataclasses.replace(
        cache.hi,
        k_pages=cp(cache.hi.k_pages, moves["hi"]),
        v_pages=cp(cache.hi.v_pages, moves["hi"]))
    lo = dataclasses.replace(
        cache.lo,
        k_pages=cp(cache.lo.k_pages, moves["lo"]),
        v_pages=cp(cache.lo.v_pages, moves["lo"]))
    return dataclasses.replace(
        cache, hi=hi, lo=lo,
        win_k_pages=cp(cache.win_k_pages, moves["win"]),
        win_v_pages=cp(cache.win_v_pages, moves["win"]))


def _write_back(cache: PagedKVCache, mx: kvc.MixedKVCache,
                rows: Optional[jnp.ndarray] = None) -> PagedKVCache:
    """Scatter a recompressed dense cache back into the paged layout,
    restricted to `rows` when given (other slots keep pages AND metadata)."""
    def seg(store: PagedStore, ts: kvc.TokenStore) -> PagedStore:
        # pools: rows-masked scatter; metadata: replaced wholesale here, the
        # caller's final row select restores the untouched slots' rows
        return PagedStore(
            _scatter_dense(store.k_pages, store.table, ts.k.codes, rows),
            _scatter_dense(store.v_pages, store.table, ts.v.codes, rows),
            store.table,
            dataclasses.replace(ts.k, codes=None),
            dataclasses.replace(ts.v, codes=None),
            ts.pos, ts.acc, ts.nnz, null_page=store.null_page)

    win_k = _scatter_dense(cache.win_k_pages, cache.win_table, mx.k_win, rows)
    win_v = _scatter_dense(cache.win_v_pages, cache.win_table, mx.v_win, rows)
    out = dataclasses.replace(
        cache, hi=seg(cache.hi, mx.hi), lo=seg(cache.lo, mx.lo),
        win_k_pages=win_k, win_v_pages=win_v,
        win_pos=mx.win_pos, win_acc=mx.win_acc, win_nnz=mx.win_nnz,
        length=mx.length, win_fill=mx.win_fill)
    if rows is None:
        return out
    sel = kvc.tree_select_rows(rows, _meta_only(out), _meta_only(cache))
    return _with_payload_of(sel, out)


def recompress(cfg: CompressionConfig, cache: PagedKVCache,
               rows: Optional[jnp.ndarray] = None, eff=None) -> PagedKVCache:
    """Fold staging pages back into the stores (paper Alg. 3): the dense
    recompression math on the gathered view, scattered back page-wise.
    `rows` restricts the write-back to a subset of slots (mask semantics
    identical to the mixed backend; for per-slot cost see recompress_slot).
    `eff` (precision map / downshift rung) passes straight through to the
    dense recompression — codes stay packed at the container width, so the
    page layout is map-independent."""
    mx = kvc.recompress(cfg, cache.dense_view(), rows=None, eff=eff)
    return _write_back(cache, mx, rows=rows)


def _slice_slot_view(cache: PagedKVCache, slot) -> kvc.MixedKVCache:
    """One slot's logical cache as a batch=1 dense `MixedKVCache`."""
    def row(x):
        return jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=0)

    def store(s: PagedStore) -> kvc.TokenStore:
        out = []
        for pages, meta in ((s.k_pages, s.k_meta), (s.v_pages, s.v_meta)):
            logical = _slot_pages(pages, s.table, slot)       # (npp,h,page,c)
            npp, h, page, c = logical.shape
            dense = jnp.swapaxes(logical, 0, 1).reshape(1, h, npp * page, c)
            dense = dense[:, :, :meta.shape[-2]]
            params = jax.tree_util.tree_map(row, (meta.scale, meta.zero,
                                                  meta.channel_scale))
            out.append(quant.QuantizedTensor(
                dense, *params, meta.bits, (1, *meta.shape[1:])))
        return kvc.TokenStore(out[0], out[1], row(s.pos), row(s.acc), row(s.nnz))

    w = cache.window
    win = []
    for pages in (cache.win_k_pages, cache.win_v_pages):
        logical = _slot_pages(pages, cache.win_table, slot)
        npp, h, page, c = logical.shape
        win.append(jnp.swapaxes(logical, 0, 1).reshape(1, h, npp * page, c)[:, :, :w])
    return kvc.MixedKVCache(
        hi=store(cache.hi), lo=store(cache.lo), k_win=win[0], v_win=win[1],
        win_pos=row(cache.win_pos), win_acc=row(cache.win_acc),
        win_nnz=row(cache.win_nnz), length=row(cache.length),
        win_fill=row(cache.win_fill))


def recompress_slot(cfg: CompressionConfig, cache: PagedKVCache,
                    slot, eff=None) -> PagedKVCache:
    """Fold ONE slot's staging pages: gather the slot to a batch=1 dense
    view, recompress at 1/batch the full-program FLOPs, scatter the result
    back onto the slot's pages + metadata row.  Bitwise the same result as
    `recompress(rows=onehot(slot))` — every recompression op is
    row-independent — at per-request instead of full-batch cost.  `eff`
    must be per-head/scalar shaped (the view is batch=1): slot folds fold
    a SCALAR rung in, not the (b,) batch rung."""
    mx1 = kvc.recompress(cfg, _slice_slot_view(cache, slot), rows=None, eff=eff)

    def seg(store: PagedStore, ts: kvc.TokenStore) -> PagedStore:
        def scat(pages, codes):
            if store.table.shape[1] == 0:
                return pages
            row = jax.lax.dynamic_slice_in_dim(store.table, slot, 1, axis=0)[0]
            return pages.at[row].set(
                _paginate(codes.astype(pages.dtype), pages.shape[2])[0])
        meta = kvc.tree_update_rows(
            _strip_store(store),
            kvc.TokenStore(dataclasses.replace(ts.k, codes=None),
                           dataclasses.replace(ts.v, codes=None),
                           ts.pos, ts.acc, ts.nnz),
            slot, axis=0)
        return dataclasses.replace(meta, k_pages=scat(store.k_pages, ts.k.codes),
                                   v_pages=scat(store.v_pages, ts.v.codes),
                                   table=store.table)

    def win_scat(pages, dense):
        if cache.win_table.shape[1] == 0:
            return pages
        row = jax.lax.dynamic_slice_in_dim(cache.win_table, slot, 1, axis=0)[0]
        return pages.at[row].set(_paginate(dense.astype(pages.dtype),
                                           pages.shape[2])[0])

    def rowup(d, s):
        return jax.lax.dynamic_update_slice_in_dim(d, s.astype(d.dtype), slot, axis=0)

    return dataclasses.replace(
        cache, hi=seg(cache.hi, mx1.hi), lo=seg(cache.lo, mx1.lo),
        win_k_pages=win_scat(cache.win_k_pages, mx1.k_win),
        win_v_pages=win_scat(cache.win_v_pages, mx1.v_win),
        win_pos=rowup(cache.win_pos, mx1.win_pos),
        win_acc=rowup(cache.win_acc, mx1.win_acc),
        win_nnz=rowup(cache.win_nnz, mx1.win_nnz),
        length=rowup(cache.length, mx1.length),
        win_fill=rowup(cache.win_fill, mx1.win_fill))


# ---------------------------------------------------------------------------
# Backend
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PagedKVBackend:
    """Paged cache layout behind the `CacheBackend` protocol.

    Stateless like `MixedKVBackend`; `page_size` is the only layout knob.
    Smaller pages waste less capacity to partial-page padding but grow the
    page table and scatter/gather fan-out; larger pages amortize addressing
    but pad each segment up to a page multiple per slot.

    `use_kernel=True` routes decode attention through the paged Pallas
    kernel (kernels/paged_qattn): pages are dequantized and consumed in
    place through the page table — no dense (slots, heads, seq, dim) gather
    per step.  Policies the kernel doesn't cover (groupwise/tokenwise
    stores) are refused at construction; the kernel-off gather+dense path
    remains the reference the kernel is verified against
    (tests/test_paged_qattn.py).

    Allocator API (`allocator`): "static" pre-assigns every slot its full
    worst case (strided round-robin, pools sized slots x ceil(cap/page));
    "freelist" provisions shared pools of `pool_fraction` x that worst case
    (plus a sink page) and starts every table entry at NULL — physical
    pages are granted/returned between jitted steps by a host-side
    `alloc.FreeListAllocator` (the continuous engine owns one), so long
    requests borrow pages freed by short ones.  The layout difference is
    invisible to the numerics: greedy engine output is bitwise
    token-identical across mixed / paged-static / paged-freelist
    (tests/test_backend_conformance.py).
    """

    ccfg: CompressionConfig
    page_size: int = DEFAULT_PAGE_SIZE
    use_kernel: bool = False
    allocator: str = "static"        # "static" | "freelist"
    pool_fraction: float = 1.0       # freelist pools as a fraction of the
    #                                  static worst case (floor: one full
    #                                  request per segment)

    def __post_init__(self):
        if self.use_kernel:
            from repro.kernels import paged_qattn
            if not paged_qattn.policy_supported(self.ccfg):
                raise ValueError(
                    f"the paged decode kernel cannot serve the "
                    f"{self.ccfg.method!r} policy (it reads channelwise-K / "
                    f"CST-V or raw stores); use paged_kernel=False")

    def init_cache(self, b, h_kv, d, max_len, dtype=jnp.bfloat16, d_v=None):
        """Empty decode cache.  allocator="freelist" returns the elastic
        layout: NULL tables over `pool_fraction`-sized shared pools, to be
        populated via an engine-level `alloc.FreeListAllocator`."""
        mx = kvc.init_cache(self.ccfg, b, h_kv, d, max_len, dtype, d_v=d_v)
        if self.allocator != "freelist":
            return from_mixed(mx, self.page_size)
        pools = tuple(
            freelist_pool_pages(b, n_pages(cap, self.page_size),
                                self.pool_fraction)
            for cap in (mx.hi.capacity, mx.lo.capacity, mx.window))
        return from_mixed_freelist(mx, self.page_size, pools)

    def compress_prefill(self, k, v, token_saliency, max_len,
                         probe_nnz=None, dtype=jnp.bfloat16, eff=None):
        """Compress prefill K/V into a fresh cache.  Always the STATIC
        layout, whatever `allocator` says: prefill slices are ephemeral
        (inserted into the long-lived decode cache at admission, then
        dropped), so elasticity buys nothing and the strided tables keep
        the op allocator-free."""
        mx = kvc.compress_prefill(self.ccfg, k, v, token_saliency, max_len,
                                  probe_nnz=probe_nnz, dtype=dtype, eff=eff)
        return from_mixed(mx, self.page_size)

    def append(self, cache, k_t, v_t, active=None):
        return append_token(cache, k_t, v_t, active=active)

    def attend(self, q, cache, scale=None, impl="ref", ctx=None, is_probe=None):
        if self.use_kernel:
            return self.attend_paged(q, cache, scale=scale,
                                     is_probe=is_probe, impl=impl, ctx=ctx)
        return kvc.attend_decode(q, cache.dense_view(), scale=scale,
                                 impl=impl, ctx=ctx)

    def attend_paged(self, q, cache, scale=None, is_probe=None,
                     impl="ref", ctx=None):
        """Beyond the protocol: decode attention that walks the page tables
        and dequantizes page-by-page in the kernel — the dense view is never
        materialized.  Same (out, slot_weights) contract as `attend`.

        Probe steps are the exception: the kernel's flash merge reassociates
        the softmax, so its slot weights agree with the reference only to
        float tolerance — enough for attention output, but recompression
        top-k's near-tied saliency ranks would drift.  When `is_probe` is
        given and any row probes this step (~probe_ratio of steps), the
        weights are recomputed through the gather path so the accumulated
        saliency state stays BITWISE identical to the gather/mixed engines
        (ZipCache's probe needs the full softmax row regardless — paper
        Eq. 8); all other steps never touch a dense view.  `impl`/`ctx`
        parameterize that probe-step recompute so it runs the SAME program
        the gather fallback would (e.g. decode_impl="int8_algebra") — the
        bitwise claim is against this backend with the kernel off."""
        from repro.kernels import paged_qattn
        dec = paged_qattn.attend_paged(q, cache, scale=scale)
        if is_probe is None:
            return dec
        def exact_w(_):
            return kvc.attend_decode(q, cache.dense_view(), scale=scale,
                                     impl=impl, ctx=ctx).slot_weights
        w = jax.lax.cond(jnp.any(is_probe), exact_w,
                         lambda _: dec.slot_weights, None)
        return kvc.DecodeAttnOut(dec.out, w)

    def update_probe(self, cache, slot_weights, is_probe):
        # metadata-only op; the mixed implementation duck-types onto the
        # paged layout (same field names, payload untouched)
        return kvc.update_probe_state(cache, slot_weights, is_probe)

    def recompress(self, cache, rows=None, eff=None):
        return recompress(self.ccfg, cache, rows=rows, eff=eff)

    def recompress_slot(self, cache, slot, eff=None):
        """Beyond the protocol: per-slot recompression at 1/batch FLOPs (the
        engine prefers this when the backend offers it)."""
        return recompress_slot(self.ccfg, cache, slot, eff=eff)

    def insert(self, cache, slice_cache, slot):
        return insert_slot(cache, slice_cache, slot)

    def free(self, cache, slot):
        return free_slot(cache, slot)

    def dense(self, cache) -> kvc.MixedKVCache:
        """Dense read-only view for consumers of the mixed layout (MLA's
        absorbed decode reads the cache directly)."""
        return cache.dense_view()

    def nbytes(self, cache) -> Tuple[int, int]:
        """(packed, overhead): packed counts LIVE payload pages only
        (page-granular) plus quantization params; overhead is metadata,
        page tables and — for the free-list layout — the unallocated pool
        pages, which `cache.nbytes_free_pool()` (and `cache_bytes`'s
        `free_pool_bytes`) breaks out separately."""
        packed = cache.nbytes_packed()
        return int(packed), int(cache.nbytes_total() - packed)
