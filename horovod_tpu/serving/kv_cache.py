"""Paged KV cache for the serving engine (docs/serving.md).

PagedAttention's memory model (vLLM, SOSP '23) applied to the TPU
runtime: instead of one contiguous ``[B, max_seq, H, D]`` cache whose
slots are mostly padding, K/V live in a fixed pool of fixed-size pages
``[n_pages, page, n_kv_heads * head_dim]`` shared by every request. Each
request owns an ordered *block table* of physical page ids; attention
follows the table (``ops/pallas/flash_attention.flash_paged_decode`` on
TPU, :func:`paged_attention_reference` elsewhere), so HBM held per
request is proportional to its actual length rounded up to one page —
the fragmentation that caps batch size in the contiguous layout is gone.

Split of responsibilities:

- **Device state** (inside the AOT-compiled steps): the page pool
  arrays, ``[blocks, n_pages + 1, page, *row]`` by what one token stores
  in each cached block (:class:`CacheRows`) — for the dense block
  ``[n_layers, n_pages + 1, page, n_kv_heads * head_dim]`` for K and for
  V: ONE row a token, every KV head's numbers side by side, so that a
  page is ``[page, n_kv_heads * head_dim]`` with the lanes full whatever
  ``head_dim`` is (:func:`dense_rows`) — donated to every step. The
  engine keeps them in ONE row-major layout
  (:func:`pool_format`) and the models' step bodies address them as one
  flat run of pages (:func:`flat_pool`, :func:`block_pages`: block
  ``b``'s page ``p`` at ``b * (n_pages + 1) + p``), so a step's only
  pool-shaped instructions are in-place scatters: a row a slot in
  decode (:func:`write_token_rows`), whole pages in the dense bodies'
  prefill (:func:`write_chunk_pages`; docs/serving.md, "Pool
  layout"). One extra *scratch page* per layer
  (physical id ``n_pages``) absorbs the writes of padded positions and
  empty slots — every store the compiled step issues targets a valid
  physical page, no predication needed.
- **Host state** (:class:`PageAllocator`, :class:`BlockTables`): the
  free list, per-slot tables and lengths as numpy arrays the scheduler
  mutates between steps and ships to the device per step (a few hundred
  int32s). Allocation happens at admission (worst-case pages for
  prompt + max_new_tokens, so a decode can never fail mid-flight);
  eviction-on-finish returns a request's pages to the free list.

Shared-prefix page reuse (hvdspec): the allocator is REFCOUNTED — one
physical page can back N block tables at once plus the
:class:`PrefixIndex`, a hash-chain over page-granularity token blocks
that lets an admitted request adopt the already-resident pages of a
matching prompt prefix. Retire then *decrements* instead of freeing;
divergence inside a block is resolved with copy-on-write
(:func:`copy_page` — allocate + one device-side page copy, drop the
shared ref). Everything stays opt-in behind HOROVOD_SERVE_PREFIX_CACHE:
with the index off, every page has refcount 1 and the allocator behaves
exactly like the PR 15 free list.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional, Sequence, Set, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout


def pool_format(sharding: jax.sharding.Sharding, ndim: int = 4) -> Format:
    """The one layout a page-pool array lives in, from allocation to the
    decode kernel's DMA: row-major over ``sharding`` (``ndim`` axes: 3 +
    the axes of the cache row, so 4 for a K or V array). A
    Pallas operand and a scatter are row-major on TPU, while the
    compiler's own choice for the pool's shape puts a page's tokens on
    the lanes — left free, every step converts each layer's pool there
    and back. The CPU backend is row-major anyway."""
    return Format(Layout(major_to_minor=tuple(range(ndim))), sharding)


@dataclasses.dataclass(frozen=True)
class CacheRows:
    """One array of a page pool, by what ONE token stores in it: ``row``
    numbers (a shape) in each of ``blocks`` cached blocks — an attention
    block that caches; a dense layer is one block of K rows
    ``(n_kv_heads * head_dim,)`` and one of V, a layer of two
    latent-attention blocks is two blocks of one ``(576,)`` row and no
    V. The array is ``[blocks, n_pages + 1, page, *row]``. ``tp_axis``:
    which axis of ``row`` tensor parallelism splits, if any."""
    name: str
    blocks: int
    row: Tuple[int, ...]
    tp_axis: Optional[int] = None


def dense_rows(n_layers: int, n_kv_heads: int, head_dim: int
               ) -> Tuple[CacheRows, ...]:
    """The dense block's pool: a K and a V array, each ONE row of
    ``n_kv_heads * head_dim`` numbers a token and layer, head after head.
    The row's last (and only) axis is what the device tiles onto its 128
    lanes: heads of 64 fill them in pairs, where a ``(n_kv_heads, 64)``
    row left every tile half padding, in HBM as in the kernel. Heads are
    contiguous in the row, so tp splits it into whole KV heads."""
    kv = (int(n_kv_heads) * int(head_dim),)
    return (CacheRows("k", int(n_layers), kv, tp_axis=0),
            CacheRows("v", int(n_layers), kv, tp_axis=0))


class PagePool:
    """Static geometry of the paged cache (all sizes fixed at engine
    build time — they key the compiled serve executables). The dense
    block's pool is a K and a V array of ``(n_kv_heads * head_dim,)``
    rows in each of ``n_layers`` blocks; a model with a cache of its own
    describes it with ``rows=`` (:class:`CacheRows`, one per array) and
    the allocator, block tables and prefix index serve it unchanged."""

    def __init__(self, n_layers: int, n_pages: int, page: int,
                 n_kv_heads: int = 0, head_dim: int = 0, dtype=jnp.float32,
                 rows: Optional[Sequence[CacheRows]] = None):
        if n_pages < 1 or page < 1:
            raise ValueError(
                f"page pool needs n_pages>=1 and page>=1, got "
                f"n_pages={n_pages}, page={page}")
        self.n_layers = int(n_layers)
        self.n_pages = int(n_pages)
        self.page = int(page)
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        if rows is None:
            rows = dense_rows(self.n_layers, self.n_kv_heads, self.head_dim)
        self.rows: Tuple[CacheRows, ...] = tuple(rows)

    @property
    def scratch_page(self) -> int:
        """Physical id of the write sink for padded/empty positions."""
        return self.n_pages

    def shapes(self) -> Tuple[Tuple[int, ...], ...]:
        """Each array's shape, ``[blocks, n_pages + 1, page, *row]`` (the
        +1 is the scratch page)."""
        return tuple((r.blocks, self.n_pages + 1, self.page) + tuple(r.row)
                     for r in self.rows)

    def alloc_arrays(self, fmt: Union[None, Format, Sequence[Format]] = None
                     ) -> Tuple[jax.Array, ...]:
        """The zeroed arrays of the pool, one per :class:`CacheRows` (the
        dense block's: ``(k_pages, v_pages)``, each
        ``[n_layers, n_pages + 1, page, n_kv_heads * head_dim]``), made in
        place in ``fmt`` (layout and sharding — the engine's
        :func:`pool_format`, one for all or one each; under tensor
        parallelism its sharding splits the row into whole KV heads) so
        no second
        pool-sized buffer exists even while allocating."""
        shapes = self.shapes()
        if fmt is None:
            return tuple(jnp.zeros(shape, self.dtype) for shape in shapes)
        fmts = [fmt] * len(shapes) if isinstance(fmt, Format) else list(fmt)
        return tuple(
            jax.jit(lambda shape=shape: jnp.zeros(shape, self.dtype),
                    out_shardings=f)()
            for shape, f in zip(shapes, fmts))

    def pages_for(self, n_tokens: int) -> int:
        return -(-max(int(n_tokens), 0) // self.page)

    def nbytes(self) -> int:
        """HBM the pool holds (every array, scratch page included)."""
        itemsize = jnp.dtype(self.dtype).itemsize
        return sum(int(np.prod(shape)) for shape in self.shapes()) * itemsize


def _pool_gauges():
    """The hvd_serve_pages_* gauges, created on first allocator state
    change (import-time creation would make kv_cache a hard dependency
    of the metrics registry's test-reset ordering)."""
    from horovod_tpu import metrics as M
    return (
        M.gauge("hvd_serve_pages_free",
                "Free pages in the serving KV pool"),
        M.gauge("hvd_serve_pages_shared",
                "Serving KV pool pages with more than one holder "
                "(N block tables and/or the prefix index)"),
    )


class PageAllocator:
    """Refcounted free-list allocator over physical page ids
    ``[0, n_pages)``. LIFO reuse keeps the working set hot; the scratch
    page is never handed out.

    A page can back N block tables at once: ``alloc`` hands pages out
    at refcount 1, ``incref`` adds a holder (another request's block
    table, or the prefix index), and ``free``/``decref`` drop one —
    the page returns to the free list only when the LAST holder lets
    go. With no sharing in play every refcount is 1 and this is the
    plain PR 15 free list."""

    def __init__(self, n_pages: int):
        self.n_pages = int(n_pages)
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self._refs: Dict[int, int] = {}
        self._gauges = None

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def shared_pages(self) -> int:
        """Pages currently held by more than one holder."""
        return sum(1 for c in self._refs.values() if c > 1)

    @property
    def held_refs(self) -> int:
        """Total outstanding references across all live pages (the
        conservation invariant the property tests pin:
        ``free_pages + live pages == n_pages`` always, regardless of
        how many holders each live page has)."""
        return sum(self._refs.values())

    def refcount(self, page: int) -> int:
        return self._refs.get(int(page), 0)

    def _publish(self) -> None:
        if self._gauges is None:
            self._gauges = _pool_gauges()
        self._gauges[0].set(len(self._free))
        self._gauges[1].set(self.shared_pages)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(
                f"KV page pool exhausted: {n} pages requested, "
                f"{len(self._free)} free of {self.n_pages} "
                f"(raise HOROVOD_SERVE_PAGES or lower "
                f"HOROVOD_SERVE_SLOTS / HOROVOD_SERVE_MAX_SEQ)")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._refs[p] = 1
        self._publish()
        return out

    def incref(self, page: int) -> None:
        """Add a holder to a LIVE page (sharing it into another block
        table or pinning it in the prefix index)."""
        p = int(page)
        if p not in self._refs:
            raise ValueError(
                f"incref of page {p} which is not allocated — a prefix "
                f"match must only hand out pages the index still holds")
        self._refs[p] += 1
        self._publish()

    def decref(self, page: int) -> bool:
        """Drop one holder; returns True when the page actually went
        back to the free list (last holder). Double-frees raise — a
        page id whose count is already zero is a bookkeeping bug, not
        backpressure."""
        p = int(page)
        if not (0 <= p < self.n_pages):
            raise ValueError(f"freeing invalid page id {p}")
        c = self._refs.get(p)
        if not c:
            raise ValueError(
                f"double free of KV page {p}: refcount is already 0 "
                f"(every holder must decref exactly once)")
        if c > 1:
            self._refs[p] = c - 1
            self._publish()
            return False
        del self._refs[p]
        self._free.append(p)
        self._publish()
        return True

    def free(self, pages: List[int]) -> None:
        """Drop one holder from each page (retire decrements instead of
        freeing; unshared pages return to the free list immediately)."""
        for p in pages:
            self.decref(p)


def _chain_hash(prev: bytes, block: np.ndarray) -> bytes:
    """One link of the prefix hash chain: ``h_i = H(h_{i-1} || block_i
    tokens)``. Chaining makes a block's identity its FULL token prefix,
    not just its own tokens — two requests share page i only when every
    token up to and including block i matches, which is exactly the
    condition under which their K/V at those positions are bitwise
    equal (K/V at a position is a function of the token prefix alone;
    chunk boundaries and co-tenants never enter the value)."""
    return hashlib.sha256(
        prev + np.ascontiguousarray(block, np.int32).tobytes()).digest()


@dataclasses.dataclass
class _PrefixEntry:
    page: int                   # physical page id (one index-held ref)
    tokens: np.ndarray          # the FULL token block backing the page
    prev: bytes                 # parent chain hash
    stamp: int                  # LRU clock


class PrefixIndex:
    """Hash-chain index of resident prompt-prefix pages
    (docs/serving.md): full page-granularity token blocks of completed
    prefills, keyed by chained hash so lookup is longest-prefix match.

    Ref discipline: every entry holds ONE allocator reference on its
    page (taken at :meth:`register`, dropped at eviction), so indexed
    pages survive the requests that wrote them. :meth:`match` only
    returns pages live entries hold — the caller increfs per adopting
    block table. Eviction is LRU over *leaf* entries whose page has no
    other holder (refcount 1): evicting leaves first keeps every
    surviving chain reachable from the root, and evicting shared pages
    would free nothing."""

    def __init__(self, page: int, allocator: PageAllocator):
        self.page = int(page)
        self.allocator = allocator
        self._entries: Dict[bytes, _PrefixEntry] = {}
        self._children: Dict[bytes, Set[bytes]] = {}
        self._clock = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def _bump(self) -> int:
        self._clock += 1
        return self._clock

    def match(self, prompt: np.ndarray
              ) -> Tuple[List[int], int, Optional[Tuple[int, int]]]:
        """Longest resident prefix of ``prompt``:
        ``(pages, skip, cow)`` where ``pages`` are the matched full
        blocks' physical ids (in block order, NOT yet increfed),
        ``skip`` counts prompt tokens those blocks cover, and ``cow``
        is an optional ``(src_page, n_tokens)`` partial-block match at
        the divergence point — the caller copy-on-writes ``src_page``
        and extends ``skip`` by ``n_tokens``. At least one prompt token
        is always left unmatched: the tail prefill must run to produce
        the first generated token's logits."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = int(prompt.size)
        max_full = max((n - 1) // self.page, 0)
        h, pages, skip = b"", [], 0
        blocks = 0
        while blocks < max_full:
            block = prompt[blocks * self.page:(blocks + 1) * self.page]
            nh = _chain_hash(h, block)
            e = self._entries.get(nh)
            if e is None:
                break
            e.stamp = self._bump()
            pages.append(e.page)
            skip += self.page
            h = nh
            blocks += 1
        # Divergence inside the next block: the longest common token
        # prefix against any child of the matched chain point is worth
        # a copy-on-write (the copied page carries valid K/V for those
        # tokens; the request overwrites the rest as it prefills).
        cow: Optional[Tuple[int, int]] = None
        rest = prompt[skip:]
        best = 0
        for ch in self._children.get(h, ()):
            e = self._entries.get(ch)
            if e is None:
                continue
            m = min(int(rest.size), self.page)
            neq = np.nonzero(e.tokens[:m] != rest[:m])[0]
            t = int(neq[0]) if neq.size else m
            t = min(t, n - 1 - skip)    # leave >=1 token to prefill
            if t > best:
                best = t
                cow = (e.page, t)
                e.stamp = self._bump()
        return pages, skip, cow

    def register(self, prompt: np.ndarray, pages: Sequence[int]) -> int:
        """Index every FULL prompt block of a freshly prefilled request
        (``pages`` in block-table order). Only full blocks enter — a
        partial last block is still being written by its owner's
        decode. New entries take an index-held ref; blocks already
        indexed (the shared prefix itself) are just LRU-refreshed.
        Returns the number of pages newly indexed."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n_full = int(prompt.size) // self.page
        h, added = b"", 0
        for i in range(min(n_full, len(pages))):
            block = prompt[i * self.page:(i + 1) * self.page]
            nh = _chain_hash(h, block)
            e = self._entries.get(nh)
            if e is None:
                self.allocator.incref(pages[i])
                self._entries[nh] = _PrefixEntry(
                    page=int(pages[i]), tokens=block.copy(), prev=h,
                    stamp=self._bump())
                self._children.setdefault(h, set()).add(nh)
                added += 1
            else:
                e.stamp = self._bump()
            h = nh
        return added

    def evict(self, n_pages_needed: int) -> int:
        """LRU-evict index-only leaf entries until the allocator can
        cover ``n_pages_needed`` (or nothing evictable remains).
        Returns pages actually freed. Entries whose page another block
        table still holds are skipped — dropping the index ref would
        free nothing and forget a prefix that is still resident."""
        freed = 0
        while self.allocator.free_pages < n_pages_needed:
            cand = [(e.stamp, h) for h, e in self._entries.items()
                    if not self._children.get(h)
                    and self.allocator.refcount(e.page) == 1]
            if not cand:
                break
            _, h = min(cand)
            e = self._entries.pop(h)
            self._children.get(e.prev, set()).discard(h)
            self._children.pop(h, None)
            if self.allocator.decref(e.page):
                freed += 1
            self.evictions += 1
        return freed

    def stats(self) -> Dict[str, int]:
        return {"entries": len(self._entries),
                "evictions": self.evictions}


class BlockTables:
    """Per-slot block tables + lengths, host-side (numpy). Unassigned
    entries hold the scratch page id so the compiled step's gathers and
    scatters always touch valid physical pages."""

    def __init__(self, n_slots: int, n_max_pages: int, scratch_page: int):
        self.n_slots = int(n_slots)
        self.n_max_pages = int(n_max_pages)
        self.scratch_page = int(scratch_page)
        self.tables = np.full((n_slots, n_max_pages), scratch_page,
                              np.int32)
        self.lengths = np.zeros((n_slots,), np.int32)

    def assign(self, slot: int, pages: List[int]) -> None:
        if len(pages) > self.n_max_pages:
            raise ValueError(
                f"request needs {len(pages)} pages but the block table "
                f"holds {self.n_max_pages} (HOROVOD_SERVE_MAX_SEQ)")
        self.tables[slot, :] = self.scratch_page
        self.tables[slot, :len(pages)] = pages
        self.lengths[slot] = 0

    def clear(self, slot: int) -> None:
        self.tables[slot, :] = self.scratch_page
        self.lengths[slot] = 0

    def device_views(self, active: Optional[np.ndarray] = None
                     ) -> Tuple[jax.Array, jax.Array]:
        """The tables and lengths as a step takes them, uploaded from
        COPIES: an upload may alias the NumPy buffer it is given, and the
        host moves its tables on while a dispatched step has yet to read
        them. Slots outside ``active`` show the scratch page and length 0,
        so their row's write lands where nobody reads."""
        if active is None:
            active = np.ones((self.n_slots,), bool)
        return (jnp.asarray(np.where(active[:, None], self.tables,
                                     np.int32(self.scratch_page))),
                jnp.asarray(np.where(active, self.lengths, np.int32(0))))


# ---------------------------------------------------------------------------
# the pool inside the compiled steps: how a block is addressed, page writes
# ---------------------------------------------------------------------------

def flat_pool(*pools: jax.Array) -> Tuple[jax.Array, ...]:
    """Each pool array ``[blocks, P+1, page, *row]`` as one run of pages
    ``[blocks*(P+1), page, *row]`` (a bitcast in the pool's row-major
    layout). The step bodies carry these whole through the layer scan and
    address a block through :func:`block_pages`, so no instruction slices
    a block's pool out or stacks it back; ``flat.reshape(pool.shape)``
    gives the pool back."""
    return tuple(p.reshape((-1,) + p.shape[2:]) for p in pools)


def block_pages(pool_shape: Tuple[int, ...], block: jax.Array,
                block_tables: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Cached block ``block`` of a pool of shape ``pool_shape`` among the
    flat run of :func:`flat_pool`: (``block_tables`` offset to the block's
    pages, the block's own scratch page). Block ``b``'s page ``p`` is flat
    page ``b*(P+1) + p``, its scratch page the last of those. The one
    place that knows."""
    stride = pool_shape[1]
    base = block * stride
    return block_tables + base, base + stride - 1


def with_index(layers):
    """Scan operand: the stacked layer parameters beside each layer's
    index (what a body hands to :func:`block_pages`)."""
    n = jax.tree.leaves(layers)[0].shape[0]
    return layers, jnp.arange(n, dtype=jnp.int32)


def write_token_rows(pages: Sequence[jax.Array], new: Sequence[jax.Array],
                     block_tables: jax.Array, positions: jax.Array,
                     valid: Optional[jax.Array] = None,
                     scratch: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, ...]:
    """Scatter one token's rows per sequence into its page, for however
    many arrays one block of the pool has (the dense block's: K and V).

    ``pages[i]`` ``[n_phys, page, *row_i]`` takes ``new[i]``
    ``[B, *row_i]``, all at the same page and offset; positions ``[B]``
    (global token index the write lands at), valid ``[B]`` bool — invalid
    writes are routed to the scratch page instead of being dropped, which
    keeps the op a plain scatter. ``scratch`` is that page's physical id:
    the last page of a single block's pool by default; over the flat pool
    both come from :func:`block_pages`."""
    page = pages[0].shape[1]
    if scratch is None:
        scratch = pages[0].shape[0] - 1
    logical = positions // page
    phys = jnp.take_along_axis(block_tables, logical[:, None],
                               axis=1)[:, 0]
    offs = positions % page
    if valid is not None:
        phys = jnp.where(valid, phys, scratch)
    return tuple(p.at[phys, offs].set(n) for p, n in zip(pages, new))


def write_chunk_rows(pages: Sequence[jax.Array], new: Sequence[jax.Array],
                     block_table: jax.Array, start: jax.Array,
                     n_real: jax.Array, scratch: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, ...]:
    """Scatter a prefill chunk's rows (one sequence) into its pages.

    ``new[i]`` ``[C, *row_i]`` for chunk positions ``start .. start + C``;
    positions at or past ``start + n_real`` are padding and land on the
    scratch page (``scratch``, as in :func:`write_token_rows`).
    block_table ``[n_max]``."""
    page = pages[0].shape[1]
    if scratch is None:
        scratch = pages[0].shape[0] - 1
    c = new[0].shape[0]
    pos = start + jnp.arange(c, dtype=jnp.int32)
    phys = jnp.take(block_table, pos // page, mode="clip")
    phys = jnp.where(jnp.arange(c) < n_real, phys, scratch)
    offs = pos % page
    return tuple(p.at[phys, offs].set(n) for p, n in zip(pages, new))


def write_chunk_pages(pages: Sequence[jax.Array], new: Sequence[jax.Array],
                      block_table: jax.Array, start: jax.Array,
                      n_real: jax.Array, scratch: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, ...]:
    """:func:`write_chunk_rows` a page at a time, for rows that fill the
    lanes (the dense block's: :func:`dense_rows`). There a page is whole
    tiles and ONE token's row a single sublane of each of them, so a
    chunk's 256 rows are 256 small scatters one after another, where the
    chunk lies in at most ``ceil(C / page) + 1`` pages wherever it
    starts: those are read, the real rows laid over them, and written back
    whole. Rows at or past ``start + n_real`` are written nowhere; a page
    the real rows do not reach is the scratch page, which gets its own
    rows back. (Not for a row that leaves lanes empty, such as a latent
    row of 576: with whole pages read and written the TPU compiler lays
    such a pool page-first inside the program and converts all of it on
    the way in and out.)"""
    page = pages[0].shape[1]
    if scratch is None:
        scratch = pages[0].shape[0] - 1
    c = new[0].shape[0]
    n = -(-c // page) + 1
    first = start // page
    logical = first + jnp.arange(n, dtype=jnp.int32)
    phys = jnp.where(logical * page < start + n_real,
                     jnp.take(block_table, logical, mode="clip"), scratch)
    pos = first * page + jnp.arange(n * page, dtype=jnp.int32)
    real = (pos >= start) & (pos < start + n_real)

    def write(p, rows):
        lead = (n * page,) + rows.shape[1:]
        placed = jax.lax.dynamic_update_slice(
            jnp.zeros(lead, rows.dtype), rows,
            (start - first * page,) + (0,) * (rows.ndim - 1))
        held = take_pages(p, phys)                    # [n, page, *row]
        merged = jnp.where(real.reshape((-1,) + (1,) * (rows.ndim - 1)),
                           placed, held.reshape(lead))
        return p.at[phys].set(merged.reshape(held.shape))

    return tuple(write(p, rows) for p, rows in zip(pages, new))


def copy_page(*pages_src_dst: jax.Array) -> Tuple[jax.Array, ...]:
    """Device-side copy-on-write body, ``copy_page(*pages, src, dst)``:
    duplicate ONE physical page across every block of every array of the
    pool (the dense block's: k_pages/v_pages ``[L, n_phys, page,
    KVH*D]``; src/dst scalar int32). One executable covers every (src, dst)
    pair — the ids are runtime operands, so admission-time COW never
    compiles. Donated by the engine and jitted in the pool's one layout:
    the update is in place."""
    *pages, src, dst = pages_src_dst
    return tuple(p.at[:, dst].set(p[:, src]) for p in pages)


# the largest slice the TPU compiler gathers in place: a larger one it splits
# into gathers over slices of the WHOLE operand, each slice a copy of its
# share of the pool, every call (a page of 128 rows of 3840 bfloat16 numbers
# is 960 KiB: compile-only for a v5e)
GATHER_SLICE_BYTES = 512 * 1024


def take_pages(pages: jax.Array, table: jax.Array) -> jax.Array:
    """``pages[table]`` along the first axis (``[..., n, page, *row]``), read
    in place. A page over ``GATHER_SLICE_BYTES`` is taken as ``k`` pieces of
    ``page / k`` rows from the pool seen as ``[P * k, page / k, *row]`` (the
    same bytes in the pool's row-major layout)."""
    k, page = 1, pages.shape[1]
    page_bytes = int(np.prod(pages.shape[1:])) * pages.dtype.itemsize
    while page_bytes > k * GATHER_SLICE_BYTES and page % (2 * k) == 0:
        k *= 2
    if k == 1:
        return jnp.take(pages, table, axis=0)
    pieces = pages.reshape((pages.shape[0] * k, page // k) + pages.shape[2:])
    index = (table[..., None] * k + jnp.arange(k, dtype=table.dtype))
    taken = jnp.take(pieces, index.reshape(table.shape[:-1] + (-1,)), axis=0)
    return taken.reshape(table.shape + pages.shape[1:])


def gather_pages(pages: jax.Array, block_table: jax.Array) -> jax.Array:
    """Contiguous ``[n_max*page, *row]`` view of one sequence's pages
    (one block of the pool; the dense block's row is ``KVH*D``) in
    block-table order — the prefill attention context (prefill is
    compute-bound; the gather copy is irrelevant there, unlike at decode
    where the kernel follows the table in place). With block tables
    ``[B, n_max]``: ``[B, n_max*page, *row]``, one view per sequence."""
    g = take_pages(pages, block_table)            # [.., n_max, page, *row]
    lead = block_table.shape[:-1]
    return g.reshape(lead + (-1,) + g.shape[len(lead) + 2:])


def paged_attention_reference(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_tables: jax.Array,
                              lengths: jax.Array, scale: float
                              ) -> jax.Array:
    """jnp fallback of ``flash_paged_decode`` (single layer): gather each
    sequence's pages, mask past its length, plain stable softmax. The
    behavioral spec the kernel is pinned against — and the dispatch
    target for shapes/backends the kernel does not support. q
    ``[B, H, D]``, pages ``[n_phys, page, KVH*D]`` (``KVH`` from q's
    ``D``). Output ``[B, H, D]`` f32; empty sequences (length 0) return
    zeros."""
    b, h, d = q.shape
    page, kvh = k_pages.shape[1], k_pages.shape[2] // d
    n_max = block_tables.shape[1]
    qpk = h // kvh

    def one(qb, table, ln):
        k = gather_pages(k_pages, table).astype(jnp.float32)
        v = gather_pages(v_pages, table).astype(jnp.float32)
        k, v = k.reshape(-1, kvh, d), v.reshape(-1, kvh, d)
        if qpk > 1:                              # GQA: group heads
            k = jnp.repeat(k, qpk, axis=1)
            v = jnp.repeat(v, qpk, axis=1)
        s = jnp.einsum("hd,shd->hs", qb.astype(jnp.float32), k) * scale
        mask = jnp.arange(n_max * page) < ln
        s = jnp.where(mask[None, :], s, -jnp.inf)
        m = jnp.max(jnp.where(mask[None, :], s, -jnp.inf), axis=-1,
                    keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)   # empty slot: all masked
        p = jnp.where(mask[None, :], jnp.exp(s - m), 0.0)
        l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("hs,shd->hd", p / l, v)

    return jax.vmap(one)(q, block_tables, lengths)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, scale: float) -> jax.Array:
    """Dispatch: flash paged-decode kernel when the backend + shapes
    support it (``enabled()``/``paged_decode_supports()``, the training-
    kernel pattern), else the jnp reference."""
    from horovod_tpu.ops.pallas import flash_attention as fa
    mode = fa.enabled()
    if mode and fa.paged_decode_supports(q, k_pages, v_pages):
        return fa.flash_paged_decode(
            q, k_pages, v_pages, block_tables, lengths, float(scale),
            interpret=(mode == "interpret"))
    return paged_attention_reference(q, k_pages, v_pages, block_tables,
                                     lengths, float(scale))
