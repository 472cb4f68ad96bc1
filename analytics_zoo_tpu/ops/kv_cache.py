"""The decode-side cache of autoregressive serving: K/V pages, and per-slot
state for the layers that keep no keys.

The serving stack's one-shot predict path recomputes the whole sequence per
request; an autoregressive decode loop doing that would pay O(T²) attention
per EMITTED token. This module is the TPU-native fix — the decode-side state
store behind ``prefill()``/``decode_step()`` of ``TransformerLM`` and
``HybridLM`` and the continuous batcher
(:mod:`analytics_zoo_tpu.serving.generation`).

**Two kinds of state, one description.** :class:`KVCacheConfig` names, layer
by layer, what a layer keeps between steps (``layer_kinds``): ``"pages"``, K
and V of every cached token in the paged pools below, addressed through the
slot's row of the page table; or ``"slot"``, a fixed-size state a slot (a
linear-attention or state-space layer's matrix state and the tail of its
short convolution: ``slot_state`` names the leaves), addressed by the slot's
index, of a size that does not grow with the sequence; or both, ``("pages",
"slot")``, for a layer that runs an attention and a state-space mixer side by
side. :func:`init_cache` builds both: pools for the layers that keep pages,
and for each leaf of ``slot_state`` one ``(n_slots, ...)`` array a layer that
keeps a slot state. Every leaf is donated into the
dispatches and updated where it lies. A model whose layers all hold pages
(``TransformerLM``) leaves ``layer_kinds`` empty and gets the pytree it always
got. What follows is about the pages:

* **Pages, not ragged buffers.** K/V live in preallocated pools of
  fixed-size pages, ``(n_pages, page_size, n_heads, head_dim)``.
  A sequence *slot* owns an int32 page-table row mapping its logical
  positions to pool pages; pages are handed out by the host-side
  :class:`PagePool` as sequences grow and returned when they retire, so HBM
  is sized for the *working set* (active tokens), not
  ``n_slots × max_seq_len`` worst case.
* **One pool per layer.** The cache pytree is ``{"k": (k_0, ..., k_{L-1}),
  "v": (v_0, ..., v_{L-1})}``: one array per layer for K and one for V, all
  indexed by the same page ids. Each block scatters its new rows into its
  own leaf and hands that leaf to the attention kernel; nothing is sliced
  out of or stored back into a larger array. With the pytree donated, XLA
  aliases every leaf input to output and the scatter writes a few rows
  where the pool lies (a stacked ``(n_layers, ...)`` pool cost a copy of one
  layer's pool out and back around every write, in every layer, every
  step).
* **One decode executable.** Every device op here has shapes fixed by the
  cache config — ``(n_slots, pages_per_slot)`` tables, ``(n_slots,)``
  lengths — and masks to each row's true length instead of reshaping, the
  same pow2-bucket discipline the serving engine uses for batch sizes. The
  whole multi-slot decode step compiles ONCE; admission/retirement never
  changes a traced shape (the ``decode-shape-stability`` graph-lint rule
  asserts exactly this).
* **Page 0 is scratch.** The pool never hands out page 0; inactive slots
  and not-yet-allocated table entries point at it, so masked lanes scatter
  harmlessly into scratch instead of needing a traced branch.

Parity: the reference's Cluster Serving has no decode path at all (one-shot
Flink inference, PAPERS.md "BigDL 2.0" streams *requests*, not tokens);
paged attention is the standard modern serving answer rebuilt here on
jnp gather/scatter so it runs on any backend and stays one jaxpr.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common.locks import traced_lock

NEG_INF = -1e30

#: Page id every unallocated / masked table entry points at. The pool never
#: allocates it, so garbage writes from inactive lanes land in scratch.
SCRATCH_PAGE = 0


#: what a layer keeps between decode steps (``KVCacheConfig.layer_kinds``)
PAGES, SLOT = "pages", "slot"


def kinds_of(entry) -> Tuple[str, ...]:
    """The kinds one entry of ``layer_kinds`` names: a layer that keeps one
    kind names it as a word, a layer that keeps both as a tuple of the two."""
    return (entry,) if isinstance(entry, str) else tuple(entry)


class StepContext(NamedTuple):
    """Where a prefill or a decode step reads and writes the cache.
    ``table``: (B, pages_per_slot) page tables; ``lengths``: (B,) — a
    prefill's true prompt lengths, a decode step's first position being
    written; ``slots``: (B,) the slot each row of a prefill fills; ``live``:
    (B,) bool, the rows of a decode step that hold a stream (the others must
    leave every state as it is)."""

    table: Any
    lengths: Any
    page_size: int
    slots: Any = None
    live: Any = None


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Static geometry of one decode cache (fixes every traced shape): the
    paged K/V pools of the layers that attend to cached keys, and the
    per-slot state of the layers that do not (module docstring)."""

    n_layers: int
    n_heads: int
    head_dim: int
    n_slots: int                       # concurrent decode sequences
    page_size: int = 16                # tokens per page
    pages_per_slot: int = 16           # max_seq_len = page_size * pages_per_slot
    n_pages: Optional[int] = None      # pool size incl. scratch (None = full)
    dtype: Any = jnp.float32
    #: per layer, ``PAGES``, ``SLOT`` or ``(PAGES, SLOT)`` for a layer that
    #: keeps both; empty = every layer holds pages
    layer_kinds: Tuple[Any, ...] = ()
    #: the leaves a layer that keeps ``SLOT`` state holds for each slot:
    #: ``(name, shape, dtype)``, the name being the leaf's key in the cache
    #: pytree and its ``kind`` in the byte accounting
    slot_state: Tuple[Tuple[str, Tuple[int, ...], Any], ...] = ()

    def __post_init__(self):
        if self.page_size < 1 or self.pages_per_slot < 1:
            raise ValueError("page_size and pages_per_slot must be >= 1")
        if self.n_pages is not None and self.n_pages < 2:
            raise ValueError("n_pages must leave room for scratch + 1 page")
        each = [kinds_of(entry) for entry in self.layer_kinds]
        if self.layer_kinds and (
                len(each) != self.n_layers
                or any(not kinds or set(kinds) - {PAGES, SLOT}
                       or len(set(kinds)) != len(kinds) for kinds in each)):
            raise ValueError(f"layer_kinds must name {PAGES!r}, {SLOT!r} or "
                             f"both, once each, for each of {self.n_layers} "
                             f"layers, got {self.layer_kinds}")
        if bool(self.n_slot_layers) != bool(self.slot_state):
            raise ValueError("slot_state names the leaves of the layers "
                             "that layer_kinds marks as holding slot state: "
                             "one without the other")

    @property
    def kinds(self) -> Tuple[Any, ...]:
        """``layer_kinds``, spelled out for a model that left it empty."""
        return self.layer_kinds or (PAGES,) * self.n_layers

    def _count(self, kind: str, below: Optional[int] = None) -> int:
        """Layers (the first ``below``) that keep ``kind``."""
        return sum(kind in kinds_of(entry) for entry in self.kinds[:below])

    @property
    def n_page_layers(self) -> int:
        return self._count(PAGES)

    @property
    def n_slot_layers(self) -> int:
        return self._count(SLOT)

    def index_in_kind(self, layer: int, kind: Optional[str] = None) -> int:
        """Which of a kind's leaves is ``layer``'s: ``cache["k"][i]`` for the
        pages it keeps, ``cache[name][i]`` for its slot state. ``kind`` has
        to be said only for a layer that keeps both."""
        kept = kinds_of(self.kinds[layer])
        if kind is None and len(kept) == 1:
            kind = kept[0]
        if kind not in kept:
            raise ValueError(f"layer {layer} keeps {kept}, not {kind!r}")
        return self._count(kind, layer)

    def bytes_by_kind(self) -> Dict[str, int]:
        """Bytes the cache holds on the device: ``pages`` (K and V pools) and
        one entry a leaf of ``slot_state``."""
        pool = (self.total_pages * self.page_size * self.n_heads
                * self.head_dim * jnp.dtype(self.dtype).itemsize)
        out = {PAGES: 2 * pool * self.n_page_layers}
        for name, shape, dtype in self.slot_state:
            out[name] = (self.n_slot_layers * self.n_slots
                         * int(np.prod(shape)) * jnp.dtype(dtype).itemsize)
        return out

    @property
    def max_seq_len(self) -> int:
        return self.page_size * self.pages_per_slot

    @property
    def total_pages(self) -> int:
        # +1: page 0 is reserved scratch and backs no sequence
        if self.n_pages is not None:
            return self.n_pages
        return self.n_slots * self.pages_per_slot + 1


#: ``{"k": one pool per page layer, "v": one pool per page layer}`` and, for
#: a model with slot layers, one entry a leaf of ``slot_state``: ``(n_slots,
#: ...)`` arrays, one per slot layer — see the module docstring;
#: ``cache["k"][i]`` is the K pool of the ``i``-th layer that holds pages.
KVCache = Dict[str, Tuple[jax.Array, ...]]


def init_cache(cfg: KVCacheConfig) -> KVCache:
    """Preallocate the K/V page pools, one ``(n_pages, page_size, n_heads,
    head_dim)`` array per page layer (zeros; contents only ever read through
    a length mask, so stale pages are invisible), and the slot layers'
    leaves, ``(n_slots,) + shape`` each (zeros: the state a sequence starts
    from; a prefill writes its slot's whole state, so a reused slot starts
    from its prompt and from nothing else)."""
    shape = (cfg.total_pages, cfg.page_size, cfg.n_heads, cfg.head_dim)
    cache = {name: tuple(jnp.zeros(shape, cfg.dtype)
                         for _ in range(cfg.n_page_layers))
             for name in ("k", "v")}
    for name, leaf_shape, dtype in cfg.slot_state:
        cache[name] = tuple(jnp.zeros((cfg.n_slots,) + tuple(leaf_shape), dtype)
                            for _ in range(cfg.n_slot_layers))
    return cache


class PagePool:
    """Host-side REFCOUNTED free-list allocator over the cache's page pool.

    Thread-safe; page 0 (scratch) is never handed out. ``alloc`` hands out
    pages at refcount 1 and raises :class:`OutOfPages` when the pool is dry —
    the batcher turns that into a truncated stream rather than a deadlock.

    Refcounts are what make shared-prefix serving safe: a page a completed
    prefill published into the :class:`PrefixCache` can back MANY streams'
    page tables at once (each holder took :meth:`incref`), and ``release``
    only reclaims it when the LAST holder lets go. Double-free and leak
    accounting survive the upgrade: releasing a page nobody holds still
    raises, and every page is at all times exactly one of *free* or *held*
    (``free_count() + held_count() == capacity`` — the conservation law the
    refcount property test drives).
    """

    def __init__(self, cfg: KVCacheConfig):
        self.cfg = cfg
        # taken under ContinuousBatcher._lock by the decode loop's page-grow
        # path and under PrefixCache._lock by publish/evict; acquires
        # nothing itself
        # zoo-lock: leaf
        self._lock = traced_lock("PagePool._lock")
        self._free: List[int] = list(range(cfg.total_pages - 1, 0, -1))
        # page id -> refcount; absent = free. alloc() starts a page at 1.
        self._refs: Dict[int, int] = {}
        self._capacity = len(self._free)

    @property
    def capacity(self) -> int:
        return self._capacity

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    def held_count(self) -> int:
        """Distinct pages currently allocated (any refcount)."""
        with self._lock:
            return len(self._refs)

    def shared_count(self) -> int:
        """Pages with refcount >= 2 — prefix pages mapped into more than
        one holder (streams and/or the prefix cache)."""
        with self._lock:
            return sum(1 for r in self._refs.values() if r >= 2)

    def ref_count(self, page: int) -> int:
        """Current refcount of ``page`` (0 = free/scratch)."""
        with self._lock:
            return self._refs.get(int(page), 0)

    def alloc(self, n: int = 1) -> List[int]:
        with self._lock:
            if n > len(self._free):
                raise OutOfPages(
                    f"requested {n} pages, {len(self._free)} free "
                    f"(capacity {self._capacity})")
            out = [self._free.pop() for _ in range(n)]
            for p in out:
                self._refs[p] = 1
        return out

    def incref(self, pages: Sequence[int]) -> None:
        """Add one reference per page — mapping an already-allocated page
        into another holder's table (prefix sharing). Increffing a free
        page is a use-after-free and raises."""
        with self._lock:
            for p in pages:
                p = int(p)
                if p == SCRATCH_PAGE:
                    continue
                if p not in self._refs:
                    raise ValueError(
                        f"incref of unallocated page {p} (use-after-free)")
                self._refs[p] += 1

    def release(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; a page returns to the free list
        when its LAST reference is dropped. Releasing a free page raises
        (double free)."""
        with self._lock:
            for p in pages:
                p = int(p)
                if p == SCRATCH_PAGE:
                    continue
                r = self._refs.get(p)
                if r is None:
                    raise ValueError(f"double free of page {p}")
                if r <= 1:
                    del self._refs[p]
                    self._free.append(p)
                else:
                    self._refs[p] = r - 1

    def check_conservation(self) -> None:
        """Assert the pool invariant: every non-scratch page is exactly one
        of free or held, and the two partitions sum to capacity."""
        with self._lock:
            free = set(self._free)
            held = set(self._refs)
            if free & held:
                raise AssertionError(
                    f"pages both free and held: {sorted(free & held)}")
            if len(self._free) != len(free):
                raise AssertionError("duplicate pages on the free list")
            if len(free) + len(held) != self._capacity:
                raise AssertionError(
                    f"page conservation violated: {len(free)} free + "
                    f"{len(held)} held != capacity {self._capacity}")


class OutOfPages(RuntimeError):
    """The page pool cannot satisfy an allocation (working set too big)."""


# ---------------------------------------------------------------------------
# content-addressed prefix cache — host-side index over published KV pages
# ---------------------------------------------------------------------------

def prefix_block_key(parent: Optional[str], tokens: np.ndarray) -> str:
    """Chain hash of one page-aligned prefix block: H(parent key, tokens).

    Keying each block by its parent's key makes a block's identity the
    identity of the WHOLE prefix through it, so lookup is a longest-prefix
    walk (block i only matches if blocks 0..i-1 matched) and two prompts
    sharing a block's tokens but not its prefix never collide."""
    h = hashlib.blake2b(digest_size=16)
    if parent is not None:
        h.update(parent.encode("ascii"))
    h.update(b"|")
    h.update(np.ascontiguousarray(tokens, dtype=np.int32).tobytes())
    return h.hexdigest()


class _PrefixEntry:
    """One published block: the pages backing ``block_tokens`` tokens of
    some prompt prefix, plus the chain bookkeeping."""

    __slots__ = ("key", "parent", "pages", "n_tokens", "last_used",
                 "active", "children")

    def __init__(self, key: str, parent: Optional[str], pages: List[int],
                 n_tokens: int, last_used: int):
        self.key = key
        self.parent = parent
        self.pages = pages          # page ids this entry holds one ref each
        self.n_tokens = n_tokens    # cumulative prefix tokens through here
        self.last_used = last_used  # logical clock, bumped per hit
        self.active = 0             # streams currently matched through here
        self.children: set = set()  # keys chained directly off this block


class PrefixMatch:
    """Result of a :meth:`PrefixCache.lookup` hit. The caller OWNS one
    pool reference per page in ``pages`` (taken by lookup) and must either
    install them in a stream's table or release them."""

    __slots__ = ("keys", "pages", "n_tokens")

    def __init__(self, keys: List[str], pages: List[int], n_tokens: int):
        self.keys = keys
        self.pages = pages
        self.n_tokens = n_tokens


class PrefixCache:
    """Content-addressed index of published prefix KV pages.

    Completed prefills :meth:`publish` their full page-aligned blocks under
    a rolling chain hash; new prefills :meth:`lookup` their prompt and get
    the longest cached prefix mapped back as shared pages (refcount bump,
    zero compute, zero new HBM). The cache holds its OWN pool reference on
    every published page, so entries survive their publisher retiring;
    eviction (:meth:`evict_to_budget` / :meth:`reclaim_pages`) is LRU over
    entries no live stream is matched through, leaf blocks first (an
    interior block is unreachable-from-root only after its children go).

    Thread-safe. All mutation is all-or-nothing under one lock — a chaos
    kill between a stream's prefill and its publish can never leave a torn
    (half-inserted) chain. The K/V *contents* of published pages are
    weight-dependent, so a hot-swap must call :meth:`invalidate`.
    """

    def __init__(self, pool: PagePool, *, block_tokens: int, page_size: int,
                 max_pages: int):
        if block_tokens < 1 or block_tokens % page_size:
            raise ValueError(
                f"prefix_block_tokens must be a positive multiple of "
                f"page_size {page_size}, got {block_tokens}")
        if max_pages < 1:
            raise ValueError(f"prefix cache budget must be >= 1 page, "
                             f"got {max_pages}")
        self.pool = pool
        self.block_tokens = int(block_tokens)
        self.page_size = int(page_size)
        self.max_pages = int(max_pages)
        # taken under ContinuousBatcher._lock (retire path) and takes
        # PagePool._lock (a leaf) for incref/release
        # zoo-lock: guards(_entries, _held_pages, _clock)
        self._lock = traced_lock("PrefixCache._lock")
        self._entries: Dict[str, _PrefixEntry] = {}
        self._held_pages = 0
        self._clock = 0
        # plain counters — the serving layer mirrors these into telemetry
        self.hits = 0
        self.misses = 0
        self.evicted_pages = 0
        self.evict_sweeps = 0

    # ------------------------------------------------------------ read side

    def _pages_per_block(self) -> int:
        return self.block_tokens // self.page_size

    def lookup(self, tokens: np.ndarray) -> Optional[PrefixMatch]:
        """Longest-prefix match of ``tokens`` against the published chains.

        On a hit, takes one pool reference per matched page FOR THE CALLER
        (atomic with the walk, so a concurrent eviction can never reclaim a
        matched page first) and marks each matched entry stream-active
        until :meth:`release_stream`. Returns ``None`` on a miss."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = int(tokens.size)
        bt = self.block_tokens
        with self._lock:
            keys: List[str] = []
            pages: List[int] = []
            matched = 0
            parent: Optional[str] = None
            while matched + bt <= n:
                key = prefix_block_key(parent, tokens[matched:matched + bt])
                entry = self._entries.get(key)
                if entry is None:
                    break
                keys.append(key)
                pages.extend(entry.pages)
                matched += bt
                parent = key
            if not keys:
                self.misses += 1
                return None
            self._clock += 1
            for k in keys:
                e = self._entries[k]
                e.last_used = self._clock
                e.active += 1
            self.pool.incref(pages)      # the caller's references
            self.hits += 1
            return PrefixMatch(keys, list(pages), matched)

    def release_stream(self, keys: Sequence[str]) -> None:
        """Drop a stream's active marks (retire/cancel/failed prefill).
        Tolerates keys already gone — an intervening :meth:`invalidate`
        cleared the index but the stream's own page refs were its safety."""
        with self._lock:
            for k in keys:
                e = self._entries.get(k)
                if e is not None and e.active > 0:
                    e.active -= 1

    # ----------------------------------------------------------- write side

    def publish(self, tokens: np.ndarray, n_tokens: int,
                pages: Sequence[int]) -> int:
        """Publish a completed prefill's FULL blocks into the index.

        ``tokens``: the prompt; ``n_tokens``: how many of them are prefilled
        (decode writes start at ``n_tokens``, so only blocks wholly below it
        are frozen and publishable); ``pages``: the stream's page ids in
        table order. The cache takes its own reference on every newly
        published page. Blocks already present are skipped (first publisher
        wins — identical content by construction). Insertion of the whole
        chain happens under one lock hold: all-or-nothing, never torn.
        Returns the number of blocks newly published."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        bt = self.block_tokens
        ppb = self._pages_per_block()
        n_full = int(n_tokens) // bt
        if n_full < 1:
            return 0
        with self._lock:
            parent: Optional[str] = None
            fresh: List[Tuple[str, Optional[str], List[int], int]] = []
            for b in range(n_full):
                key = prefix_block_key(parent, tokens[b * bt:(b + 1) * bt])
                if key not in self._entries:
                    blk = [int(p) for p in pages[b * ppb:(b + 1) * ppb]]
                    fresh.append((key, parent, blk, (b + 1) * bt))
                parent = key
            if not fresh:
                return 0
            self._clock += 1
            for key, par, blk, ntok in fresh:
                self.pool.incref(blk)   # the cache's own references
                self._entries[key] = _PrefixEntry(key, par, blk, ntok,
                                                  self._clock)
                self._held_pages += len(blk)
                if par is not None:
                    self._entries[par].children.add(key)
        return len(fresh)

    # ------------------------------------------------------------- eviction

    def _remove_locked(self, entry: _PrefixEntry) -> None:
        del self._entries[entry.key]
        self._held_pages -= len(entry.pages)
        if entry.parent is not None:
            par = self._entries.get(entry.parent)
            if par is not None:
                par.children.discard(entry.key)
        self.pool.release(entry.pages)

    def _evict_locked(self, done) -> Tuple[int, int]:
        """LRU-evict leaf entries with no active streams until ``done()``
        or no candidates remain. Caller holds the lock."""
        n_entries = n_pages = 0
        while not done():
            cands = [e for e in self._entries.values()
                     if not e.children and e.active == 0]
            if not cands:
                break
            victim = min(cands, key=lambda e: e.last_used)
            self._remove_locked(victim)
            n_entries += 1
            n_pages += len(victim.pages)
        return n_entries, n_pages

    def evict_to_budget(self) -> Dict[str, int]:
        """Shrink cache-held pages to ``max_pages`` (LRU, leaf-first).
        Returns sweep stats (zeros when already under budget)."""
        with self._lock:
            if self._held_pages <= self.max_pages:
                return {"entries": 0, "pages": 0, "held_pages":
                        self._held_pages}
            n_entries, n_pages = self._evict_locked(
                lambda: self._held_pages <= self.max_pages)
            self.evict_sweeps += 1
            self.evicted_pages += n_pages
            return {"entries": n_entries, "pages": n_pages,
                    "held_pages": self._held_pages}

    def reclaim_pages(self, need_free: int) -> int:
        """Pool-pressure valve: evict (LRU, leaf-first) until the POOL has
        ``need_free`` free pages or nothing evictable remains. Returns
        pages released — cache-held-but-unreferenced HBM is reclaimable
        memory, not occupancy."""
        with self._lock:
            n_entries, n_pages = self._evict_locked(
                lambda: self.pool.free_count() >= need_free)
            if n_pages:
                self.evict_sweeps += 1
                self.evicted_pages += n_pages
            return n_pages

    def invalidate(self) -> int:
        """Drop EVERY entry and the cache's page references — the hot-swap
        hook (published K/V was computed under the old weights). Streams
        matched through dropped entries are unaffected: they hold their own
        page references and never re-read the index. Returns pages
        released."""
        with self._lock:
            released = 0
            for e in self._entries.values():
                self.pool.release(e.pages)
                released += len(e.pages)
            self._entries.clear()
            self._held_pages = 0
            return released

    # ---------------------------------------------------------- diagnostics

    def held_pages(self) -> int:
        with self._lock:
            return self._held_pages

    def reclaimable_pages(self) -> int:
        """Cache-held pages whose ONLY reference is the cache's (refcount
        1, entry not stream-active): what an eviction sweep would actually
        hand back to the free list right now."""
        with self._lock:
            return sum(1 for e in self._entries.values() if e.active == 0
                       for p in e.pages if self.pool.ref_count(p) == 1)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            entries = len(self._entries)
            held = self._held_pages
            active = sum(1 for e in self._entries.values() if e.active)
        total = self.hits + self.misses
        return {
            "entries": entries,
            "held_pages": held,
            "budget_pages": self.max_pages,
            "block_tokens": self.block_tokens,
            "stream_active_entries": active,
            "reclaimable_pages": self.reclaimable_pages(),
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": round(self.hits / total, 4) if total else 0.0,
            "evicted_pages": self.evicted_pages,
            "evict_sweeps": self.evict_sweeps,
        }


# ---------------------------------------------------------------------------
# device ops — all shapes fixed by KVCacheConfig; traced once
# ---------------------------------------------------------------------------

def copy_page(cache: KVCache, src, dst) -> KVCache:
    """Copy one page's K and V in every page layer's pool, ``src`` -> ``dst`` —
    the copy-on-write op for the one partially-shared boundary page of a
    full-prompt prefix hit. ``src``/``dst`` are traced int32 scalars, so
    every (src, dst) pair rides ONE compiled executable; jit with the cache
    donated and the copy is an in-place page-sized update of each pool, not
    a second pool."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return {**cache, **{name: tuple(pages.at[dst].set(pages[src])
                                    for pages in cache[name])
                        for name in ("k", "v")}}


def paged_write(pages: jax.Array, table: jax.Array, pos: jax.Array,
                new: jax.Array, *, page_size: int) -> jax.Array:
    """Write one token's K or V per slot.

    ``pages``: (P, page_size, H, D) — ONE layer's pool.
    ``table``: (B, pages_per_slot) int32; ``pos``: (B,) int32 (the position
    being written, i.e. the slot's current length); ``new``: (B, H, D).
    Masked/inactive slots must carry table rows full of ``SCRATCH_PAGE``.
    """
    page_idx = pos // page_size
    offset = pos % page_size
    page_ids = jnp.take_along_axis(table, page_idx[:, None], axis=1)[:, 0]
    return pages.at[page_ids, offset].set(new.astype(pages.dtype))


def paged_read(pages: jax.Array, table: jax.Array) -> jax.Array:
    """Gather a slot-major contiguous view of one layer's cache.

    ``pages``: (P, page_size, H, D); ``table``: (B, pages_per_slot) →
    (B, pages_per_slot * page_size, H, D). Fixed output shape — reads beyond
    a slot's true length surface scratch/stale values that the attention
    mask removes.
    """
    b, pps = table.shape
    gathered = pages[table]                      # (B, PPS, page, H, D)
    return gathered.reshape(b, pps * pages.shape[1], *pages.shape[2:])


def prefill_write(pages: jax.Array, table: jax.Array, kv: jax.Array,
                  *, page_size: int) -> jax.Array:
    """Scatter a whole prefill's K or V into the pool.

    ``kv``: (B, T_bucket, H, D) with T_bucket divisible by ``page_size``;
    table entries past the allocated prefix are ``SCRATCH_PAGE``, so bucket
    padding scatters into scratch.
    """
    b, t, h, d = kv.shape
    if t % page_size:
        raise ValueError(f"prefill bucket {t} must divide page_size "
                         f"{page_size}")
    if h < 8:
        # a pool of fewer heads than the 8 rows of a tile (grouped KV heads:
        # 4) lies in tiles of its own rows, and round a scatter of page tiles
        # the TPU's compiler re-lays the WHOLE pool with the page's tokens on
        # the tiled axis, two copies of it a prefill a layer (134 MB each in
        # gen-falconh1-chat-steady; PERF.md section 6, PR 50); token rows it
        # scatters where the pool lies
        return paged_write_multi(pages, table, jnp.zeros((b,), jnp.int32), kv,
                                 page_size=page_size)
    n_pages = t // page_size
    tiles = kv.reshape(b, n_pages, page_size, h, d).astype(pages.dtype)
    return pages.at[table[:, :n_pages]].set(tiles)


def paged_write_multi(pages: jax.Array, table: jax.Array, pos: jax.Array,
                      new: jax.Array, *, page_size: int) -> jax.Array:
    """Write ``T`` consecutive tokens' K or V per slot (the speculative
    verify step's batched twin of :func:`paged_write`).

    ``pages``: (P, page_size, H, D); ``table``: (B, pages_per_slot) int32;
    ``pos``: (B,) int32 — the FIRST position written per slot; ``new``:
    (B, T, H, D) — tokens land at positions ``pos .. pos+T-1``. The caller
    guarantees ``pos + T <= pages_per_slot * page_size`` (the batcher
    retires a slot before its tail can spill past the table). Masked slots
    carry scratch-only table rows, so their writes land in scratch.
    """
    t = new.shape[1]
    positions = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]  # (B,T)
    page_idx = positions // page_size
    offsets = positions % page_size
    page_ids = jnp.take_along_axis(table, page_idx, axis=1)          # (B,T)
    return pages.at[page_ids, offsets].set(new.astype(pages.dtype))


def for_query_heads(kv: jax.Array, n_q_heads: int) -> jax.Array:
    """K or V ``(B, T, H_kv, D)`` as ``n_q_heads = g x H_kv`` query heads
    read it: query head ``j`` attends KV head ``j // g`` (grouped-query
    attention). With ``g = 1`` it is ``kv`` itself."""
    g = n_q_heads // kv.shape[2]
    return kv if g == 1 else jnp.repeat(kv, g, axis=2)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array) -> jax.Array:
    """Single-query attention against a cached prefix, masked to each row's
    true length.

    ``q``: (B, H, D); ``k``/``v``: (B, T_max, H_kv, D), ``H`` a multiple of
    ``H_kv`` (:func:`for_query_heads`); ``lengths``: (B,) —
    number of VALID cache positions (the new token's K/V already written, so
    the query attends to itself). Plain dot attention on purpose: at query
    length 1 flash tiling is pure overhead (see
    ``ops.attention.prefer_flash_single_device``); softmax statistics in f32.
    """
    d = q.shape[-1]
    k, v = for_query_heads(k, q.shape[1]), for_query_heads(v, q.shape[1])
    scores = jnp.einsum("bhd,bthd->bht", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(d).astype(np.float32)
    t = k.shape[1]
    mask = jnp.arange(t, dtype=jnp.int32)[None, :] < lengths[:, None]  # (B,T)
    scores = jnp.where(mask[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bht,bthd->bhd", probs.astype(v.dtype), v)


def decode_attention_multi(q: jax.Array, k: jax.Array, v: jax.Array,
                           lengths: jax.Array) -> jax.Array:
    """Multi-query decode attention: ``T`` new tokens per slot against the
    cached prefix — the reference/fallback path for the speculative verify
    step (the fused twin is :func:`~analytics_zoo_tpu.ops.paged_attention.
    paged_attention` at q_len>1).

    ``q``: (B, T, H, D); ``k``/``v``: (B, T_max, H_kv, D), ``H`` a multiple
    of ``H_kv`` (:func:`for_query_heads`); ``lengths``: (B,) —
    VALID cache positions *including* the T new tokens (their K/V already
    written). Query ``i`` attends to positions ``<= lengths - T + i``:
    causal among the new tokens, full prefix before them. At T=1 this is
    exactly :func:`decode_attention` (bound = lengths - 1).
    """
    t_new = q.shape[1]
    d = q.shape[-1]
    k, v = for_query_heads(k, q.shape[2]), for_query_heads(v, q.shape[2])
    scores = jnp.einsum("bqhd,bthd->bhqt", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(d).astype(np.float32)
    t = k.shape[1]
    kv_pos = jnp.arange(t, dtype=jnp.int32)[None, None, None, :]
    q_idx = jnp.arange(t_new, dtype=jnp.int32)[None, None, :, None]
    bound = lengths[:, None, None, None] - t_new + q_idx
    scores = jnp.where(kv_pos <= bound, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqt,bthd->bqhd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# sampling — per-request keys so continuous-batch scheduling never changes a
# stream's tokens (determinism gate in tests/test_generation.py)
# ---------------------------------------------------------------------------

def sample_tokens(logits: jax.Array, seeds: jax.Array, token_idx: jax.Array,
                  temperature: jax.Array, *, top_k: int = 0,
                  return_probs: bool = False):
    """Sample one token per row under an explicit per-request PRNG key.

    ``logits``: (B, V) — any float dtype, upcast to f32 for the softmax.
    ``seeds``: (B,) uint32/int — per-REQUEST seed; ``token_idx``: (B,) —
    the row's generated-token ordinal. The key is
    ``fold_in(PRNGKey(seed), token_idx)``: token i of request r samples
    identically no matter which slot or decode step it lands in, which is
    what makes continuous admit/retire scheduling reproducible.
    ``temperature``: (B,) f32; rows at <= 0 take argmax (greedy).
    ``top_k`` (static): 0 = full distribution, else restrict to the k
    highest-logit tokens.

    ``return_probs`` (static): additionally return the (B, V) f32
    post-temperature/top_k distribution each row sampled from — the
    per-token probabilities the speculative accept/reject rule consumes
    (:mod:`analytics_zoo_tpu.ops.speculative`). The token path is
    UNCHANGED either way (existing streams stay bit-identical; greedy rows'
    probs are the temperature-floored softmax, ≈ one-hot on the argmax).
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(temperature.astype(jnp.float32), 1e-6)[:, None]
    scaled = logits / temp
    if top_k:
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled >= kth, scaled, NEG_INF)

    def one(row, seed, idx):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), idx)
        return jax.random.categorical(key, row)

    sampled = jax.vmap(one)(scaled, seeds.astype(jnp.uint32),
                            token_idx.astype(jnp.uint32)).astype(jnp.int32)
    tokens = jnp.where(temperature <= 0, greedy, sampled)
    if not return_probs:
        return tokens
    return tokens, jax.nn.softmax(scaled, axis=-1)


__all__ = [
    "KVCacheConfig", "OutOfPages", "PAGES", "PagePool", "PrefixCache",
    "PrefixMatch", "SCRATCH_PAGE", "SLOT", "copy_page", "decode_attention",
    "decode_attention_multi", "for_query_heads", "init_cache", "kinds_of",
    "paged_read", "paged_write",
    "paged_write_multi", "prefill_write", "prefix_block_key",
    "sample_tokens",
]
