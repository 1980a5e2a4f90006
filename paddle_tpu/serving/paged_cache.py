"""Block/paged KV cache: a fixed pool of pages + host-side block tables.

The reference ships a production inference stack
(paddle/fluid/inference/) whose KV memory is a dense per-call slab;
models/generation.py kept that shape — the cache is `[B, T]`-dense and
dies with the call, so a finished request can't release its memory
without re-batching everyone else. The serving-native form (vLLM's
PagedAttention insight, TPU-statically-shaped here) splits the cache
into fixed-size PAGES:

- device side: per layer, one K pool and one V pool of shape
  ``[n_blocks, block_size, n_heads * head_dim]`` — one row a token,
  heads side by side in the lanes — allocated once at engine build,
  donated through every compiled prefill/decode call so XLA updates
  the pages in place (graph_lint's donation rule proves the aliasing).
  Heads and head size are merged because of the TPU's default layouts:
  a 4-D ``[n_blocks, block_size, n_heads, 64]`` array lives on the
  device pages-minor-most (head 64 fills half of the 128 lanes, so XLA
  avoids the row-major form), while the programs' scatters and the
  decode kernel address pages row-major, and every dispatch then
  converted each pool at entry and back at exit. The merged row's
  default layout IS row-major (and lane-dense), so a donated pool goes
  in, is written in place and comes out with no conversion;
- host side: a free-list allocator and a per-request block table
  (request -> ordered page ids). A request's cache is the list of
  pages its table names; logical token position ``p`` lives in page
  ``table[p // block_size]`` at offset ``p % block_size``.

Eviction of a finished request is therefore a host-side list append —
no device copy, no neighbor movement, no recompile. Block id 0 is
reserved as SCRATCH: it is never allocated, and masked/padded rows in
the compiled programs route their writes there, so inactive lanes need
no conditional scatter.

Allocation is whole-lifetime: ``alloc(req, prompt + max_new)`` reserves
every page the request can ever touch at admission, so a running decode
can never OOM mid-stream (admission control is the only backpressure
point).

**Prefix sharing (copy-on-write).** Because a token's K/V depends only
on the tokens BEFORE it, any page holding a full ``block_size``-token
chunk of a prompt is reusable verbatim by every request whose prompt
starts with the same tokens — system prompts become a pointer trick.
With ``prefix_sharing=True`` every page carries a REFCOUNT, and a
radix index over full-page token chunks maps prompt prefixes to the
pages that already hold their K/V:

- ``alloc_shared`` matches the longest indexed prefix (capped one
  token short of the prompt, so the suffix prefill always has >= 1
  real token), points the new table at the shared pages (refcount++),
  and takes fresh pages only for the unshared tail;
- ``register_prefix`` (after the suffix prefill lands) adopts the
  request's full-prompt pages into the index (the index holds its own
  reference), so the NEXT request with this prefix shares them;
- ``free`` decrements; a page returns to the free list only at
  refcount zero — index-held pages survive their creator and are
  reclaimed LRU-leaf-first when admission needs pages
  (``available_pages`` counts them as allocatable);
- ``ensure_writable`` is the copy-on-write guard: before any in-place
  write to a page with refcount > 1, the writer gets a private copy
  (one jitted page-copy program, pools donated) and the readers keep
  the original bytes. The engine's write patterns never hit shared
  pages by construction (shared pages hold only full-prompt chunks;
  decode writes start at prompt_len), so the guard is the invariant
  safety net, not a hot path.

The invariants tests pin: per-page refcounts equal the number of
tables + index nodes naming the page, shared pages are never freed
while referenced, and 1 (scratch) + free + live == n_blocks with
shared pages counted ONCE (``n_live`` is distinct pages).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["PagedKVCache"]


class _RadixNode:
    """One full-page chunk of an indexed prompt prefix. The path from
    the root to a node spells the token prefix; ``page`` holds that
    chunk's K/V (the index owns one refcount on it)."""
    __slots__ = ("chunk", "page", "children", "parent", "tick")

    def __init__(self, chunk: Tuple[int, ...], page: int, parent,
                 tick: int):
        self.chunk = chunk
        self.page = int(page)
        self.children: Dict[Tuple[int, ...], "_RadixNode"] = {}
        self.parent = parent
        self.tick = tick


class _RadixIndex:
    """Radix tree over ``block_size``-token chunks -> page ids, with
    LRU ticks for leaf-first reclaim."""

    def __init__(self, block_size: int):
        self.block_size = int(block_size)
        self.children: Dict[Tuple[int, ...], _RadixNode] = {}
        self._tick = 0
        self.n_nodes = 0

    def _chunks(self, ids) -> List[Tuple[int, ...]]:
        bs = self.block_size
        ids = [int(t) for t in ids]
        return [tuple(ids[i * bs:(i + 1) * bs])
                for i in range(len(ids) // bs)]

    def match(self, ids, max_pages: int) -> List[int]:
        """Longest indexed prefix of ``ids`` in full pages (<=
        max_pages); touches the matched path's LRU ticks."""
        self._tick += 1
        pages: List[int] = []
        kids = self.children
        for chunk in self._chunks(ids)[:max_pages]:
            node = kids.get(chunk)
            if node is None:
                break
            node.tick = self._tick
            pages.append(node.page)
            kids = node.children
        return pages

    def insert(self, ids, pages: Sequence[int],
               n_pages: int) -> List[int]:
        """Index the first ``n_pages`` full chunks of ``ids`` against
        ``pages``; returns the pages NEWLY adopted (caller owes each
        one refcount). Chunks already present keep their existing page
        (first writer wins — both hold identical K/V bytes)."""
        self._tick += 1
        adopted: List[int] = []
        parent = None
        kids = self.children
        for i, chunk in enumerate(self._chunks(ids)[:n_pages]):
            node = kids.get(chunk)
            if node is None:
                node = _RadixNode(chunk, pages[i], parent, self._tick)
                kids[chunk] = node
                self.n_nodes += 1
                adopted.append(node.page)
            else:
                node.tick = self._tick
            parent = node
            kids = node.children
        return adopted

    def pop_lru_leaf(self) -> Optional[_RadixNode]:
        """Remove and return the least-recently-touched leaf (reclaim
        drops subtrees leaf-first so every remaining path stays
        matchable)."""
        leaf = None
        stack = list(self.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            elif leaf is None or n.tick < leaf.tick:
                leaf = n
        if leaf is None:
            return None
        kids = (leaf.parent.children if leaf.parent is not None
                else self.children)
        del kids[leaf.chunk]
        self.n_nodes -= 1
        return leaf

    def pages(self) -> List[int]:
        out: List[int] = []
        stack = list(self.children.values())
        while stack:
            n = stack.pop()
            out.append(n.page)
            stack.extend(n.children.values())
        return out


class PagedKVCache:
    """Fixed page pool + host-side block-table allocator.

    ``pools`` is the device pytree (a tuple over layers of (k, v) page
    pools) the compiled programs consume and return; the engine swaps
    the attribute after every donated call. Everything else is host
    bookkeeping.
    """

    def __init__(self, n_layers: int, n_blocks: int, block_size: int,
                 n_heads: int, head_dim: int, dtype="float32",
                 prefix_sharing: bool = False, pool_sharding=None,
                 tp: int = 1):
        if n_blocks < 2:
            raise ValueError(
                f"n_blocks={n_blocks}: need at least 1 allocatable "
                "page beyond the reserved scratch block 0")
        if block_size < 1:
            raise ValueError(f"block_size={block_size} must be >= 1")
        import jax.numpy as jnp
        self.n_layers = int(n_layers)
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        self.dtype = jnp.dtype(dtype)
        # tensor parallelism: n_heads stays the GLOBAL head count —
        # every host-side structure (tables, free list, refcounts,
        # radix index, sizing math) is tp-invariant; only the device
        # pools shard, each chip holding heads/tp of every page: its
        # own whole heads, a contiguous (n_heads/tp)*head_dim of the
        # merged axis (pool_sharding = NamedSharding over 'tp')
        self.tp = int(tp)
        self.pool_sharding = pool_sharding
        shape = (self.n_blocks, self.block_size,
                 self.n_heads * self.head_dim)

        def _pool():
            z = jnp.zeros(shape, self.dtype)
            if pool_sharding is not None:
                import jax
                z = jax.device_put(z, pool_sharding)
            return z

        self.pools = tuple((_pool(), _pool())
                           for _ in range(self.n_layers))
        # LIFO free list: hot reuse keeps the working set of pages
        # small (freshly-freed pages go to the next admission)
        self._free: List[int] = list(range(self.n_blocks - 1, 0, -1))
        self._tables: Dict[object, List[int]] = {}
        # page -> refcount over live pages (tables + index holds);
        # maintained even without sharing so n_live/conservation is
        # one code path
        self._ref: Dict[int, int] = {}
        self.prefix_sharing = bool(prefix_sharing)
        self._radix = (_RadixIndex(self.block_size)
                       if self.prefix_sharing else None)
        self._copy = None                      # jitted COW page copy
        # sharing receipts (host counters; the engine mirrors them to
        # the gated serving.* series)
        self.prefix_hits = 0
        self.shared_pages_matched = 0
        self.cow_copies = 0
        self.reclaimed_pages = 0

    # -- sizing --------------------------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        """Pages needed to hold n_tokens."""
        return -(-int(n_tokens) // self.block_size)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        """DISTINCT live pages — a page shared by k tables (and/or the
        prefix index) counts once; conservation is
        ``1 + n_free + n_live == n_blocks``."""
        return len(self._ref)

    @property
    def n_shared(self) -> int:
        return sum(1 for c in self._ref.values() if c > 1)

    def _n_reclaimable(self) -> int:
        """Index-held pages no live table references — droppable by
        LRU reclaim, so admission may count them as allocatable."""
        if self._radix is None:
            return 0
        return sum(1 for p in self._radix.pages()
                   if self._ref.get(p, 0) == 1)

    @property
    def available_pages(self) -> int:
        """Free pages plus index-exclusive (reclaimable) ones — the
        number admission control may promise."""
        return len(self._free) + self._n_reclaimable()

    def can_alloc(self, n_tokens: int) -> bool:
        return self.blocks_for(n_tokens) <= self.available_pages

    def stats(self) -> Dict[str, float]:
        """Occupancy snapshot for the memory plane's per-tick gauges:
        pages live/free/scratch (conservation: live + free + 1 ==
        n_blocks, the invariant check's arithmetic — live counts
        shared pages ONCE), occupancy over the allocatable pool, and
        the device bytes the pools pin (fixed at build — the serving
        cache's whole HBM story)."""
        allocatable = self.n_blocks - 1
        live = self.n_live
        page_bytes = (self.block_size * self.n_heads * self.head_dim
                      * self.dtype.itemsize)
        out = {
            "pages_live": live,
            "pages_free": len(self._free),
            "pages_scratch": 1,
            "occupancy": (live / allocatable) if allocatable else 0.0,
            "requests": len(self._tables),
            "pool_bytes": 2 * self.n_layers * self.n_blocks
            * page_bytes,
        }
        out["pool_bytes_per_chip"] = out["pool_bytes"] // self.tp
        if self.prefix_sharing:
            out.update({
                "pages_shared": self.n_shared,
                "prefix_nodes": self._radix.n_nodes,
                "prefix_hits": self.prefix_hits,
                "shared_pages_matched": self.shared_pages_matched,
                "cow_copies": self.cow_copies,
                "reclaimed_pages": self.reclaimed_pages,
            })
        return out

    # -- page bookkeeping ----------------------------------------------------
    def _take_pages(self, need: int, who) -> List[int]:
        """Pop ``need`` fresh pages (refcount 1 each), reclaiming
        index-exclusive pages LRU-leaf-first when the free list runs
        short."""
        if need > len(self._free):
            self._reclaim(need - len(self._free))
        if need > len(self._free):
            raise MemoryError(
                f"paged cache exhausted: need {need} pages for "
                f"{who!r}, {len(self._free)} free "
                f"(pool {self.n_blocks - 1} allocatable)")
        pages = [self._free.pop() for _ in range(need)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def _decref(self, page: int) -> int:
        """Drop one reference; returns 1 when the page went back to
        the free list."""
        c = self._ref[page] - 1
        if c:
            self._ref[page] = c
            return 0
        del self._ref[page]
        self._free.append(page)
        return 1

    def _reclaim(self, shortfall: int):
        """Evict least-recently-used index leaves until ``shortfall``
        pages came free (or the index runs out of droppable leaves).
        Dropping a leaf whose page a live table still shares frees
        nothing now — the page returns when the request retires — so
        the loop counts only real free-list gains."""
        if self._radix is None:
            return
        freed = 0
        while freed < shortfall:
            leaf = self._radix.pop_lru_leaf()
            if leaf is None:
                break
            got = self._decref(leaf.page)
            freed += got
            self.reclaimed_pages += got

    # -- allocate / free -----------------------------------------------------
    def alloc(self, req_id, n_tokens: int) -> List[int]:
        """Reserve the request's whole-lifetime page list. Raises on
        double-alloc or pool exhaustion (admission control must check
        ``can_alloc`` first — running out mid-decode is a bug)."""
        if req_id in self._tables:
            raise ValueError(f"request {req_id!r} already holds pages")
        blocks = self._take_pages(self.blocks_for(n_tokens), req_id)
        self._tables[req_id] = blocks
        return list(blocks)

    def alloc_shared(self, req_id, n_tokens: int,
                     prompt_ids) -> Tuple[List[int], int]:
        """Prefix-sharing admission: match the longest indexed prefix
        of ``prompt_ids`` (full pages only, capped one token short of
        the prompt so the suffix prefill keeps >= 1 real token), share
        those pages (refcount++), and take fresh pages for the rest of
        the whole-lifetime reservation. Returns ``(blocks,
        shared_tokens)``."""
        if self._radix is None:
            raise RuntimeError("prefix_sharing is disabled on this "
                               "cache")
        if req_id in self._tables:
            raise ValueError(f"request {req_id!r} already holds pages")
        prompt_len = len(prompt_ids)
        cap = (prompt_len - 1) // self.block_size
        shared = self._radix.match(prompt_ids, cap)
        need = self.blocks_for(n_tokens) - len(shared)
        fresh = self._take_pages(need, req_id)
        for p in shared:
            self._ref[p] += 1
        self._tables[req_id] = list(shared) + fresh
        if shared:
            self.prefix_hits += 1
            self.shared_pages_matched += len(shared)
        return list(self._tables[req_id]), len(shared) * self.block_size

    def register_prefix(self, req_id, prompt_ids) -> int:
        """Adopt the request's full-prompt-chunk pages into the radix
        index (call AFTER its prefill landed — the pages must hold
        real K/V). The index takes its own refcount on each newly
        adopted page, so they outlive the request. Returns the number
        adopted."""
        if self._radix is None:
            return 0
        table = self._tables[req_id]
        full = len(prompt_ids) // self.block_size
        adopted = self._radix.insert(prompt_ids, table, full)
        for p in adopted:
            self._ref[p] += 1
        return len(adopted)

    def free(self, req_id) -> List[int]:
        """Drop a finished request's references — a host list splice;
        pages return to the free list at refcount zero, shared pages
        stay live for their other holders."""
        blocks = self._tables.pop(req_id, None)
        if blocks is None:
            raise KeyError(f"request {req_id!r} holds no pages")
        for p in blocks:
            self._decref(p)
        return blocks

    def table(self, req_id) -> List[int]:
        return list(self._tables[req_id])

    def live_requests(self) -> List:
        return list(self._tables)

    # -- copy-on-write -------------------------------------------------------
    def _copy_page_fn(self):
        if self._copy is None:
            import jax

            def cp(pools, src, dst):
                return tuple((k.at[dst].set(k[src]),
                              v.at[dst].set(v[src]))
                             for (k, v) in pools)
            self._copy = jax.jit(cp, donate_argnums=(0,))
        return self._copy

    def copy_executables(self) -> int:
        return 0 if self._copy is None else int(self._copy._cache_size())

    def span_counts(self) -> dict:
        """What the engine writes of this cache on its ``step`` span:
        nothing yet (no reader asks for the pages' counts there)."""
        return {}

    def warm_copy(self):
        """Compile the COW page-copy program up front (scratch ->
        scratch is a junk-safe no-op write) so a first real copy never
        recompiles mid-traffic."""
        self.pools = self._copy_page_fn()(
            self.pools, np.int32(0), np.int32(0))
        return self

    def ensure_writable(self, req_id, first_pos: int,
                        n_pos: int) -> int:
        """Copy-on-write guard: before in-place writes to logical
        positions ``[first_pos, first_pos + n_pos)``, give the writer
        a PRIVATE copy of any covered page with refcount > 1 — the
        readers (other tables, the index) keep the original bytes.
        Returns the number of pages copied (0 on the engine's write
        patterns: shared pages hold only full-prompt chunks and decode
        writes start at prompt_len)."""
        if n_pos < 1:
            return 0
        table = self._tables[req_id]
        bs = self.block_size
        copies = 0
        last = min((first_pos + n_pos - 1) // bs, len(table) - 1)
        for idx in range(first_pos // bs, last + 1):
            pid = table[idx]
            if self._ref.get(pid, 0) > 1:
                new = self._take_pages(1, req_id)[0]
                self.pools = self._copy_page_fn()(
                    self.pools, np.int32(pid), np.int32(new))
                self._decref(pid)
                table[idx] = new
                copies += 1
        self.cow_copies += copies
        return copies

    # -- program feed --------------------------------------------------------
    def table_array(self, req_ids: Sequence, width: int) -> np.ndarray:
        """Padded ``[len(req_ids), width]`` int32 block-table array for
        the compiled programs. Missing entries (rows shorter than
        width, or req_id None = a dummy admission lane) point at the
        scratch block 0 — writes land there, reads are masked."""
        out = np.zeros((len(req_ids), width), np.int32)
        for i, rid in enumerate(req_ids):
            if rid is None:
                continue
            blocks = self._tables[rid]
            if len(blocks) > width:
                raise ValueError(
                    f"request {rid!r} holds {len(blocks)} pages > "
                    f"table width {width}")
            out[i, :len(blocks)] = blocks
        return out

    # -- invariants ----------------------------------------------------------
    def check_invariants(self):
        """Refcount conservation + scratch never handed out. Without
        sharing this is the old contract verbatim (no page in two live
        tables); with sharing every page's refcount must equal the
        number of tables plus index nodes naming it, and shared pages
        count ONCE in the live total. Cheap enough to call every
        scheduler step in tests."""
        counts: Dict[int, int] = {}
        for t in self._tables.values():
            for p in t:
                counts[p] = counts.get(p, 0) + 1
        if not self.prefix_sharing and any(c > 1
                                           for c in counts.values()):
            raise AssertionError("a page is shared by two live requests")
        if self._radix is not None:
            idx_pages = self._radix.pages()
            if len(idx_pages) != len(set(idx_pages)):
                raise AssertionError(
                    "a page is held by two radix nodes")
            for p in idx_pages:
                counts[p] = counts.get(p, 0) + 1
        if counts != self._ref:
            raise AssertionError(
                f"refcounts drifted: expected {counts}, "
                f"cache holds {self._ref}")
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            raise AssertionError("duplicate page on the free list")
        if set(counts) & free_set:
            raise AssertionError("page both live and free")
        if 0 in counts or 0 in free_set:
            raise AssertionError("scratch block 0 was allocated")
        total = 1 + len(self._free) + len(counts)
        if total != self.n_blocks:
            raise AssertionError(
                f"page conservation broken: 1 scratch + "
                f"{len(self._free)} free + {len(counts)} live != "
                f"{self.n_blocks}")
        return True
