"""Per-replica prefix KV cache: refcounted, content-addressed pages
inside the engine's shared page pool.

The cache does NOT own device memory — every cached page lives in the
same per-layer flat pool the engine's slots allocate from (page 0
stays the reserved garbage page). What the cache owns is the HOST
bookkeeping that lets finished prefills outlive their slot:

- a trie of :class:`PrefixNode`, one node per cached full page,
  keyed by the digest of the token prefix THROUGH that page (flat in
  value — ``keys.token_prefix_digest(tokens, (depth+1)*page_tokens)``
  — and drawn page by page from ``keys.iter_chain_digests``, which
  hashes each prompt token once) — so two prompts sharing the first k
  pages share the first k nodes;
- a refcount per node (slots currently mapping the page into their
  page table) — pinned pages are immutable and never freed;
- an LRU over EVICTABLE nodes: ``refs == 0`` and no children.
  Leaf-first eviction keeps every cached chain prefix-closed, which
  is what makes lookup's "walk down while present" correct.

What an admission costs here grows with ITS pages alone (PERF.md
section 6, PR 38): lookup and adoption hash each prompt token once,
and the eviction victim comes off a heap — no pass over the trie on
the engine's step path.

Threading: all mutation happens on the engine thread (the same
discipline as the page allocator); no locks here.

Safety argument for sharing (docs/serving.md "Prefix KV cache"): the
paged attend write path scatters at ``positions >= start`` only, and
a slot that pinned k pages prefills with ``positions = k*page_tokens``
— pinned pages are never written by construction, so a cached page's
K/V rows are bitwise-frozen from insert to eviction. The recycling
stress test extends the zero-stale-bleed proof to this regime.
"""

from __future__ import annotations

import heapq
from itertools import islice
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from tpunet.serve.prefixcache import keys


class PrefixNode:
    """One cached full page of prefill K/V.

    ``depth`` d covers tokens ``[d*page_tokens, (d+1)*page_tokens)``;
    ``digest`` is the flat digest of the token prefix through the end
    of this page; ``parent`` is the depth d-1 node (None at depth 0).
    ``page`` is the pool page index holding the rows. ``refs`` counts
    slots whose page table currently maps this page. ``tick`` is the
    cache's logical clock at last touch (LRU order); ``seq`` the
    node's insertion number, which orders nodes of one tick (one
    ``pin`` / ``unpin`` call stamps its whole chain alike).
    """

    __slots__ = ("digest", "parent", "children", "page", "refs",
                 "tick", "depth", "seq")

    def __init__(self, digest: str, parent: Optional["PrefixNode"],
                 depth: int, page: int):
        self.digest = digest
        self.parent = parent
        self.children: set = set()
        self.page = page
        self.refs = 0
        self.tick = 0
        self.depth = depth
        self.seq = 0


class PrefixCache:
    """Bounded trie of refcounted prefix pages (host side only).

    ``capacity`` bounds how many pool pages the cache may hold at
    refs == 0 + refs > 0 combined — the engine sizes it below the
    pool so paying slots always have headroom, and calls
    :meth:`evict_one` under pool pressure before failing an
    allocation.
    """

    def __init__(self, page_tokens: int, capacity: int, *,
                 registry=None):
        self.page_tokens = int(page_tokens)
        self.capacity = int(capacity)
        self._nodes: Dict[str, PrefixNode] = {}
        self._tick = 0
        self._seq = 0
        # (tick, seq, node) of every evictable node, and of nodes that
        # were evictable at that tick and are not now: evict_one skips
        # those (lazy invalidation), _offer bounds how many pile up.
        self._lru: List[Tuple[int, int, PrefixNode]] = []
        self._reg = registry
        if registry is not None:
            self._c_lookups = registry.counter("serve_prefix_lookups_total")
            self._c_hits = registry.counter("serve_prefix_hits_total")
            self._c_hit_tokens = registry.counter(
                "serve_prefix_hit_tokens_total")
            self._c_inserts = registry.counter("serve_prefix_inserts_total")
            self._c_evictions = registry.counter(
                "serve_prefix_evictions_total")
            self._g_pages = registry.gauge("serve_prefix_pages_cached")
        else:
            self._c_lookups = self._c_hits = self._c_hit_tokens = None
            self._c_inserts = self._c_evictions = self._g_pages = None

    # -- introspection ---------------------------------------------------

    def __len__(self) -> int:
        return len(self._nodes)

    @property
    def pages_cached(self) -> int:
        return len(self._nodes)

    def pinned_pages(self) -> int:
        return sum(1 for n in self._nodes.values() if n.refs > 0)

    def evictable_pages(self) -> int:
        return sum(1 for n in self._nodes.values()
                   if n.refs == 0 and not n.children)

    def get(self, digest: str) -> Optional[PrefixNode]:
        return self._nodes.get(digest)

    # -- lookup / pin ----------------------------------------------------

    def lookup(self, tokens: Sequence[int], max_pages: int, *,
               digests: Optional[Iterator[str]] = None
               ) -> List[PrefixNode]:
        """The longest cached chain covering the first full pages of
        ``tokens``, capped at ``max_pages`` — counted as one lookup
        (and one hit when non-empty). Does NOT pin; the engine pins
        only once the slot's remaining allocation succeeded.

        Lazy: the walk stops hashing at the first miss. ``digests`` is
        a ``keys.iter_chain_digests`` over ``tokens`` from depth 0 that
        the caller goes on drawing from — after a full chain its next
        element keys depth ``max_pages`` (the engine's COW source)."""
        chain: List[PrefixNode] = []
        pt = self.page_tokens
        if digests is None:
            digests = keys.iter_chain_digests(tokens, pt, max_pages)
        for digest in islice(digests, max_pages):
            node = self._nodes.get(digest)
            if node is None:
                break
            chain.append(node)
        if self._c_lookups is not None:
            self._c_lookups.inc()
            if chain:
                self._c_hits.inc()
                self._c_hit_tokens.inc(len(chain) * pt)
        return chain

    def pin(self, nodes: Sequence[PrefixNode]) -> None:
        """refcount++ each node (slot admission mapped its page)."""
        self._tick += 1
        for n in nodes:
            if self._lru and self._lru[-1][2] is n:
                # Insert-then-pin, the engine's adoption: the entry
                # insert just queued is still the heap's last leaf and
                # comes off at once instead of going stale.
                self._lru.pop()
            n.refs += 1
            n.tick = self._tick

    def unpin(self, nodes: Sequence[PrefixNode]) -> None:
        """refcount-- each node (slot released its page table). The
        page stays cached — eviction, not release, returns it to the
        free list."""
        self._tick += 1
        for n in nodes:
            n.refs -= 1
            assert n.refs >= 0, "prefix page unpinned below zero"
            n.tick = self._tick
            self._offer(n)

    # -- insert / evict --------------------------------------------------

    def insert(self, digest: str, parent: Optional[PrefixNode],
               depth: int, page: int) -> PrefixNode:
        """Adopt ``page`` (already holding the rows for this chain
        position) as a cached node. The caller has already checked
        ``get(digest) is None`` — concurrent-duplicate dedup is the
        engine's job because the duplicate page must go back to the
        pool. The node is returned UNPINNED; the caller pins it if a
        slot still maps it."""
        assert digest not in self._nodes
        node = PrefixNode(digest, parent, depth, page)
        if parent is not None:
            parent.children.add(node)
        self._tick += 1
        node.tick = self._tick
        self._seq += 1
        node.seq = self._seq
        self._nodes[digest] = node
        self._offer(node)
        if self._c_inserts is not None:
            self._c_inserts.inc()
            self._g_pages.set(len(self._nodes))
        return node

    def _offer(self, node: PrefixNode) -> None:
        """Queue ``node`` for eviction at its present tick if it is
        evictable. Called wherever a node can BECOME evictable: its
        insert, its last unpin, the eviction of its last child."""
        if node.refs or node.children:
            return
        if len(self._lru) > 2 * len(self._nodes) + 64:
            # Pins and re-pins of a cache that never fills leave stale
            # entries nobody pops: rebuild from the live ones.
            self._lru = [(n.tick, n.seq, n) for n in self._nodes.values()
                         if n.refs == 0 and not n.children and n is not node]
            heapq.heapify(self._lru)
        heapq.heappush(self._lru, (node.tick, node.seq, node))

    def evict_one(self) -> Optional[int]:
        """Drop the least-recently-touched evictable node (refs == 0,
        no children; of one tick, the one inserted first) and return
        its pool page for the free list; None when nothing is
        evictable (every cached page is pinned by a live slot or
        interior to a pinned chain)."""
        victim: Optional[PrefixNode] = None
        while self._lru:
            tick, _, n = heapq.heappop(self._lru)
            if n.tick == tick and n.refs == 0 and not n.children \
                    and self._nodes.get(n.digest) is n:
                victim = n
                break
        if victim is None:
            return None
        del self._nodes[victim.digest]
        parent = victim.parent
        if parent is not None:
            parent.children.discard(victim)
            self._offer(parent)
        if self._c_evictions is not None:
            self._c_evictions.inc()
            self._g_pages.set(len(self._nodes))
        return victim.page
