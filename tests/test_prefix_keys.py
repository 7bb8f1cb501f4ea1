"""The prefix cache's two host-side invariants (PR 38), held without an
engine: every digest keeps the value the flat per-token hash gave it,
and ``PrefixCache.evict_one`` picks the node a scan over the whole trie
picks — while neither costs more than the admission's own pages.

The references live HERE: ``naive_digest`` is the per-token loop the
module ran before its chain went one-pass, ``scan_victim`` the pass over
all nodes ``evict_one`` made before it kept a heap. The golden literals
were printed by the parent commit's ``token_prefix_digest``.
"""

import hashlib
import random

import numpy as np
import pytest

from tpunet.serve.prefixcache import PrefixCache, PrefixStore, keys
from tpunet.serve.prefixcache import cache as cache_mod

from _serve_script import counting_hashlib

PAGE = 16

#: 100 tokens that touch both ends of int32 and both signs.
GOLDEN_TOKENS = [(i * 2654435761) % 50257 for i in range(96)] \
    + [0, -1, 2**31 - 1, -2**31]

GOLDEN = {
    0: "e3b0c44298fc1c14",
    1: "df3f619804a92fdb",
    15: "243b5c9d35d09170",
    16: "020401e04fd02b84",
    17: "09b586a6e9657787",
    32: "57486c9e11889c44",
    96: "365f440ff1f36b2b",
    100: "f0bacef9ecc076cc",
}


def naive_digest(tokens, n):
    h = hashlib.sha256()
    for t in tokens[:n]:
        h.update(int(t).to_bytes(4, "little", signed=True))
    return h.hexdigest()[:16]


def scan_victim(cache):
    victim = None
    for n in cache._nodes.values():
        if n.refs == 0 and not n.children:
            if victim is None or n.tick < victim.tick:
                victim = n
    return victim


# ---------------------------------------------------------------------------
# the key chain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", sorted(GOLDEN))
def test_digests_keep_the_parents_values(n):
    assert keys.token_prefix_digest(GOLDEN_TOKENS, n) == GOLDEN[n]
    assert naive_digest(GOLDEN_TOKENS, n) == GOLDEN[n]


def test_chain_and_store_names_keep_the_parents_values(tmp_path):
    """The spill store's file names ARE the chain's digests: a store
    directory another version wrote is found again."""
    chain = keys.chain_digests(GOLDEN_TOKENS, PAGE, 6)
    assert chain == [GOLDEN[16], GOLDEN[32]] + chain[2:5] + [GOLDEN[96]]
    store = PrefixStore(str(tmp_path), "feedc0de")
    for d, digest in enumerate(chain):
        assert store.save(digest, chain[d - 1] if d else keys.ROOT, d, [])
    names = sorted(p.name for p in tmp_path.glob("*.pfx"))
    assert names == sorted(
        f"feedc0de-{naive_digest(GOLDEN_TOKENS, (d + 1) * PAGE)}.pfx"
        for d in range(6))
    assert names[0] == "feedc0de-020401e04fd02b84.pfx"
    assert [e["digest"] for e in store.load_all()] == chain


@pytest.mark.parametrize("kind", ["list", "int32", "int64", "strided"])
@pytest.mark.parametrize("length", [0, 1, PAGE - 1, PAGE, PAGE + 1,
                                    3 * PAGE, 5 * PAGE + 7, 333])
def test_one_pass_chain_equals_the_per_token_reference(length, kind):
    rng = np.random.default_rng(1000 * length + len(kind))
    toks = rng.integers(-2**31, 2**31, size=length).tolist()
    given = {"list": lambda: toks,
             "int32": lambda: np.asarray(toks, np.int32),
             "int64": lambda: np.asarray(toks, np.int64),
             "strided": lambda: np.repeat(
                 np.asarray(toks, np.int32), 2)[::2]}[kind]()
    for n in {0, 1, length // 2, length, length + 5}:
        assert keys.token_prefix_digest(given, n) == naive_digest(toks, n)
    pages = length // PAGE
    want = [naive_digest(toks, (d + 1) * PAGE) for d in range(pages)]
    assert keys.chain_digests(given, PAGE, pages) == want
    for start in {0, pages // 2, pages}:
        assert list(keys.iter_chain_digests(
            given, PAGE, pages, start)) == want[start:]
    # past the tokens a digest covers what there is, as a slice does
    assert keys.chain_digests(given, PAGE, pages + 1)[-1] \
        == naive_digest(toks, length)


@pytest.mark.parametrize("bad", [
    [2**31], [-2**31 - 1], [1, 2, 2**40], [2**63], [2**70],
    np.asarray([5, 2**31], np.int64), np.asarray([2**32 - 1], np.uint32)],
    ids=["int32+1", "int32-1", "2^40", "2^63", "2^70", "int64", "uint32"])
def test_a_token_outside_int32_raises(bad):
    with pytest.raises(OverflowError):
        naive_digest(list(bad), len(bad))
    with pytest.raises(OverflowError):
        keys.token_prefix_digest(bad, len(bad))
    with pytest.raises(OverflowError):
        list(keys.iter_chain_digests(bad, 1, len(bad)))


def test_the_chain_is_lazy(monkeypatch):
    """Nothing is hashed before the first digest is drawn, and one page
    per digest after it: a lookup that misses at page 0 hashes one."""
    fed = []

    toks = np.arange(40 * PAGE, dtype=np.int32)
    monkeypatch.setattr(keys, "hashlib", counting_hashlib(fed))
    chain = keys.iter_chain_digests(toks, PAGE, 40)
    assert fed == []
    assert next(chain) == naive_digest(toks, PAGE)
    assert sum(fed) == 4 * PAGE
    cache = PrefixCache(PAGE, 64)
    del fed[:]
    assert cache.lookup(toks, 39) == []
    assert sum(fed) == 4 * PAGE


# ---------------------------------------------------------------------------
# the eviction victim
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_evict_one_picks_the_scans_victim(seed):
    """A few thousand random inserts, pins, unpins and evictions over
    chains that share parents: the victim is the scan's every time,
    ``None`` exactly when the scan finds none, and a parent is next
    once its last child went."""
    rng = random.Random(seed)
    cache = PrefixCache(4, 10**9)
    held = []                  # lists of nodes some "slot" has pinned
    serial = [0]

    def chain_to(node):
        out = []
        while node is not None:
            out.append(node)
            node = node.parent
        return out[::-1]

    def insert_some():
        live = list(cache._nodes.values())
        parent = rng.choice(live) if live and rng.random() < 0.8 else None
        for _ in range(rng.randint(1, 4)):
            serial[0] += 1
            parent = cache.insert(
                f"d{serial[0]:08d}", parent,
                0 if parent is None else parent.depth + 1, serial[0])
            if rng.random() < 0.6:
                # as the engine does: adopted pages are pinned one by one
                cache.pin([parent])
                held.append([parent])

    evicted = nones = freed_parents = 0
    for _ in range(4000):
        op = rng.random()
        live = list(cache._nodes.values())
        if op < 0.30 or not live:
            insert_some()
        elif op < 0.45:
            nodes = chain_to(rng.choice(live))
            cache.pin(nodes)
            held.append(nodes)
        elif op < 0.65 and held:
            cache.unpin(held.pop(rng.randrange(len(held))))
        else:
            for _ in range(rng.randint(1, 6)):
                want = scan_victim(cache)
                had_siblings = want is not None and want.parent is not None \
                    and len(want.parent.children) > 1
                page = cache.evict_one()
                if want is None:
                    assert page is None
                    nones += 1
                    break
                assert page == want.page
                assert want.digest not in cache._nodes
                evicted += 1
                if want.parent is not None and not had_siblings \
                        and want.parent.refs == 0:
                    freed_parents += 1
    # drain: what is left goes in the scan's order to the last node
    for nodes in held:
        cache.unpin(nodes)
    while True:
        want = scan_victim(cache)
        page = cache.evict_one()
        if want is None:
            assert page is None
            break
        assert page == want.page
    assert len(cache) == 0
    assert evicted > 500 and nones > 10 and freed_parents > 50


def test_nodes_of_one_tick_go_in_insertion_order():
    """One ``unpin`` stamps its whole list alike; among equals the scan
    takes the one inserted first, and so does the heap — also after a
    digest was evicted and inserted again (it is then the youngest)."""
    cache = PrefixCache(4, 64)
    a, b, c = (cache.insert(d, None, 0, p) for d, p in
               (("a", 1), ("b", 2), ("c", 3)))
    cache.pin([c, a, b])
    cache.unpin([c, a, b])
    assert cache.evict_one() == 1
    a2 = cache.insert("a", None, 0, 4)
    cache.pin([a2, b, c])
    cache.unpin([a2, b, c])
    assert [cache.evict_one() for _ in range(4)] == [2, 3, 4, None]


class _NoScan(dict):
    """``PrefixCache._nodes`` that counts every pass over itself."""
    scans = 0

    def values(self):
        type(self).scans += 1
        return super().values()

    __iter__ = items = keys = None       # nothing else may walk it


def _counting_heapq(monkeypatch):
    import heapq
    calls = {"push": 0, "pop": 0}

    class Shim:
        heapify = staticmethod(heapq.heapify)

        @staticmethod
        def heappush(heap, item):
            calls["push"] += 1
            heapq.heappush(heap, item)

        @staticmethod
        def heappop(heap):
            calls["pop"] += 1
            return heapq.heappop(heap)

    monkeypatch.setattr(cache_mod, "heapq", Shim)
    return calls


def test_evict_one_on_a_full_cache_visits_a_node_not_the_trie(monkeypatch):
    """The 4k cell's regime — capacity 1,536, prompts of 128 to 256
    pages, every insert behind an eviction (six slots, so that what is
    pinned always leaves a victim): an eviction pops ONE heap entry
    (adoption's insert-then-pin leaves none stale) and nothing walks
    ``_nodes``. Stale entries come from hits and from children put
    under unpinned parents; each is popped once, and the tests around
    this one hold the order and the heap's size with those."""
    calls = _counting_heapq(monkeypatch)
    cache = PrefixCache(PAGE, 1536)
    cache._nodes = _NoScan()
    _NoScan.scans = 0
    slots = [None] * 6
    serial = 0
    evictions = 0
    for admission in range(40):
        s = admission % 6
        if slots[s] is not None:
            cache.unpin(slots[s])
        pinned, prev = [], None
        for depth in range((128, 171, 214, 256)[admission * 7 % 4]):
            while cache.pages_cached >= cache.capacity:
                before = calls["pop"]
                assert cache.evict_one() is not None
                evictions += 1
                assert calls["pop"] - before == 1
            serial += 1
            prev = cache.insert(f"{serial:x}", prev, depth, serial)
            cache.pin([prev])
            pinned.append(prev)
        slots[s] = pinned
    assert evictions > 4000
    assert calls["pop"] == evictions
    assert len(cache._lru) <= 2 * len(cache) + 65
    assert _NoScan.scans == 0
    # a quiet full cache: one pop an eviction
    for pinned in slots:
        cache.unpin(pinned)
    for _ in range(len(cache)):
        before = calls["pop"]
        assert cache.evict_one() is not None
        assert calls["pop"] - before == 1
    assert cache.evict_one() is None and _NoScan.scans == 0


def test_the_heap_stays_bounded_when_nothing_is_ever_evicted():
    """Hits on a cache that never fills pin and unpin the same leaves
    for ever; the entries they leave stale are swept, not kept."""
    cache = PrefixCache(4, 64)
    prev, chain = None, []
    for d in range(6):
        prev = cache.insert(f"n{d}", prev, d, d + 1)
        chain.append(prev)
    other = cache.insert("other", chain[2], 3, 9)
    for i in range(5000):
        nodes = chain if i % 2 else chain[:3] + [other]
        cache.pin(nodes)
        cache.unpin(nodes)
        assert len(cache._lru) <= 2 * len(cache) + 65
    want = []
    while (v := scan_victim(cache)) is not None:
        want.append(v.page)
        assert cache.evict_one() == v.page
    assert len(want) == 7 and cache.evict_one() is None
