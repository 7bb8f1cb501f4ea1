"""Fleet-wide prefix KV cache (docs/serving.md "Prefix KV cache").

Prefill pages as first-class, immutable, content-addressed objects:

- :mod:`keys` — the token-prefix digest convention shared with the
  router's affinity hashing and the spill store's file names;
- :mod:`cache` — the per-replica refcounted trie of pages living
  inside the engine's paged KV pool (pin on admission, unpin on
  release, LRU-evict under pool pressure);
- :mod:`store` — shared-filesystem spill/warm-start via the fsatomic
  first-writer-wins commit the AOT store proved.

The engine (tpunet/serve/engine.py) is the only writer; the router
only hashes digests.
"""

from tpunet.serve.prefixcache.cache import PrefixCache, PrefixNode
from tpunet.serve.prefixcache.keys import (ROOT, chain_digests,
                                           iter_chain_digests,
                                           token_prefix_digest)
from tpunet.serve.prefixcache.store import PrefixStore, build_prefix_store

__all__ = [
    "PrefixCache",
    "PrefixNode",
    "PrefixStore",
    "ROOT",
    "build_prefix_store",
    "chain_digests",
    "iter_chain_digests",
    "token_prefix_digest",
]
