"""Content addresses for prefix KV pages.

ONE digest convention shared by the three parties that must agree on
what "the same prefix" means (docs/serving.md "Prefix KV cache"):

- the router's rendezvous affinity key (``tpunet.router.balance``
  hashes ``token_prefix_digest`` so shared-prefix traffic lands on
  the replica already holding those pages),
- the per-replica in-pool cache (``PrefixCache`` keys each cached
  page by the digest of the token prefix THROUGH that page),
- the shared-filesystem spill store (``PrefixStore`` names entries
  ``<store_digest>-<chain_digest>`` so a respawned replica loads
  exactly the prefixes the fleet's routers are steering at it).

The digest is FLAT in VALUE: sha256 over the little-endian int32
bytes of ``tokens[:n]``, first 16 hex characters, whatever page size
or chaining order a consumer has — a chained/rolling form would couple
every consumer to the chaining order. It is ONE PASS in COST: sha256
is a streaming hash, so a prompt's whole key chain comes from one hash
object fed each page's bytes once and copied at each page boundary
(:func:`iter_chain_digests`), and every digest keeps the value a
fresh hash of the whole prefix gives. Re-hashing the prefix per page
boundary, token by token, was what this module did first; on a
4,096-token prompt it held the device idle for 65 ms an admission
(PERF.md section 6, PR 37 and 38).

The value may never change: the spill store's file names, a warm start
from a store another version wrote, and the router's affinity key all
ARE these digests (tests/test_prefix_keys.py pins them as literals).

Config partitioning (model fingerprint, kv levers, jax version,
device kind) is deliberately NOT folded in here — the in-pool cache
lives inside one engine so every entry trivially shares its config,
and the spill store scopes files by its own ``store_digest`` prefix.
Keeping token digests config-free is what lets the router (which
knows nothing about model configs) hash the same bytes.
"""

from __future__ import annotations

import hashlib
from typing import Iterator, Sequence

import numpy as np

#: Parent key of a depth-0 cache node (no token prefix above it).
ROOT = "root"

_INT32 = np.iinfo(np.int32)


def _int32_bytes(tokens: Sequence[int], n: int) -> memoryview:
    """``tokens[:n]`` as little-endian int32 bytes (the dtype prompts
    are staged in on the host). A list (the router hands JSON lists)
    and an integer array of any width give the same bytes; a token
    outside int32 raises ``OverflowError`` rather than wrap."""
    arr = np.asarray(tokens[:n])
    if arr.dtype != np.int32:
        if arr.dtype.kind not in "iu":
            # No integer array (an empty list, JSON floats, integers
            # past 64 bits): int() says what each is, as it always has.
            arr = np.array([int(t) for t in tokens[:n]], dtype=object)
        if arr.size and not (_INT32.min <= arr.min()
                             and arr.max() <= _INT32.max):
            raise OverflowError("token does not fit int32")
    return memoryview(np.ascontiguousarray(arr, dtype="<i4")).cast("B")


def token_prefix_digest(tokens: Sequence[int], n: int) -> str:
    """Stable 16-hex digest of ``tokens[:n]``."""
    return hashlib.sha256(_int32_bytes(tokens, n)).hexdigest()[:16]


def iter_chain_digests(tokens: Sequence[int], page_tokens: int,
                       pages: int, start: int = 0) -> Iterator[str]:
    """Digests of the token prefix through each full page ``start`` ..
    ``pages - 1``, lazily and in one pass: element ``d`` equals
    ``token_prefix_digest(tokens, (d + 1) * page_tokens)`` and keys the
    page covering tokens ``[d*page_tokens, (d+1)*page_tokens)``. The
    pages before ``start`` are hashed (once) but not yielded."""
    buf = _int32_bytes(tokens, pages * page_tokens)
    step = 4 * page_tokens
    h = hashlib.sha256(buf[:start * step])
    for d in range(start, pages):
        h.update(buf[d * step:(d + 1) * step])
        yield h.copy().hexdigest()[:16]


def chain_digests(tokens: Sequence[int], page_tokens: int,
                  pages: int) -> list:
    """The first ``pages`` digests of :func:`iter_chain_digests`."""
    return list(iter_chain_digests(tokens, page_tokens, pages))
