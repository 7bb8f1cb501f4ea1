"""Two ways to serve one fixed script of requests on a tiny engine,
for the tests of the decode loop's one step in flight
(tests/test_serve_paged.py, tests/test_latent_lm.py).

``drive`` runs the engine's own ``_iterate`` on the test's thread (the
engine is never started), so which iteration admits which request is
the script's and not the scheduler's. ``sequential_tokens`` is the
plain loop the engine ran before it looked ahead, written out over
``Engine._dispatch_step``: dispatch, read, then build the next call —
every token is on the host before the step that consumes it goes out.

A script is ``[(iteration, prompt, request keywords)]``;
``staggered_script`` is the one most tests serve. ``counting_hashlib``
counts what the prefix cache's key chain feeds its hash
(tests/test_prefix_keys.py, tests/test_serve_paged.py).
"""

import numpy as np

from tpunet.serve import GenerateRequest
from tpunet.serve.engine import _Slot


SAMPLING = {
    "greedy": [dict(temperature=0.0)] * 4,
    "seeded": [dict(temperature=0.9, seed=11),
               dict(temperature=1.3, top_k=5, seed=12),
               dict(temperature=0.7, top_p=0.8, seed=13),
               dict(temperature=1.0, top_k=7, top_p=0.9, seed=14)],
}


def staggered_script(sampling, vocab, seed=32):
    """Four requests of unequal prompt and output lengths, admitted at
    iterations 0, 0, 3 and 7: rows join a step in flight, leave it,
    and a slot is taken again."""
    rng = np.random.default_rng(seed)
    return [(at, rng.integers(0, vocab, size=n).astype(np.int32),
             dict(max_new_tokens=new, **kw))
            for (at, n, new), kw in zip(
                ((0, 5, 9), (0, 11, 4), (3, 7, 12), (7, 3, 6)), sampling)]


def counting_hashlib(fed):
    """A stand-in for the ``hashlib`` module a test puts in
    ``prefixcache.keys``: its ``sha256`` appends to ``fed`` the length
    of every buffer the hash is given (constructor and ``update``
    alike), so a test counts bytes and calls instead of timing them."""
    import hashlib

    class Counting:
        def __init__(self, data=b""):
            self._h = hashlib.sha256(data)
            fed.append(len(data))

        def update(self, data):
            fed.append(len(data))
            self._h.update(data)

        def copy(self):
            twin = Counting.__new__(Counting)
            twin._h = self._h.copy()
            return twin

        def hexdigest(self):
            return self._h.hexdigest()

    class Shim:
        sha256 = Counting

    return Shim


def drive(eng, script, after=None, limit=400):
    """Submit each request at its iteration, iterate until every
    request has finished; ``after(k, reqs)`` runs after iteration
    ``k``. Returns the requests in script order."""
    reqs = [None] * len(script)
    for k in range(limit):
        for j, (at, prompt, kw) in enumerate(script):
            if at == k:
                reqs[j] = eng.submit(prompt, **kw)
        eng._iterate()
        if after is not None:
            after(k, reqs)
        if all(r is not None and r.done for r in reqs):
            return reqs
    raise AssertionError(f"script not served in {limit} iterations")


def sequential_tokens(eng, script):
    """The tokens of each request of ``script`` from a loop that
    dispatches a step, reads it, and only then builds the next one.
    Uses ``eng``'s programs, pool and allocator and leaves it dirty:
    give it an engine of its own."""
    reqs = [GenerateRequest(prompt, **kw) for _, prompt, kw in script]
    waiting = sorted(range(len(script)), key=lambda j: script[j][0])
    max_len = eng.max_seq_len

    def finish_if_done(i, slot, tok):
        req = slot.req
        if (req.stop_token is not None and tok == req.stop_token) \
                or slot.generated >= req.max_new_tokens \
                or slot.pos + 1 > max_len:
            eng._active[i] = None
            eng._release_pages(i, slot)

    k = 0
    while waiting or any(s is not None for s in eng._active):
        while waiting and script[waiting[0]][0] <= k \
                and None in eng._active:
            req = reqs[waiting.pop(0)]
            i = eng._active.index(None)
            n = int(req.prompt.size)
            req.max_new_tokens = min(req.max_new_tokens, max_len - n)
            bucket = eng.bucket_for(n)
            slot = _Slot(req, pos=n, next_token=0)
            slot.pages = eng._alloc_pages_for(i, n)
            assert slot.pages is not None
            eng._active[i] = slot
            one_row = eng._rows_at(bucket) == 1
            rows, row = (1, 0) if one_row else (eng.slots, i)
            toks = np.zeros((rows, bucket), np.int32)
            toks[row, :n] = req.prompt
            active = np.zeros((rows,), bool)
            active[row] = True
            last_idx = np.zeros((rows,), np.int32)
            last_idx[row] = n - 1
            eng._cache, sampled = eng._dispatch_step(
                toks, np.zeros((rows,), np.int32), active, last_idx,
                i if one_row else None)
            slot.generated += 1
            slot.next_token = int(np.asarray(sampled)[row])
            req.tokens.append(slot.next_token)
            finish_if_done(i, slot, slot.next_token)
        live = [(i, s) for i, s in enumerate(eng._active) if s is not None]
        if live:
            toks = np.zeros((eng.slots, 1), np.int32)
            positions = np.zeros((eng.slots,), np.int32)
            active = np.zeros((eng.slots,), bool)
            for i, slot in live:
                assert eng._ensure_page_capacity(i, slot)
                toks[i, 0] = slot.next_token
                positions[i] = slot.pos
                active[i] = True
            eng._cache, sampled = eng._dispatch_step(
                toks, positions, active, np.zeros((eng.slots,), np.int32))
            sampled = np.asarray(sampled)
            for i, slot in live:
                slot.pos += 1
                slot.generated += 1
                slot.next_token = int(sampled[i])
                slot.req.tokens.append(slot.next_token)
                finish_if_done(i, slot, slot.next_token)
        k += 1
    return [list(r.tokens) for r in reqs]
