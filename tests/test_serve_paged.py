"""Paged KV cache + device-side batched sampling + int8 KV (PR 12).

Covers the serve engine's memory and sampling hot paths on a
tiny CPU LM: the device sampler against argmax and filter_logits
(greedy bit-identical; seeded stochastic draws stay inside
filter_logits' support and are deterministic per (seed, step)),
page-recycling/fragmentation stress
(churn until every page has been reused; no stale-KV bleed across slot
reuse), pool-exhaustion preemption resuming token-identically, the
int8 eval-parity gate, the effective-budget satellite, and AOT
cold-start of the paged+fused program set. PR 18 extends the stress
and parity coverage to the prefix KV cache: refcounted/COW page
semantics, suffix-only prefill on hits, eviction under pool pressure,
cache-on/off greedy parity, and the shared-filesystem
spill/warm-start round trip. One engine here serves over a mesh
(``model=2`` of the forced CPU devices): the only ``[slots, bucket]``
prefill path left.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpunet.config import ModelConfig, ServeConfig
from tpunet.models import create_model, init_variables
from tpunet.models.lm import filter_logits, generate
from tpunet.serve import Engine, GenerateRequest, PromptTooLongError

from _serve_script import (SAMPLING, counting_hashlib, drive,
                           sequential_tokens, staggered_script)

TINY = ModelConfig(name="lm", vit_hidden=32, vit_depth=2, vit_heads=2,
                   dropout_rate=0.0, dtype="float32", vocab_size=31,
                   max_seq_len=48)


@pytest.fixture(scope="module")
def tiny_lm():
    model = create_model(TINY)
    variables = init_variables(model, jax.random.PRNGKey(0), seq_len=8)
    return model, variables


def make_engine(tiny_lm, mesh=None, **cfg_kw):
    model, variables = tiny_lm
    cfg_kw.setdefault("slots", 4)
    cfg_kw.setdefault("queue_max", 16)
    cfg_kw.setdefault("prefill_buckets", (8, 16))
    cfg_kw.setdefault("default_max_new_tokens", 6)
    cfg_kw.setdefault("emit_every_s", 0.0)
    return Engine(model, variables, ServeConfig(**cfg_kw), mesh=mesh)


def make_mesh_engine(tiny_lm, **cfg_kw):
    """The same engine served tensor-parallel over two of the CPU
    devices conftest.py forces (heads and MLP split over 'model')."""
    from tpunet.config import MeshConfig
    from tpunet.infer.generate import load_lm
    from tpunet.parallel import make_mesh
    mesh = make_mesh(MeshConfig(data=1, model=2))
    return make_engine(load_lm(TINY, variables=tiny_lm[1], mesh=mesh),
                       mesh=mesh, **cfg_kw)


def prompts(n, rng_seed=0, lo=2, hi=9):
    rng = np.random.default_rng(rng_seed)
    return [rng.integers(0, TINY.vocab_size,
                         size=int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def solo_greedy(tiny_lm, prompt, n_new):
    model, variables = tiny_lm
    out = generate(model, variables, np.asarray(prompt)[None],
                   n_new=n_new)
    return np.asarray(out)[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# the device sampler against argmax and filter_logits
# ---------------------------------------------------------------------------

def test_batched_sample_greedy_is_bitwise_argmax():
    """Greedy rows (temperature <= 0) of the device sampler must equal
    np.argmax on the same float32 logits — the
    invariant that keeps greedy serve output token-identical to solo
    generate."""
    from tpunet.serve.sampling import batched_sample
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(16, 64)).astype(np.float32) * 3
    n = logits.shape[0]
    toks = np.asarray(batched_sample(
        jnp.asarray(logits), np.zeros(n, np.float32),
        np.zeros(n, np.int32), np.zeros(n, np.float32),
        np.arange(n, dtype=np.int32), np.zeros(n, np.int32)))
    np.testing.assert_array_equal(toks, np.argmax(logits, axis=-1))


def test_batched_sample_support_matches_filter_logits():
    """Per-row stochastic draws over many steps must stay inside the
    support filter_logits admits for that row's (temperature, top_k,
    top_p) — the device path may not sample tokens the reference
    warper would have filtered out. Rows carry DIFFERENT parameters in
    one batch (the whole point of the per-row sampler)."""
    from tpunet.serve.sampling import batched_sample
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(3, 24)).astype(np.float32) * 2
    params = [(0.8, 3, 0.0), (1.2, 0, 0.7), (0.6, 5, 0.8)]
    temp = np.asarray([p[0] for p in params], np.float32)
    top_k = np.asarray([p[1] for p in params], np.int32)
    top_p = np.asarray([p[2] for p in params], np.float32)
    allowed = []
    for row, (t, k, p) in zip(logits, params):
        ref = np.asarray(filter_logits(jnp.asarray(row)[None] / t,
                                       top_k=k, top_p=p))[0]
        allowed.append(set(np.nonzero(np.isfinite(ref))[0].tolist()))
    seeds = np.asarray([7, 8, 9], np.int32)
    # Rows are independent counter-based draws keyed by (seed, step)
    # alone, so ONE [60*3, V] call draws bitwise the same tokens as 60
    # separate [3, V] calls — without 60 eager dispatches of the whole
    # sort/softmax/cumsum pipeline.
    n_steps = 60
    steps = np.repeat(np.arange(n_steps, dtype=np.int32), 3)
    toks = np.asarray(batched_sample(
        jnp.asarray(np.tile(logits, (n_steps, 1))),
        np.tile(temp, n_steps), np.tile(top_k, n_steps),
        np.tile(top_p, n_steps), np.tile(seeds, n_steps), steps))
    seen = [set(), set(), set()]
    for j, t in enumerate(toks):
        seen[j % 3].add(int(t))
    for i in range(3):
        assert seen[i] <= allowed[i], (params[i], seen[i] - allowed[i])
        # every filter keeps the argmax reachable
        assert int(np.argmax(logits[i])) in allowed[i]


def test_batched_sample_deterministic_per_seed_and_step():
    """The counter-based key fold: same (seed, step) reproduces the
    same token, a different seed or step (almost surely) moves at
    least one row — and rows are independent (changing row 0's seed
    never changes row 1's draw)."""
    from tpunet.serve.sampling import batched_sample
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    temp = np.full(4, 1.0, np.float32)
    zk = np.zeros(4, np.int32)
    zp = np.zeros(4, np.float32)
    seeds = np.asarray([1, 2, 3, 4], np.int32)
    step0 = np.zeros(4, np.int32)
    a = np.asarray(batched_sample(logits, temp, zk, zp, seeds, step0))
    b = np.asarray(batched_sample(logits, temp, zk, zp, seeds, step0))
    np.testing.assert_array_equal(a, b)
    seeds2 = seeds.copy()
    seeds2[0] = 99
    c = np.asarray(batched_sample(logits, temp, zk, zp, seeds2, step0))
    np.testing.assert_array_equal(a[1:], c[1:])  # row independence
    # 12 steps in one tiled call (rows independent, see the support
    # test above), regrouped per step.
    steps = np.repeat(np.arange(12, dtype=np.int32), 4)
    tiled = np.asarray(batched_sample(
        jnp.asarray(np.tile(np.asarray(logits), (12, 1))),
        np.tile(temp, 12), np.tile(zk, 12), np.tile(zp, 12),
        np.tile(seeds, 12), steps))
    draws = {tuple(tiled[s * 4:(s + 1) * 4].tolist()) for s in range(12)}
    assert len(draws) > 1  # steps actually advance the stream


def test_seed_validated_at_admission():
    """A bad seed is a client error at admission (the frontend maps
    ValueError to HTTP 400), never a silent int32 stream collision
    in the device sampler's key fold (seeds past bit 31)."""
    with pytest.raises(ValueError, match="seed"):
        GenerateRequest(np.arange(1, 4), max_new_tokens=2, seed=-3)
    with pytest.raises(ValueError, match="seed"):
        GenerateRequest(np.arange(1, 4), max_new_tokens=2, seed=2 ** 31)
    GenerateRequest(np.arange(1, 4), max_new_tokens=2, seed=2 ** 31 - 1)


def test_engine_fused_sampler_greedy_matches_solo_generate(tiny_lm):
    """Four co-resident greedy requests through the default engine
    (the sampler fused onto the step) each get solo generate's
    tokens."""
    ps = prompts(4, rng_seed=11)
    eng = make_engine(tiny_lm).start()
    try:
        reqs = [eng.submit(p, max_new_tokens=5) for p in ps]
        outs = [r.result(timeout=120) for r in reqs]
    finally:
        eng.stop()
    assert outs == [solo_greedy(tiny_lm, p, 5) for p in ps]


# ---------------------------------------------------------------------------
# paged pool: recycling / fragmentation / preemption
# ---------------------------------------------------------------------------

def test_page_recycling_stress_no_stale_kv_bleed(tiny_lm):
    """Churn admissions through a small pool until EVERY usable page
    has been allocated at least once and the allocation count proves
    reuse; every request's greedy output must still match solo decode
    — a recycled page leaking its previous occupant's K/V would
    diverge immediately."""
    eng = make_engine(tiny_lm, slots=2, kv_pages=8, kv_page_tokens=4,
                      prefix_cache=False).start()
    try:
        wave = 0
        # Requests of 5-8 prompt + 8 new tokens span 4 pages each, so
        # two co-residents demand the WHOLE 8-page pool; LIFO
        # recycling alone would otherwise keep cold pages cold.
        # prefix_cache=False pins the PR-12 contract: with no cache
        # retaining prompt pages, release returns every page.
        while wave < 12 and (len(eng._kv_pages_touched)
                             < eng.kv_pages_usable or wave < 4):
            ps = prompts(4, rng_seed=100 + wave, lo=5, hi=9)
            reqs = [eng.submit(p, max_new_tokens=8) for p in ps]
            for p, r in zip(ps, reqs):
                assert r.result(timeout=120) == \
                    solo_greedy(tiny_lm, p, 8), f"wave {wave} diverged"
            wave += 1
        assert eng._kv_pages_touched == set(
            range(1, eng.kv_pages_usable + 1)), "pages never all used"
        snap = eng.registry.snapshot()
        assert snap["serve_kv_page_allocs_total"] > eng.kv_pages_usable, \
            "allocation count proves no page was ever recycled"
        assert len(eng._free_pages) == eng.kv_pages_usable
        assert snap["serve_kv_pages_used"] == 0
    finally:
        eng.stop()


def test_pool_exhaustion_preempts_and_resumes_token_identically(tiny_lm):
    """5 usable pages x 4 tokens cannot hold two full-length
    co-residents: the engine must preempt the youngest blocked slot
    back to the queue and resume it by re-prefilling prompt+generated
    — every request still finishes with exactly the solo-greedy
    tokens."""
    eng = make_engine(tiny_lm, slots=2, kv_pages=5, kv_page_tokens=4,
                      default_max_new_tokens=12).start()
    try:
        ps = prompts(4, rng_seed=1, lo=6, hi=7)
        reqs = [eng.submit(p, max_new_tokens=12) for p in ps]
        for p, r in zip(ps, reqs):
            assert r.result(timeout=120) == solo_greedy(tiny_lm, p, 12)
            assert r.finish_reason == "length"
        snap = eng.registry.snapshot()
        assert snap["serve_kv_preemptions_total"] >= 1
        assert sum(r.preemptions for r in reqs) >= 1
    finally:
        eng.stop()


def test_preempt_victim_prefers_resumable_slots(tiny_lm):
    """Victim selection under pool exhaustion: a slot whose
    prompt+generated has outgrown the largest prefill bucket cannot be
    re-prefilled, so preempting it would error a healthy in-flight
    request — the YOUNGEST RESUMABLE slot must be chosen instead, and
    an unresumable one only when there is no alternative."""
    from tpunet.serve.engine import _Slot

    eng = make_engine(tiny_lm)          # buckets (8, 16)
    old_long = _Slot(GenerateRequest(np.ones(6, np.int32),
                                     max_new_tokens=30),
                     pos=20, next_token=1, seq=1)
    old_long.req.tokens.extend([1] * 14)     # resume size 20 > 16
    young_short = _Slot(GenerateRequest(np.ones(4, np.int32),
                                        max_new_tokens=30),
                        pos=8, next_token=1, seq=2)
    young_short.req.tokens.extend([1] * 4)   # resume size 8 <= 16
    # youngest overall is resumable -> picked (slot index 1)
    assert eng._choose_preempt_victim(
        [(0, old_long), (1, young_short)]) == 1
    # youngest overall unresumable, older resumable exists -> the
    # OLDER resumable one is picked, never the unresumable youngest
    young_long = _Slot(GenerateRequest(np.ones(6, np.int32),
                                       max_new_tokens=30),
                       pos=20, next_token=1, seq=3)
    young_long.req.tokens.extend([1] * 14)
    assert eng._choose_preempt_victim(
        [(1, young_short), (2, young_long)]) == 1
    # every blocked slot unresumable -> youngest fails (unavoidable)
    assert eng._choose_preempt_victim(
        [(0, old_long), (2, young_long)]) == 2


def test_request_that_cannot_fit_pool_rejected_up_front(tiny_lm):
    """Completability guard: a request whose full length exceeds the
    whole pool would preempt itself forever — submit rejects it."""
    eng = make_engine(tiny_lm, slots=2, kv_pages=5, kv_page_tokens=4)
    with pytest.raises(PromptTooLongError):
        eng.submit(np.ones(8, np.int32), max_new_tokens=40)
    assert eng.registry.snapshot()["serve_requests_rejected"] == 1


def test_paged_engine_mid_flight_admission_matches_solo_generate(tiny_lm):
    """The paged pool is the same math as solo generate's module-
    clocked cache: identical greedy tokens across a mid-flight
    admission pattern (six requests through two slots)."""
    import time
    eng = make_engine(tiny_lm, slots=2).start()
    try:
        ps = prompts(6, rng_seed=42)
        reqs = []
        for i, p in enumerate(ps):
            reqs.append(eng.submit(p, max_new_tokens=5))
            if i % 2 == 1:
                time.sleep(0.01)
        outs = [r.result(timeout=120) for r in reqs]
    finally:
        eng.stop()
    assert outs == [solo_greedy(tiny_lm, p, 5) for p in ps]


# ---------------------------------------------------------------------------
# prefill calls as wide as the row they admit (PR 28)
# ---------------------------------------------------------------------------

def _token_rows(text, width):
    """Batch rows of the ``[rows, width]`` int32 token parameter of a
    compiled masked step's entry computation."""
    import re
    found = re.findall(rf"s32\[(\d+),{width}\]\S* parameter\(",
                       text[text.index("ENTRY"):])
    assert len(found) == 1, found
    return int(found[0])


def _spy_prefill_calls(eng):
    """Record every bucket-wide ``_dispatch_step`` call of ``eng``:
    its token shape, its slot, and whether the pool rows of pages that
    OTHER slots own came out of the call bit-identical."""
    calls = []
    dispatch = eng._dispatch_step

    def pool(cache):
        return [np.asarray(leaf)
                for leaf in jax.tree_util.tree_leaves(cache)]

    def spy(toks, positions, active, last_idx, slot_i=None, **kw):
        if toks.shape[1] == 1:
            return dispatch(toks, positions, active, last_idx, slot_i,
                            **kw)
        before = pool(eng._cache) if slot_i is not None else None
        out = dispatch(toks, positions, active, last_idx, slot_i)
        frozen = None
        if slot_i is not None:
            pt = eng.page_tokens
            rows = np.concatenate(
                [np.arange(page * pt, (page + 1) * pt)
                 for j, slot in enumerate(eng._active)
                 if slot is not None and j != slot_i
                 for page in slot.pages] or [np.arange(0)]).astype(int)
            frozen = all(np.array_equal(b[rows], a[rows])
                         for b, a in zip(before, pool(out[0])))
        calls.append({"shape": toks.shape, "slot": slot_i,
                      "others_frozen": frozen})
        return out

    eng._dispatch_step = spy
    return calls


MESHES = pytest.mark.parametrize(
    "build", [make_engine, make_mesh_engine], ids=["one_device", "model2"])


@MESHES
@pytest.mark.parametrize("k", [1, 3, 4])
def test_paged_prefill_is_one_row_call_per_admitted_request(tiny_lm, k,
                                                            build):
    """k requests admitted together (prompts in both buckets): an
    engine on one device dispatches k prefill calls of ``[1, bucket]``
    tokens, each leaving the pages of every other slot bit-identical;
    an engine over a mesh dispatches one ``[slots, bucket]`` call per
    bucket; both serve solo generate's tokens. The gauge and the
    padded-token counter say which shape an engine runs."""
    rng = np.random.default_rng(28)
    ps = [rng.integers(0, TINY.vocab_size, size=n).astype(np.int32)
          for n in (5, 12, 7, 14)[:k]]
    buckets = [8 if p.size <= 8 else 16 for p in ps]

    eng = build(tiny_lm, prefix_cache=False)
    calls = _spy_prefill_calls(eng)
    # queued before the engine thread runs: one admission takes all
    reqs = [eng.submit(p, max_new_tokens=5) for p in ps]
    eng.start()
    try:
        got = [r.result(timeout=120) for r in reqs]
    finally:
        eng.stop()
    assert eng.error is None
    assert got == [solo_greedy(tiny_lm, p, 5) for p in ps]
    snap = eng.registry.snapshot()
    assert snap["serve_prefill_tokens_total"] == sum(p.size for p in ps)
    if eng.mesh is None:
        # _admit groups by bucket, ascending; admission order within
        assert [c["shape"] for c in calls] == \
            [(1, b) for b in sorted(buckets)]
        assert sorted(c["slot"] for c in calls) == list(range(k))
        assert all(c["others_frozen"] for c in calls)
        assert snap["serve_prefill_rows_per_call"] == 1
        assert snap["serve_prefills_total"] == k
        assert snap["serve_prefill_padded_tokens_total"] == sum(buckets)
    else:
        assert [c["shape"] for c in calls] == \
            [(eng.slots, b) for b in sorted(set(buckets))]
        assert all(c["slot"] is None for c in calls)
        assert snap["serve_prefill_rows_per_call"] == eng.slots
        assert snap["serve_prefill_padded_tokens_total"] == \
            eng.slots * sum(set(buckets))


@MESHES
def test_program_texts_have_the_rows_the_engine_dispatches(tiny_lm,
                                                           build):
    """``program_texts()`` lowers what ``_dispatch_step`` runs: the
    decode program is ``[slots, 1]``, the ``w<bucket>`` programs have a
    one-row token parameter on one device and ``slots`` rows over a
    mesh — under the labels they always had."""
    eng = build(tiny_lm)
    texts = eng.program_texts()
    assert sorted(texts) == ["jit__masked_step/w1", "jit__masked_step/w16",
                             "jit__masked_step/w8"]
    assert _token_rows(texts["jit__masked_step/w1"], 1) == eng.slots
    for bucket in (8, 16):
        assert _token_rows(texts[f"jit__masked_step/w{bucket}"],
                           bucket) == (1 if eng.mesh is None
                                       else eng.slots)


@MESHES
def test_step_avals_state_the_masked_steps_signature(tiny_lm, build):
    """``_step_avals`` is the one statement of ``_masked_step``'s
    signature: fifteen entries in its parameters' order, ``slots`` rows
    at width 1 and ``_prefill_rows`` at a bucket, the table columns a
    fresh admission reaches (tests/test_serve_reach.py) — and the jit
    program traces at exactly those shapes."""
    import inspect
    eng = build(tiny_lm)
    names = list(inspect.signature(eng._step).parameters)
    assert names == ["params", "cache", "tokens", "positions", "active",
                     "page_table", "last_idx", "temp", "top_k", "top_p",
                     "seeds", "steps", "prev", "from_prev", "state_rows"]
    for width, rows in ((1, eng.slots),
                        (16, 1 if eng.mesh is None else eng.slots)):
        avals = eng._step_avals(width)
        assert len(avals) == len(names)
        by_name = dict(zip(names, avals))
        assert by_name["tokens"].shape == (rows, width)
        assert by_name["page_table"].shape == (rows, eng._reach(width))
        assert by_name["active"].dtype == by_name["from_prev"].dtype \
            == bool
        for name in names[3:5] + names[6:]:
            assert by_name[name].shape == (rows,), name
        assert [by_name[n].dtype for n in ("temp", "top_p")] == \
            [np.float32, np.float32]
        cache, toks = jax.eval_shape(eng._step, *avals)
        assert toks.shape == (rows,) and toks.dtype == np.int32
        assert jax.tree_util.tree_structure(cache) == \
            jax.tree_util.tree_structure(eng._cache)


# ---------------------------------------------------------------------------
# prefix KV cache: refcounted content-addressed pages (PR 18)
# ---------------------------------------------------------------------------

def test_prefix_cache_trie_pin_release_evict_order():
    """Host-side trie semantics in isolation: lookup walks the longest
    cached chain; interleaved pin/release keeps refcounts exact (a
    double-pinned node survives one release); eviction is leaf-first
    (never orphans a cached chain) and LRU among evictable nodes."""
    from tpunet.serve.prefixcache import PrefixCache, chain_digests, \
        token_prefix_digest

    c = PrefixCache(page_tokens=4, capacity=8)
    toks = list(range(12))
    d = chain_digests(toks, 4, 3)
    n0 = c.insert(d[0], None, 0, 5)
    n1 = c.insert(d[1], n0, 1, 6)
    n2 = c.insert(d[2], n1, 2, 7)
    assert [n.page for n in c.lookup(toks, 3)] == [5, 6, 7]
    assert [n.page for n in c.lookup(toks, 2)] == [5, 6]
    assert c.lookup([9] * 12, 3) == []
    # every node pinned -> nothing evictable
    c.pin([n0, n1, n2])
    assert c.evict_one() is None
    # releasing the leaf exposes exactly the leaf; interior nodes with
    # children stay, so the surviving trie is always prefix-closed
    c.unpin([n2])
    assert c.evict_one() == 7
    assert c.lookup(toks, 3) == [n0, n1]
    c.unpin([n0, n1])
    assert c.evict_one() == 6
    assert c.evict_one() == 5
    assert c.evict_one() is None and c.pages_cached == 0
    # interleaved pin/release: two pins need two releases
    m = c.insert(token_prefix_digest([3, 3, 3, 3], 4), None, 0, 2)
    c.pin([m])
    c.pin([m])
    c.unpin([m])
    assert c.evict_one() is None
    c.unpin([m])
    assert c.evict_one() == 2
    # LRU: the older untouched root goes first
    a = c.insert(token_prefix_digest([1] * 4, 4), None, 0, 3)
    b = c.insert(token_prefix_digest([2] * 4, 4), None, 0, 4)
    c.pin([a])
    c.unpin([a])            # touches a after b's insert
    assert c.evict_one() == 4
    assert c.evict_one() == 3


def test_prefix_hit_pins_pages_and_prefills_suffix_only(tiny_lm):
    """A second request sharing the first two prompt pages must pin
    them from the cache and prefill ONLY the suffix — measured by the
    serve_prefill_tokens_total delta — while staying token-identical
    to solo decode (stale or misattributed prefix K/V would diverge
    immediately)."""
    eng = make_engine(tiny_lm, slots=2, kv_pages=16,
                      kv_page_tokens=4).start()
    try:
        rng = np.random.default_rng(13)
        shared = rng.integers(0, TINY.vocab_size, size=8).astype(np.int32)
        p1 = np.concatenate([shared, rng.integers(
            0, TINY.vocab_size, size=3).astype(np.int32)])
        p2 = np.concatenate([shared, rng.integers(
            0, TINY.vocab_size, size=2).astype(np.int32)])
        out1 = eng.submit(p1, max_new_tokens=5).result(timeout=120)
        pre1 = eng.registry.snapshot()["serve_prefill_tokens_total"]
        assert pre1 == p1.size          # cold request: full prefill
        out2 = eng.submit(p2, max_new_tokens=5).result(timeout=120)
        snap = eng.registry.snapshot()
        assert snap["serve_prefill_tokens_total"] - pre1 == p2.size - 8
        assert snap["serve_prefix_hits_total"] >= 1
        assert snap["serve_prefix_hit_tokens_total"] >= 8
        assert snap["serve_prefix_inserts_total"] >= 2
        assert snap["serve_prefix_pages_cached"] >= 2
    finally:
        eng.stop()
    assert out1 == solo_greedy(tiny_lm, p1, 5)
    assert out2 == solo_greedy(tiny_lm, p2, 5)


def test_prefix_cow_identical_prompt_and_divergence(tiny_lm):
    """Copy-on-write at the divergence page: an identical page-aligned
    prompt re-uses the full cached chain but COPIES the last page into
    a private one (decode will write past it); a prompt diverging
    INSIDE the second page pins only the first and re-prefills from
    the divergence page without COW. Both stay solo-greedy-identical —
    a COW copy sharing mutable state with the source would corrupt the
    cached page for later hits."""
    eng = make_engine(tiny_lm, slots=2, kv_pages=16,
                      kv_page_tokens=4).start()
    try:
        rng = np.random.default_rng(17)
        p = rng.integers(0, TINY.vocab_size, size=8).astype(np.int32)
        out1 = eng.submit(p, max_new_tokens=6).result(timeout=120)
        out2 = eng.submit(p, max_new_tokens=6).result(timeout=120)
        cow = eng.registry.snapshot()["serve_prefix_cow_total"]
        assert cow >= 1
        q = p.copy()
        q[5] = (int(q[5]) + 1) % TINY.vocab_size   # diverge in page 1
        out3 = eng.submit(q, max_new_tokens=6).result(timeout=120)
        snap = eng.registry.snapshot()
        assert snap["serve_prefix_hits_total"] >= 2
        assert snap["serve_prefix_cow_total"] == cow   # divergence != COW
        # the COW'd source page is still served intact after both
        out4 = eng.submit(p, max_new_tokens=6).result(timeout=120)
    finally:
        eng.stop()
    assert out1 == out2 == out4 == solo_greedy(tiny_lm, p, 6)
    assert out3 == solo_greedy(tiny_lm, q, 6)


def test_prefix_churn_stress_refcounted_pages_no_stale_bleed(tiny_lm):
    """The PR-12 recycling stress extended to the refcounted/COW
    regime: with the prefix cache ON over a pool two co-residents can
    exhaust, pages continuously migrate free list -> slot -> cache ->
    (eviction) -> free list, repeated prompts hit cached pages, and
    every request must STILL match solo decode — any stale K/V bleed
    through a recycled or cached page diverges greedy output. At
    quiesce every pool page is either free or cached-unpinned
    (nothing leaks)."""
    eng = make_engine(tiny_lm, slots=2, kv_pages=8,
                      kv_page_tokens=4).start()
    try:
        for wave in range(8):
            # seeds repeat across waves -> identical prompts recur and
            # exercise hits/COW against pages that churned in between
            ps = prompts(4, rng_seed=200 + wave % 3, lo=5, hi=9)
            reqs = [eng.submit(p, max_new_tokens=8) for p in ps]
            for p, r in zip(ps, reqs):
                assert r.result(timeout=120) == \
                    solo_greedy(tiny_lm, p, 8), f"wave {wave} diverged"
        # a back-to-back repeat at quiesce must hit the cache
        fixed = prompts(1, rng_seed=999, lo=8, hi=9)[0]
        a = eng.submit(fixed, max_new_tokens=4).result(timeout=120)
        b = eng.submit(fixed, max_new_tokens=4).result(timeout=120)
        assert a == b == solo_greedy(tiny_lm, fixed, 4)
        snap = eng.registry.snapshot()
        assert snap["serve_prefix_evictions_total"] >= 1, \
            "pool pressure never evicted a cached page"
        assert snap["serve_prefix_hits_total"] >= 1
        assert eng._prefix.pinned_pages() == 0
        assert len(eng._free_pages) + eng._prefix.pages_cached \
            == eng.kv_pages_usable, "a pool page leaked"
    finally:
        eng.stop()


def test_prefix_cache_parity_on_off(tiny_lm):
    """Greedy output over a shared-prefix workload is identical with
    the cache on and the cache off, and is solo generate's —
    the cache is a pure compute-elision, never a math change."""
    rng = np.random.default_rng(31)
    shared = rng.integers(0, TINY.vocab_size, size=8).astype(np.int32)
    ps = [np.concatenate([shared, rng.integers(
        0, TINY.vocab_size, size=k).astype(np.int32)])
        for k in (3, 2, 5, 1)]
    outs = {}
    for label, kw in (("cache", {}),
                      ("nocache", {"prefix_cache": False})):
        eng = make_engine(tiny_lm, slots=2, **kw).start()
        try:
            outs[label] = [eng.submit(p, max_new_tokens=5)
                           .result(timeout=120) for p in ps]
        finally:
            eng.stop()
    assert outs["cache"] == outs["nocache"]
    for p, o in zip(ps, outs["cache"]):
        assert o == solo_greedy(tiny_lm, p, 5)


def test_prefix_spill_and_warm_start_roundtrip(tmp_path, tiny_lm):
    """Shared-filesystem warm start: replica 1 spills its adopted
    prefix pages write-through; a FRESH replica 2 sharing the store
    directory adopts them at boot (warm_loads), and its very first
    shared-prefix request prefills only the suffix while staying
    solo-greedy-identical — the full pickle -> fs -> pool round trip
    must reproduce the K/V rows bitwise."""
    from tpunet.serve.prefixcache import build_prefix_store

    model, variables = tiny_lm
    cfg = ServeConfig(slots=2, queue_max=8, prefill_buckets=(16,),
                      default_max_new_tokens=6, emit_every_s=0.0,
                      kv_pages=12, kv_page_tokens=4)
    store = build_prefix_store(str(tmp_path), TINY, cfg)
    rng = np.random.default_rng(21)
    shared = rng.integers(0, TINY.vocab_size, size=8).astype(np.int32)
    p1 = np.concatenate([shared, rng.integers(
        0, TINY.vocab_size, size=3).astype(np.int32)])
    eng = Engine(model, variables, cfg, prefix_store=store).start()
    try:
        out1 = eng.submit(p1, max_new_tokens=5).result(timeout=120)
    finally:
        eng.stop()
    assert out1 == solo_greedy(tiny_lm, p1, 5)
    assert eng.registry.snapshot()["serve_prefix_spills_total"] >= 2
    assert any(f.name.endswith(".pfx") for f in tmp_path.iterdir())

    eng2 = Engine(model, variables, cfg, prefix_store=store).start()
    try:
        assert eng2.registry.snapshot()[
            "serve_prefix_warm_loads_total"] >= 2
        p2 = np.concatenate([shared, rng.integers(
            0, TINY.vocab_size, size=2).astype(np.int32)])
        out2 = eng2.submit(p2, max_new_tokens=5).result(timeout=120)
    finally:
        eng2.stop()
    assert out2 == solo_greedy(tiny_lm, p2, 5)
    snap2 = eng2.registry.snapshot()
    assert snap2["serve_prefix_hits_total"] >= 1
    assert snap2["serve_prefix_hit_tokens_total"] >= 8
    # the warmed replica never prefilled the shared prefix at all
    assert snap2["serve_prefill_tokens_total"] == p2.size - 8


def _prefix_counts(eng):
    snap = eng.registry.snapshot()
    return tuple(int(snap.get(f"serve_prefix_{k}_total", 0)) for k in
                 ("lookups", "hits", "hit_tokens", "inserts", "evictions",
                  "cow"))


def test_prefix_counters_per_admission_are_the_parents(tiny_lm):
    """Nothing is adopted less, later or conditionally (PR 38): over a
    fixed script — shared prefixes, repeats, a cache of 5 pages that
    has to evict for every admission after the first two — lookups, hits, hit tokens,
    inserts, evictions and COW copies read after every iteration what
    the commit before the one-pass chain and the victim heap read
    (the literals were printed by that commit running this test)."""
    rng = np.random.default_rng(38)
    a = rng.integers(0, TINY.vocab_size, size=12).astype(np.int32)
    b = np.concatenate([a[:8], rng.integers(
        0, TINY.vocab_size, size=5).astype(np.int32)])
    c = rng.integers(0, TINY.vocab_size, size=16).astype(np.int32)
    d = rng.integers(0, TINY.vocab_size, size=9).astype(np.int32)
    script = [(0, a, dict(max_new_tokens=4)),
              (0, b, dict(max_new_tokens=3)),
              (9, c, dict(max_new_tokens=5)),
              (12, a, dict(max_new_tokens=3)),
              (20, d, dict(max_new_tokens=4)),
              (21, b, dict(max_new_tokens=6)),
              (30, c, dict(max_new_tokens=2)),
              (31, a, dict(max_new_tokens=5)),
              (40, c[:13], dict(max_new_tokens=3))]
    eng = make_engine(tiny_lm, slots=2, kv_pages=14, kv_page_tokens=4,
                      prefix_cache_pages=5)
    seen = []

    def after(k, reqs):
        now = _prefix_counts(eng)
        if not seen or seen[-1][1] != now:
            seen.append((k, now))

    reqs = drive(eng, script, after=after)
    for (_, prompt, kw), req in zip(script, reqs):
        assert req.tokens == solo_greedy(tiny_lm, prompt,
                                         kw["max_new_tokens"])
    assert seen == PARENT_PREFIX_COUNTS


#: (iteration, (lookups, hits, hit tokens, inserts, evictions, COW)) at
#: each iteration that moved a counter, as commit cfb319f read them.
PARENT_PREFIX_COUNTS = [
    (0, (2, 0, 0, 4, 0, 0)), (9, (3, 0, 0, 8, 3, 0)),
    (12, (4, 1, 4, 8, 3, 0)), (20, (5, 1, 4, 10, 5, 0)),
    (21, (6, 2, 8, 12, 7, 0)), (30, (7, 2, 8, 16, 11, 0)),
    (31, (8, 3, 12, 18, 13, 0)), (40, (9, 4, 20, 19, 14, 0))]


def test_an_admission_hashes_each_prompt_token_once_a_pass(tiny_lm,
                                                           monkeypatch):
    """Counted, not timed: admitting an n-token prompt feeds the hash at
    most 4 n bytes in ``_fit`` (lookup and COW source off ONE chain) and
    4 n in ``_adopt_prefix_pages``, in a number of ``update`` calls that
    grows with the pages — for a prompt the cache has never seen and for
    a page-aligned repeat that hits every page and copies the last."""
    from tpunet.serve.prefixcache import keys as pk
    fed = []
    monkeypatch.setattr(pk, "hashlib", counting_hashlib(fed))
    eng = make_engine(tiny_lm, slots=2, kv_pages=24, kv_page_tokens=4)
    prompt = np.random.default_rng(5).integers(
        0, TINY.vocab_size, size=16).astype(np.int32)
    pages, passes = 4, {}
    fit, adopt = eng._fit, eng._adopt_prefix_pages

    def counted(name, fn):
        def run(*args, **kw):
            before = len(fed)
            out = fn(*args, **kw)
            passes.setdefault(name, []).append(fed[before:])
            return out
        return run

    monkeypatch.setattr(eng, "_fit", counted("fit", fit))
    monkeypatch.setattr(eng, "_adopt_prefix_pages",
                        counted("adopt", adopt))
    for turn in ("miss", "hit"):
        fed.clear()
        passes.clear()
        (req,) = drive(eng, [(0, prompt, dict(max_new_tokens=3))])
        assert req.tokens == solo_greedy(tiny_lm, prompt, 3)
        for name in ("fit", "adopt"):
            calls = [c for c in passes[name] if c]
            assert len(calls) == 1, (turn, name, passes)
            assert sum(calls[0]) <= 4 * prompt.size
            assert len(calls[0]) <= pages + 1
        assert sum(fed) <= 2 * 4 * prompt.size
    snap = eng.registry.snapshot()
    assert snap["serve_prefix_cow_total"] == 1
    assert snap["serve_prefix_hit_tokens_total"] == 12
    assert snap["serve_prefix_inserts_total"] == 4


def test_spilled_file_names_are_the_flat_digests(tmp_path, tiny_lm):
    """A store directory written by any version names a page by the flat
    digest of the tokens through it: the per-token reference, kept here,
    names the files this engine spills."""
    import hashlib
    from tpunet.serve.prefixcache import build_prefix_store

    def flat(tokens, n):
        h = hashlib.sha256()
        for t in tokens[:n]:
            h.update(int(t).to_bytes(4, "little", signed=True))
        return h.hexdigest()[:16]

    model, variables = tiny_lm
    cfg = ServeConfig(slots=2, queue_max=8, prefill_buckets=(16,),
                      default_max_new_tokens=6, emit_every_s=0.0,
                      kv_pages=12, kv_page_tokens=4)
    store = build_prefix_store(str(tmp_path), TINY, cfg)
    prompt = np.random.default_rng(31).integers(
        0, TINY.vocab_size, size=14).astype(np.int32)
    eng = Engine(model, variables, cfg, prefix_store=store)
    drive(eng, [(0, prompt, dict(max_new_tokens=2))])
    assert sorted(p.name for p in tmp_path.glob("*.pfx")) == sorted(
        f"{store.store_digest}-{flat(prompt, 4 * (d + 1))}.pfx"
        for d in range(3))
    assert [(e["depth"], e["parent"]) for e in store.load_all()] == [
        (0, "root"), (1, flat(prompt, 4)), (2, flat(prompt, 8))]


def test_prefix_store_of_the_old_row_shape_is_refused(tmp_path, tiny_lm):
    """A page spilled as ``[page_tokens, H, D]`` rows (the pool's shape
    before the rows went flat and lane-padded) is never mapped into the
    ``[page_tokens, W]`` pool: build_prefix_store scopes the new layout
    by its digest, and an old-shape entry that still turns up under
    this digest is skipped by its shape — no warm load, no crash, and
    the request prefills everything and is still solo-greedy-exact."""
    from tpunet.serve.prefixcache import build_prefix_store
    from tpunet.serve.prefixcache.store import PrefixStore

    model, variables = tiny_lm
    cfg = ServeConfig(slots=2, queue_max=8, prefill_buckets=(16,),
                      default_max_new_tokens=6, emit_every_s=0.0,
                      kv_pages=12, kv_page_tokens=4)
    good = build_prefix_store(str(tmp_path / "good"), TINY, cfg)
    (tmp_path / "good").mkdir()
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, TINY.vocab_size, size=11).astype(np.int32)
    eng = Engine(model, variables, cfg, prefix_store=good).start()
    try:
        eng.submit(prompt, max_new_tokens=2).result(timeout=120)
    finally:
        eng.stop()
    entries = list(good.load_all())
    assert len(entries) >= 2
    heads, head_dim = TINY.vit_heads, TINY.vit_hidden // TINY.vit_heads
    assert all(r.shape == (4, 128) for e in entries for r in e["rows"])

    (tmp_path / "old").mkdir()
    old = PrefixStore(str(tmp_path / "old"), good.store_digest)
    for e in entries:
        rows = [r[:, :heads * head_dim].reshape(4, heads, head_dim)
                for r in e["rows"]]
        assert old.save(e["digest"], e["parent"], e["depth"], rows)
    eng2 = Engine(model, variables, cfg, prefix_store=old).start()
    try:
        assert eng2.registry.snapshot().get(
            "serve_prefix_warm_loads_total", 0) == 0
        out = eng2.submit(prompt, max_new_tokens=5).result(timeout=120)
    finally:
        eng2.stop()
    assert out == solo_greedy(tiny_lm, prompt, 5)
    snap = eng2.registry.snapshot()
    assert snap["serve_prefix_hits_total"] == 0
    assert snap["serve_prefill_tokens_total"] == prompt.size


# ---------------------------------------------------------------------------
# int8 KV parity gate
# ---------------------------------------------------------------------------

# A top-1/top-2 logit margin below this is a tie as far as int8 KV is
# concerned: the tiny random model's logits have a std of ~0.13, and
# absmax int8 rounds each K/V element by up to 1/254 of its row's max,
# which moves a logit by a few 1e-4. Decided steps (the smallest other
# margins across the six prompts are 7e-3..9e-3) must still agree.
INT8_NEAR_TIE = 2e-3


def _float_margin(tiny_lm, tokens):
    """Top-1 minus top-2 of the float model's next-token logits."""
    model, variables = tiny_lm
    logits = model.apply(variables, np.asarray(tokens, np.int32)[None],
                         train=False)
    top = np.sort(np.asarray(logits[0, -1], np.float64))
    return float(top[-1] - top[-2])


def test_int8_kv_eval_parity_gate(tiny_lm):
    """The eval-parity gate for --kv-dtype int8: greedy decode through
    quantized pages must be token-identical to the float32 path on the
    tiny model across a prompt spread, at every step the float model
    actually DECIDES. (Quantization error exists — this gate is what
    keeps it below argmax-flipping size; a model where it trips must
    not ship int8 KV.)

    A step whose float top-2 margin is under INT8_NEAR_TIE is a coin
    flip for any perturbation, XLA:CPU's own reduction order between
    jax versions included — under jax 0.9.0 seed 2's third token sits
    on a 6e-4 margin and int8 takes the other side, where the seed's
    jax happened to agree. Such a step may diverge (at most one across
    the spread); a divergence anywhere else fails."""
    eng = make_engine(tiny_lm, kv_dtype="int8").start()
    near_ties = 0
    try:
        for seed in range(6):
            p = prompts(1, rng_seed=seed)[0]
            req = eng.submit(p, max_new_tokens=6)
            out = req.result(timeout=120)
            assert req.finish_reason == "length", (req.finish_reason,
                                                   req.error)
            ref = solo_greedy(tiny_lm, p, 6)
            if out == ref:
                continue
            step = next(i for i, (a, b) in enumerate(zip(out, ref))
                        if a != b)
            margin = _float_margin(tiny_lm, list(p) + ref[:step])
            assert margin < INT8_NEAR_TIE, (
                f"int8 KV diverged on seed {seed} at step {step}, where "
                f"the float margin is {margin:.4g}: {out} vs {ref}")
            near_ties += 1
    finally:
        eng.stop()
    assert near_ties <= 1, near_ties


def test_int8_kv_halves_bf16_page_cost(tiny_lm):
    """The capacity claim, measured: int8 pages (payload + scale
    sidecar) cost less than half the float32 pages and at most ~60%
    of bf16 pages for this head size."""
    sizes = {}
    for dtype in ("auto", "bf16", "int8"):
        eng = make_engine(tiny_lm, kv_dtype=dtype)
        sizes[dtype] = eng.kv_bytes_per_token()
    assert sizes["int8"] < sizes["auto"] / 2
    assert sizes["int8"] < sizes["bf16"] * 0.75
    assert sizes["bf16"] == pytest.approx(sizes["auto"] / 2)


# ---------------------------------------------------------------------------
# effective-budget satellite
# ---------------------------------------------------------------------------

def test_submit_records_requested_and_effective_budget(tiny_lm):
    """The admission clamp is explicit now: requested_max_new_tokens
    keeps the client's ask, max_new_tokens becomes the effective
    budget (operator cap, then KV-length clamp)."""
    eng = make_engine(tiny_lm, prefill_buckets=(48,),
                      max_new_tokens_cap=2048).start()
    try:
        req = eng.submit(np.ones(40, np.int32), max_new_tokens=100)
        out = req.result(timeout=60)
        assert req.requested_max_new_tokens == 100
        assert req.max_new_tokens == 8          # 48 - 40
        assert len(out) == 8
        # the cap clamp is recorded the same way
        eng2 = make_engine(tiny_lm, max_new_tokens_cap=3)
        r2 = eng2.submit(np.ones(4, np.int32), max_new_tokens=50)
        assert r2.requested_max_new_tokens == 50
        assert r2.max_new_tokens == 3
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# obs: kv gauges + record fields
# ---------------------------------------------------------------------------

def test_kv_gauges_and_serve_record_fields(tiny_lm):
    from tpunet.serve.engine import build_serve_record
    eng = make_engine(tiny_lm, kv_pages=10, kv_page_tokens=8)
    snap = eng.registry.snapshot()
    assert snap["serve_kv_pages_total"] == 10
    assert snap["serve_kv_pages_used"] == 0
    assert snap["serve_kv_bytes_per_token"] > 0
    rec = build_serve_record(eng.registry, queue_depth=0,
                             active_slots=0, slots=4, uptime_s=1.0,
                             window_s=1.0)
    assert rec["kv_pages_total"] == 10
    assert rec["kv_pages_used"] == 0
    assert rec["kv_bytes_per_token"] > 0
    # off the TPU the decode program attends through the dense path
    assert snap["serve_decode_attend_kernel"] == 0


# ---------------------------------------------------------------------------
# AOT warm-start of the engine's program set
# ---------------------------------------------------------------------------


def _answer(engine, prompt, new_tokens):
    """Greedy tokens of one request — which must not have ended in
    error: ``result()`` returns the tokens whatever the finish reason,
    so a dead engine reads as an empty answer."""
    req = engine.submit(prompt, max_new_tokens=new_tokens)
    tokens = req.result(timeout=120)
    assert req.finish_reason in ("length", "stop"), \
        (req.finish_reason, req.error, engine.error)
    assert engine.error is None and not req.error
    assert len(tokens) == new_tokens
    return tokens


def test_paged_aot_store_roundtrip(tmp_path, tiny_lm):
    """The paged decode + fused-sampling program joins the serialized
    closed set: a second boot deserializes every program ('loaded')
    and produces token-identical greedy output; flipping a paging
    lever is a clean store MISS, never a stale executable."""
    from tpunet.serve.engine import build_aot_store

    model, variables = tiny_lm
    cfg = ServeConfig(slots=2, queue_max=4, prefill_buckets=(16,),
                      default_max_new_tokens=8, emit_every_s=0.0,
                      kv_pages=12, kv_page_tokens=8)
    store = build_aot_store(str(tmp_path), TINY, cfg)
    prompt = np.arange(5, dtype=np.int32)

    eng = Engine(model, variables, cfg, aot_store=store).start()
    try:
        toks1 = _answer(eng, prompt, 5)
    finally:
        eng.stop()
    assert all(v.startswith("compiled") for v in eng.aot_status.values())

    eng2 = Engine(model, variables, cfg, aot_store=store).start()
    try:
        toks2 = _answer(eng2, prompt, 5)
    finally:
        eng2.stop()
    assert eng2.aot_status == {"w1": "loaded", "k4w16": "loaded"}
    assert toks2 == toks1 == solo_greedy(tiny_lm, prompt, 5)
    # the store's own executables: [slots, 1] decode, [1, 16] prefill
    texts = eng2.program_texts()
    assert _token_rows(texts["jit__masked_step/w1"], 1) == 2
    assert _token_rows(texts["jit__masked_step/w16"], 16) == 1

    # A different kv_dtype selects a different program set: clean MISS.
    cfg_int8 = ServeConfig(slots=2, queue_max=4, prefill_buckets=(16,),
                           default_max_new_tokens=8, emit_every_s=0.0,
                           kv_pages=12, kv_page_tokens=8,
                           kv_dtype="int8")
    store_int8 = build_aot_store(str(tmp_path), TINY, cfg_int8)
    eng3 = Engine(model, variables, cfg_int8,
                  aot_store=store_int8).start()
    try:
        _answer(eng3, prompt, 2)
    finally:
        eng3.stop()
    assert all(v.startswith("compiled")
               for v in eng3.aot_status.values())


def test_aot_store_written_before_the_options_went_is_a_clean_miss(
        tmp_path, tiny_lm):
    """A store whose digest still held ``paged_kv`` and
    ``device_sampling`` (any ``--aot-cache`` directory written before
    they were deleted, ``masked_step_r1`` prefill entries and all) is
    keyed apart from today's: the engine loads nothing from it,
    compiles once and saves under the one ``masked_step`` name; the
    boot after that loads."""
    import dataclasses
    import os

    from tpunet.serve.engine import build_aot_store
    from tpunet.utils.cache import AotProgramStore, serializable_compile

    model, variables = tiny_lm
    cfg = ServeConfig(slots=2, queue_max=4, prefill_buckets=(16,),
                      default_max_new_tokens=8, emit_every_s=0.0,
                      kv_pages=12, kv_page_tokens=8)
    old = AotProgramStore(str(tmp_path), AotProgramStore.digest({
        "model": dataclasses.asdict(TINY), "slots": cfg.slots,
        "prefill_buckets": list(cfg.prefill_buckets),
        "paged_kv": True, "kv_pages": cfg.kv_pages,
        "kv_page_tokens": cfg.kv_page_tokens, "kv_dtype": cfg.kv_dtype,
        "device_sampling": True, "spec_decode": False, "spec_k": 4,
        "spec_draft_width_mult": 0.5}))
    store = build_aot_store(str(tmp_path), TINY, cfg)
    assert store.config_digest != old.config_digest
    # what such a store holds: the same programs under the old names
    writer = Engine(model, variables, cfg)
    for width, name in ((1, "masked_step"), (16, "masked_step_r1")):
        with serializable_compile():
            program = writer._step.lower(
                *writer._step_avals(width)).compile()
        assert old.save(name, f"w{width}", program)
    def entries():
        return {f for f in os.listdir(tmp_path) if f.endswith(".aotx")}

    before = entries()
    assert len(before) == 2

    eng = Engine(model, variables, cfg, aot_store=store).start()
    try:
        prompt = np.arange(5, dtype=np.int32)
        assert _answer(eng, prompt, 5) == solo_greedy(tiny_lm, prompt, 5)
    finally:
        eng.stop()
    assert eng.aot_status == {"w1": "compiled+saved",
                              "k4w16": "compiled+saved"}
    added = sorted(entries() - before)
    assert [f.split("-")[:2] for f in added] == \
        [["masked_step", "k4w16"], ["masked_step", "w1"]]
    assert all(store.config_digest in f for f in added)
    assert Engine(model, variables, cfg, aot_store=store).aot_status == \
        {"w1": "loaded", "k4w16": "loaded"}


def test_aot_save_is_load_verified(tmp_path, monkeypatch):
    """save() proves the blob deserializes before committing it — an
    executable that serializes into an unloadable blob (the persistent-
    compile-cache poison mode) must yield False and write NOTHING, so
    a later boot can never trust a poisoned entry."""
    from jax.experimental import serialize_executable

    from tpunet.utils.cache import AotProgramStore

    store = AotProgramStore(str(tmp_path), "digest")
    monkeypatch.setattr(serialize_executable, "serialize",
                        lambda compiled: (b"blob", None, None))
    monkeypatch.setattr(
        serialize_executable, "deserialize_and_load",
        lambda *a, **kw: (_ for _ in ()).throw(
            RuntimeError("Symbols not found")))
    assert store.save("masked_step", "w16", object()) is False
    assert not list(tmp_path.iterdir())

    monkeypatch.setattr(serialize_executable, "deserialize_and_load",
                        lambda *a, **kw: object())
    assert store.save("masked_step", "w16", object()) is True
    assert any(p.name.endswith(".aotx") for p in tmp_path.iterdir())


def test_serializable_compile_restores_cache_flag():
    """AOT-destined compiles run with the persistent compilation cache
    OFF (a cache-served executable saves a poison blob) and the flag is
    restored afterwards, including on the exception path."""
    from tpunet.utils.cache import serializable_compile

    prev = jax.config.jax_enable_compilation_cache
    with serializable_compile():
        assert jax.config.jax_enable_compilation_cache is False
    assert jax.config.jax_enable_compilation_cache == prev
    with pytest.raises(ValueError):
        with serializable_compile():
            raise ValueError("boom")
    assert jax.config.jax_enable_compilation_cache == prev


# ---------------------------------------------------------------------------
# speculative decoding over the paged pool
# ---------------------------------------------------------------------------


def _pool_clean(eng):
    """Quiesce invariant: every usable page is either on the free list
    or resident in the prefix cache — a rewind or release that dropped
    a page shows up here immediately."""
    cached = eng._prefix.pages_cached if eng._prefix else 0
    return len(eng._free_pages) + cached == eng.kv_pages_usable


def test_spec_config_rejects_no_drafts_and_no_drafter_width(tiny_lm):
    """A burst of no draft tokens and a drafter of no width are config
    errors, not silent downgrades."""
    for bad in (dict(spec_k=0), dict(spec_draft_width_mult=0.0)):
        with pytest.raises(ValueError):
            make_engine(tiny_lm, spec_decode=True, **bad)


def test_spec_greedy_bitwise_identical_both_acceptance_extremes(tiny_lm):
    """Greedy spec-on output must be BITWISE spec-off at both ends of
    the acceptance spectrum: a width_mult-1.0 drafter (the serving
    model drafting for itself — every draft accepted) and a random-
    init half-width drafter (near-total rejection — every cycle falls
    back to the one verified token). Every emitted token comes from
    the verify program, so acceptance can only change SPEED."""
    ps = prompts(5, rng_seed=7)
    solo = [solo_greedy(tiny_lm, p, 10) for p in ps]
    for wm, expect_all_accepted in ((1.0, True), (0.5, False)):
        eng = make_engine(tiny_lm, spec_decode=True, spec_k=3,
                          spec_draft_width_mult=wm).start()
        try:
            reqs = [eng.submit(p, max_new_tokens=10) for p in ps]
            outs = [r.result(timeout=120) for r in reqs]
        finally:
            eng.stop()
        assert outs == solo, f"wm={wm} diverged from solo greedy"
        snap = eng.registry.snapshot()
        drafted = snap["serve_spec_draft_tokens_total"]
        acc = snap["serve_spec_accepted_tokens_total"]
        rej = snap["serve_spec_rejected_tokens_total"]
        assert drafted > 0 and snap["serve_spec_verify_steps_total"] > 0
        assert acc + rej == drafted
        if expect_all_accepted:
            assert acc == drafted, "self-speculation must accept all"
        else:
            assert rej > 0, "random drafter should see rejections"
        assert _pool_clean(eng), "rewind/release leaked a page"


def test_spec_sampled_stream_identical_and_preempt_deterministic(tiny_lm):
    """Sampled requests: spec-on draws each position with the same
    (seed, step) counter key the sequential loop would have used, so
    the stream is bitwise spec-off — including across a pool-pressure
    preemption, where the resumed slot continues its exact sample
    sequence (steps0 = len(req.tokens) re-derives the key)."""
    kw = dict(max_new_tokens=8, temperature=0.9, top_k=5, seed=123)
    ps = prompts(4, rng_seed=11, lo=6, hi=7)

    def run(**cfg_kw):
        eng = make_engine(tiny_lm, **cfg_kw).start()
        try:
            reqs = [eng.submit(p, **kw) for p in ps]
            return eng, [r.result(timeout=120) for r in reqs]
        finally:
            eng.stop()

    _, base = run()
    eng_on, sampled = run(spec_decode=True, spec_k=3,
                          spec_draft_width_mult=0.5)
    assert sampled == base, "spec-on sampled stream diverged"
    assert _pool_clean(eng_on)
    # Tight pool: two co-residents cannot both finish without a
    # preemption; the preempted request must still produce the same
    # sampled stream after resume-prefill.
    eng_tight, tight = run(spec_decode=True, spec_k=3,
                           spec_draft_width_mult=0.5, slots=2,
                           kv_pages=5, kv_page_tokens=4)
    assert tight == base, "preempt-resume broke sample determinism"
    assert eng_tight.registry.snapshot()[
        "serve_kv_preemptions_total"] >= 1, \
        "pool never preempted; the resume path was not exercised"
    assert _pool_clean(eng_tight)


def test_spec_rejection_rewind_recycles_pages(tiny_lm):
    """The leak test for cursor rewind: a random half-width drafter
    rejects nearly everything, so every burst allocates pages through
    pos+K and rewinds most of them. Churn waves over a small pool
    until every page has been reused; greedy parity proves recycled
    pages carry no stale K/V from a rewound burst, and at quiesce
    free + prefix-cached must equal the whole pool."""
    eng = make_engine(tiny_lm, slots=2, kv_pages=8, kv_page_tokens=4,
                      prefix_cache=False, spec_decode=True, spec_k=3,
                      spec_draft_width_mult=0.5).start()
    try:
        for wave in range(3):
            ps = prompts(4, rng_seed=300 + wave, lo=5, hi=9)
            reqs = [eng.submit(p, max_new_tokens=8) for p in ps]
            for p, r in zip(ps, reqs):
                assert r.result(timeout=120) == \
                    solo_greedy(tiny_lm, p, 8), f"wave {wave} diverged"
        snap = eng.registry.snapshot()
        assert snap["serve_spec_rejected_tokens_total"] > 0
        assert snap["serve_kv_page_allocs_total"] > eng.kv_pages_usable
        assert len(eng._free_pages) == eng.kv_pages_usable, \
            "a rewound or released page leaked"
        assert snap["serve_kv_pages_used"] == 0
    finally:
        eng.stop()


def test_spec_rewind_clamps_at_pinned_prefix_pages(tiny_lm):
    """A rejection rewind must never free or zero a page the slot
    pinned from the prefix cache: with a shared page-aligned prompt
    and a heavily-rejecting drafter, later requests keep hitting the
    SAME cached pages and must stay solo-greedy-identical — a rewind
    that clawed back (or a burst that overwrote) a shared page would
    corrupt every later hit."""
    eng = make_engine(tiny_lm, slots=2, kv_pages=16, kv_page_tokens=4,
                      spec_decode=True, spec_k=3,
                      spec_draft_width_mult=0.5).start()
    try:
        rng = np.random.default_rng(23)
        p = rng.integers(0, TINY.vocab_size, size=8).astype(np.int32)
        outs = [eng.submit(p, max_new_tokens=6).result(timeout=120)
                for _ in range(3)]
        snap = eng.registry.snapshot()
        assert snap["serve_prefix_hits_total"] >= 2
        assert snap["serve_spec_rejected_tokens_total"] > 0
        assert _pool_clean(eng)
    finally:
        eng.stop()
    want = solo_greedy(tiny_lm, p, 6)
    assert outs == [want] * 3, \
        "a spec rewind or draft write disturbed shared prefix pages"


def test_spec_serve_record_and_instruments(tiny_lm):
    """The ops contract: a spec engine's serve record carries the
    spec_* fields (docs/metrics_schema.md obs_serve) with coherent
    derived rates, and the serve_spec_* instruments exist on the
    registry."""
    from tpunet.serve.engine import build_serve_record

    eng = make_engine(tiny_lm, spec_decode=True, spec_k=3,
                      spec_draft_width_mult=1.0).start()
    try:
        eng.submit(prompts(1, rng_seed=3)[0],
                   max_new_tokens=8).result(timeout=120)
    finally:
        eng.stop()
    rec = build_serve_record(eng.registry, queue_depth=0,
                             active_slots=0, slots=4, uptime_s=1.0,
                             window_s=1.0)
    assert rec["spec_draft_tokens_total"] > 0
    assert rec["spec_accepted_tokens_total"] \
        + rec["spec_rejected_tokens_total"] \
        == rec["spec_draft_tokens_total"]
    assert rec["spec_verify_steps_total"] > 0
    assert rec["spec_acceptance_rate"] == 1.0   # self-speculation
    assert rec["spec_accepted_tokens_per_verify"] > 0
    assert eng.registry.snapshot()[
        "serve_spec_acceptance_rate"] == 1.0


# ---------------------------------------------------------------------------
# one decode step in flight (PR 32): step N+1 is dispatched before step
# N's tokens are read. The engine is driven on the test's thread
# (tests/_serve_script.py), so each script is one fixed order of calls.
# ---------------------------------------------------------------------------

@MESHES
@pytest.mark.parametrize("sampling", sorted(SAMPLING))
def test_lookahead_tokens_equal_the_sequential_loops(tiny_lm, build,
                                                     sampling):
    """The engine's tokens, one step ahead of the host, are bit-equal
    to a loop that reads every step before it builds the next — greedy
    and seeded rows alike (the sampler's step comes from the slot's own
    count, which a token in flight has already advanced)."""
    script = staggered_script(SAMPLING[sampling], TINY.vocab_size)
    want = sequential_tokens(build(tiny_lm, slots=3, prefix_cache=False),
                             script)
    eng = build(tiny_lm, slots=3)
    reqs = drive(eng, script)
    assert [r.tokens for r in reqs] == want
    assert [len(t) for t in want] == [9, 4, 12, 6]
    assert all(r.finish_reason == "length" for r in reqs)
    snap = eng.registry.snapshot()
    assert snap["serve_tokens_total"] == sum(len(t) for t in want)
    assert snap["serve_decode_steps_overlapped_total"] > 0
    assert snap.get("serve_decode_rows_discarded_total", 0) == 0
    assert eng._in_flight is None and _pool_clean(eng)
    # one program a width: the forwarded tokens are a device array
    # from the first call on (a mesh engine traces its bucket-8 program
    # twice, for the pool as built and as a step returns it, with or
    # without a step in flight)
    assert eng._step._cache_size() == (3 if eng.mesh is None else 4)


def _stream(tiny_lm, prompt, n_new, **kw):
    eng = make_engine(tiny_lm, **kw)
    (req,) = drive(eng, [(0, prompt, dict(max_new_tokens=n_new))])
    return req.tokens


def _first_seen_at(stream, lo):
    """An index >= lo whose token did not occur before it."""
    return next(k for k in range(lo, len(stream))
                if stream[k] not in stream[:k])


def test_stop_token_ends_the_stream_and_discards_the_row_ahead(tiny_lm):
    """A stop token read from step N was sampled after step N+1 went
    out with the row live: the stream ends at the stop token, that row
    of N+1 is counted as discarded and never pushed, the slot's pages
    return to the free list once, and the next request admitted into
    them serves what it serves alone."""
    p, q = prompts(2, rng_seed=5, lo=5, hi=6)
    alone = _stream(tiny_lm, p, 12, slots=1, prefix_cache=False)
    k = _first_seen_at(alone, 2)
    eng = make_engine(tiny_lm, slots=1, prefix_cache=False)
    reqs = drive(eng, [(0, p, dict(max_new_tokens=12,
                                   stop_token=alone[k])),
                       (1, q, dict(max_new_tokens=8))])
    assert reqs[0].tokens == alone[:k + 1]
    assert reqs[0].finish_reason == "stop"
    snap = eng.registry.snapshot()
    assert snap["serve_decode_rows_discarded_total"] == 1
    assert snap["serve_tokens_total"] == k + 1 + 8
    assert sorted(eng._free_pages) == \
        list(range(1, eng.kv_pages_usable + 1))
    assert reqs[1].tokens == solo_greedy(tiny_lm, q, 8)


@pytest.mark.parametrize("how,reason", [("cancel", "cancelled"),
                                        ("deadline", "deadline")])
def test_cancel_and_deadline_mid_stream_discard_the_row_in_flight(
        tiny_lm, how, reason):
    """A cancel or a deadline is seen at the reap after a step went out
    with the row live: the request ends with what had been pushed, the
    row in flight is discarded, the neighbour's stream is untouched."""
    p, q = prompts(2, rng_seed=6, lo=4, hi=8)
    eng = make_engine(tiny_lm, slots=2, prefix_cache=False)

    def after(k, reqs):
        if k == 3:
            assert eng._in_flight is not None
            if how == "cancel":
                reqs[0].cancel()
            else:
                reqs[0].deadline_t = time.perf_counter()

    reqs = drive(eng, [(0, p, dict(max_new_tokens=20)),
                       (0, q, dict(max_new_tokens=9))], after=after)
    assert reqs[0].finish_reason == reason
    got = reqs[0].tokens
    assert 0 < len(got) < 20 and got == solo_greedy(tiny_lm, p, 20)[:len(got)]
    assert reqs[1].tokens == solo_greedy(tiny_lm, q, 9)
    snap = eng.registry.snapshot()
    assert snap["serve_decode_rows_discarded_total"] == 1
    assert snap["serve_tokens_total"] == len(got) + 9
    assert _pool_clean(eng)


def test_finish_at_max_seq_len_is_known_at_dispatch(tiny_lm):
    """A request that runs into the KV length ends by count: its last
    token's row is not dispatched again, nothing is discarded, and the
    tokens are the sequential loop's."""
    p = prompts(1, rng_seed=7, lo=14, hi=15)[0]
    script = [(0, p, dict(max_new_tokens=200))]
    want = sequential_tokens(make_engine(tiny_lm, prefix_cache=False),
                             script)
    eng = make_engine(tiny_lm)
    (req,) = drive(eng, script)
    assert req.tokens == want[0]
    assert len(req.tokens) == TINY.max_seq_len - p.size
    assert req.finish_reason == "length"
    snap = eng.registry.snapshot()
    assert snap.get("serve_decode_rows_discarded_total", 0) == 0
    assert snap["serve_decode_steps_total"] == len(req.tokens) - 1


def test_preemption_with_a_step_in_flight_resumes_token_identically(
        tiny_lm):
    """Page pressure with a step in flight: the step is read before the
    victim is re-queued (its prompt + EVERY generated token), so the
    resumed request's tokens equal an unpreempted run's."""
    eng = make_engine(tiny_lm, slots=2, kv_pages=5, kv_page_tokens=4,
                      default_max_new_tokens=12)
    ps = prompts(4, rng_seed=1, lo=6, hi=7)
    preempted_with_flight = []
    preempt = eng._preempt_slot

    def spy(slot_i):
        preempted_with_flight.append(eng._in_flight is not None)
        preempt(slot_i)

    eng._preempt_slot = spy
    reqs = drive(eng, [(0, p, dict(max_new_tokens=12)) for p in ps])
    assert [r.tokens for r in reqs] == \
        [solo_greedy(tiny_lm, p, 12) for p in ps]
    assert preempted_with_flight and not any(preempted_with_flight)
    assert sum(r.preemptions for r in reqs) >= 1
    assert eng.registry.snapshot()["serve_tokens_total"] == 4 * 12


def test_shared_prefix_is_unchanged_by_a_neighbours_overrun(tiny_lm):
    """Prefix cache on: a stop-token overrun in a neighbouring slot
    writes one K/V row past that slot's stream, on a private page —
    the adopted pages of a shared prefix hold the same bits after it,
    and a later request behind that prefix serves what it serves
    alone."""
    rng = np.random.default_rng(8)
    shared = rng.integers(0, TINY.vocab_size, size=8).astype(np.int32)
    first = np.concatenate([shared, [3, 4]]).astype(np.int32)
    later = np.concatenate([shared, [5]]).astype(np.int32)
    other = prompts(1, rng_seed=9, lo=6, hi=7)[0]
    alone = _stream(tiny_lm, other, 10, slots=1, kv_page_tokens=4)
    k = _first_seen_at(alone, 2)
    eng = make_engine(tiny_lm, slots=2, kv_page_tokens=4)
    bits = {}

    def after(k_iter, reqs):
        if k_iter == 0:
            bits["before"] = [eng._read_page_rows(n.page)
                              for n in eng._active[0].pinned]

    reqs = drive(eng, [(0, first, dict(max_new_tokens=14)),
                       (0, other, dict(max_new_tokens=10,
                                       stop_token=alone[k])),
                       (40, later, dict(max_new_tokens=6))], after=after)
    assert len(bits["before"]) == 2              # two adopted pages
    pinned = eng._prefix.lookup(later, 2)
    after_bits = [eng._read_page_rows(n.page) for n in pinned]
    for b, a in zip(bits["before"], after_bits):
        assert all(np.array_equal(x, y) for x, y in zip(b, a))
    assert reqs[1].tokens == alone[:k + 1]
    snap = eng.registry.snapshot()
    assert snap["serve_decode_rows_discarded_total"] == 1
    assert snap["serve_prefix_hits_total"] >= 1
    assert reqs[0].tokens == solo_greedy(tiny_lm, first, 14)
    assert reqs[2].tokens == solo_greedy(tiny_lm, later, 6)


@pytest.mark.parametrize("how,reason", [("drain", "drain"),
                                        ("stop", "cancelled")])
def test_drain_and_stop_push_the_step_in_flight_once(tiny_lm, how, reason):
    """``drain`` and ``stop`` read the step in flight before they
    finish the survivors: every token the device computed for an
    unfinished request is pushed, exactly once."""
    ps = prompts(3, rng_seed=10)
    eng = make_engine(tiny_lm, prefix_cache=False)
    reqs = [eng.submit(p, max_new_tokens=30) for p in ps]
    for _ in range(4):
        eng._iterate()
    assert eng._in_flight is not None
    sampled = [s.generated for s in eng._active[:3]]
    assert [len(r.tokens) for r in reqs] == [n - 1 for n in sampled]
    if how == "drain":
        assert eng.drain(timeout=0.0) is False
    else:
        eng.stop()
    assert eng._in_flight is None
    for p, r, n in zip(ps, reqs, sampled):
        assert r.finish_reason == reason
        assert r.tokens == solo_greedy(tiny_lm, p, 30)[:n]
        events = [v for kind, v in r.events(timeout=5) if kind == "token"]
        assert events == r.tokens
    assert eng.registry.snapshot()["serve_tokens_total"] == sum(sampled)


def test_a_call_in_flight_owns_its_host_buffers(tiny_lm):
    """The allocator rewrites ``_page_table`` in place while a call may
    still be in flight (and the CPU backend may alias host memory): no
    numpy argument of a dispatch shares memory with engine state, and
    a table zeroed right after the dispatch changes no token."""
    p, q = prompts(2, rng_seed=11, lo=5, hi=9)
    eng = make_engine(tiny_lm, slots=2, prefix_cache=False)
    step, shared = eng._step, []

    def spy(*args):
        shared.extend(
            a for a in args if isinstance(a, np.ndarray)
            and any(np.shares_memory(a, mine) for mine in
                    (eng._page_table, eng._inactive_tok)))
        return step(*args)

    eng._step = spy
    reqs = [eng.submit(x, max_new_tokens=8) for x in (p, q)]
    eng._iterate()                       # prefill both, dispatch step 1
    for _ in range(3):
        eng._iterate()                   # dispatch N+1, read N
        assert eng._in_flight is not None
        table = eng._page_table.copy()
        eng._page_table[:] = 0           # N+1 is in flight
        eng._drain_decode()
        eng._page_table[:] = table
    while not all(r.done for r in reqs):
        eng._iterate()
    assert not shared
    assert [r.tokens for r in reqs] == [solo_greedy(tiny_lm, x, 8)
                                        for x in (p, q)]


def test_one_decode_span_a_step_and_the_overlapped_share(tiny_lm,
                                                         monkeypatch):
    """On a fixed script: exactly one ``tpunet/serve_decode`` span per
    ``serve_decode_steps_total``, each read inside a
    ``tpunet/serve_decode_wait`` span, and every step overlapped but
    the first after an admission (or a drain)."""
    import contextlib
    from tpunet.serve import engine as engine_mod
    from tpunet.serve.engine import build_serve_record

    opened, depth = [], []

    @contextlib.contextmanager
    def counting(name, **args):
        opened.append((name, tuple(depth)))
        depth.append(name)
        try:
            yield
        finally:
            depth.pop()

    # the trace annotation every ``Engine._phase`` opens
    monkeypatch.setattr(engine_mod, "span", counting)
    script = staggered_script(SAMPLING["greedy"], TINY.vocab_size)
    eng = make_engine(tiny_lm, slots=3, prefix_cache=False)
    reqs = drive(eng, script)
    snap = eng.registry.snapshot()
    steps = int(snap["serve_decode_steps_total"])
    admissions = int(snap["serve_prefills_total"])
    assert admissions == 4
    names = [n for n, _ in opened]
    assert names.count("tpunet/serve_decode") == steps
    assert names.count("tpunet/serve_decode_wait") == steps
    # a read happens under the dispatch of the next step, behind a
    # prefill call, or alone where nothing is left to dispatch
    assert {under[-1:] for n, under in opened
            if n == "tpunet/serve_decode_wait"} <= {
        ("tpunet/serve_decode",), ("tpunet/serve_prefill",), ()}
    overlapped = int(snap["serve_decode_steps_overlapped_total"])
    assert steps - admissions - 1 <= overlapped < steps
    assert overlapped / steps >= 0.75
    assert int(snap["serve_tokens_total"]) == \
        sum(len(r.tokens) for r in reqs)
    rec = build_serve_record(eng.registry, queue_depth=0, active_slots=0,
                             slots=3, uptime_s=1.0, window_s=1.0)
    assert rec["decode_steps_overlapped_total"] == overlapped
    assert rec["decode_rows_discarded_total"] == 0
    assert rec["token_latency_count"] == steps
