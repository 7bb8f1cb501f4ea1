"""The hybrid decoder (``latent_lm`` with ``model_type`` ``qwen3_next``:
gated-delta-rule linear attention beside gated grouped-query attention,
softmax-routed experts beside a gated shared one) against its plain
reference (``benchmark/reference/qwen3-next-80b-a3b.py``) at a tiny size
on the CPU: 4 layers L L L F, 4 of 8 experts held, 2 KV heads under 4
query heads, chunks of 8.

Each mixer alone and the whole model = the reference; a chunked prefill
then width-1 decode steps through the ``Engine``'s own masked step and
state pool = the reference's full pass (prompts shorter than, equal to
and not a multiple of the chunk); the chunked delta rule = the
token-by-token one from a non-zero state; a slot's next tenant is not
touched by what the last one left, an idle row's state by a step; the
shares add up to the uncut layer; an engine over a fixed-state model
builds no prefix cache and refuses speculative decoding.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from tpunet.config import ModelConfig, ServeConfig
from tpunet.models import create_model, hybrid_mixers
from tpunet.models.latent_lm import LatentArch
from tpunet.models.moe import RoutedShareMlp
from tpunet.serve import Engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = harness.load_module(
    os.path.join(REPO, "benchmark", "reference", "qwen3-next-80b-a3b.py"),
    "reference_qwen3next_for_hybrid_lm_test")

PUBLISHED_E, HELD = 8, (2, 3, 4, 5)
VOCAB, MAX_LEN, SEED, CHUNK = 50, 64, 2000000011, 8
HYBRID_TINY = dict(
    model_type="qwen3_next", hidden_size=64, intermediate_size=128,
    rms_norm_eps=1e-6, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    num_experts_per_tok=2, moe_intermediate_size=32,
    shared_expert_intermediate_size=32)
CFG = dict(
    HYBRID_TINY, num_hidden_layers=4,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    num_experts=len(HELD), num_experts_published=PUBLISHED_E,
    held_experts=list(HELD), vocab_size=VOCAB, param_dtype="float32")
_NOT_ARCH = ("num_experts_published", "vocab_size", "param_dtype",
             "shared_expert_intermediate_size")


def arch_keys(cfg):
    """The configuration's keys as ``ModelConfig.latent`` takes them."""
    out = {k: v for k, v in cfg.items() if k not in _NOT_ARCH}
    out["layer_types"] = cfg["layer_types"][:cfg["num_hidden_layers"]]
    out.update(num_experts=cfg["num_experts_published"],
               first_k_dense_replace=0)
    return out


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    monkeypatch.setattr(hybrid_mixers, "_CHUNK", CHUNK)


def build(cfg):
    model = create_model(ModelConfig(
        name="latent_lm", vocab_size=VOCAB, max_seq_len=MAX_LEN,
        dtype="float32", param_dtype="float32", latent=arch_keys(cfg)))
    params = weights.make_tree(REF.param_spec(cfg, "serve"), SEED)
    return model, params, REF.make_params(cfg, "serve", SEED)


@pytest.fixture(scope="module")
def tiny():
    return build(CFG)


def ref_logits(ref_params, tokens, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.logits_fn(ref_params, jnp.asarray(tokens),
                                        REF.sizes(cfg, "serve"), "float32"))


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def served_gap(ref_params, prompt, served, cfg=CFG):
    """How far each served token's reference logit lies below the
    position's best."""
    lg = ref_logits(ref_params, np.concatenate([prompt, served]), cfg)
    at = len(prompt) - 1 + np.arange(len(served))
    return lg[at].max(-1) - lg[at, served]


# -- (a) the mixers and the model, plain forward ------------------------------

def test_parameter_tree_is_the_reference_spec(tiny):
    model, params, _ = tiny
    init = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(dict(init["params"])) == shapes(params)


@pytest.mark.parametrize("part", ["linear_attention", "full_attention",
                                  "experts", "model"])
def test_plain_forward_is_the_reference(tiny, part):
    model, params, ref_params = tiny
    s = REF.sizes(CFG, "serve")
    arch = LatentArch.from_mapping(arch_keys(CFG))
    r = np.random.default_rng(3)
    u = jnp.asarray(r.normal(size=(2, 21, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        if part == "model":
            toks = np.stack([tokens_of(21, 1), tokens_of(21, 2)])
            got = np.asarray(model.apply({"params": params},
                                         jnp.asarray(toks)))
            want = [ref_logits(ref_params, row) for row in toks]
        elif part == "experts":
            p = params["block00"]["moe"]
            got = np.asarray(RoutedShareMlp(
                PUBLISHED_E, 32, 2, held=HELD, scoring="softmax",
                shared_gate=True, dtype=jnp.float32).apply({"params": p}, u))
            want = [np.asarray(REF.expert_layer(row, p, s, "float32"))
                    for row in u]
        else:
            block, name, fn = (
                ("block00", "linear_attn", REF.linear_attention)
                if part == "linear_attention"
                else ("block03", "attn", REF.full_attention))
            p = params[block][name]
            mixer = (hybrid_mixers.GatedDeltaNet
                     if part == "linear_attention"
                     else hybrid_mixers.GatedAttention)(
                arch, part, dtype=jnp.float32)
            got = np.asarray(mixer.apply({"params": p}, u))
            want = [np.asarray(fn(row, p, s, "float32")) for row in u]
    for row in range(2):
        np.testing.assert_allclose(got[row], want[row], atol=2e-5)
    assert np.abs(want[0]).max() > 1e-3


def test_zero_centred_norms_are_not_plain_ones(tiny):
    """``1 + w`` against ``w``: the reference with the offset left out
    is another model (what the comparisons above would not notice if
    the weights were drawn as ones)."""
    _, _, ref_params = tiny
    toks = tokens_of(12, 4)
    plain = dict(ref_params)
    for k in [k for k in plain if k.endswith(("ln1", "ln2")) or k == "ln"]:
        plain[k] = plain[k] - 1.0
    assert np.abs(ref_logits(plain, toks)
                  - ref_logits(ref_params, toks)).max() > 1e-3


# -- (c) the two forms of the delta rule --------------------------------------

@pytest.mark.parametrize("t,chunk", [(5, 8), (8, 8), (21, 8), (64, 16),
                                     (128, 64)])
def test_chunked_delta_rule_is_the_token_by_token_one(t, chunk):
    """From a non-zero state, with fast and slow heads, and positions
    that change nothing (beta 0, g 0) inside and at the end."""
    r = np.random.default_rng(t)
    b, h, dk, dv = 2, 3, 16, 8
    n = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)  # noqa: E731
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q, k, v = unit(n(b, t, h, dk)) / 4, unit(n(b, t, h, dk)), n(b, t, h, dv)
    g = -jnp.exp(jnp.asarray([-4.0, 0.0, 3.0])) * jax.nn.softplus(n(b, t, h))
    beta = jax.nn.sigmoid(n(b, t, h))
    real = jnp.asarray(r.random((b, t)) > 0.2).at[:, t - 2:].set(False)
    g, beta = (jnp.where(real[..., None], x, 0.0) for x in (g, beta))
    s0 = n(b, h, dk, dv)
    o_seq, s_seq = hybrid_mixers.gdn_sequential(q, k, v, g, beta, s0)
    o_chk, s_chk = hybrid_mixers.gdn_chunked(q, k, v, g, beta, s0, chunk)
    np.testing.assert_allclose(np.asarray(o_chk), np.asarray(o_seq),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_chk), np.asarray(s_seq),
                               atol=2e-5)
    assert np.abs(np.asarray(s_seq) - np.asarray(s0)).max() > 0.1


# -- (b) prefill, then decode, through the engine's step and pool -------------

def new_engine(model, params, **kw):
    kw = {"slots": 3, "queue_max": 8, "prefill_buckets": (32,),
          "kv_page_tokens": 4, "emit_every_s": 0.0, **kw}
    return Engine(model, {"params": params}, ServeConfig(**kw))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _apply_as_the_masked_step(model, paged_kv, params, cache, toks,
                              positions, active, table, last_idx,
                              state_rows):
    return model.apply(
        {"params": params, "cache": cache}, toks, decode=True,
        pos_offset=positions, decode_active=active, paged_kv=paged_kv,
        page_table=table, mutable=["cache"], lengths=last_idx + 1,
        state_rows=state_rows)


def logits_and_dispatch(eng, toks, positions, active, last_idx, slot_i=None):
    """One call of the engine's masked step on the engine's pool, and the
    same call kept as logits (``model.apply`` with the arguments the
    masked step hands it). Returns ``(logits, sampled)``; the engine's
    cache is the masked step's own output."""
    rows = toks.shape[0]
    table = (eng._page_table if slot_i is None
             else eng._page_table[slot_i:slot_i + 1]).copy()
    state_rows = (None if rows == eng.slots
                  else jnp.asarray([slot_i], jnp.int32))
    with jax.default_matmul_precision("highest"):
        logits, mutated = _apply_as_the_masked_step(
            eng.model, eng._paged_kv, eng.variables["params"], eng._cache,
            jnp.asarray(toks), jnp.asarray(positions, jnp.int32),
            jnp.asarray(active), jnp.asarray(table),
            jnp.asarray(last_idx, jnp.int32), state_rows)
        eng._cache, sampled = eng._dispatch_step(
            toks, np.asarray(positions, np.int32), np.asarray(active),
            np.asarray(last_idx, np.int32), slot_i)
    for got, want in zip(jax.tree_util.tree_leaves(eng._cache),
                         jax.tree_util.tree_leaves(mutated["cache"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
    return np.asarray(logits), np.asarray(sampled)


@pytest.mark.parametrize("n", [5, 8, 21, 29])
def test_prefill_then_decode_through_the_engines_pool_is_the_reference(
        tiny, n):
    """An ``n``-token prompt through a ``[1, 32]`` row call into slot 1
    (shorter than the chunk, the chunk, not a multiple of it; all shorter
    than the bucket), then 6 ``[3, 1]`` decode steps with slots 0 and 2
    idle: every logit is the reference's over the ``n + 6`` tokens, and
    the token the masked step samples is that logit's best."""
    model, params, ref_params = tiny
    eng = new_engine(model, params)
    seq = tokens_of(n + 6, n)
    want = ref_logits(ref_params, seq)
    assert eng._alloc_pages_for(1, MAX_LEN) is not None
    toks = np.zeros((1, 32), np.int32)
    toks[0, :n] = seq[:n]
    toks[0, n:] = tokens_of(32 - n, 99)         # a padded tail that is not 0
    lg, sampled = logits_and_dispatch(eng, toks, [0], [True], [n - 1], 1)
    np.testing.assert_allclose(lg[0, :n], want[:n], atol=3e-5)
    assert sampled[0] == want[n - 1].argmax()
    for j in range(6):
        step = np.zeros((3, 1), np.int32)
        step[1, 0] = seq[n + j]
        lg, sampled = logits_and_dispatch(
            eng, step, [0, n + j, 0], [False, True, False], [0, 0, 0])
        np.testing.assert_allclose(lg[1, 0], want[n + j], atol=3e-5)
        assert sampled[1] == want[n + j].argmax()


# -- (d) a slot's next tenant; an idle row ------------------------------------

def state_leaves(eng):
    flat = weights.flatten(jax.tree_util.tree_map(np.asarray, eng._cache))
    return {k: v for k, v in flat.items() if "linear_attn" in k}


def test_an_idle_rows_state_is_bit_unchanged_by_a_step(tiny):
    model, params, _ = tiny
    eng = new_engine(model, params)
    for slot in (0, 2):
        assert eng._alloc_pages_for(slot, MAX_LEN) is not None
        toks = np.zeros((1, 32), np.int32)
        toks[0, :13] = tokens_of(13, slot)
        eng._cache, _ = eng._dispatch_step(
            toks, np.zeros(1, np.int32), np.ones(1, bool),
            np.full(1, 12, np.int32), slot)
    before = state_leaves(eng)
    assert len(before) == 6 and all(v[[0, 2]].any() and not v[1].any()
                                    for v in before.values())
    step = np.zeros((3, 1), np.int32)
    eng._cache, _ = eng._dispatch_step(
        step, np.asarray([13, 0, 0], np.int32),
        np.asarray([True, False, False]), np.zeros(3, np.int32))
    after = state_leaves(eng)
    for name in before:
        assert (after[name][0] != before[name][0]).any()
        for idle in (1, 2):
            np.testing.assert_array_equal(
                after[name][idle].view(np.uint32),
                before[name][idle].view(np.uint32))


def test_a_slots_next_tenant_gets_what_a_fresh_engine_gives(tiny):
    """One slot: a long request, then a shorter one into the state row
    the first left behind — its tokens are bit-equal to those of an
    engine that never served the first, and the reference's best."""
    from _serve_script import drive
    model, params, ref_params = tiny
    first = (0, tokens_of(27, 5), dict(max_new_tokens=9, temperature=0.0))
    second = (1, tokens_of(6, 6), dict(max_new_tokens=8, temperature=0.0))
    eng = new_engine(model, params, slots=1)
    reqs = drive(eng, [first, second])
    alone = drive(new_engine(model, params, slots=1), [second])
    assert reqs[1].tokens == alone[0].tokens and len(reqs[1].tokens) == 8
    for (_, prompt, _), req in zip((first, second), reqs):
        gap = served_gap(ref_params, prompt, np.asarray(req.tokens, np.int32))
        assert gap.max() < 1e-4, gap
    snap = eng.registry.snapshot()
    assert snap["serve_state_rows_reset_total"] == 2


# -- (e) the expert layer's share ----------------------------------------------

def _moe_params(seed=9):
    r = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(0.3 * r.normal(size=s), jnp.float32)  # noqa: E731
    return {"router": n(64, PUBLISHED_E),
            "experts_gate": n(PUBLISHED_E, 64, 32),
            "experts_up": n(PUBLISHED_E, 64, 32),
            "experts_down": n(PUBLISHED_E, 32, 64), "shared_gate": n(64, 32),
            "shared_up": n(64, 32), "shared_down": n(32, 64),
            "shared_expert_gate": n(64, 1)}


def _share_of(p, held):
    take = jnp.asarray(held)
    return {k: (v[take] if k.startswith("experts_") else v)
            for k, v in p.items()}


@pytest.mark.parametrize("shares", [((0, 1, 2, 3), (4, 5, 6, 7)),
                                    ((0, 1), (2, 3), (4, 5), (6, 7))])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    p = _moe_params()
    s = REF.sizes(CFG, "serve")
    u = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(REF.expert_layer(
            u, p, s, "float32", held=list(range(PUBLISHED_E))))
        shared = np.asarray(REF.shared_expert(u, p, "float32"))
        total = shared.copy()             # what every chip computes: once
        for held in shares:
            part = np.asarray(RoutedShareMlp(
                PUBLISHED_E, 32, 2, held=held, scoring="softmax",
                shared_gate=True, dtype=jnp.float32).apply(
                    {"params": _share_of(p, held)}, u))
            np.testing.assert_allclose(part, np.asarray(REF.expert_layer(
                u, _share_of(p, held), s, "float32", held=list(held))),
                atol=2e-5)
            total += part - shared
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert np.abs(whole - shared).max() > 0.01     # the experts matter


# -- (f) what a fixed state rules out ------------------------------------------

def test_engine_over_a_fixed_state_builds_no_prefix_cache(tiny):
    """``ServeConfig.prefix_cache`` is on by default; two requests behind
    one shared prefix of 16 pages are both prefilled whole and both give
    the reference's best tokens."""
    model, params, ref_params = tiny
    eng = new_engine(model, params, prefill_buckets=(48,),
                     kv_page_tokens=2).start()
    assert eng.cfg.prefix_cache and eng._prefix is None
    try:
        shared = tokens_of(32, 5)
        prompts = [np.concatenate([shared, tokens_of(5, 6)]),
                   np.concatenate([shared, tokens_of(9, 8)])]
        first = eng.submit(prompts[0], max_new_tokens=7, temperature=0.0)
        first.result(timeout=300.0)
        second = eng.submit(prompts[1], max_new_tokens=6, temperature=0.0)
        second.result(timeout=300.0)
        snap = eng.registry.snapshot()
    finally:
        eng.stop()
    for prompt, req in zip(prompts, (first, second)):
        assert req.finish_reason == "length" and not req.error
        gap = served_gap(ref_params, prompt, np.asarray(req.tokens, np.int32))
        assert gap.max() < 1e-4, gap
    assert snap["serve_prefix_cache_enabled"] == 0
    assert "serve_prefix_hits_total" not in snap
    assert snap["serve_prefill_tokens_total"] == 37 + 41
    assert snap["serve_state_rows_reset_total"] == 2
    per_slot = 3 * (4 * 16 * 16 + 3 * 128) * 4
    assert snap["serve_state_bytes_per_slot"] == per_slot
    assert snap["serve_state_pool_bytes"] == 3 * per_slot == sum(
        v.nbytes for v in state_leaves(eng).values())
    assert snap["serve_cache_bytes_per_token_kv"] == 2 * 128 * 4
    assert (snap["serve_experts_held"], snap["serve_experts_total"]) == (4, 8)
    assert "serve_moe_chunk_rows" not in snap    # no reader: PR 37
    assert snap["serve_decode_attend_kernel"] == 0        # off the TPU
    assert eng.kv_pool_bytes() == sum(
        v.nbytes for v in jax.tree_util.tree_leaves(eng._cache)) \
        - 3 * per_slot


def test_engine_over_a_fixed_state_refuses_spec_decode(tiny):
    model, params, _ = tiny
    with pytest.raises(ValueError, match="cannot rewind"):
        new_engine(model, params, spec_decode=True,
                   spec_draft_width_mult=1.0)


def test_grouped_attention_alone_keeps_the_prefix_cache():
    """Every layer ``full_attention``: nothing is kept per slot, so the
    prefix cache is built, the second request's prefill starts after the
    adopted pages (the pooled-keys path), and both give the reference's
    best tokens."""
    cfg = dict(CFG, num_hidden_layers=2, layer_types=["full_attention"] * 2)
    model, params, ref_params = build(cfg)
    eng = new_engine(model, params, prefill_buckets=(16, 48)).start()
    assert eng._prefix is not None and model.state_bytes_per_slot == 0
    try:
        shared = tokens_of(24, 5)
        prompts = [np.concatenate([shared, tokens_of(5, 6)]),
                   np.concatenate([shared, tokens_of(9, 8)])]
        reqs = []
        for p in prompts:
            reqs.append(eng.submit(p, max_new_tokens=6, temperature=0.0))
            reqs[-1].result(timeout=300.0)
        snap = eng.registry.snapshot()
    finally:
        eng.stop()
    assert snap["serve_prefix_hits_total"] >= 1
    assert "serve_state_pool_bytes" not in snap
    for prompt, req in zip(prompts, reqs):
        gap = served_gap(ref_params, prompt, np.asarray(req.tokens, np.int32),
                         cfg)
        assert gap.max() < 1e-4, gap


def test_what_is_not_built_says_so(tiny):
    model, params, _ = tiny
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="no backward"):
        model.apply({"params": params}, toks, train=True)
    with pytest.raises(ValueError, match="layer_types"):
        LatentArch.from_mapping(dict(arch_keys(CFG), layer_types=[
            "sliding_attention"] * 4))
    with pytest.raises(ValueError, match="model_type"):
        LatentArch.from_mapping(dict(arch_keys(CFG), model_type="other"))
