"""The parallel decoder (``latent_lm`` with ``model_type``
``cohere2_moe``: one LayerNorm a block feeding grouped-query attention
and the expert layer side by side, sliding layers with interleaved
rotary positions under a window beside full layers with no positions,
sigmoid-routed experts beside averaged shared ones, a tied scaled head)
against its plain reference
(``benchmark/reference/command-a-plus-05-2026.py``) at a tiny size on
the CPU: 2 periods S S S F, a window of 24 keys over 8-token pages, 8
query heads over 2 KV heads, 4 of 8 experts held, top-2, 2 shared.

The mixers alone and the whole model = the reference; a prefill, then
width-1 decode steps through the ``Engine``'s own masked step and page
pool, contexts several windows long = the reference's full pass; once
more through an ADOPTED prefix (the continued row form); the shares add
up to the uncut layer; the fused shared product = the mean of the
experts; interleaved rotary = a hand-written pair rotation; the
published widths count 4,733,292,544 parameters.
"""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from tpunet.config import ModelConfig, ServeConfig
from tpunet.models import create_model, hybrid_mixers
from tpunet.models.latent_lm import LatentArch, rope
from tpunet.models.moe import RoutedShareMlp
from tpunet.serve import Engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = harness.load_module(
    os.path.join(REPO, "benchmark", "reference",
                 "command-a-plus-05-2026.py"),
    "reference_command_a_for_parallel_lm_test")

PUBLISHED_E, HELD = 8, (1, 2, 5, 6)
VOCAB, MAX_LEN, SEED, WINDOW, PT = 50, 128, 2000000011, 24, 8
CFG = dict(
    model_type="cohere2_moe", hidden_size=64, intermediate_size=32,
    layer_norm_eps=1e-5, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, rope_theta=50000, rotary_pct=1, sliding_window=WINDOW,
    logit_scale=0.5, num_experts_per_tok=2, num_shared_experts=2,
    num_hidden_layers=8,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
    num_experts=len(HELD), num_experts_published=PUBLISHED_E,
    held_experts=list(HELD), vocab_size=VOCAB, param_dtype="float32")
_NOT_ARCH = ("num_experts_published", "vocab_size", "param_dtype")


def arch_keys(cfg):
    """The configuration's keys as ``ModelConfig.latent`` takes them."""
    out = {k: v for k, v in cfg.items() if k not in _NOT_ARCH}
    out["layer_types"] = cfg["layer_types"][:cfg["num_hidden_layers"]]
    out.update(num_experts=cfg["num_experts_published"],
               first_k_dense_replace=0)
    return out


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
    lg = ref_logits(ref_params, np.concatenate([prompt, served]), cfg)
    at = len(prompt) - 1 + np.arange(len(served))
    return lg[at].max(-1) - lg[at, served]


# -- (a) the parts and the model, plain forward -------------------------------

def test_parameter_tree_is_the_reference_spec(tiny):
    """One norm a block, no head (tied), no router bias."""
    model, params, _ = tiny
    init = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(dict(init["params"])) == shapes(params)
    assert set(params["block00"]) == {"ln1", "attn", "moe"}
    assert "head" not in params and "router_bias" not in params["block00"][
        "moe"]


@pytest.mark.parametrize("part", ["sliding_attention", "full_attention",
                                  "experts", "model"])
def test_plain_forward_is_the_reference(tiny, part):
    """Rows of 61 tokens: 2.5 windows."""
    model, params, ref_params = tiny
    s = REF.sizes(CFG, "serve")
    arch = LatentArch.from_mapping(arch_keys(CFG))
    r = np.random.default_rng(3)
    u = jnp.asarray(r.normal(size=(2, 61, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        if part == "model":
            toks = np.stack([tokens_of(61, 1), tokens_of(61, 2)])
            got = np.asarray(model.apply({"params": params},
                                         jnp.asarray(toks)))
            want = [ref_logits(ref_params, row) for row in toks]
        elif part == "experts":
            p = params["block00"]["moe"]
            got = np.asarray(RoutedShareMlp(
                PUBLISHED_E, 32, 2, held=HELD, n_shared=2, router_bias=False,
                dtype=jnp.float32).apply({"params": p}, u))
            want = [np.asarray(REF.expert_layer(row, p, s, "float32"))
                    for row in u]
        else:
            block = "block00" if part == "sliding_attention" else "block03"
            p = params[block]["attn"]
            got = np.asarray(hybrid_mixers.GroupedQueryAttention(
                arch, part, dtype=jnp.float32).apply({"params": p}, u))
            want = [np.asarray(REF.attention(row, p, s, part, "float32"))
                    for row in u]
    for row in range(2):
        np.testing.assert_allclose(got[row], want[row], atol=1e-4)
    assert np.abs(want[0]).max() > 1e-3


def test_the_window_and_the_positions_are_seen(tiny):
    """What the comparisons above would not notice if they were blind:
    the reference with a longer window, and the reference with the two
    layer kinds swapped, are other models."""
    _, _, ref_params = tiny
    toks = tokens_of(61, 4)
    base = ref_logits(ref_params, toks)
    wider = ref_logits(ref_params, toks, dict(CFG, sliding_window=40))
    assert np.abs(wider[:WINDOW] - base[:WINDOW]).max() < 1e-5
    assert np.abs(wider[WINDOW:] - base[WINDOW:]).max() > 1e-3
    swapped = ref_logits(ref_params, toks, dict(CFG, layer_types=(
        ["full_attention"] + ["sliding_attention"] * 3) * 2))
    assert np.abs(swapped - base).max() > 1e-3


def test_interleaved_rotary_is_a_hand_written_pair_rotation():
    r = np.random.default_rng(0)
    x = r.normal(size=(2, 5, 3, 16))
    pos = np.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]])
    got = np.asarray(rope(jnp.asarray(x, jnp.float32), jnp.asarray(pos),
                          50000.0, interleaved=True), np.float64)
    want = np.zeros_like(x)
    for j in range(8):
        ang = pos * 50000.0 ** (-2 * j / 16)
        c, s_ = np.cos(ang)[..., None], np.sin(ang)[..., None]
        a, b = x[..., 2 * j], x[..., 2 * j + 1]
        want[..., 2 * j] = a * c - b * s_
        want[..., 2 * j + 1] = b * c + a * s_
    np.testing.assert_allclose(got, want, atol=1e-5)
    # and it is not the rotate-half layout
    half = np.asarray(rope(jnp.asarray(x, jnp.float32), jnp.asarray(pos),
                           50000.0))
    assert np.abs(half - want).max() > 0.1


# -- (b) prefill, then decode, through the engine's step and pool -------------

def new_engine(model, params, **kw):
    kw = {"slots": 3, "queue_max": 8, "prefill_buckets": (96,),
          "kv_page_tokens": PT, "emit_every_s": 0.0, **kw}
    return Engine(model, {"params": params}, ServeConfig(**kw))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _apply_as_the_masked_step(model, paged_kv, params, cache, toks,
                              positions, active, table):
    return model.apply(
        {"params": params, "cache": cache}, toks, decode=True,
        pos_offset=positions, decode_active=active, paged_kv=paged_kv,
        page_table=table, mutable=["cache"])


def logits_and_dispatch(eng, toks, positions, active, last_idx, slot_i=None):
    """One call of the engine's masked step on the engine's pool, and the
    same call kept as logits. Returns ``(logits, sampled)``."""
    table = (eng._page_table if slot_i is None
             else eng._page_table[slot_i:slot_i + 1]).copy()
    with jax.default_matmul_precision("highest"):
        logits, mutated = _apply_as_the_masked_step(
            eng.model, eng._paged_kv, eng.variables["params"], eng._cache,
            jnp.asarray(toks), jnp.asarray(positions, jnp.int32),
            jnp.asarray(active), jnp.asarray(table))
        eng._cache, sampled = eng._dispatch_step(
            toks, np.asarray(positions, np.int32), np.asarray(active),
            np.asarray(last_idx, np.int32), slot_i)
    for got, want in zip(jax.tree_util.tree_leaves(eng._cache),
                         jax.tree_util.tree_leaves(mutated["cache"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
    return np.asarray(logits), np.asarray(sampled)


@pytest.mark.parametrize("n", [5, 24, 61, 90])
def test_prefill_then_decode_through_the_engines_pool_is_the_reference(
        tiny, n):
    """An ``n``-token prompt (inside the window, the window, 2.5 and 3.75
    windows) through a ``[1, 96]`` row call into slot 1, then 10
    ``[3, 1]`` decode steps with slots 0 and 2 idle (so a 24-token
    prompt walks past its window on the way): every logit is the
    reference's over the ``n + 10`` tokens."""
    model, params, ref_params = tiny
    eng = new_engine(model, params)
    seq = tokens_of(n + 10, n)
    want = ref_logits(ref_params, seq)
    assert eng._alloc_pages_for(1, MAX_LEN) is not None
    toks = np.zeros((1, 96), np.int32)
    toks[0, :n] = seq[:n]
    toks[0, n:] = tokens_of(96 - n, 99)         # a padded tail that is not 0
    lg, sampled = logits_and_dispatch(eng, toks, [0], [True], [n - 1], 1)
    np.testing.assert_allclose(lg[0, :n], want[:n], atol=1e-4)
    assert sampled[0] == want[n - 1].argmax()
    for j in range(10):
        step = np.zeros((3, 1), np.int32)
        step[1, 0] = seq[n + j]
        lg, sampled = logits_and_dispatch(
            eng, step, [0, n + j, 0], [False, True, False], [0, 0, 0])
        np.testing.assert_allclose(lg[1, 0], want[n + j], atol=1e-4)
        assert sampled[1] == want[n + j].argmax()


def test_an_adopted_prefix_continues_the_row_past_its_window(tiny):
    """The prefix cache is on (nothing is kept per slot): the second
    request adopts 6 pages = 48 tokens = two windows, its prefill starts
    there (the pooled-keys form, a windowed block over its own stretch
    of keys), and both requests give the reference's best tokens; the
    engine's gauges say what the cache holds and what of it is dead."""
    model, params, ref_params = tiny
    eng = new_engine(model, params, prefill_buckets=(16, 96)).start()
    assert eng._prefix is not None and model.state_bytes_per_slot == 0
    try:
        shared = tokens_of(52, 5)
        prompts = [np.concatenate([shared, tokens_of(5, 6)]),
                   np.concatenate([shared, tokens_of(9, 8)])]
        reqs = []
        for p in prompts:
            reqs.append(eng.submit(p, max_new_tokens=12, temperature=0.0))
            reqs[-1].result(timeout=300.0)
    finally:
        eng.stop()
    snap = eng.registry.snapshot()
    assert snap["serve_prefix_hits_total"] >= 1
    assert snap["serve_prefill_tokens_total"] < sum(map(len, prompts))
    for prompt, req in zip(prompts, reqs):
        assert req.finish_reason == "length" and not req.error
        gap = served_gap(ref_params, prompt, np.asarray(req.tokens, np.int32))
        assert gap.max() < 1e-4, gap
    # K and V rows of 2 x 16 -> 128 lanes, float32, in 6 and 2 layers
    assert snap["serve_cache_bytes_per_token_kv_window"] == 6 * 2 * 128 * 4
    assert snap["serve_cache_bytes_per_token_kv"] == 2 * 2 * 128 * 4
    assert "serve_state_pool_bytes" not in snap
    assert (snap["serve_experts_held"], snap["serve_experts_total"]) == (4, 8)
    assert snap["serve_decode_attend_kernel"] == 0        # off the TPU
    # decode steps ran at 58..68 and 62..72 tokens held: of ceil(len / 8)
    # pages (len - 24) // 8 lie behind the window, in 6 of 8 layers
    dead = held = 0
    for n in (57, 61):
        for length in range(n + 1, n + 12):     # 11 steps: token 0 is
            dead += (length - WINDOW) // PT     # the prefill's
            held += -(-length // PT)
    assert snap["serve_cache_window_dead_pct"] == pytest.approx(
        100.0 * 0.75 * dead / held, abs=1e-3)


def test_windowed_share_of_a_tokens_cache_bytes():
    """What the engine reads from the mixers' statements: the longest
    window and the windowed layers' share of a token's paged bytes; a
    model whose every layer reads its whole row counts nothing."""
    from tpunet.serve.engine import _windowed_cache
    full = {"paged": {"kv": (256, jnp.float32)}, "state": {}}
    latent = {"paged": {"latent": (640, jnp.bfloat16),
                        "index": (128, jnp.float32)}, "state": {}}
    slide = {"paged": {"kv_window": (256, jnp.float32)}, "window": 24}
    assert _windowed_cache([]) == (0, 0.0)
    assert _windowed_cache([full, latent, dict(full, window=None)]) \
        == (0, 0.0)
    assert _windowed_cache([slide, slide, dict(slide, window=40), full]) \
        == (40, 0.75)


# -- (c) the expert layer's share ----------------------------------------------

def _moe_params(seed=9, n_shared=4):
    r = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(0.3 * r.normal(size=s), jnp.float32)  # noqa: E731
    fs = n_shared * 32
    return {"router": n(64, PUBLISHED_E),
            "experts_gate": n(PUBLISHED_E, 64, 32),
            "experts_up": n(PUBLISHED_E, 64, 32),
            "experts_down": n(PUBLISHED_E, 32, 64), "shared_gate": n(64, fs),
            "shared_up": n(64, fs), "shared_down": n(fs, 64)}


def _share_of(p, held):
    take = jnp.asarray(held)
    return {k: (v[take] if k.startswith("experts_") else v)
            for k, v in p.items()}


@pytest.mark.parametrize("shares", [((0, 1, 2, 3), (4, 5, 6, 7)),
                                    ((0, 1), (2, 3), (4, 5), (6, 7))])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The routed parts of all shares + the averaged shared experts
    counted once = the uncut reference layer."""
    p = _moe_params()
    s = dict(REF.sizes(CFG, "serve"), num_shared_experts=4)
    u = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(REF.expert_layer(
            u, p, s, "float32", held=list(range(PUBLISHED_E))))
        shared = np.asarray(REF.shared_part(u, p, s, "float32"))
        total = shared.copy()             # what every chip computes: once
        for held in shares:
            part = np.asarray(RoutedShareMlp(
                PUBLISHED_E, 32, 2, held=held, n_shared=4, router_bias=False,
                dtype=jnp.float32).apply({"params": _share_of(p, held)}, u))
            np.testing.assert_allclose(part, np.asarray(REF.expert_layer(
                u, _share_of(p, held), s, "float32", held=list(held))),
                atol=2e-5)
            total += part - shared
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert np.abs(whole - shared).max() > 0.01     # the experts matter


def test_the_fused_shared_product_is_the_mean_of_the_experts():
    """One gated product ``4 x 32`` wide times 1/4 = the four experts
    computed apart and averaged (the layer's output less its routed
    part, which the reference computes)."""
    from tpunet.models.moe import gated_silu
    p = _moe_params(seed=4)
    u = jnp.asarray(np.random.default_rng(2).normal(size=(17, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        apart = [np.asarray(gated_silu(
            u, p["shared_gate"][:, j * 32:(j + 1) * 32],
            p["shared_up"][:, j * 32:(j + 1) * 32],
            p["shared_down"][j * 32:(j + 1) * 32], jnp.float32))
            for j in range(4)]
        share = _share_of(p, [3])
        got = np.asarray(RoutedShareMlp(
            PUBLISHED_E, 32, 2, held=(3,), n_shared=4, router_bias=False,
            dtype=jnp.float32).apply({"params": share}, u)) \
            - np.asarray(REF.routed_part(
                u, share, REF.sizes(CFG, "serve"), "float32", held=[3]))
    np.testing.assert_allclose(got, np.mean(apart, axis=0), atol=2e-5)
    assert np.abs(apart[0] - apart[1]).max() > 0.01


def test_sigmoid_routing_without_a_bias_has_no_bias_leaf():
    layer = RoutedShareMlp(PUBLISHED_E, 32, 2, router_bias=False,
                           dtype=jnp.float32)
    u = jnp.zeros((3, 64), jnp.float32)
    leaves = layer.init(jax.random.PRNGKey(0), u)["params"]
    assert "router_bias" not in leaves
    assert "router_bias" in RoutedShareMlp(
        PUBLISHED_E, 32, 2, dtype=jnp.float32).init(
            jax.random.PRNGKey(0), u)["params"]


# -- (d) the published widths and what is not built ----------------------------

def test_published_widths_count_the_cut(tmp_path):
    """``jax.eval_shape`` of the model the benchmark's configuration
    builds: 4,733,292,544 parameters, every leaf bfloat16."""
    config = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "command-a-plus-05-2026.json")))
    model = create_model(ModelConfig(**config["program"]["model"]))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32)))["params"]
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(np.prod(x.shape)) for x in leaves) == 4_733_292_544
    assert {x.dtype for x in leaves} == {jnp.dtype("bfloat16")}
    spec = REF.param_spec(config, "serve")
    assert {k: v.shape for k, v in weights.flatten(shapes).items()} \
        == {k: tuple(shape) for k, (shape, _, _) in spec.items()}
    # per token: K and V of 8 x 128 in bfloat16, 3 sliding + 1 full layer
    gauges = model.serve_gauges()
    assert gauges["serve_cache_bytes_per_token_kv_window"] == 12_288
    assert gauges["serve_cache_bytes_per_token_kv"] == 4_096
    assert [s["window"] for s in model.cache_specs()] == [4096] * 3 + [None]
    assert all(s["decode_kernel"] for s in model.cache_specs())


@pytest.mark.parametrize("extra,match", [
    ({"q_lora_rank": 1024}, "does not read"),
    ({"kv_lora_rank": 512}, "does not read"),
    ({"linear_num_key_heads": 16}, "does not read"),
    ({"partial_rotary_factor": 0.5}, "does not read"),
    ({"rms_norm_eps": 1e-6}, "does not read"),
    ({"no_such_key": 1}, "does not read"),
    ({"use_parallel_block": False}, "built with"),
    ({"use_qk_norm": True}, "built with"),
    ({"position_embedding_type": "rope"}, "built with"),
    ({"shared_expert_combination_strategy": "sum"}, "built with"),
    ({"first_k_dense_replace": 1}, "prefix dense"),
    ({"sliding_window": None}, "sliding_window"),
    ({"layer_types": ["linear_attention"] * 8}, "layer_types"),
])
def test_what_the_family_does_not_read_or_build_says_so(extra, match):
    with pytest.raises(ValueError, match=match):
        LatentArch.from_mapping(dict(arch_keys(CFG), **extra))


@pytest.mark.parametrize("key", ["sliding_window", "num_shared_experts",
                                 "logit_scale", "rotary_pct",
                                 "layer_norm_eps"])
def test_the_other_families_do_not_read_this_ones_keys(key):
    latent = dict(hidden_size=64, num_hidden_layers=1,
                  layer_types=["full_attention"], intermediate_size=128)
    LatentArch.from_mapping(latent)
    with pytest.raises(ValueError, match="only cohere2_moe"):
        LatentArch.from_mapping(dict(latent, **{key: 2}))


def test_the_published_switches_are_taken_at_what_is_built(tiny):
    arch = LatentArch.from_mapping(dict(
        arch_keys(CFG), use_parallel_block=True, use_qk_norm=False,
        position_embedding_type="rope_gptj", tie_word_embeddings=True,
        expert_selection_fn="sigmoid", norm_topk_prob=True,
        shared_expert_combination_strategy="average",
        use_gated_activation=True, hidden_act="silu", attention_bias=False))
    assert arch == LatentArch.from_mapping(arch_keys(CFG))
    assert arch.moe_intermediate_size == 32 and arch.parallel
    model, params, _ = tiny
    with pytest.raises(ValueError, match="trains without|no backward|train"):
        model.apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                    train=True, decode=True)
