"""The latent-attention decoder (``latent_lm``) against its plain
reference (``benchmark/reference/dots3-note-prev.py``) at a tiny size on
the CPU: all four layer kinds (dense + full, expert + full, expert +
sliding, and a full layer whose sequence outgrows ``index_topk``),
``index_topk`` and the window both shorter than the sequence.

Plain forward = reference logits; prefill then absorbed decode through
the page pool = reference full forward (unequal lengths, an idle slot, a
prefill that starts after cached pages); the same through ``Engine``
with a shared prefix and the prefix cache on; the share test (the parts
every share gives, the shared expert counted once, add up to the uncut
layer); every token on one expert loses none; the vocabulary slice; the
gauges and the routing load.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chip_smoke
from benchmark import harness, weights
from tpunet.config import ModelConfig, ServeConfig
from tpunet.models import create_model, latent_lm
from tpunet.models.moe import RoutedShareMlp
from tpunet.models.vit import PagedKV
from tpunet.serve import Engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = harness.load_module(
    os.path.join(REPO, "benchmark", "reference", "dots3-note-prev.py"),
    "reference_dots3_for_latent_lm_test")

PUBLISHED_E, HELD = 8, (2, 3, 4, 5)
VOCAB, MAX_LEN, SEED = 50, 48, 2000000011
CFG = dict(
    chip_smoke.LATENT_TINY, num_hidden_layers=5,
    layer_types=["full_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention", "full_attention"],
    first_k_dense_replace=1, rms_norm_eps=1e-5, rope_theta=8e7,
    swa_rope_theta=5e4, apply_mla_qkv_lora_rescale=True,
    n_routed_experts=len(HELD), n_routed_experts_published=PUBLISHED_E,
    routed_scaling_factor=1.0, held_experts=list(HELD), vocab_size=VOCAB,
    param_dtype="float32")
_NOT_ARCH = ("n_routed_experts_published", "vocab_size", "param_dtype")


def arch_keys(cfg):
    """The configuration's keys as ``ModelConfig.latent`` takes them."""
    out = {k: v for k, v in cfg.items() if k not in _NOT_ARCH}
    out["layer_types"] = cfg["layer_types"][:cfg["num_hidden_layers"]]
    out["n_routed_experts"] = cfg["n_routed_experts_published"]
    return out


@pytest.fixture(scope="module")
def tiny():
    model = create_model(ModelConfig(
        name="latent_lm", vocab_size=VOCAB, max_seq_len=MAX_LEN,
        dtype="float32", param_dtype="float32", latent=arch_keys(CFG)))
    params = weights.make_tree(REF.param_spec(CFG, "serve"), SEED)
    return model, params, REF.make_params(CFG, "serve", SEED)


def ref_logits(ref_params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.logits_fn(ref_params, jnp.asarray(tokens),
                                        REF.sizes(CFG, "serve"), "float32"))


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def test_parameter_tree_is_the_reference_spec(tiny):
    model, params, _ = tiny
    init = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(dict(init["params"])) == shapes(params)


def test_plain_forward_is_the_reference(tiny):
    model, params, ref_params = tiny
    toks = np.stack([tokens_of(24, 1), tokens_of(24, 2)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(toks)))
    assert got.shape == (2, 24, VOCAB)            # logits over the slice
    for row in range(2):
        np.testing.assert_allclose(got[row], ref_logits(ref_params, toks[row]),
                                   atol=2e-5)


def test_paged_prefill_then_absorbed_decode_is_the_reference(tiny):
    """Row 0: a 13-token prompt in a 16-wide prefill. Row 1: idle. Row
    2: 8 tokens prefilled, then 10 more in a second call that starts
    after the cached pages. Then 6 absorbed decode steps of rows 0 and
    2 at their own positions; every logit against the reference's full
    forward over the same tokens."""
    model, params, ref_params = tiny
    pt, per_row = 4, MAX_LEN // 4
    paged = PagedKV(pages=3 * per_row + 1, page_tokens=pt)
    table = np.zeros((3, per_row), np.int32)
    table[0] = 1 + np.arange(per_row)
    table[2] = 1 + per_row + np.arange(per_row)
    cache = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((3, MAX_LEN), jnp.int32),
        decode=True, paged_kv=paged, page_table=jnp.asarray(table)))["cache"]
    cache = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                   cache)

    @jax.jit
    def step(cache, toks, pos, active):
        return model.apply({"params": params, "cache": cache}, toks,
                           decode=True, pos_offset=pos, decode_active=active,
                           paged_kv=paged, page_table=jnp.asarray(table),
                           mutable=["cache"])

    seq0, seq2 = tokens_of(19, 3), tokens_of(24, 4)
    want0, want2 = ref_logits(ref_params, seq0), ref_logits(ref_params, seq2)

    def call(rows, pos, active):
        width = max(len(r) for r in rows)
        toks = np.zeros((3, width), np.int32)
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
        with jax.default_matmul_precision("highest"):
            lg, mut = step(cache, jnp.asarray(toks),
                           jnp.asarray(pos, jnp.int32), jnp.asarray(active))
        return np.asarray(lg), mut["cache"]

    pad16 = lambda r: np.concatenate([r, np.zeros(16 - len(r), np.int32)])  # noqa: E731
    lg, cache = call([pad16(seq0[:13]), pad16([]), pad16(seq2[:8])],
                     [0, 0, 0], [True, False, True])
    np.testing.assert_allclose(lg[0, :13], want0[:13], atol=2e-5)
    np.testing.assert_allclose(lg[2, :8], want2[:8], atol=2e-5)
    lg, cache = call([pad16([]), pad16([]), pad16(seq2[8:18])],
                     [0, 0, 8], [False, False, True])
    np.testing.assert_allclose(lg[2, :10], want2[8:18], atol=2e-5)
    for j in range(6):
        lg, cache = call([seq0[13 + j:14 + j], [0], seq2[18 + j:19 + j]],
                         [13 + j, 0, 18 + j], [True, False, True])
        np.testing.assert_allclose(lg[0, 0], want0[13 + j], atol=2e-5)
        np.testing.assert_allclose(lg[2, 0], want2[18 + j], atol=2e-5)


def test_engine_serves_the_references_best_tokens(tiny):
    """Three slots, unequal prompts, two of them behind one shared
    prefix with the prefix cache on: every served token's reference
    logit is the position's best (to rounding), ids stay inside the
    vocabulary slice."""
    model, params, ref_params = tiny
    engine = Engine(model, {"params": params}, ServeConfig(
        slots=3, queue_max=8, prefill_buckets=(8, 24), kv_page_tokens=4,
        emit_every_s=0.0)).start()
    try:
        shared = tokens_of(12, 5)
        prompts = [np.concatenate([shared, tokens_of(5, 6)]),
                   tokens_of(7, 7),
                   np.concatenate([shared, tokens_of(9, 8)])]
        first = engine.submit(prompts[0], max_new_tokens=9, temperature=0.0)
        first.result(timeout=300.0)
        rest = [engine.submit(p, max_new_tokens=n, temperature=0.0)
                for p, n in zip(prompts[1:], (12, 7))]
        for r in rest:
            r.result(timeout=300.0)
        snap = engine.registry.snapshot()
    finally:
        engine.stop()
    assert snap["serve_prefix_hits_total"] >= 1
    assert snap["serve_cache_bytes_per_token_latent"] == 2 * 128 * 4
    assert snap["serve_cache_bytes_per_token_index"] == 2 * 128 * 4
    assert snap["serve_cache_bytes_per_token_window"] == 3 * 128 * 4
    assert (snap["serve_experts_held"], snap["serve_experts_total"]) == (4, 8)
    assert "serve_moe_chunk_rows" not in snap    # no reader: PR 37
    assert snap["serve_decode_attend_kernel"] == 0
    for prompt, req in zip(prompts, [first] + rest):
        assert req.finish_reason == "length" and not req.error
        served = np.asarray(req.tokens, np.int32)
        assert served.max() < VOCAB
        lg = ref_logits(ref_params, np.concatenate([prompt, served]))
        at = len(prompt) - 1 + np.arange(len(served))
        gap = lg[at].max(-1) - lg[at, served]
        assert gap.max() < 1e-4, gap


@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
def test_engine_one_step_ahead_serves_the_sequential_loops_tokens(tiny,
                                                                  sampling):
    """The second decoder behind the same loop: with step N+1 dispatched
    before step N's tokens are read, every request's tokens are bit-equal
    to a loop that reads each step before it builds the next
    (tests/_serve_script.py) — unequal lengths, admitted at different
    iterations, greedy and seeded."""
    from _serve_script import (SAMPLING, drive, sequential_tokens,
                               staggered_script)
    model, params, _ = tiny

    def build():
        return Engine(model, {"params": params}, ServeConfig(
            slots=3, queue_max=8, prefill_buckets=(8, 24),
            kv_page_tokens=4, prefix_cache=False, emit_every_s=0.0))

    script = staggered_script(SAMPLING[sampling], VOCAB)
    want = sequential_tokens(build(), script)
    engine = build()
    reqs = drive(engine, script)
    assert [r.tokens for r in reqs] == want
    assert [len(t) for t in want] == [9, 4, 12, 6]
    snap = engine.registry.snapshot()
    assert snap["serve_decode_steps_overlapped_total"] \
        >= snap["serve_decode_steps_total"] - 4 - 1
    assert snap.get("serve_decode_rows_discarded_total", 0) == 0


# -- the expert layer's share --------------------------------------------------

def _layer(held):
    return RoutedShareMlp(PUBLISHED_E, 32, 2, held=held, dtype=jnp.float32)


def _moe_params(seed=9):
    r = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(0.3 * r.normal(size=s), jnp.float32)  # noqa: E731
    return {"router": n(64, PUBLISHED_E), "router_bias": n(PUBLISHED_E),
            "experts_gate": n(PUBLISHED_E, 64, 32),
            "experts_up": n(PUBLISHED_E, 64, 32),
            "experts_down": n(PUBLISHED_E, 32, 64), "shared_gate": n(64, 32),
            "shared_up": n(64, 32), "shared_down": n(32, 64)}


def _share_of(p, held):
    take = jnp.asarray(held)
    return {k: (v[take] if k.startswith("experts_") else v)
            for k, v in p.items()}


def _ref_layer(u, p, held):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.expert_layer(
            u, p, REF.sizes(CFG, "serve"), "float32", held=list(held)))


def test_the_shares_add_up_to_the_uncut_layer():
    p = _moe_params()
    u = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    everyone = tuple(range(PUBLISHED_E))
    whole = _ref_layer(u, p, everyone)
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(REF._gated(u, p["shared_gate"], p["shared_up"],
                                       p["shared_down"], "float32"))
        total = shared.copy()             # what every chip computes: once
        for held in ((0, 1), (2, 3), (4, 5), (6, 7)):
            part = np.asarray(_layer(held).apply(
                {"params": _share_of(p, held)}, u))
            np.testing.assert_allclose(
                part, _ref_layer(u, _share_of(p, held), held), atol=2e-5)
            total += part - shared
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert np.abs(whole - shared).max() > 0.01     # the experts matter


def test_every_token_on_one_expert_loses_none():
    """No capacity: a bias that sends every token to experts 4 and 5
    gives those experts every token, in a flat call and row by row with
    an idle row skipped."""
    p = _moe_params()
    p["router_bias"] = jnp.zeros(PUBLISHED_E).at[jnp.asarray([4, 5])].set(50.0)
    u = jnp.asarray(np.random.default_rng(2).normal(size=(3, 16, 64)),
                    jnp.float32)
    share = _share_of(p, HELD)
    with jax.default_matmul_precision("highest"):
        flat, state = _layer(HELD).apply({"params": share},
                                         u.reshape(48, 64), mutable=["stats"])
        rows = _layer(HELD).apply({"params": share}, u,
                                  jnp.asarray([True, False, True]))
    want = _ref_layer(u.reshape(48, 64), share, HELD)
    np.testing.assert_allclose(np.asarray(flat), want, atol=2e-5)
    rows = np.asarray(rows)
    np.testing.assert_allclose(rows[[0, 2]], want.reshape(3, 16, 64)[[0, 2]],
                               atol=2e-5)
    assert not rows[1].any()
    load = state["stats"]["routing"][0]
    assert float(load["held_pair_share"]) == 1.0
    assert float(load["held_load_max_over_mean"]) == 2.0   # 2 of 4 held


def test_routing_load_costs_nothing_unless_asked(tiny):
    model, params, _ = tiny
    toks = jnp.asarray(tokens_of(8, 3))[None]
    out = model.apply({"params": params}, toks)
    assert isinstance(out, jax.Array)               # no collection came back
    _, state = model.apply({"params": params}, toks, mutable=["stats"])
    share = state["stats"]["block01"]["moe"]["routing"][0]["held_pair_share"]
    assert 0.0 <= float(share[0]) <= 1.0


@pytest.mark.parametrize("k", [1, 3, 7])
def test_top_k_mask_takes_what_top_k_takes(k):
    r = np.random.default_rng(k)
    x = r.normal(size=(5, 12)).astype(np.float32)
    x[0, [2, 5, 9]] = 0.25                          # equals at the cut
    x[1, 4:] = -np.inf                              # fewer than k left
    x[2] = np.round(x[2])                           # many equals
    got = np.asarray(latent_lm.top_k_mask(jnp.asarray(x), k))
    _, idx = jax.lax.top_k(jnp.asarray(x), k)
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    assert (got == want).all()


def test_what_is_not_built_says_so(tiny):
    model, params, _ = tiny
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="pages"):
        model.apply({"params": params}, toks, decode=True, mutable=["cache"])
    with pytest.raises(ValueError, match="packed"):
        model.apply({"params": params}, toks, segment_ids=toks)
    # an indexer's selection and sliding layers have no backward
    with pytest.raises(ValueError, match="without an indexer"):
        model.apply({"params": params}, toks, train=True)
    with pytest.raises(ValueError, match="unknown keys"):
        latent_lm.LatentArch.from_mapping({"hidden": 4})
