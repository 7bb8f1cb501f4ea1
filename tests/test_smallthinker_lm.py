"""The fourth family under ``latent_lm`` (``model_type``
``smallthinker``: the router reads the block's input before attention,
grouped-query attention with rotate-half rotary window layers beside a
position-free global one, softmax top-k over ReGLU experts with no
shared one) against its plain reference
(``benchmark/reference/smallthinker-21ba3b.py``) at a tiny size on the
CPU: one period G S S S, a window of 12 keys under rows of 32 tokens, 6
query heads over 2 KV heads, 4 of 8 experts held, top-3.

Forward logits, the loss and every gradient leaf in float32, with the
flash kernels' bodies interpreted; in bfloat16 within a tolerance the
float8 control fails; the four shares of an expert layer add up to the
uncut reference layer; the router reads the block's input (``ln1``'s
weight moves no choice) and is trained; a prefill then decode steps
through the ``Engine``'s pool give the reference's logits.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.reference import _numerics as N
from tpunet.config import ModelConfig
from tpunet.models import create_model
from tpunet.models.latent_lm import LatentArch
from tpunet.models.moe import RoutedShareMlp, router_logits

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))
sys.path.insert(0, os.path.join(REPO, "tests"))
import bench_tiny_smallthinker as tiny  # noqa: E402
from test_parallel_gqa_lm import logits_and_dispatch, new_engine  # noqa: E402

REF = harness.load_module(
    os.path.join(REPO, "benchmark", "reference", "smallthinker-21ba3b.py"),
    "reference_smallthinker_for_lm_test")
CONFIG = tiny.shrink(harness.load_json(
    "benchmark", "configs", "smallthinker-21ba3b.json", root=REPO))
SIZES = REF.sizes(CONFIG, "train")
SEED, BATCH, SEQ = 2000000011, 2, 32


def model_of(dtype="float32", remat=True):
    return create_model(ModelConfig(**{**CONFIG["program"]["model"],
                                       "dtype": dtype, "remat": remat}))


def tokens_of(rows, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, tiny.VOCAB, (rows, n)).astype(np.int32)


@pytest.fixture(scope="module")
def seeded():
    spec = REF.param_spec(CONFIG, "train")
    return weights.make_tree(spec, SEED), REF.make_params(CONFIG, "train",
                                                          SEED)


def next_token_loss(model, params, toks):
    logits = model.apply({"params": params}, toks, train=True)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.mean(jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1))


@pytest.fixture(scope="module")
def reference_step(seeded):
    _, ref_params = seeded
    toks = jnp.asarray(tokens_of(BATCH, SEQ, 3))
    with jax.default_matmul_precision("highest"):
        out = {}
        for precision in N.PRECISIONS:
            loss, grads = REF.loss_and_grads_fn(CONFIG, "train", precision)(
                ref_params, toks, None, None)
            out[precision] = (float(loss), {p: np.asarray(g)
                                            for p, g in grads.items()})
    return toks, out


# -- (i) forward, loss and every gradient leaf ----------------------------------

def test_parameter_tree_is_the_reference_spec(seeded):
    """The router is the block's; the expert layer has the held experts
    and nothing shared."""
    params, _ = seeded
    init = model_of().init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(dict(init["params"])) == shapes(params)
    assert set(params["block01"]) == {"ln1", "ln2", "router", "attn", "moe"}
    assert set(params["block01"]["moe"]) == {"experts_gate", "experts_up",
                                             "experts_down"}
    assert set(params["block01"]["attn"]) == {"q_proj", "k_proj", "v_proj",
                                              "o_proj"}
    assert params["block00"]["attn"]["k_proj"].shape == (48, 2 * 16)


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "recomputed"])
def test_logits_are_the_references(seeded, remat):
    params, ref_params = seeded
    model = model_of(remat=remat)
    toks = tokens_of(BATCH, SEQ, 1)
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, jnp.asarray(toks), train=True)
        plain = model.apply({"params": params}, jnp.asarray(toks))
        for row in range(BATCH):
            want = REF.logits_fn(ref_params, jnp.asarray(toks[row]), SIZES,
                                 "float32")
            np.testing.assert_allclose(got[row], want, atol=1e-5)
    # the row-at-a-time forward (serving's, evaluation's) is the same model
    np.testing.assert_allclose(plain, got, atol=1e-5)
    assert got.shape == (BATCH, SEQ, tiny.VOCAB)


@pytest.mark.parametrize("kernels", ["dense", "interpreted"])
def test_loss_and_every_gradient_leaf_are_the_references(
        seeded, reference_step, kernels, monkeypatch):
    """Off the TPU the mixer takes ``grouped_window_attention``; with the
    kernels' bodies interpreted (blocks of 8: a band of 3 under the
    12-key window) the same numbers come through the flash forward with
    its log-sum-exp, dQ on the band and dK/dV by KV head."""
    params, _ = seeded
    toks, ref = reference_step
    want_loss, want = ref["float32"]
    if kernels == "interpreted":
        from tpunet.ops import flash
        real = flash.flash_prefill
        monkeypatch.setattr(flash, "flash_prefill", lambda *a, **k: real(
            *a, **{**k, "block": 8, "interpret": True}))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: next_token_loss(model_of(), p, toks))(params)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    grads = weights.flatten(grads)
    assert set(grads) == set(want)
    for path, g in grads.items():
        np.testing.assert_allclose(np.asarray(g), want[path], atol=1e-5,
                                   err_msg=path)
        assert np.abs(want[path]).max() > 0, path


def test_every_token_on_the_same_experts_is_still_the_references(
        seeded, monkeypatch):
    """The regime of the cell's first chip readings (PERF.md section 6,
    PR 42): a common component fifty times the token-specific one in the
    embedding rows, so the raw-stream router puts every token of a layer
    on the same three experts and one held expert's pairs fill chunk
    after chunk of the walk. In float32 the loss and every gradient leaf
    are still the reference's: the skewed path has no fault of its own."""
    from tpunet.models import moe
    monkeypatch.setattr(moe, "PAIR_CHUNK", 16)
    params, ref_params = seeded
    common = np.random.default_rng(11).normal(size=48).astype(np.float32)
    rows = 0.02 * params["embed"]["embedding"] + common
    params = {**params, "embed": {"embedding": rows}}
    ref_params = {**ref_params, "embed/embedding": rows}
    toks = jnp.asarray(tokens_of(BATCH, SEQ, 7))
    with jax.default_matmul_precision("highest"):
        want_loss, want = REF.loss_and_grads_fn(CONFIG, "train", "float32")(
            ref_params, toks, None, None)
        loss, grads = jax.value_and_grad(
            lambda p: next_token_loss(model_of(), p, toks))(params)
        _, idx = jax.lax.top_k(router_logits(
            rows[toks.reshape(-1)], params["block00"]["router"]), 3)
    load = np.bincount(np.asarray(idx).ravel(), minlength=8)[tiny.HELD]
    assert load.max() == BATCH * SEQ > 3 * 16, load      # every token, 4 chunks
    assert load.max() / load.mean() >= 2, load
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    grads = weights.flatten(grads)
    for path, g in grads.items():
        np.testing.assert_allclose(np.asarray(g), np.asarray(want[path]),
                                   rtol=1e-5, atol=1e-5, err_msg=path)


def test_bfloat16_holds_a_tolerance_the_float8_control_fails(
        seeded, reference_step):
    """The cell's comparison at the tiny size: per-leaf gradient norms of
    the bfloat16 program against the float32 reference's, by the worst
    leaf. bfloat16 rounds 8 bits, float8 3-4 (read here: 0.0011 and
    0.033): the limit stands more than 3x over the first and more than
    3x under the second."""
    params, _ = seeded
    toks, ref = reference_step
    _, want = ref["float32"]
    _, low = ref["fp8"]
    loss, grads = jax.value_and_grad(
        lambda p: next_token_loss(model_of("bfloat16"), p, toks))(params)
    norms = lambda g: {p: float(np.linalg.norm(np.asarray(  # noqa: E731
        v, np.float32))) for p, v in g.items()}
    got_gap, where = N.worst_leaf_gap(norms(weights.flatten(grads)),
                                      norms(want))
    low_gap, _ = N.worst_leaf_gap(norms(low), norms(want))
    tolerance = 0.006
    assert got_gap < tolerance / 3, where
    assert low_gap > 3 * tolerance
    assert float(loss) == pytest.approx(ref["float32"][0], rel=2e-3)


# -- (ii) the share ties to the model -------------------------------------------

def _layer_params(seed=9):
    r = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(0.3 * r.normal(size=s), jnp.float32)  # noqa: E731
    e = tiny.PUBLISHED_E
    return {"router": n(48, e), "experts_gate": n(e, 48, 24),
            "experts_up": n(e, 48, 24), "experts_down": n(e, 24, 48)}


def _share_of(p, held):
    take = jnp.asarray(held)
    return {k: v[take] for k, v in p.items() if k.startswith("experts_")}


@pytest.mark.parametrize("shares", [((0, 1), (2, 3), (4, 5), (6, 7)),
                                    ((0, 3, 5, 6), (1, 2, 4, 7))])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The expert layer's outputs over all shares = the uncut reference
    layer: there is no shared expert to count once. The router's logits
    come from ANOTHER input than the experts read."""
    p = _layer_params()
    r = np.random.default_rng(1)
    x, n = (jnp.asarray(r.normal(size=(40, 48)), jnp.float32)
            for _ in range(2))
    with jax.default_matmul_precision("highest"):
        logits = router_logits(x, p["router"])
        whole = np.asarray(REF.expert_layer(
            n, logits, p, SIZES, "float32",
            held=list(range(tiny.PUBLISHED_E))))
        total = np.zeros_like(whole)
        for held in shares:
            part = np.asarray(RoutedShareMlp(
                tiny.PUBLISHED_E, 24, 3, held=held, scoring="softmax",
                n_shared=0, act="relu", dtype=jnp.float32).apply(
                    {"params": _share_of(p, held)}, n, None, logits))
            np.testing.assert_allclose(part, np.asarray(REF.expert_layer(
                n, logits, _share_of(p, held), SIZES, "float32",
                held=list(held))), atol=2e-5)
            total += part
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert np.abs(whole).max() > 0.01


def test_an_expert_layer_without_shared_experts_has_no_shared_leaves():
    u = jnp.zeros((4, 48), jnp.float32)
    own = RoutedShareMlp(8, 24, 3, scoring="softmax", n_shared=0,
                         act="relu").init(jax.random.PRNGKey(0), u)
    assert set(own["params"]) == {"router", "experts_gate", "experts_up",
                                  "experts_down"}
    handed = RoutedShareMlp(8, 24, 3, scoring="softmax", n_shared=0).init(
        jax.random.PRNGKey(0), u, None, jnp.zeros((4, 8)))
    assert set(handed["params"]) == {"experts_gate", "experts_up",
                                     "experts_down"}
    with pytest.raises(ValueError, match="route by softmax"):
        RoutedShareMlp(8, 24, 3, n_shared=0).init(
            jax.random.PRNGKey(0), u, None, jnp.zeros((4, 8)))


def test_relu_gates_the_experts_forward_and_coming_back(monkeypatch):
    """ReGLU through the chunked walk and its recomputing backward: the
    same numbers, and the same gradients, as one call over all pairs."""
    from tpunet.models import moe
    p = _layer_params(4)
    r = np.random.default_rng(2)
    u = jnp.asarray(r.normal(size=(40, 48)), jnp.float32)
    g = jnp.asarray(r.normal(size=(40, 48)), jnp.float32)
    held = (1, 2, 6)

    def run(act):
        def f(u_, *ws):
            y, _ = moe.routed_share(u_, p["router"], None, *ws, held,
                                    top_k=3, dtype=jnp.float32, act=act)
            return jnp.sum(y * g)
        ws = [p[k][jnp.asarray(held)] for k in
              ("experts_gate", "experts_up", "experts_down")]
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3))(u, *ws)

    with jax.default_matmul_precision("highest"):
        whole = run("relu")
        silu = run("silu")
        monkeypatch.setattr(moe, "PAIR_CHUNK", 16)       # 120 pairs: 8 chunks
        chunked = run("relu")
    assert abs(float(whole[0]) - float(silu[0])) > 1e-3
    np.testing.assert_allclose(chunked[0], whole[0], rtol=1e-5)
    for a, b in zip(chunked[1], whole[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_a_collapsed_router_at_the_cells_pair_count_is_the_references():
    """The cell's 8,192 tokens x top-6 of 64 = 49,152 pairs under the
    real ``PAIR_CHUNK``, every token on the same six experts, five of
    them among the 16 held: 40,960 held pairs walk five full chunks,
    8,192 rows on each of five experts and none on eleven (load 3.2x
    the mean). In float32 the layer and every gradient are the plain
    loop's: the walk has no fault that waits for a skewed load."""
    from tpunet.models import moe
    t, c, f, e, k = 8192, 32, 16, 64, 6
    held = tuple(range(16))
    r = np.random.default_rng(21)
    n = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)  # noqa: E731
    u, g = n(t, c), n(t, c)
    common = np.zeros(e, np.float32)
    common[[0, 3, 7, 9, 12, 40]] = [2.0, 1.8, 1.6, 1.4, 1.2, 1.0]
    logits = jnp.asarray(common) + 0.05 * n(t, e)
    p = {"experts_gate": 0.3 * n(16, c, f), "experts_up": 0.3 * n(16, c, f),
         "experts_down": 0.3 * n(16, f, c)}
    sizes = {"moe_num_active_primary_experts": k, "held_experts": list(held)}
    assert t * k > 4 * moe.PAIR_CHUNK

    def ours(u_, logits_, p_):
        y, stats = moe.routed_share(
            u_, None, None, p_["experts_gate"], p_["experts_up"],
            p_["experts_down"], held, top_k=k, dtype=jnp.float32,
            logits=logits_, act="relu")
        return jnp.sum(y * g), stats

    def plain(u_, logits_, p_):
        return jnp.sum(REF.expert_layer(u_, logits_, p_, sizes, "float32")
                       * g)

    with jax.default_matmul_precision("highest"):
        (got, stats), got_g = jax.value_and_grad(
            ours, argnums=(0, 1, 2), has_aux=True)(u, logits, p)
        want, want_g = jax.value_and_grad(plain, argnums=(0, 1, 2))(
            u, logits, p)
    assert float(stats["held_pair_share"]) == pytest.approx(5 / 6)
    assert float(stats["held_load_max_over_mean"]) == pytest.approx(3.2)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        scale = float(jnp.abs(b).max())
        assert scale > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * scale)


# -- (iii) the router reads the block's input -----------------------------------

def test_the_router_reads_the_blocks_input_and_is_trained(
        seeded, monkeypatch):
    """``ln1``'s weight stands between the block's input and attention,
    not between it and the router: another weight there moves no choice
    (another router does), and every block's router has a gradient."""
    from tpunet.models import moe
    from tpunet.models.latent_lm import LatentBlock

    params, _ = seeded
    arch = LatentArch.from_mapping(CONFIG["program"]["model"]["latent"])
    layer = LatentBlock(arch, "sliding_attention", dense=False,
                        dtype=jnp.float32)
    r = np.random.default_rng(5)
    x = jnp.asarray(r.normal(size=(1, SEQ, 48)), jnp.float32)
    p = params["block01"]
    seen = []
    real = moe.route_softmax

    def recording(*a, **k):
        idx, weight = real(*a, **k)
        seen.append(np.sort(np.asarray(idx), axis=-1))
        return idx, weight

    monkeypatch.setattr(moe, "route_softmax", recording)
    other_norm = {**p, "ln1": jnp.asarray(r.uniform(0.2, 5.0, 48),
                                          jnp.float32)}
    other_router = {**p, "router": p["router"][:, ::-1]}
    with jax.default_matmul_precision("highest"):
        # (a training call: every token at once, nothing traced)
        outs = [layer.apply({"params": q}, x, False, None, None, None, None,
                            True) for q in (p, other_norm, other_router)]
    assert len(seen) == 3 and seen[0].shape == (SEQ, 3)
    np.testing.assert_array_equal(seen[0], seen[1])
    assert not np.array_equal(seen[0], seen[2])
    assert np.abs(np.asarray(outs[0] - outs[1])).max() > 1e-3   # attention moved
    monkeypatch.setattr(moe, "route_softmax", real)
    toks = jnp.asarray(tokens_of(1, SEQ, 7))
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda q: next_token_loss(model_of(), q, toks))(
            params)
    for i in range(4):
        assert float(jnp.linalg.norm(grads[f"block{i:02d}"]["router"])) > 0


# -- (iv) prefill, then decode, through the engine's step and pool ---------------

@pytest.mark.parametrize("n", [5, 12, 31])
def test_prefill_then_decode_through_the_engines_pool_is_the_reference(
        seeded, n):
    """An ``n``-token prompt (inside the window, the window, 2.5
    windows) through a ``[1, 48]`` row call into slot 1, then 8
    ``[3, 1]`` decode steps with slots 0 and 2 idle: every logit is the
    reference's over the ``n + 8`` tokens. The block is one code: the
    family that trains also serves."""
    params, ref_params = seeded
    eng = new_engine(model_of(remat=False), params, prefill_buckets=(48,),
                     kv_page_tokens=4)
    seq = tokens_of(1, n + 8, n)[0]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF.logits_fn(ref_params, jnp.asarray(seq), SIZES,
                                        "float32"))
    assert eng._alloc_pages_for(1, 64) is not None
    toks = np.zeros((1, 48), np.int32)
    toks[0, :n] = seq[:n]
    toks[0, n:] = tokens_of(1, 48 - n, 99)[0]    # a padded tail that is not 0
    lg, sampled = logits_and_dispatch(eng, toks, [0], [True], [n - 1], 1)
    np.testing.assert_allclose(lg[0, :n], want[:n], atol=1e-4)
    assert sampled[0] == want[n - 1].argmax()
    for j in range(8):
        step = np.zeros((3, 1), np.int32)
        step[1, 0] = seq[n + j]
        lg, sampled = logits_and_dispatch(
            eng, step, [0, n + j, 0], [False, True, False], [0, 0, 0])
        np.testing.assert_allclose(lg[1, 0], want[n + j], atol=1e-4)
        assert sampled[1] == want[n + j].argmax()


# -- the mapping from the published keys ----------------------------------------

def test_the_published_keys_map_onto_the_arch():
    arch = LatentArch.from_mapping(CONFIG["program"]["model"]["latent"])
    assert arch.early_router and not (arch.hybrid or arch.parallel)
    assert arch.layer_types == ("full_attention", "sliding_attention",
                                "sliding_attention", "sliding_attention")
    assert (arch.n_routed_experts, arch.num_experts_per_tok,
            arch.moe_intermediate_size, arch.num_shared_experts,
            arch.first_k_dense_replace) == (8, 3, 24, 0, 0)
    window, full = arch.gqa("sliding_attention"), arch.gqa("full_attention")
    assert (window["window"], window["rotary"], window["layout"],
            window["scope"]) == (12, 16, "rotate_half", "tpunet_gqa_window")
    assert (full["window"], full["rotary"], full["scope"]) == (
        None, 0, "tpunet_gqa_full")
    assert not (window["gated"] or window["qk_norm"])
    # the switches are taken at what is built, and then dropped
    built = {"moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
             "tie_word_embeddings": False, "rope_scaling": None}
    assert LatentArch.from_mapping(
        {**CONFIG["program"]["model"]["latent"], **built}) == arch


@pytest.mark.parametrize("extra,match", [
    ({"norm_topk_prob": False}, "built with norm_topk_prob"),
    ({"moe_primary_router_apply_softmax": False}, "built with"),
    ({"tie_word_embeddings": True}, "built with"),
    ({"kv_lora_rank": 512}, "does not read"),
    ({"sliding_window": 12}, "does not read"),
    ({"expert_act": "silu"}, "set by a family's key mapping"),
    ({"rotary_layout": "interleaved"}, "set by a family's key mapping"),
    ({"num_shared_experts": 1}, "does not read"),
    ({"rope_layout": [0, 1, 1, 0]}, "windowed layers"),
    ({"num_key_value_heads": 4}, "divides num_attention_heads"),
])
def test_what_the_family_does_not_read_or_build_says_so(extra, match):
    with pytest.raises(ValueError, match=match):
        LatentArch.from_mapping({**CONFIG["program"]["model"]["latent"],
                                 **extra})


@pytest.mark.parametrize("key,match", [
    ("rope_layout", "unknown keys"), ("moe_ffn_hidden_size", "unknown keys"),
    ("moe_num_primary_experts", "unknown keys"),
    ("expert_act", "set by a family's key mapping")])
def test_the_other_families_do_not_read_this_ones_keys(key, match):
    mapped = dict(CONFIG["program"]["model"]["latent"], expert_act="relu")
    with pytest.raises(ValueError, match=match):
        LatentArch.from_mapping({
            "hidden_size": 64, "num_hidden_layers": 1, "intermediate_size": 96,
            "layer_types": ["full_attention"], key: mapped[key]})


def test_the_trainers_gauge_is_the_kernels_band():
    from tpunet.ops.flash import _band_blocks
    full = harness.load_json("benchmark", "configs",
                             "smallthinker-21ba3b.json", root=REPO)
    model = create_model(ModelConfig(**full["program"]["model"]))
    gauges = model.train_gauges(8192)
    assert gauges["train_experts_held"] == 16
    assert gauges["train_experts_total"] == 64
    # 108 of 136 causal 512-blocks: what grid (…, 16, 9) visits
    band = _band_blocks(4096, 512, 16)
    assert gauges["train_attn_window_blocks_visited_pct"] == pytest.approx(
        100.0 * sum(min(i + 1, band) for i in range(16)) / 136)
    assert gauges["train_attn_window_blocks_visited_pct"] == pytest.approx(
        79.41, abs=0.01)
    # a family without a windowed training path says nothing of a band
    glm = harness.load_json("benchmark", "configs", "glm-4.7-flash.json",
                            root=REPO)
    assert set(create_model(ModelConfig(
        **glm["program"]["model"])).train_gauges(8192)) == {
            "train_experts_held", "train_experts_total"}
