"""The second decoder (``latent_lm``) under the ``Trainer``, against its
plain reference (``benchmark/reference/glm-4.7-flash.py``) at a tiny
size on the CPU: a dense layer and two expert layers without indexer,
gate or rescale, 4 of 8 experts held, the multi-token-prediction module.

Both logits and, through one real train step of the ``Trainer``, the
loss and every leaf's gradient, with and without per-block
recomputation; the selection bias only chooses (zero gradient, zero
Adam update); the eight shares of an expert layer add up to the uncut
layer, forward and input-gradient; the counters and gauges; and what
the cells that share this code must keep: ``lm``'s train step lowers to
the parent's text and ``dots3-note-prev``'s tiny engine serves the
parent's tokens.
"""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chip_smoke
from benchmark import harness, weights
from tpunet.config import (CheckpointConfig, DataConfig, ModelConfig,
                           OptimConfig, ServeConfig, TrainConfig)
from tpunet.models import create_model, moe
from tpunet.models.moe import RoutedShareMlp
from tpunet.train import metrics as M
from tpunet.train.state import create_train_state
from tpunet.train.steps import make_lm_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))
import bench_tiny_glm  # noqa: E402

REF = harness.load_module(
    os.path.join(REPO, "benchmark", "reference", "glm-4.7-flash.py"),
    "reference_glm_for_latent_train_test")
DOTS3 = harness.load_module(
    os.path.join(REPO, "benchmark", "reference", "dots3-note-prev.py"),
    "reference_dots3_for_latent_train_test")
CONFIG = bench_tiny_glm.shrink(harness.load_json(
    "benchmark", "configs", "glm-4.7-flash.json", root=REPO))
SEED, BATCH, SEQ = 2000000011, 2, 32
PAIR_CHUNK = 16         # rows of sorted pairs a pass, for ``stepped``
B1 = CONFIG["optimizer"]["b1"]


def model_config(remat: bool) -> ModelConfig:
    return ModelConfig(**{**CONFIG["program"]["model"], "remat": remat})


def tokens_of(rows, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, bench_tiny_glm.VOCAB, (rows, n)).astype(np.int32)


@pytest.fixture(scope="module")
def seeded():
    spec = REF.param_spec(CONFIG, "train")
    return weights.make_tree(spec, SEED), REF.make_params(CONFIG, "train",
                                                          SEED)


@pytest.fixture(scope="module", params=[False, True],
                ids=["kept", "recomputed"])
def stepped(request, seeded, tmp_path_factory):
    """One real step of a ``Trainer`` from the seed's weights: the
    state before and after, the step's metrics, the registry."""
    from tpunet.obs.registry import MemorySink
    from tpunet.parallel import make_mesh
    from tpunet.train.loop import Trainer

    params, _ = seeded
    x = tokens_of(BATCH, SEQ, 3)
    cfg = TrainConfig(
        seed=1, model=model_config(request.param),
        data=DataConfig(**CONFIG["program"]["data"], batch_size=BATCH),
        optim=OptimConfig(**CONFIG["program"]["optim"]),
        checkpoint=CheckpointConfig(
            directory=str(tmp_path_factory.mktemp("ckpt")), save_best=False,
            save_last=False))
    y = np.zeros(BATCH, np.int32)
    # one device, as on the chip (latent_lm has no mesh lowering)
    trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh, jax.devices()[:1]),
                      dataset=(x, y, x, y))
    sink = MemorySink()
    trainer.obs.add_sink(sink)
    # a row's (token, expert) pairs outnumber a chunk, as at the real
    # size: the step's expert layers walk them in chunks and stop after
    # the held ones, forward, recomputed and coming back
    chunked = pytest.MonkeyPatch()
    chunked.setattr(moe, "PAIR_CHUNK", PAIR_CHUNK)
    try:
        trainer.state = trainer.state.replace(params=jax.device_put(
            params, jax.tree_util.tree_map(lambda a: a.sharding,
                                           trainer.state.params)))
        before = jax.tree_util.tree_map(np.asarray, trainer.state.params)
        M.STEP_MEAN_TOTALS.clear()
        summary = trainer.train_one_epoch(0)
        after = trainer.state
        mu = next(s.mu for s in jax.tree_util.tree_leaves(
            after.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
            if hasattr(s, "mu"))
        return {"x": x, "summary": summary, "before": weights.flatten(before),
                "after": weights.flatten(jax.tree_util.tree_map(
                    np.asarray, after.params)),
                "grads": {p: np.asarray(v) / (1.0 - B1)
                          for p, v in weights.flatten(mu).items()},
                "gauges": trainer.obs.registry.snapshot(),
                "records": sink.by_kind("obs_train_means"),
                "totals": dict(M.STEP_MEAN_TOTALS)}
    finally:
        chunked.undo()
        trainer.close()


@pytest.fixture(scope="module")
def reference_step(seeded, stepped):
    _, ref_params = seeded
    with jax.default_matmul_precision("highest"):
        loss, grads = REF.loss_and_grads_fn(CONFIG, "train", "float32")(
            ref_params, jnp.asarray(stepped["x"]), None, None)
        return float(loss), {p: np.asarray(g) for p, g in grads.items()}


# -- the forward --------------------------------------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["kept", "recomputed"])
def test_both_logits_are_the_references(seeded, remat):
    params, ref_params = seeded
    model = create_model(model_config(remat))
    toks = tokens_of(BATCH, SEQ, 1)
    s = REF.sizes(CONFIG, "train")
    with jax.default_matmul_precision("highest"):
        got, ahead = model.apply({"params": params}, jnp.asarray(toks),
                                 train=True)
        plain = model.apply({"params": params}, jnp.asarray(toks))
        for row in range(BATCH):
            want, want_ahead = REF.logits_fn(ref_params,
                                             jnp.asarray(toks[row]), s,
                                             "float32")
            np.testing.assert_allclose(got[row], want, atol=2e-5)
            np.testing.assert_allclose(ahead[row], want_ahead, atol=2e-5)
    assert got.shape == ahead.shape == (BATCH, SEQ, bench_tiny_glm.VOCAB)
    # the row-at-a-time forward (serving's, evaluation's) is the same model
    np.testing.assert_allclose(plain, got, atol=2e-5)


def test_parameter_tree_is_the_reference_spec(seeded):
    params, _ = seeded
    init = create_model(model_config(False)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(dict(init["params"])) == shapes(params)
    attn = init["params"]["block01"]["attn"]
    assert set(attn) == {"dq", "q_norm", "uq", "dkv", "kv_norm", "ukv", "out"}
    assert set(init["params"]["mtp"]) == {"enorm", "hnorm", "eh_proj",
                                          "block", "ln"}


def test_a_plain_full_layer_also_serves(seeded):
    """Without an indexer a full layer's prefill keeps the causal mask
    alone and its absorbed decode reads every cached position: the
    engine's greedy tokens are the reference's best (the module is not
    run when serving)."""
    from tpunet.serve import Engine

    params, ref_params = seeded
    engine = Engine(create_model(model_config(False)), {"params": params},
                    ServeConfig(slots=2, queue_max=4, prefill_buckets=(8, 24),
                                kv_page_tokens=4, emit_every_s=0.0)).start()
    try:
        prompts = [tokens_of(1, 13, 5)[0], tokens_of(1, 6, 6)[0]]
        reqs = [engine.submit(p, max_new_tokens=n, temperature=0.0)
                for p, n in zip(prompts, (9, 12))]
        for r in reqs:
            r.result(timeout=300.0)
        gauges = engine.registry.snapshot()
    finally:
        engine.stop()
    assert "serve_cache_bytes_per_token_index" not in gauges
    assert gauges["serve_cache_bytes_per_token_latent"] == 3 * 128 * 4
    s = REF.sizes(CONFIG, "train")
    for prompt, req in zip(prompts, reqs):
        assert req.finish_reason == "length" and not req.error
        served = np.asarray(req.tokens, np.int32)
        with jax.default_matmul_precision("highest"):
            lg, _ = REF.logits_fn(ref_params, jnp.asarray(
                np.concatenate([prompt, served])), s, "float32")
        at = len(prompt) - 1 + np.arange(len(served))
        lg = np.asarray(lg)
        assert (lg[at].max(-1) - lg[at, served]).max() < 1e-4


# -- one step of the Trainer --------------------------------------------------

def test_the_steps_loss_is_the_references(stepped, reference_step):
    loss, _ = reference_step
    assert stepped["summary"]["loss"] == pytest.approx(loss, rel=2e-6)
    assert stepped["summary"]["count"] == BATCH * (SEQ - 1)
    means = stepped["records"][-1]
    lam = CONFIG["mtp_loss_weight"]
    assert (means["train_main_loss"] + lam * means["train_mtp_loss"]
            == pytest.approx(loss, rel=1e-5))


def test_every_leafs_gradient_is_the_references(stepped, reference_step):
    _, want = reference_step
    assert set(stepped["grads"]) == set(want)
    scale = float(np.median([np.abs(g).max() for g in want.values()]))
    for path, g in want.items():
        np.testing.assert_allclose(
            stepped["grads"][path], g, rtol=2e-4,
            atol=2e-5 * max(scale, float(np.abs(g).max())), err_msg=path)


def test_the_selection_bias_only_chooses(stepped, reference_step):
    """Zero gradient from the program and from the reference, and a
    zero Adam update: the leaf is what it was."""
    _, want = reference_step
    biases = [p for p in stepped["grads"] if p.endswith("router_bias")]
    assert sorted(biases) == ["block01/moe/router_bias",
                              "block02/moe/router_bias",
                              "mtp/block/moe/router_bias"]
    for path in biases:
        assert not stepped["grads"][path].any() and not want[path].any()
        assert (stepped["after"][path] == stepped["before"][path]).all()
        router = path.replace("router_bias", "router")
        assert np.abs(stepped["grads"][router]).max() > 0
        assert (stepped["after"][router] != stepped["before"][router]).any()


def test_counters_and_gauges_of_the_step(stepped):
    g = stepped["gauges"]
    assert (g["train_experts_held"], g["train_experts_total"]) == (4, 8)
    assert g["train_params_resident_bytes"] == 4 * sum(
        a.size for a in stepped["before"].values())
    assert 0.3 < g["train_moe_held_pair_share"] < 0.7      # 4 of 8 held
    assert g["train_moe_held_load_max_over_mean"] >= 1.0
    # a row's chunks run up to the last held pair: ceil(held / chunk) of
    # ceil(pairs / chunk), so from the held share to a chunk over it
    # (the prediction module's rows are a token short of a whole chunk)
    top_k = CONFIG["program"]["model"]["latent"]["num_experts_per_tok"]
    chunks = -(-SEQ * top_k // PAIR_CHUNK)
    assert chunks > 1
    assert ((SEQ - 1) * top_k / (chunks * PAIR_CHUNK)
            * g["train_moe_held_pair_share"]
            <= g["train_moe_chunks_run_share"]
            < min(1.0, g["train_moe_held_pair_share"] + 1.0 / chunks))
    assert stepped["totals"]["moe_chunks_run_share"] == pytest.approx(
        g["train_moe_chunks_run_share"], abs=1e-6)
    assert g["train_main_loss"] > 0 and g["train_mtp_loss"] > 0
    (record,) = stepped["records"]
    assert record["train_moe_held_pair_share"] == pytest.approx(
        g["train_moe_held_pair_share"], abs=1e-6)
    totals = stepped["totals"]          # what a benchmark reader reaches
    assert totals["steps"] == 1.0
    assert totals["moe_held_pair_share"] == pytest.approx(
        g["train_moe_held_pair_share"], abs=1e-6)
    reader = harness.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", "readers", "train_step_mean.py"),
        "reader_train_step_mean")
    saved = dict(M.STEP_MEAN_TOTALS)
    try:
        M.STEP_MEAN_TOTALS.clear()
        assert reader.read({}, {"key": "moe_held_pair_share"}) is None
        M.STEP_MEAN_TOTALS.update(totals)
        assert reader.read({}, {"key": "moe_held_pair_share",
                                "scale": 100.0}) == pytest.approx(
            100.0 * g["train_moe_held_pair_share"], abs=1e-4)
    finally:
        M.STEP_MEAN_TOTALS.clear()
        M.STEP_MEAN_TOTALS.update(saved)


def test_what_the_train_path_does_not_build_says_so(seeded):
    params, _ = seeded
    toks = jnp.zeros((1, 8), jnp.int32)
    model = create_model(model_config(False))
    with pytest.raises(ValueError, match="without a cache"):
        model.apply({"params": params}, toks, train=True, decode=True)
    indexed = dict(CONFIG["program"]["model"]["latent"], index_topk=4,
                   index_n_heads=2, index_head_dim=8)
    other = create_model(ModelConfig(**{**CONFIG["program"]["model"],
                                        "latent": indexed}))
    with pytest.raises(ValueError, match="without an indexer"):
        other.init(jax.random.PRNGKey(0), toks, train=True)


# -- the share ----------------------------------------------------------------

EXPERTS, SHARES = 16, 8


def _moe_params(seed=9):
    r = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(0.3 * r.normal(size=s), jnp.float32)  # noqa: E731
    return {"router": n(64, EXPERTS), "router_bias": 0.1 * n(EXPERTS),
            "experts_gate": n(EXPERTS, 64, 32),
            "experts_up": n(EXPERTS, 64, 32),
            "experts_down": n(EXPERTS, 32, 64), "shared_gate": n(64, 32),
            "shared_up": n(64, 32), "shared_down": n(32, 64)}


def _share_of(p, held):
    take = jnp.asarray(held)
    return {k: (v[take] if k.startswith("experts_") else v)
            for k, v in p.items()}


@pytest.mark.parametrize("what", ["forward", "input_gradient"])
def test_the_eight_shares_add_up_to_the_uncut_layer(what):
    """Held 0-1, 2-3, ..., 14-15 of 16 (the configuration's 0-7, 8-15,
    ... of 64 in small): the routed parts of all eight shares plus the
    shared expert ONCE are the uncut reference's layer, and so are the
    gradients they send back to the layer's input."""
    p = _moe_params()
    u = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    cot = jnp.asarray(np.random.default_rng(2).normal(size=(40, 64)),
                      jnp.float32)
    s = {"num_experts_per_tok": 2, "routed_scaling_factor": 1.8,
         "held_experts": list(range(EXPERTS))}

    def of(fn):
        if what == "forward":
            return np.asarray(fn(u))
        return np.asarray(jax.grad(lambda u_: jnp.sum(fn(u_) * cot))(u))

    with jax.default_matmul_precision("highest"):
        whole = of(lambda u_: REF.expert_layer(u_, p, s, "float32"))
        shared = of(lambda u_: REF._gated(
            u_, p["shared_gate"], p["shared_up"], p["shared_down"],
            "float32"))
        total = shared.copy()             # what every chip computes: once
        for i in range(SHARES):
            held = (2 * i, 2 * i + 1)
            layer = RoutedShareMlp(EXPERTS, 32, 2, held=held, scaling=1.8,
                                   dtype=jnp.float32)
            part = of(lambda u_: layer.apply(
                {"params": _share_of(p, held)}, u_))
            total += part - shared
    np.testing.assert_allclose(total, whole, atol=1e-4)
    assert np.abs(whole - shared).max() > 0.01     # the experts matter


def test_rows_no_group_holds_send_nothing_back(monkeypatch):
    """On the TPU ``ragged_dot`` and its transposes leave the rows
    outside every group unwritten (the first chip run of PR 31 read NaN
    gradients from a finite loss). A double that writes NaN there, going
    forward and coming back, moves no gradient of the layer."""
    real = jax.lax.ragged_dot

    def unwritten(lhs, rhs, group_sizes):
        inside = lambda m: (jnp.arange(m)[:, None]  # noqa: E731
                            < jnp.sum(group_sizes))

        def clean(l_, r_):
            return real(jnp.where(inside(l_.shape[0]), l_, 0), r_,
                        group_sizes)

        @jax.custom_vjp
        def f(l_, r_):
            out = clean(l_, r_)
            return jnp.where(inside(out.shape[0]), out, jnp.nan)

        def bwd(res, g):
            d_l, d_r = jax.vjp(clean, *res)[1](
                jnp.where(inside(g.shape[0]), g, 0))
            return jnp.where(inside(d_l.shape[0]), d_l, jnp.nan), d_r

        f.defvjp(lambda l_, r_: (f(l_, r_), (l_, r_)), bwd)
        return f(lhs, rhs)

    p = _share_of(_moe_params(), (0, 1, 2))
    u = jnp.asarray(np.random.default_rng(3).normal(size=(24, 64)),
                    jnp.float32)
    layer = RoutedShareMlp(EXPERTS, 32, 2, held=(0, 1, 2), scaling=1.8,
                           dtype=jnp.float32)

    def grads():
        return jax.grad(lambda p_, u_: jnp.sum(jnp.square(layer.apply(
            {"params": p_}, u_))), argnums=(0, 1))(p, u)

    want = grads()
    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    got = grads()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# -- what the cells that share this code keep ---------------------------------

# Recorded on the CPU with the two functions below; a change that means
# to alter either program records them again and says so. The tokens
# are the parent commit's of PR 36 (6d7e8bd); the LM step's texts are
# PR 43's, which meant to alter it: `steps._ce_loss` picks its target
# by a compare and brings its own backward (tests/test_ce_loss.py).
LM_STEP_SHA256 = {
    1: "36ae9e8468d497e848df1b858c79dfb182f254417c270e44975dce2a7adef227",
    2: "92011fe64c2e81a208636d8a4f5a59e78f84350fec5aef31e1e2ccc45b889cf0"}
DOTS3_TOKENS = [[40, 19, 40, 25, 42, 28, 27, 11, 11],
                [32, 32, 32, 32, 32, 32, 32, 32, 32, 32, 32, 32],
                [16, 8, 9, 42, 28, 13, 19]]


def lm_step_text(accum: int) -> str:
    mc = ModelConfig(name="lm", vit_hidden=32, vit_depth=2, vit_heads=2,
                     vocab_size=64, max_seq_len=32, dtype="float32",
                     attention="dense")
    oc = OptimConfig(grad_accum=accum)
    state = jax.eval_shape(lambda: create_train_state(
        mc, oc, jax.random.PRNGKey(0), image_size=32, steps_per_epoch=4,
        epochs=2, seq_len=16))
    return jax.jit(make_lm_train_step(oc, mc)).lower(
        state, jax.ShapeDtypeStruct((4, 16), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()


def dots3_tokens():
    from tpunet.serve import Engine

    held = (2, 3, 4, 5)
    cfg = dict(
        chip_smoke.LATENT_TINY, num_hidden_layers=5,
        layer_types=["full_attention", "full_attention", "sliding_attention",
                     "sliding_attention", "sliding_attention"],
        first_k_dense_replace=1, rms_norm_eps=1e-5, rope_theta=8e7,
        swa_rope_theta=5e4, apply_mla_qkv_lora_rescale=True,
        n_routed_experts=len(held), n_routed_experts_published=8,
        routed_scaling_factor=1.0, held_experts=list(held), vocab_size=50,
        param_dtype="float32")
    arch = {k: v for k, v in cfg.items()
            if k not in ("n_routed_experts_published", "vocab_size",
                         "param_dtype")}
    arch["n_routed_experts"] = 8
    model = create_model(ModelConfig(
        name="latent_lm", vocab_size=50, max_seq_len=48, dtype="float32",
        param_dtype="float32", latent=arch))
    params = weights.make_tree(DOTS3.param_spec(cfg, "serve"), SEED)
    engine = Engine(model, {"params": params}, ServeConfig(
        slots=3, queue_max=8, prefill_buckets=(8, 24), kv_page_tokens=4,
        emit_every_s=0.0)).start()
    try:
        r = np.random.default_rng(11)
        reqs = [engine.submit(r.integers(0, 50, n).astype(np.int32),
                              max_new_tokens=m, temperature=0.0)
                for n, m in ((17, 9), (7, 12), (21, 7))]
        for q in reqs:
            q.result(timeout=300.0)
        return [[int(t) for t in q.tokens] for q in reqs]
    finally:
        engine.stop()


@pytest.mark.parametrize("accum", [1, 2])
def test_lm_train_step_lowers_to_the_parents_text(accum):
    got = hashlib.sha256(lm_step_text(accum).encode()).hexdigest()
    assert got == LM_STEP_SHA256[accum]


def test_dots3_tiny_engine_serves_the_parents_tokens():
    assert json.dumps(dots3_tokens()) == json.dumps(DOTS3_TOKENS)
