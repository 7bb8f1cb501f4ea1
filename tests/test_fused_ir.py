"""Fused inverted-residual 1x1 kernel pair tests (tpunet/ops/fused_ir.py
+ its model integration behind ModelConfig.fused_ir).

The contract under test:

- the Pallas forward/backward pair (exercised via ``interpret=True`` on
  CPU) is numerically identical to ``jax.vjp`` of the XLA reference
  composition — logits AND gradients — across stride-1 / stride-2
  blocks, odd H/W, channel counts off the 128-lane multiple, bf16,
  residual-add and no-residual blocks;
- dispatch is per-shape and per-backend with the ``TPUNET_FUSED_IR_REF``
  escape hatch, and off-TPU the reference path makes ``fused_ir``
  on/off numerically indistinguishable;
- the variable tree is bit-compatible across the flag (checkpoints flip
  freely) and eval logits are bit-identical (eval never takes the
  fused path).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpunet.config import ModelConfig
from tpunet.models import create_model
from tpunet.models.mobilenetv2 import (INVERTED_RESIDUAL_SETTINGS,
                                       InvertedResidual)
from tpunet.ops import fused_ir


def _rand(key, shape, dtype, scale=1.0):
    return (scale * jax.random.normal(jax.random.PRNGKey(key), shape)
            ).astype(dtype)


def _rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-6)


# ------------------------------------------------------- kernel parity

@pytest.mark.parametrize("act", [True, False])
@pytest.mark.parametrize(
    "shape,dtype,tol",
    [((2, 8, 8, 16, 24), jnp.float32, 1e-5),
     ((2, 7, 9, 13, 24), jnp.float32, 1e-5),    # odd H/W, off-lane Ci
     ((1, 5, 5, 8, 10), jnp.float32, 1e-5),     # off-lane Co
     ((2, 8, 8, 16, 24), jnp.bfloat16, 2e-2),
     ((2, 7, 7, 24, 16), jnp.bfloat16, 2e-2)])
def test_kernel_parity_fwd_and_grad(shape, dtype, tol, act):
    """Interpret-mode kernel pair vs jax.vjp of the XLA reference:
    outputs, batch stats, and all four input cotangents."""
    n, h, w, ci, co = shape
    x = _rand(0, (n, h, w, ci), dtype)
    wgt = _rand(1, (ci, co), dtype, scale=0.1)
    scale = 1.0 + 0.5 * _rand(2, (co,), jnp.float32)
    bias = 0.1 * _rand(3, (co,), jnp.float32)
    # Deterministic non-uniform cotangent; the loss reads only `out`
    # (the mean/var outputs feed the non-differentiated running-stat
    # update in the model — their cotangents are zero by contract).
    ct = jnp.cos(jnp.arange(n * h * w * co, dtype=jnp.float32)
                 ).reshape(n, h, w, co)

    def run(fn):
        def loss(x, wgt, scale, bias):
            out, mean, var = fn(x, wgt, scale, bias, act, 1e-5)
            return jnp.sum(out.astype(jnp.float32) * ct), (out, mean, var)
        (_, aux), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True)(x, wgt, scale, bias)
        return aux + grads

    ref = run(fused_ir.conv1x1_bn_act_reference)
    ker = run(functools.partial(fused_ir.conv1x1_bn_act, interpret=True))
    names = ("out", "mean", "var", "dx", "dw", "dscale", "dbias")
    for name, a, b in zip(names, ref, ker):
        assert _rel_err(a, b) < tol, (name, shape, _rel_err(a, b))


def test_kernel_output_dtype_and_shapes():
    x = _rand(0, (2, 8, 8, 16), jnp.bfloat16)
    w = _rand(1, (16, 24), jnp.bfloat16)
    out, mean, var = fused_ir.conv1x1_bn_act(
        x, w, jnp.ones(24), jnp.zeros(24), interpret=True)
    assert out.shape == (2, 8, 8, 24) and out.dtype == jnp.bfloat16
    assert mean.shape == (24,) and mean.dtype == jnp.float32
    assert var.shape == (24,) and var.dtype == jnp.float32
    assert bool(jnp.all(var >= 0.0))
    assert bool(jnp.all(out >= 0.0)) and bool(jnp.all(out <= 6.0))  # ReLU6


# ------------------------------------------------- block-level parity

def _block_pair(features, stride, in_features, dtype):
    mk = functools.partial(InvertedResidual, features, stride=stride,
                           expand_ratio=6, dtype=dtype)
    return mk(fused_ir=False), mk(fused_ir=True)


@pytest.mark.parametrize(
    "in_features,features,stride,hw,dtype,tol,floor",
    [(16, 16, 1, (8, 8), jnp.float32, 1e-3, 5e-4),  # residual add
     (16, 24, 1, (8, 8), jnp.float32, 1e-3, 5e-4),  # no residual
     (16, 24, 2, (9, 7), jnp.float32, 1e-3, 5e-4),  # stride-2, odd H/W
     (16, 16, 1, (8, 8), jnp.bfloat16, 3e-2, 5e-1)])
def test_block_parity_through_interpret_kernels(monkeypatch, in_features,
                                                features, stride, hw,
                                                dtype, tol, floor):
    """A full inverted-residual block (expand -> depthwise -> project,
    plus the residual add where shapes allow) run through the Pallas
    pair in interpret mode must match the fused_ir=False block — value
    and gradients wrt params and input. Gradient comparisons are
    normalized by each leaf's own scale with an absolute floor: at
    init several leaves (depthwise kernel, project bn bias, the input
    cotangent) are near zero BY CANCELLATION, where FP reassociation
    noise dominates any relative metric."""
    orig = fused_ir.conv1x1_bn_act
    monkeypatch.setattr(fused_ir, "conv1x1_bn_act",
                        functools.partial(orig, interpret=True))
    ref_blk, fused_blk = _block_pair(features, stride, in_features, dtype)
    x = _rand(0, (2, *hw, in_features), dtype)
    vs = ref_blk.init(jax.random.PRNGKey(1), x, True)

    def loss(blk, params, x):
        y, _ = blk.apply({"params": params,
                          "batch_stats": vs["batch_stats"]}, x, True,
                         mutable=["batch_stats"])
        return jnp.sum(y.astype(jnp.float32) ** 2)

    lr, (gr_p, gr_x) = jax.value_and_grad(
        functools.partial(loss, ref_blk), argnums=(0, 1))(vs["params"], x)
    lf, (gf_p, gf_x) = jax.value_and_grad(
        functools.partial(loss, fused_blk), argnums=(0, 1))(vs["params"], x)
    def close(a, b, what):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        atol = max(tol * float(np.max(np.abs(a))), floor)
        assert np.max(np.abs(a - b)) < atol, \
            (what, float(np.max(np.abs(a - b))), float(np.max(np.abs(a))))

    assert _rel_err(lr, lf) < tol
    close(gr_x, gf_x, "d input")
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(gr_p),
            jax.tree_util.tree_leaves_with_path(gf_p)):
        close(a, b, jax.tree_util.keystr(path))


def test_running_stats_update_parity():
    """The batch_stats mutation (running mean/var) matches across the
    flag — the kernel's stats feed the same flax update."""
    ref_blk, fused_blk = _block_pair(16, 1, 16, jnp.float32)
    x = _rand(0, (2, 8, 8, 16), jnp.float32)
    vs = ref_blk.init(jax.random.PRNGKey(1), x, True)
    _, mr = ref_blk.apply(vs, x, True, mutable=["batch_stats"])
    _, mf = fused_blk.apply(vs, x, True, mutable=["batch_stats"])
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        mr["batch_stats"], mf["batch_stats"])


# ------------------------------------------------------------ dispatch

def test_dispatch_off_tpu_is_reference(monkeypatch):
    assert jax.default_backend() != "tpu"
    assert not fused_ir.use_fused_ir_kernel((8, 28, 28, 96))


def _ir_1x1_input_shapes(batch=128, image=224):
    """(unit, input shape) of the 33 train-mode 1x1 ConvBN units of the
    inverted-residual stack at ``image`` px, walked from the settings
    table as MobileNetV2.__call__ walks it (the stride-2 stem hands
    block00 32 channels at image/2)."""
    shapes, hw, ci, idx = [], image // 2, 32, 0
    for t, c, n, s in INVERTED_RESIDUAL_SETTINGS:
        for i in range(n):
            if t != 1:
                shapes.append((f"block{idx:02d}/expand",
                               (batch, hw, hw, ci)))
            hw //= s if i == 0 else 1       # the depthwise's stride
            shapes.append((f"block{idx:02d}/project",
                           (batch, hw, hw, ci * t)))
            ci, idx = c, idx + 1
    return shapes


IR_1X1_SHAPES = _ir_1x1_input_shapes()

# The recorded v5e verdict (the table in fused_ir._kernel_pays'
# docstring; PERF.md section 6, PR 44): the units whose D-arm beat the
# compiler's convolution in the step. None did.
ENGAGED_ON_V5E = frozenset()


def test_ir_1x1_shapes_are_the_models(monkeypatch):
    """The walk above hands the dispatch test exactly the shapes the
    224px model hands ``conv1x1_bn_act`` at batch 128, in order."""
    seen = []
    real = fused_ir.conv1x1_bn_act

    def recording(x, *args, **kwargs):
        seen.append(tuple(x.shape))
        return real(x, *args, **kwargs)

    monkeypatch.setattr(fused_ir, "conv1x1_bn_act", recording)
    model = create_model(ModelConfig(fused_bn=True, fused_ir=True))
    jax.eval_shape(
        lambda x: model.init({"params": jax.random.PRNGKey(0),
                              "dropout": jax.random.PRNGKey(1)},
                             x, train=True),
        jax.ShapeDtypeStruct((128, 224, 224, 3), jnp.float32))
    assert len(IR_1X1_SHAPES) == 33
    assert seen == [shape for _, shape in IR_1X1_SHAPES]


@pytest.mark.parametrize("unit,shape", IR_1X1_SHAPES,
                         ids=[u for u, _ in IR_1X1_SHAPES])
def test_dispatch_per_shape_on_tpu(monkeypatch, unit, shape):
    """On the TPU each unit takes the Pallas pair exactly where the
    chip's A/B said it beats the compiler's convolution."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("TPUNET_FUSED_IR_REF", raising=False)
    assert fused_ir.use_fused_ir_kernel(shape) == (unit in ENGAGED_ON_V5E)


def test_escape_hatch_env_var(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("TPUNET_FUSED_IR_REF", "1")
    # Whatever the recorded verdict says of a shape, the hatch wins.
    monkeypatch.setattr(fused_ir, "_kernel_pays", lambda shape: True)
    assert not fused_ir.use_fused_ir_kernel((512, 112, 112, 16))
    # And the public op still runs (reference path) with the hatch set
    # on a "TPU" backend — no Pallas lowering is attempted.
    x = _rand(0, (1, 8, 8, 16), jnp.float32)
    w = _rand(1, (16, 24), jnp.float32)
    out, _, _ = fused_ir.conv1x1_bn_act(x, w, jnp.ones(24), jnp.zeros(24))
    ref, _, _ = fused_ir.conv1x1_bn_act_reference(
        x, w, jnp.ones(24), jnp.zeros(24), True, 1e-5)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


# ----------------------------------------------- model-level contract

def _model_and_vars(fused_flag, block_remat=False, dtype="float32"):
    cfg = ModelConfig(width_mult=0.5, fused_bn=True, fused_ir=fused_flag,
                      block_remat=block_remat, dtype=dtype)
    model = create_model(cfg)
    x = _rand(0, (2, 32, 32, 3), jnp.float32)
    vs = model.init({"params": jax.random.PRNGKey(0),
                     "dropout": jax.random.PRNGKey(1)}, x, train=True)
    return model, vs, x


def test_variable_tree_invariant_across_flag():
    _, v_off, _ = _model_and_vars(False)
    _, v_on, _ = _model_and_vars(True)
    assert jax.tree_util.tree_structure(v_off) == \
        jax.tree_util.tree_structure(v_on)
    shapes_off = jax.tree_util.tree_map(lambda a: a.shape, v_off)
    shapes_on = jax.tree_util.tree_map(lambda a: a.shape, v_on)
    assert shapes_off == shapes_on


def test_eval_logits_bit_identical_across_flag():
    """Eval mode never takes the fused path, so flipping the flag on a
    checkpoint changes eval logits by ZERO bits."""
    m_off, vs, x = _model_and_vars(False)
    m_on, _, _ = _model_and_vars(True)
    out_off = m_off.apply(vs, x, train=False)
    out_on = m_on.apply(vs, x, train=False)
    assert np.array_equal(np.asarray(out_off), np.asarray(out_on))


def test_train_logits_parity_across_flag_off_tpu():
    """Off-TPU the dispatch runs the reference, whose ops mirror the
    unfused module path — train logits agree to FP-reassociation."""
    m_off, vs, x = _model_and_vars(False)
    m_on, _, _ = _model_and_vars(True)
    rngs = {"dropout": jax.random.PRNGKey(2)}
    out_off, _ = m_off.apply(vs, x, train=True, rngs=rngs,
                             mutable=["batch_stats"])
    out_on, _ = m_on.apply(vs, x, train=True, rngs=rngs,
                           mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(out_off), np.asarray(out_on),
                               rtol=1e-4, atol=1e-4)


def test_composes_with_block_remat():
    """fused_ir + block_remat: gradients flow and match the non-remat
    fused model (remat changes scheduling, not math)."""
    m_plain, vs, x = _model_and_vars(True, block_remat=False)
    m_remat, _, _ = _model_and_vars(True, block_remat=True)

    def loss(model, params):
        out, _ = model.apply({"params": params,
                              "batch_stats": vs["batch_stats"]},
                             x, train=True,
                             rngs={"dropout": jax.random.PRNGKey(2)},
                             mutable=["batch_stats"])
        return jnp.sum(out ** 2)

    g_plain = jax.grad(functools.partial(loss, m_plain))(vs["params"])
    g_remat = jax.grad(functools.partial(loss, m_remat))(vs["params"])
    # Remat replays change XLA fusion, hence rounding — reassociation
    # tolerance, amplified through 17 BN blocks.
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-3),
        g_plain, g_remat)
