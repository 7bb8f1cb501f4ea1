"""Pallas kernel tests (interpret mode on the CPU mesh).

Parity target: tpunet.ops.depthwise_conv3x3 must match the XLA
reference depthwise conv (the op torchvision's MobileNetV2 runs via
cuDNN in the reference project) for every shape MobileNetV2 uses.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpunet.ops import depthwise_conv3x3, depthwise_conv3x3_reference

# (h, c, stride) covering every depthwise layer of MobileNetV2 @224
MOBILENET_SHAPES = [
    (112, 32, 1),
    (112, 96, 2),
    (56, 144, 1),
    (56, 144, 2),
    (28, 192, 1),
    (28, 192, 2),
    (14, 384, 1),
    (14, 576, 1),
    (14, 576, 2),
    (7, 960, 1),
]


def _rand(shape, seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


@pytest.mark.parametrize("h,c,stride", MOBILENET_SHAPES)
def test_matches_reference(h, c, stride):
    x = _rand((2, h, h, c), 0)
    w = _rand((3, 3, c), 1)
    got = depthwise_conv3x3(x, w, stride, True)
    want = depthwise_conv3x3_reference(x, w, stride)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_odd_size_and_stride2():
    x = _rand((1, 7, 7, 16), 2)
    w = _rand((3, 3, 16), 3)
    got = depthwise_conv3x3(x, w, 2, True)
    want = depthwise_conv3x3_reference(x, w, 2)
    assert got.shape == (1, 4, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_bfloat16_accumulates_in_f32():
    x = _rand((2, 28, 28, 64), 4, jnp.bfloat16)
    w = _rand((3, 3, 64), 5, jnp.bfloat16)
    got = depthwise_conv3x3(x, w, 1, True)
    assert got.dtype == jnp.bfloat16
    want = depthwise_conv3x3_reference(
        x.astype(jnp.float32), w.astype(jnp.float32), 1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


@pytest.mark.slow
def test_gradients_match_reference():
    x = _rand((2, 14, 14, 32), 6)
    w = _rand((3, 3, 32), 7)

    def loss_pallas(x, w):
        return jnp.sum(depthwise_conv3x3(x, w, 1, True) ** 2)

    def loss_ref(x, w):
        return jnp.sum(depthwise_conv3x3_reference(x, w, 1) ** 2)

    gx, gw = jax.grad(loss_pallas, argnums=(0, 1))(x, w)
    rx, rw = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=1e-4, atol=1e-4)


def test_jit_composes():
    # NOTE: the op is already batched over N; nothing vmaps over it.
    x = _rand((4, 28, 28, 8), 8)
    w = _rand((3, 3, 8), 9)
    f = jax.jit(lambda x, w: depthwise_conv3x3(x, w, 1, True))
    np.testing.assert_allclose(
        np.asarray(f(x, w)),
        np.asarray(depthwise_conv3x3_reference(x, w, 1)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_model_flag_same_params_same_logits(monkeypatch):
    """The pallas and XLA depthwise paths share one parameter tree and
    produce the same logits (ModelConfig.use_pallas_depthwise).

    Off-TPU the op defaults to the XLA reference, so force the kernel
    into interpret mode to actually exercise the Pallas path here."""
    import tpunet.ops as ops
    from tpunet.config import ModelConfig
    from tpunet.models import create_model, init_variables

    orig = ops.depthwise_conv3x3
    monkeypatch.setattr(
        ops, "depthwise_conv3x3",
        lambda x, w, stride=1, interpret=None: orig(x, w, stride, True))

    cfg = ModelConfig(dtype="float32", width_mult=0.5,
                      use_pallas_depthwise=False)  # explicit: XLA path
    ref = create_model(cfg)
    pal = create_model(dataclasses.replace(cfg, use_pallas_depthwise=True))
    variables = init_variables(ref, jax.random.PRNGKey(0), image_size=32)
    assert (jax.tree_util.tree_structure(variables) ==
            jax.tree_util.tree_structure(
                init_variables(pal, jax.random.PRNGKey(0), image_size=32)))
    x = _rand((2, 32, 32, 3), 10)
    a = ref.apply(variables, x, train=False)
    b = pal.apply(variables, x, train=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# IO-aware Pallas backward kernels (dx/dw): parity vs the XLA reference
# transpose, in interpret mode on CPU. Non-slow on small shapes (tier-1
# runs these); the full MobileNetV2 shape sweep is slow-marked.
# ---------------------------------------------------------------------------

def _bwd_pair(h, w_, c, stride, seed, dtype=jnp.float32):
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (2, h, w_, c), dtype)
    w = jax.random.normal(kw, (3, 3, c), dtype)
    ho = (h - 1) // stride + 1
    wo = (w_ - 1) // stride + 1
    g = jax.random.normal(kg, (2, ho, wo, c), dtype)

    def vjp_of(f):
        _, vjp = jax.vjp(lambda xx, ww: f(xx, ww), x, w)
        return vjp(g)

    got = vjp_of(lambda xx, ww: depthwise_conv3x3(xx, ww, stride, True))
    want = vjp_of(lambda xx, ww: depthwise_conv3x3_reference(xx, ww,
                                                            stride))
    return got, want


# Odd H/W, non-square, channel counts off the 128-lane multiple — the
# property grid the stripe/halo + in-VMEM dilation logic must survive.
@pytest.mark.parametrize("h,w,c,stride", [
    (8, 8, 16, 1),
    (8, 8, 16, 2),
    (7, 7, 24, 1),      # odd H/W stride 1
    (7, 7, 24, 2),      # odd H/W stride 2 (dx phantom-row slice)
    (7, 9, 40, 1),      # non-square, off-lane channels
    (9, 7, 40, 2),
    (5, 5, 8, 2),
    (4, 6, 3, 2),       # tiny + odd channel count
])
def test_backward_kernels_match_reference(h, w, c, stride):
    (gx, gw), (rx, rw) = _bwd_pair(h, w, c, stride, seed=h * 31 + stride)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=2e-4, atol=2e-4)


def test_backward_kernels_bf16_accumulate_f32():
    """bf16 inputs: gradients come back bf16 but match the f32
    reference within bf16 rounding (the kernels accumulate in f32)."""
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (2, 8, 8, 32))
    w = jax.random.normal(kw, (3, 3, 32))
    g = jax.random.normal(kg, (2, 4, 4, 32))

    def vjp_of(f, x, w, g):
        _, vjp = jax.vjp(f, x, w)
        return vjp(g)

    gx, gw = vjp_of(
        lambda xx, ww: depthwise_conv3x3(xx, ww, 2, True),
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        g.astype(jnp.bfloat16))
    assert gx.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16
    rx, rw = vjp_of(
        lambda xx, ww: depthwise_conv3x3_reference(xx, ww, 2), x, w, g)
    np.testing.assert_allclose(np.asarray(gx, np.float32),
                               np.asarray(rx), rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(gw, np.float32),
                               np.asarray(rw), rtol=5e-2, atol=5e-2)


def test_backward_reference_escape_hatch(monkeypatch):
    """TPUNET_DEPTHWISE_REF_BWD=1 routes backward through the XLA
    reference transpose even when the kernels are requested."""
    monkeypatch.setenv("TPUNET_DEPTHWISE_REF_BWD", "1")
    (gx, gw), (rx, rw) = _bwd_pair(6, 6, 8, 1, seed=4)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.slow
@pytest.mark.parametrize("h,c,stride", MOBILENET_SHAPES)
def test_backward_kernels_mobilenet_shapes(h, c, stride):
    (gx, gw), (rx, rw) = _bwd_pair(h, h, c, stride, seed=c)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=5e-4, atol=5e-4)
