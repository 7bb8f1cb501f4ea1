"""``steps._ce_loss`` with integer labels (PR 43): optax's value to the
bit, optax's gradient, and a backward that is one elementwise pass —
no ``scatter``, which the TPU compiler wrapped in serial loops over
the positions (PERF.md section 6, PR 43)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpunet.train.steps import _ce_loss

# the image step's, an LM's, and a vocabulary that is no multiple of 128
SHAPES = [(128, 10), (2, 63, 1000), (1, 17, 50257)]
IDS = ["128x10", "2x63x1000", "1x17x50257"]

oracle = optax.softmax_cross_entropy_with_integer_labels


def draw(shape, dtype=jnp.float32, seed=0):
    k_logits, k_targets, k_weights = jax.random.split(
        jax.random.PRNGKey(seed), 3)
    logits = (4.0 * jax.random.normal(k_logits, shape)).astype(dtype)
    targets = jax.random.randint(k_targets, shape[:-1], 0, shape[-1])
    weights = jax.random.bernoulli(k_weights, 0.6, shape[:-1])
    # one target at each end of the vocabulary, one position left out
    targets = targets.reshape(-1).at[0].set(0).at[1].set(
        shape[-1] - 1).reshape(shape[:-1])
    weights = weights.reshape(-1).at[2].set(False).reshape(shape[:-1])
    return logits, targets, weights.astype(jnp.float32)


def reductions(weights):
    """What a caller does with the per-position loss: the plain step's
    mean, the packed step's weighted sum over the valid count, the
    accumulation path's share of a global count."""
    return {
        "mean": lambda ce: ce.mean(),
        "weighted": lambda ce: jnp.sum(ce * weights)
        / jnp.maximum(jnp.sum(weights), 1.0),
        "over_total": lambda ce: jnp.sum(ce * weights) / 37.0,
    }


@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_value_is_optax_to_the_bit(shape):
    logits, targets, _ = draw(shape)
    got = jax.jit(lambda x, t: _ce_loss(x, t, 0.0))(logits, targets)
    want = jax.jit(oracle)(logits, targets)
    assert got.dtype == jnp.float32 and got.shape == shape[:-1]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(_ce_loss(logits, targets, 0.0)),
                                  np.asarray(oracle(logits, targets)))


@pytest.mark.parametrize("how", ["mean", "weighted", "over_total"])
@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_gradient_is_optax_under_every_callers_reduction(shape, how):
    logits, targets, weights = draw(shape, seed=1)
    reduce = reductions(weights)[how]
    got = jax.grad(lambda x: reduce(_ce_loss(x, targets, 0.0)))(logits)
    want = jax.grad(lambda x: reduce(oracle(x, targets)))(logits)
    assert got.dtype == logits.dtype
    scale = float(jnp.abs(want).max())
    assert scale > 0
    assert float(jnp.abs(got - want).max()) <= 1e-6 * scale
    if how != "mean":
        left_out = np.asarray(weights) == 0
        assert left_out.any()
        assert not np.asarray(got)[left_out].any()      # exactly zero rows
        assert np.asarray(got)[~left_out].any(-1).all()


@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_lowered_gradient_holds_no_scatter_and_no_loop(shape):
    logits, targets, _ = draw(shape)
    lower = lambda loss: jax.jit(jax.grad(           # noqa: E731
        lambda x, t: loss(x, t).mean())).lower(logits, targets).as_text()
    text = lower(lambda x, t: _ce_loss(x, t, 0.0))
    for word in ("scatter", "while", "dynamic_update_slice", "gather"):
        assert word not in text, word
    assert "scatter" in lower(oracle)       # what the words above guard


@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_bfloat16_logits_give_a_float32_loss_and_their_own_gradient(shape):
    logits, targets, _ = draw(shape, jnp.bfloat16, seed=2)
    got, grad = jax.value_and_grad(
        lambda x: _ce_loss(x, targets, 0.0).mean())(logits)
    assert got.dtype == jnp.float32 and grad.dtype == jnp.bfloat16
    wide = logits.astype(jnp.float32)
    want, wgrad = jax.value_and_grad(
        lambda x: oracle(x, targets).mean())(wide)
    assert float(got) == float(want)    # the arithmetic is float32
    scale = float(jnp.abs(wgrad).max())
    assert float(jnp.abs(grad.astype(jnp.float32) - wgrad).max()) \
        <= 2.0 ** -8 * scale            # one rounding to bfloat16


def test_under_checkpoint_and_vmap_and_scan():
    """The accumulation path scans the micro step over microbatches, a
    block's recomputation wraps callers in ``jax.checkpoint``."""
    logits, targets, weights = draw((4, 2, 31, 257), seed=3)

    def micro(x, t, w):
        return jnp.sum(_ce_loss(x, t, 0.0) * w)

    def micro_oracle(x, t, w):
        return jnp.sum(oracle(x, t) * w)

    want = jax.vmap(jax.grad(micro_oracle))(logits, targets, weights)
    scale = float(jnp.abs(want).max())
    close = lambda got: float(jnp.abs(got - want).max()) <= 1e-6 * scale  # noqa: E731

    assert close(jax.vmap(jax.grad(micro))(logits, targets, weights))
    assert close(jax.vmap(jax.grad(jax.checkpoint(micro)))(
        logits, targets, weights))
    _, scanned = jax.lax.scan(
        lambda carry, inp: (carry, jax.grad(micro)(*inp)), 0.0,
        (logits, targets, weights))
    assert close(scanned)
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(lambda x, t: _ce_loss(x, t, 0.0))(
            logits, targets)),
        np.asarray(oracle(logits, targets)))


def test_smoothed_branch_is_untouched():
    logits, targets, _ = draw((2, 63, 1000))
    want = optax.softmax_cross_entropy(logits, optax.smooth_labels(
        jax.nn.one_hot(targets, 1000), 0.1))
    np.testing.assert_array_equal(np.asarray(_ce_loss(logits, targets, 0.1)),
                                  np.asarray(want))
