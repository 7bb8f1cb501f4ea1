"""Plain reference for MobileNetV2 (Sandler et al., arXiv:1801.04381;
torchvision ``mobilenet_v2``) with a ``num_classes`` head, in training
mode: stem conv3x3/s2 -> 17 inverted-residual blocks (1x1 expand ->
3x3 depthwise -> 1x1 linear projection, residual where shapes allow)
-> conv1x1(1280) -> global average pool -> dropout -> linear.
BatchNorm uses the batch's own statistics (biased variance, eps 1e-5);
activations are ReLU6.

Float32, every product at ``highest`` precision, plain
``lax.conv_general_dilated``, no kernel and no fusion option; each
block is rematerialised so the batch the cell times fits beside its
gradients. It imports nothing of the program and takes none of its
arrays: weights come from ``benchmark/weights.py`` and the seed, the
augmentation from ``_augment_cifar224.py``, and the dropout mask from
``flax.linen.Dropout`` drawn at the module path the published layout
gives it (``Dropout_0`` under the root), which is how any flax model of
this layout draws it.
"""

from __future__ import annotations

from benchmark import weights
from benchmark.reference import _augment_cifar224 as A
from benchmark.reference import _numerics as N

BN_EPS = 1e-5


def _divisible(v, d=8):
    new = max(d, int(v + d / 2) // d * d)
    return new + d if new < 0.9 * v else new


def layout(cfg: dict):
    """[(block name, in, hidden or 0, out, stride)] plus stem/head sizes."""
    wm = cfg["width_mult"]
    stem = _divisible(32 * wm)
    blocks, cin, idx = [], stem, 0
    for t, c, n, s in cfg["inverted_residual_setting"]:
        cout = _divisible(c * wm)
        for i in range(n):
            blocks.append((f"block{idx:02d}", cin, cin * t if t != 1 else 0,
                           cout, s if i == 0 else 1))
            cin, idx = cout, idx + 1
    return stem, blocks, _divisible(cfg["last_channel"] * max(1.0, wm))


def _convbn(spec, name, k, cin, cout, groups=1):
    shape = (k, k, cin // groups, cout)
    fan_out = k * k * cout // groups         # torch kaiming_normal, fan_out
    spec[f"{name}/conv/kernel"] = (shape, "normal", (2.0 / fan_out) ** 0.5)
    spec[f"{name}/bn/scale"] = ((cout,), "ones", 0.0)
    spec[f"{name}/bn/bias"] = ((cout,), "zeros", 0.0)


def param_spec(cfg: dict, section: str = "train") -> dict:
    stem, blocks, head = layout(cfg)
    spec: dict = {}
    _convbn(spec, "stem", 3, 3, stem)
    for name, cin, hidden, cout, _ in blocks:
        if hidden:
            _convbn(spec, f"{name}/expand", 1, cin, hidden)
        mid = hidden or cin
        _convbn(spec, f"{name}/depthwise", 3, mid, mid, groups=mid)
        _convbn(spec, f"{name}/project", 1, mid, cout)
    _convbn(spec, "head", 1, blocks[-1][3], head)
    spec["classifier/kernel"] = ((head, cfg["num_classes"]), "normal", 0.01)
    spec["classifier/bias"] = ((cfg["num_classes"],), "zeros", 0.0)
    return spec


def make_params(cfg: dict, section: str, seed: int) -> dict:
    """Flat float32 params, one jitted call."""
    return weights.flatten(weights.make_tree(param_spec(cfg, section), seed))


def _conv(x, w, stride, groups, precision):
    import jax

    pad = (w.shape[0] - 1) // 2
    return N.quant(jax.lax.conv_general_dilated(
        N.quant(x, precision), N.quant(w, precision), (stride, stride),
        ((pad, pad), (pad, pad)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=jax.lax.Precision.HIGHEST),
        precision)


def _convbn_apply(p, name, x, stride, groups, act, precision, sink=None):
    import jax.numpy as jnp

    x = _conv(x, p[f"{name}/conv/kernel"], stride, groups, precision)
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    if sink is not None:
        sink[name] = (mean, var)
    x = ((x - mean) / jnp.sqrt(var + BN_EPS) * p[f"{name}/bn/scale"]
         + p[f"{name}/bn/bias"])
    return jnp.clip(x, 0.0, 6.0) if act else x


def _dropout(x, rate, key):
    import flax.linen as nn

    class _Root(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dropout(rate, deterministic=False)(x)

    return _Root().apply({}, x, rngs={"dropout": key})


def logits_fn(params: dict, images, cfg: dict, dropout_key, precision,
              sink=None):
    """images [B,S,S,3] float32 (normalised) -> logits [B, classes].
    ``sink`` (a dict) collects each BatchNorm's batch mean and variance
    by layer name; the blocks are then not rematerialised."""
    import jax
    import jax.numpy as jnp

    _, blocks, _ = layout(cfg)
    x = _convbn_apply(params, "stem", images, 2, 1, True, precision, sink)
    for name, cin, hidden, cout, stride in blocks:
        def block(x, p, name=name, hidden=hidden, stride=stride, cin=cin,
                  cout=cout):
            y = x
            if hidden:
                y = _convbn_apply(p, f"{name}/expand", y, 1, 1, True,
                                  precision, sink)
            mid = hidden or cin
            y = _convbn_apply(p, f"{name}/depthwise", y, stride, mid, True,
                              precision, sink)
            y = _convbn_apply(p, f"{name}/project", y, 1, 1, False,
                              precision, sink)
            return y + x if stride == 1 and cin == cout else y

        sub = {k: v for k, v in params.items() if k.startswith(name + "/")}
        x = (block if sink is not None else jax.checkpoint(block))(x, sub)
    x = _convbn_apply(params, "head", x, 1, 1, True, precision, sink)
    x = jnp.mean(x, (1, 2))
    if cfg["dropout_rate"] > 0:
        x = _dropout(x, cfg["dropout_rate"], dropout_key)
    return N.mm(x, params["classifier/kernel"], precision) \
        + params["classifier/bias"]


def loss_and_grads_fn(cfg: dict, section: str, precision: str):
    """``f(params, x [B,32,32,3] uint8, y [B], key) -> (loss, grads)``.
    The step's key splits in two: augmentation, then dropout."""
    import jax
    import jax.numpy as jnp

    aug = cfg["augment"]

    def loss(params, images, y, dropout_key):
        lg = logits_fn(params, images, cfg, dropout_key, precision)
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))

    @jax.jit
    def f(params, x, y, key):
        aug_key, dropout_key = jax.random.split(key)
        images = A.augment(aug_key, x, aug)
        return jax.value_and_grad(loss)(params, images, y, dropout_key)

    return f


def batch_stats_fn(cfg: dict, section: str, precision: str):
    """``f(params, x, key) -> {layer: (mean, var)}``: every BatchNorm's
    batch statistics in the first step's forward pass — what the
    program's running statistics hold after one step, less their
    initial values. The shallow layers' read rounding before depth has
    amplified it."""
    import jax

    @jax.jit
    def f(params, x, key):
        aug_key, dropout_key = jax.random.split(key)
        sink: dict = {}
        logits_fn(params, A.augment(aug_key, x, cfg["augment"]), cfg,
                  dropout_key, precision, sink)
        return sink

    return f
