"""MobileNetV2: construction, parameter count, and every unit, block
and the checkpoint format held to references written here.

The references are ``jax.lax`` / numpy alone, in float32: a
convolution with explicit symmetric padding, batch normalisation from
its definition (two-pass biased variance, momentum 0.9 on the running
statistics, eps 1e-5), a clamp to [0, 6]. The units are walked from
``INVERTED_RESIDUAL_SETTINGS`` the way ``MobileNetV2.__call__`` walks
it, and the walk is held to what the model itself calls.
"""

import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util
from jax import lax

from tpunet.config import DataConfig, ModelConfig, OptimConfig
from tpunet.models import create_model, init_variables, num_params
from tpunet.models.mobilenetv2 import (INVERTED_RESIDUAL_SETTINGS, ConvBN,
                                       InvertedResidual)

MOMENTUM, EPS = 0.9, 1e-5
BATCH, IMAGE = 4, 64


@pytest.fixture(scope="module")
def model_and_vars():
    model = create_model(ModelConfig(dtype="float32"))
    variables = init_variables(model, jax.random.PRNGKey(0), image_size=64)
    return model, variables


def test_param_count_matches_reference(model_and_vars):
    # Reference logs "Total parameters: 2236682" (cifar_mpi_gpu128_26188.out:30)
    _, variables = model_and_vars
    assert num_params(variables["params"]) == 2_236_682


@pytest.mark.slow
def test_forward_shapes_and_dtype(model_and_vars):
    model, variables = model_and_vars
    x = jnp.zeros((2, 64, 64, 3), jnp.float32)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32


@pytest.mark.slow
def test_train_mode_updates_batch_stats(model_and_vars):
    model, variables = model_and_vars
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 64, 3))
    logits, mutated = model.apply(
        variables, x, train=True,
        rngs={"dropout": jax.random.PRNGKey(2)},
        mutable=["batch_stats"])
    assert logits.shape == (4, 10)
    old = variables["batch_stats"]["stem"]["bn"]["mean"]
    new = mutated["batch_stats"]["stem"]["bn"]["mean"]
    assert not jnp.allclose(old, new)


def test_jit_matches_eager(model_and_vars):
    model, variables = model_and_vars
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64, 3))
    eager = model.apply(variables, x, train=False)
    jitted = jax.jit(lambda v, x: model.apply(v, x, train=False))(variables, x)
    assert jnp.allclose(eager, jitted, atol=1e-5)


def test_width_multiplier_changes_params():
    small = create_model(ModelConfig(width_mult=0.5, dtype="float32"))
    variables = init_variables(small, jax.random.PRNGKey(0), image_size=32)
    assert num_params(variables["params"]) < 2_236_682
    x = jnp.zeros((1, 32, 32, 3))
    assert small.apply(variables, x, train=False).shape == (1, 10)


# ------------------------------------------------- the walk of the units

def _walk(image=IMAGE, stages=None, stem=32, head=1280):
    """Every ``ConvBN`` unit as ``(path, input hw, input channels,
    attributes)`` and every block as ``(name, input hw, input channels,
    features, stride, expand_ratio)``, from the settings table."""
    stages = stages or [c for _, c, _, _ in INVERTED_RESIDUAL_SETTINGS]
    units = [("stem", image, 3,
              dict(features=stem, kernel=3, stride=2, groups=1, act=True))]
    blocks, hw, ci, idx = [], image // 2, stem, 0
    for (t, _, n, s), c in zip(INVERTED_RESIDUAL_SETTINGS, stages):
        for i in range(n):
            name, stride, hidden = f"block{idx:02d}", s if i == 0 else 1, ci * t
            blocks.append((name, hw, ci, c, stride, t))
            if t != 1:
                units.append((f"{name}/expand", hw, ci, dict(
                    features=hidden, kernel=1, stride=1, groups=1,
                    act=True)))
            units.append((f"{name}/depthwise", hw, hidden, dict(
                features=hidden, kernel=3, stride=stride, groups=hidden,
                act=True)))
            hw = (hw - 1) // stride + 1
            units.append((f"{name}/project", hw, hidden, dict(
                features=c, kernel=1, stride=1, groups=1, act=False)))
            ci, idx = c, idx + 1
    units.append(("head", hw, ci, dict(features=head, kernel=1, stride=1,
                                       groups=1, act=True)))
    return units, blocks


UNITS, BLOCKS = _walk()


def test_the_walk_is_the_models():
    """The walk hands the unit and block tests exactly what the 1.0
    model calls at 64 px, in order: every ``ConvBN`` and
    ``InvertedResidual`` with its attributes and input shape."""
    seen_units, seen_blocks = [], []

    def record(next_fn, args, kwargs, context):
        m = context.module
        if context.method_name == "__call__" and isinstance(m, ConvBN):
            seen_units.append(("/".join(m.path), args[0].shape[1],
                               args[0].shape[-1], dict(
                features=m.features, kernel=m.kernel, stride=m.stride,
                groups=m.groups, act=m.act)))
        if (context.method_name == "__call__"
                and isinstance(m, InvertedResidual)):
            seen_blocks.append((m.name, args[0].shape[1], args[0].shape[-1],
                                m.features, m.stride, m.expand_ratio))
        return next_fn(*args, **kwargs)

    model = create_model(ModelConfig())

    def init(x):
        with nn.intercept_methods(record):
            return model.init({"params": jax.random.PRNGKey(0),
                               "dropout": jax.random.PRNGKey(1)},
                              x, train=True)

    jax.eval_shape(init, jax.ShapeDtypeStruct((BATCH, IMAGE, IMAGE, 3),
                                              jnp.float32))
    assert (len(UNITS), len(BLOCKS)) == (52, 17)
    assert seen_units == UNITS
    assert seen_blocks == BLOCKS


# ------------------------------------------------- the plain references

def _conv(x, kernel, stride, groups, padding):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=lax.Precision.HIGHEST)


def _plain_unit(variables, x, train, *, kernel, stride, groups, act,
                features=None):
    """Conv (symmetric padding (k-1)/2, no bias) -> batch norm -> clamp.
    Returns the output and the running statistics after the call
    (``features`` is the kernel's last dimension: not read)."""
    p, s = variables["params"], variables["batch_stats"]
    pad = (kernel - 1) // 2
    y = _conv(x, p["conv"]["kernel"], stride, groups, ((pad, pad),) * 2)
    mean, var = s["bn"]["mean"], s["bn"]["var"]
    stats = s
    if train:
        mean = jnp.mean(y, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
        stats = {"bn": {
            "mean": MOMENTUM * s["bn"]["mean"] + (1 - MOMENTUM) * mean,
            "var": MOMENTUM * s["bn"]["var"] + (1 - MOMENTUM) * var}}
    out = (y - mean) / jnp.sqrt(var + EPS) * p["bn"]["scale"] + p["bn"]["bias"]
    if act:
        out = jnp.clip(out, 0.0, 6.0)
    return out, stats


def _normal(rng, shape):
    return rng.standard_normal(shape, np.float32)


def _unit_variables(seed, ci, attrs):
    """Variables no unit is initialised with: a kernel at fan-in
    scale, a scale and a bias off 1 and 0, running statistics off 0
    and 1 — so that each of them shows in the output."""
    k, co, groups = attrs["kernel"], attrs["features"], attrs["groups"]
    rng = np.random.default_rng(seed)
    fan_in = k * k * ci // groups
    return {
        "params": {
            "conv": {"kernel": _normal(rng, (k, k, ci // groups, co))
                     / np.sqrt(fan_in)},
            "bn": {"scale": 1.0 + 0.3 * _normal(rng, (co,)),
                   "bias": 0.5 + 0.3 * _normal(rng, (co,))}},
        "batch_stats": {"bn": {
            "mean": 0.2 * _normal(rng, (co,)),
            "var": 1.0 + 0.5 * rng.random((co,), np.float32)}}}


def _close(got, want, tol, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, what
    err = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6)
    assert err < tol, (what, err)


def _cotangent(shape):
    return 1.0 + 0.5 * jnp.cos(jnp.arange(
        np.prod(shape), dtype=jnp.float32)).reshape(shape)


@pytest.mark.parametrize("unit", range(len(UNITS)),
                         ids=[u[0] for u in UNITS])
def test_conv_bn_unit_matches_plain_reference(unit):
    """One ``ConvBN`` at its own channels, stride, groups and ``act``:
    the training output, the ``batch_stats`` it writes, the gradients to
    its input, kernel, scale and bias, and the evaluation output from
    the running statistics."""
    path, hw, ci, attrs = UNITS[unit]
    module = ConvBN(dtype=jnp.float32, **attrs)
    variables = _unit_variables(unit, ci, attrs)
    x = _normal(np.random.default_rng(100 + unit), (BATCH, hw, hw, ci))

    def program(variables, x, train):
        y, mutated = module.apply(variables, x, train,
                                  mutable=["batch_stats"])
        return y, mutated["batch_stats"]

    def reference(variables, x, train):
        return _plain_unit(variables, x, train, **attrs)

    @jax.jit
    def run(variables, x):
        out = {}
        for name, fn in (("program", program), ("reference", reference)):
            def loss(params, x):
                y, stats = fn({**variables, "params": params}, x, True)
                return jnp.sum(y * _cotangent(y.shape)), (y, stats)
            (_, (y, stats)), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(variables["params"], x)
            out[name] = dict(train=y, stats=stats, grads=grads,
                             eval=fn(variables, x, False)[0])
        return out

    out = jax.device_get(run(variables, x))
    got, want = out["program"], out["reference"]
    _close(got["train"], want["train"], 1e-4, "train output")
    _close(got["eval"], want["eval"], 1e-4, "eval output")
    for stat in ("mean", "var"):
        _close(got["stats"]["bn"][stat], want["stats"]["bn"][stat], 1e-5,
               f"batch_stats {stat}")
    _close(got["grads"][1], want["grads"][1], 1e-3, "dx")
    for mod, leaf in (("conv", "kernel"), ("bn", "scale"), ("bn", "bias")):
        _close(got["grads"][0][mod][leaf], want["grads"][0][mod][leaf],
               1e-3, f"d{leaf}")
    if attrs["act"]:
        assert got["train"].min() >= 0.0 and got["train"].max() <= 6.0
        clamped = np.mean((got["train"] == 0.0) | (got["train"] == 6.0))
        assert 0.0 < clamped < 1.0            # the clamp is exercised


@pytest.mark.parametrize("block", range(len(BLOCKS)),
                         ids=[b[0] for b in BLOCKS])
def test_inverted_residual_block_wiring(block):
    """One block: output shape and stride, the subtree's names, the
    residual added exactly where stride 1 meets equal channels, and the
    value and input gradient of the composition of the plain units."""
    name, hw, ci, features, stride, t = BLOCKS[block]
    module = InvertedResidual(features, stride=stride, expand_ratio=t,
                              dtype=jnp.float32)
    x = _normal(np.random.default_rng(200 + block), (BATCH, hw, hw, ci))
    inner = [(p.split("/")[1], uci, attrs) for p, _, uci, attrs in UNITS
             if p.startswith(name + "/")]
    names = [n for n, _, _ in inner]
    assert names == (["expand"] if t != 1 else []) + ["depthwise", "project"]
    variables = {"params": {}, "batch_stats": {}}
    for i, (n, uci, attrs) in enumerate(inner):
        v = _unit_variables(1000 * block + i, uci, attrs)
        variables["params"][n] = v["params"]
        variables["batch_stats"][n] = v["batch_stats"]
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x))
    assert (jax.tree_util.tree_map(lambda a: a.shape, dict(shapes))
            == jax.tree_util.tree_map(lambda a: a.shape, variables))

    def program(x):
        return module.apply(variables, x, True, mutable=["batch_stats"])[0]

    def composed(x, residual):
        y = x
        for n, _, attrs in inner:
            y, _ = _plain_unit({k: v[n] for k, v in variables.items()},
                               y, True, **attrs)
        return y + x if residual else y

    residual = stride == 1 and ci == features
    assert residual == (name in {
        "block02", "block04", "block05", "block07", "block08", "block09",
        "block11", "block12", "block14", "block15"})

    @jax.jit
    def run(x):
        out = {}
        for label, fn in (("program", program),
                          ("composed", lambda x: composed(x, residual))):
            def loss(x):
                y = fn(x)
                return jnp.sum(y * _cotangent(y.shape)), y
            (_, y), dx = jax.value_and_grad(loss, has_aux=True)(x)
            out[label] = dict(y=y, dx=dx)
        return out

    out = jax.device_get(run(x))
    ho = (hw - 1) // stride + 1
    got, want = out["program"], out["composed"]
    assert got["y"].shape == (BATCH, ho, ho, features)
    _close(got["y"], want["y"], 1e-4, "value")
    _close(got["dx"], want["dx"], 1e-3, "dx")
    if residual:
        # Without the residual the composition is another function.
        assert np.max(np.abs(got["y"] - (want["y"] - x))) > 0.1


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("h", [7, 8, 14, 15])
def test_3x3_padding_is_torch_padding_1(h, stride):
    """A grouped 3x3 pads one pixel on every side, as torch's
    ``padding=1``: at even sizes under stride 2 that is NOT XLA's
    ``SAME`` (which pads (0, 1)) and converted weights depend on it."""
    c = 8
    attrs = dict(features=c, kernel=3, stride=stride, groups=c, act=False)
    variables = _unit_variables(h * 10 + stride, c, attrs)
    x = _normal(np.random.default_rng(h), (2, h, h, c))
    p, s = variables["params"], variables["batch_stats"]["bn"]

    @jax.jit
    def run(x):
        return (ConvBN(dtype=jnp.float32, **attrs).apply(variables, x, False),
                _conv(x, p["conv"]["kernel"], stride, c, ((1, 1), (1, 1))),
                _conv(x, p["conv"]["kernel"], stride, c, "SAME"))

    def finish(y):
        return ((y - s["mean"]) / np.sqrt(s["var"] + EPS)
                * p["bn"]["scale"] + p["bn"]["bias"])

    got, padded, same = jax.device_get(run(x))
    ho = (h + 2 - 3) // stride + 1
    assert got.shape == same.shape == (2, ho, ho, c)
    _close(got, finish(padded), 1e-5, "padding 1")
    differs = not np.allclose(got, finish(same), atol=1e-3)
    assert differs == (stride == 2 and h % 2 == 0)


UNIT_KINDS = {       # (input channels, attributes), from the 1.0 model
    "stem": (3, dict(features=32, kernel=3, stride=2, groups=1, act=True)),
    "expand": (16, dict(features=96, kernel=1, stride=1, groups=1,
                        act=True)),
    "depthwise": (96, dict(features=96, kernel=3, stride=2, groups=96,
                           act=True)),
    "project": (96, dict(features=24, kernel=1, stride=1, groups=1,
                         act=False)),
    "head": (320, dict(features=1280, kernel=1, stride=1, groups=1,
                       act=True)),
}


@pytest.mark.parametrize("kind", list(UNIT_KINDS))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_unit_dtypes(dtype, kind):
    """The activation leaves in the compute dtype; parameters and
    statistics are float32 whatever it is, and the statistics are
    REDUCED in float32: over the 1,024 to 4,096 values of a channel
    the mean and the variance lie within a thousandth of the
    activation's size of a float64 reduction (the compiler may keep a
    bfloat16 convolution's output unrounded inside the unit, which is
    what the bound leaves room for); a bfloat16 accumulator does not."""
    ci, attrs = UNIT_KINDS[kind]
    module = ConvBN(dtype=dtype, **attrs)
    x = _normal(np.random.default_rng(7), (4, 32, 32, ci)) + 0.5
    pad = (attrs["kernel"] - 1) // 2

    @jax.jit
    def run(x):
        variables = module.init(jax.random.PRNGKey(8), x)
        y, mutated = module.apply(variables, x, True,
                                  mutable=["batch_stats"])
        conv = lax.conv_general_dilated(
            x.astype(dtype),
            variables["params"]["conv"]["kernel"].astype(dtype),
            (attrs["stride"],) * 2, ((pad, pad),) * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=attrs["groups"])
        return (variables, y, mutated["batch_stats"]["bn"],
                module.apply(variables, x, False), conv)

    variables, y, written, y_eval, conv = run(x)
    for leaf in jax.tree_util.tree_leaves(variables):
        assert leaf.dtype == jnp.float32
    assert y.dtype == y_eval.dtype == conv.dtype == dtype
    assert written["mean"].dtype == written["var"].dtype == jnp.float32
    conv = np.asarray(conv, np.float64)
    assert conv.shape[:3] == (4, 32 // attrs["stride"], 32 // attrs["stride"])
    mean = conv.mean(axis=(0, 1, 2))
    square = (conv ** 2).mean(axis=(0, 1, 2))
    rms = np.sqrt(square.mean())
    got_mean = np.asarray(written["mean"]) / (1 - MOMENTUM)
    got_var = (np.asarray(written["var"]) - MOMENTUM) / (1 - MOMENTUM)
    assert np.max(np.abs(got_mean - mean)) < 1e-3 * rms
    assert np.max(np.abs(got_var - (square - mean ** 2))) < 2e-3 * rms ** 2
    # The control: the widest channel's squares summed one after
    # another into a bfloat16 accumulator fail the same bound.
    column = conv[..., np.argmax(square)].reshape(-1) ** 2
    narrow = float(np.cumsum(column.astype(jnp.bfloat16),
                             dtype=jnp.bfloat16)[-1]) / column.size
    assert abs(narrow - square.max()) > 2e-3 * rms ** 2


# ------------------------------------------------- the checkpoint format

def _model_shapes(width):
    model = create_model(ModelConfig(width_mult=width))
    return jax.eval_shape(
        lambda x: model.init({"params": jax.random.PRNGKey(0),
                              "dropout": jax.random.PRNGKey(1)},
                             x, train=True),
        jax.ShapeDtypeStruct((2, IMAGE, IMAGE, 3), jnp.float32))


# sha256 over the sorted "<collection>/<path> <shape> <dtype>" lines of
# ``params`` and ``batch_stats``, taken from the tree at PR 44 with its
# four model levers all off and all on (one digest either way).
TREE_DIGESTS = {
    1.0: "fc0642797ba073da6d1ff855516e97f4358632d031afd38851b12b592c4b4634",
    0.5: "99394619c8ef25484702a241d96669f65cb2f4456f10a884d20c68a4c4cfce61",
}


@pytest.mark.parametrize("width", [1.0, 0.5])
def test_variable_tree_is_the_checkpoint_format(width):
    """Checkpoints, the torch converter and the benchmark's reference
    address leaves by path: ``stem``, ``blockNN/{expand,depthwise,
    project}``, ``head`` — each with ``conv/kernel``, ``bn/{scale,
    bias}`` and ``batch_stats`` ``bn/{mean,var}`` — and ``classifier``,
    all float32."""
    shapes = _model_shapes(width)
    assert set(shapes) == {"params", "batch_stats"}
    flat = traverse_util.flatten_dict(dict(shapes), sep="/")
    lines = sorted(f"{p} {tuple(a.shape)} {a.dtype}" for p, a in flat.items())
    assert len(lines) == 52 * 5 + 2
    assert "params/block00/depthwise/conv/kernel" in flat
    assert "params/block00/expand/conv/kernel" not in flat
    assert "batch_stats/head/bn/var" in flat
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()
    assert digest == TREE_DIGESTS[width], lines


# torchvision's MobileNetV2 rounds every width-scaled channel count with
# ``_make_divisible(c * width, 8)`` (to the nearest multiple of 8, at
# least 8, and never more than 10 % below the scaled count); the last
# convolution keeps 1280 below width 1. (stem, the seven stages, head):
CHANNELS = {
    0.35: (16, (8, 8, 16, 24, 32, 56, 112), 1280),
    0.5: (16, (8, 16, 16, 32, 48, 80, 160), 1280),
    0.75: (24, (16, 24, 24, 48, 72, 120, 240), 1280),
    1.4: (48, (24, 32, 48, 88, 136, 224, 448), 1792),
}


@pytest.mark.parametrize("width", list(CHANNELS))
def test_width_multiplier_channels(width):
    """Every unit's channel count at the widths converted torchvision
    weights come in."""
    stem, stages, head = CHANNELS[width]
    units, _ = _walk(stages=stages, stem=stem, head=head)
    params = _model_shapes(width)["params"]
    got = {}
    for path, leaf in traverse_util.flatten_dict(dict(params),
                                                 sep="/").items():
        if path.endswith("conv/kernel"):
            got[path[:-len("/conv/kernel")]] = leaf.shape
    want = {path: (a["kernel"], a["kernel"], ci // a["groups"], a["features"])
            for path, _, ci, a in units}
    assert got == want
    assert params["classifier"]["kernel"].shape == (head, 10)


# ------------------------------------------------- the train step

def test_default_step_lowers_to_no_custom_call():
    """The default train step, lowered for the TPU, calls no kernel:
    convolutions, BatchNorm and ReLU6 are left to the compiler."""
    from tpunet.train.state import create_train_state
    from tpunet.train.steps import make_train_step

    model_cfg, optim_cfg = ModelConfig(), OptimConfig()
    data_cfg = DataConfig(dataset="synthetic", batch_size=8)
    state = jax.eval_shape(lambda: create_train_state(
        model_cfg, optim_cfg, jax.random.PRNGKey(0),
        image_size=data_cfg.image_size, steps_per_epoch=4, epochs=1))
    step = make_train_step(data_cfg, optim_cfg, model_cfg, None)
    text = jax.jit(step).trace(
        state, jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.uint8),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert "stablehlo.convolution" in text
    assert "custom_call" not in text
