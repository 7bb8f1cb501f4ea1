"""MobileNetV2 in Flax (linen), TPU-native (NHWC, bf16 compute).

Functional equivalent of the reference model — torchvision
``models.mobilenet_v2(pretrained=True)`` with the classifier head swapped
to ``nn.Linear(in_features, 10)`` (cifar10_mpi_mobilenet_224.py:137-139,
cifar10_serial_mobilenet_224.py:70-72; 2,236,682 params for width 1.0 /
10 classes, logged at cifar_mpi_gpu128_26188.out:30) — re-implemented
from the MobileNetV2 paper recipe (Sandler et al., 2018):

  stem Conv3x3/s2(32) -> 17 inverted-residual blocks with
  (expansion t, channels c, repeats n, stride s) =
  (1,16,1,1) (6,24,2,2) (6,32,3,2) (6,64,4,2) (6,96,3,1) (6,160,3,2)
  (6,320,1,1) -> Conv1x1(1280) -> global avg pool -> dropout ->
  Linear(num_classes); ReLU6 activations, BatchNorm eps 1e-5 /
  momentum 0.1 (torch convention; flax decay 0.9).

Layout choices are TPU-first: NHWC images, channels padded by XLA onto
the MXU lanes, bfloat16 compute with float32 params/statistics. Explicit
((1,1),(1,1)) padding on 3x3 convs matches torch's padding=1 semantics
exactly (XLA 'SAME' pads stride-2 convs asymmetrically (0,1), which would
break converted-weight parity).
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpunet.config import ModelConfig

# (expansion, out_channels, num_blocks, first_stride)
INVERTED_RESIDUAL_SETTINGS: Tuple[Tuple[int, int, int, int], ...] = (
    (1, 16, 1, 1),
    (6, 24, 2, 2),
    (6, 32, 3, 2),
    (6, 64, 4, 2),
    (6, 96, 3, 1),
    (6, 160, 3, 2),
    (6, 320, 1, 1),
)

# torch nn.init.kaiming_normal_(mode="fan_out") for convs; normal(0, 0.01)
# for the classifier — matching torchvision's from-scratch init so training
# without pretrained weights behaves comparably.
conv_init = nn.initializers.variance_scaling(2.0, "fan_out", "normal")
dense_init = nn.initializers.normal(stddev=0.01)

# BatchNorm hyperparameters (torch momentum 0.1 == flax decay 0.9) —
# single source of truth for every BN path (nn.BatchNorm, FusedBNAct,
# _FusedIRBN): the fused paths promise checkpoint/numerics parity with
# the plain path, which a per-call-site literal drifting would break.
BN_MOMENTUM = 0.9
BN_EPSILON = 1e-5


def _make_divisible(v: float, divisor: int = 8) -> int:
    """Round channel counts like torchvision does for width multipliers."""
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


class PallasDepthwise(nn.Module):
    """3x3 depthwise conv through the Pallas kernel (tpunet.ops).

    Parameter name/shape ('kernel', (3, 3, 1, C)) matches nn.Conv with
    feature_group_count=C exactly, so checkpoints and converted torch
    weights are interchangeable between the two paths.
    """

    features: int
    stride: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        from tpunet.ops import depthwise_conv3x3
        kernel = self.param("kernel", conv_init, (3, 3, 1, self.features),
                            self.param_dtype)
        w = kernel[:, :, 0, :].astype(self.dtype)
        return depthwise_conv3x3(x.astype(self.dtype), w, self.stride)


class FusedBNAct(nn.Module):
    """Train-mode BatchNorm + optional ReLU6 as ONE fusable region.

    Byte-level restructuring of ``nn.BatchNorm`` + separate clamp for
    an HBM-bound model (same math, same variable layout — 'scale'/
    'bias' params and 'mean'/'var' float32 batch_stats — so
    checkpoints and converted torch weights are interchangeable with
    the ``nn.BatchNorm`` path):

    - the batch-stat reduction is a single pass (mean of x and of x*x
      reduced together, Var = E[x^2] - E[x]^2 like flax's
      use_fast_variance) — one read of the activation;
    - normalize, scale/shift, and clamp are folded into one
      per-channel FMA + clamp (y = x * inv + shift with inv/shift
      precomputed per channel in f32), one read + one write of the
      activation with no separate normalized-activation round-trip;
    - bf16 residency: the written activation is exactly
      ``self.dtype`` (asserted), statistics stay f32.

    The remaining second read of the activation (stats pass +
    normalize pass) is inherent to training BatchNorm; everything else
    is elementwise in one fusable region.
    """

    act: bool = True
    momentum: float = BN_MOMENTUM
    epsilon: float = BN_EPSILON
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,),
                           self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (c,),
                          self.param_dtype)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, jnp.float32), (c,))
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, jnp.float32), (c,))
        if train:
            axes = tuple(range(x.ndim - 1))
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axes)
            # Same fusion reduces both sums in one pass over x.
            var = jnp.maximum(0.0, jnp.mean(xf * xf, axes) - mean * mean)
            # Named for the block-remat saved-residual policy: the
            # (C,)-sized stats are saved so the backward replay never
            # re-reduces a full activation (see MobileNetV2.__call__).
            from jax.ad_checkpoint import checkpoint_name
            mean = checkpoint_name(mean, "tpunet_bn_stats")
            var = checkpoint_name(var, "tpunet_bn_stats")
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * var
        else:
            mean, var = ra_mean.value, ra_var.value
        inv = jax.lax.rsqrt(var + self.epsilon) * scale.astype(jnp.float32)
        shift = bias.astype(jnp.float32) - mean * inv
        y = x.astype(jnp.float32) * inv + shift
        if self.act:
            y = jnp.minimum(jnp.maximum(y, 0.0), 6.0)  # ReLU6
        y = y.astype(self.dtype)
        assert y.dtype == jnp.dtype(self.dtype)  # bf16 residency
        return y


class _Conv1x1Kernel(nn.Module):
    """Parameter holder for the fused-IR 1x1 conv path: the 'kernel'
    param ((1, 1, Ci, Co), same name/shape/init as ``nn.Conv`` with
    use_bias=False) lives under the same 'conv' module path, so
    checkpoints and converted torch weights are interchangeable with
    the unfused path."""

    features: int
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, in_features: int):
        return self.param("kernel", conv_init,
                          (1, 1, in_features, self.features),
                          self.param_dtype)


class _FusedIRBN(nn.Module):
    """BN affine params + running stats for the fused-IR path, living
    under the same 'bn' module path (scale/bias params, f32 mean/var
    batch_stats) as ``FusedBNAct``/``nn.BatchNorm`` — identical
    variable tree, flippable on existing checkpoints. The conv + batch
    stats + normalize/clamp all run inside
    ``tpunet.ops.fused_ir.conv1x1_bn_act`` (one-pass Pallas kernel on
    TPU where the shape pays, the exact FusedBNAct math elsewhere);
    this module contributes the parameters and consumes the returned
    batch stats for the running-average update."""

    act: bool = True
    momentum: float = BN_MOMENTUM
    epsilon: float = BN_EPSILON
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, kernel):
        c = kernel.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,),
                           self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (c,),
                          self.param_dtype)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, jnp.float32), (c,))
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, jnp.float32), (c,))
        from tpunet.ops import fused_ir
        y, mean, var = fused_ir.conv1x1_bn_act(
            x.astype(self.dtype), kernel[0, 0].astype(self.dtype),
            scale, bias, act=self.act, eps=self.epsilon)
        if not self.is_initializing():
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1 - m) * mean
            ra_var.value = m * ra_var.value + (1 - m) * var
        y = y.astype(self.dtype)
        assert y.dtype == jnp.dtype(self.dtype)  # bf16 residency
        return y


class ConvBN(nn.Module):
    """Conv + BatchNorm (+ optional ReLU6), the MobileNetV2 building unit.

    ``fused_bn`` expresses BN + clamp through ``FusedBNAct`` — one
    jax.numpy epilogue region; off (``ModelConfig``'s default since the
    v5e A/B of PERF.md section 6, PR 44: 29% faster in the step), the
    original ``nn.BatchNorm`` + separate ReLU6 path, which the TPU
    compiler fuses into its convolutions (bit-compatible variable
    trees either way). ``fused_ir`` (needs ``fused_bn``; train-mode
    1x1 convs only) routes conv + batch stats through
    tpunet/ops/fused_ir.py: the one-pass Pallas pair where
    ``fused_ir._kernel_pays`` engages it (nowhere on the v5e, by the
    same A/B), FusedBNAct's math elsewhere. Eval mode always takes the
    plain path, so eval logits are bit-identical across the flag.
    """

    features: int
    kernel: int = 3
    stride: int = 1
    groups: int = 1
    act: bool = True
    use_pallas: bool = False
    fused_bn: bool = True
    fused_ir: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        if (self.fused_ir and self.fused_bn and train
                and self.kernel == 1 and self.stride == 1
                and self.groups == 1):
            kernel = _Conv1x1Kernel(self.features,
                                    param_dtype=self.param_dtype,
                                    name="conv")(x.shape[-1])
            return _FusedIRBN(act=self.act, momentum=BN_MOMENTUM,
                              epsilon=BN_EPSILON,
                              dtype=self.dtype,
                              param_dtype=self.param_dtype,
                              name="bn")(x, kernel)
        pad = (self.kernel - 1) // 2
        if (self.use_pallas and self.kernel == 3 and self.groups > 1
                and self.groups == self.features == x.shape[-1]):
            x = PallasDepthwise(self.features, self.stride, dtype=self.dtype,
                                param_dtype=self.param_dtype, name="conv")(x)
        else:
            x = nn.Conv(
                self.features,
                (self.kernel, self.kernel),
                strides=(self.stride, self.stride),
                padding=((pad, pad), (pad, pad)),
                feature_group_count=self.groups,
                use_bias=False,
                kernel_init=conv_init,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="conv",
            )(x)
        # Conv outputs are the ONLY activation-sized residuals the
        # block-remat policy keeps: the forward materializes them
        # regardless (they feed the next conv), so saving them is
        # free, and the backward replay recomputes just the
        # elementwise BN/ReLU6 epilogues from them (no conv re-runs).
        from jax.ad_checkpoint import checkpoint_name
        x = checkpoint_name(x, "tpunet_convout")
        if self.fused_bn:
            return FusedBNAct(act=self.act, momentum=BN_MOMENTUM,
                              epsilon=BN_EPSILON,
                              dtype=self.dtype,
                              param_dtype=self.param_dtype,
                              name="bn")(x, train)
        x = nn.BatchNorm(
            use_running_average=not train,
            momentum=BN_MOMENTUM,
            epsilon=BN_EPSILON,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="bn",
        )(x)
        if self.act:
            x = jnp.minimum(jnp.maximum(x, 0.0), 6.0)  # ReLU6
        return x


class InvertedResidual(nn.Module):
    """Expansion -> depthwise -> linear projection, with residual add."""

    features: int
    stride: int
    expand_ratio: int
    use_pallas: bool = False
    fused_bn: bool = True
    fused_ir: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        in_features = x.shape[-1]
        hidden = in_features * self.expand_ratio
        y = x
        if self.expand_ratio != 1:
            y = ConvBN(hidden, kernel=1, fused_bn=self.fused_bn,
                       fused_ir=self.fused_ir, dtype=self.dtype,
                       param_dtype=self.param_dtype, name="expand")(y, train)
        y = ConvBN(hidden, kernel=3, stride=self.stride, groups=hidden,
                   use_pallas=self.use_pallas, fused_bn=self.fused_bn,
                   dtype=self.dtype, param_dtype=self.param_dtype,
                   name="depthwise")(y, train)
        y = ConvBN(self.features, kernel=1, act=False,
                   fused_bn=self.fused_bn, fused_ir=self.fused_ir,
                   dtype=self.dtype,
                   param_dtype=self.param_dtype, name="project")(y, train)
        if self.stride == 1 and in_features == self.features:
            y = y + x
        return y


class MobileNetV2(nn.Module):
    """MobileNetV2 backbone + linear classifier head.

    __call__(x, train) expects NHWC float images (already normalized) and
    returns logits in float32. BatchNorm statistics live in the
    ``batch_stats`` collection; dropout needs an rng when train=True.
    """

    num_classes: int = 10
    width_mult: float = 1.0
    dropout_rate: float = 0.2
    use_pallas: bool = False
    fused_bn: bool = True
    fused_ir: bool = False
    block_remat: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        stem_ch = _make_divisible(32 * self.width_mult)
        x = ConvBN(stem_ch, kernel=3, stride=2, fused_bn=self.fused_bn,
                   dtype=self.dtype,
                   param_dtype=self.param_dtype, name="stem")(x, train)
        # Saved-residual policy: rematerialize each inverted-residual
        # block in the backward pass saving ONLY conv outputs (which
        # the forward materializes anyway — they feed the next conv)
        # and the (C,)-sized BN batch stats. The BN/ReLU6 epilogue
        # intermediates never round-trip through HBM as autodiff
        # residuals — the backward replay recomputes them elementwise
        # from the saved conv outputs (fusing into the backward
        # consumers), and no convolution is ever re-executed (the
        # nothing_saveable policy would re-run and re-WRITE every conv
        # in the replay — measurably more bytes, not fewer). Parameter
        # trees are identical with the flag off.
        Block = InvertedResidual
        if self.block_remat:
            policy = jax.checkpoint_policies.save_only_these_names(
                "tpunet_convout", "tpunet_bn_stats")
            Block = nn.remat(InvertedResidual, static_argnums=(2,),
                             policy=policy)
        idx = 0
        for t, c, n, s in INVERTED_RESIDUAL_SETTINGS:
            out_ch = _make_divisible(c * self.width_mult)
            for i in range(n):
                x = Block(
                    out_ch, stride=s if i == 0 else 1, expand_ratio=t,
                    use_pallas=self.use_pallas, fused_bn=self.fused_bn,
                    fused_ir=self.fused_ir,
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name=f"block{idx:02d}")(x, train)
                idx += 1
        head_ch = _make_divisible(1280 * max(1.0, self.width_mult))
        x = ConvBN(head_ch, kernel=1, fused_bn=self.fused_bn,
                   dtype=self.dtype,
                   param_dtype=self.param_dtype, name="head")(x, train)
        x = jnp.mean(x, axis=(1, 2))  # global average pool, NHWC -> NC
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(self.num_classes, kernel_init=dense_init,
                     dtype=self.dtype, param_dtype=self.param_dtype,
                     name="classifier")(x)
        return x.astype(jnp.float32)


def create_model(cfg: ModelConfig) -> MobileNetV2:
    if cfg.name != "mobilenet_v2":
        raise ValueError(f"unknown model {cfg.name!r}")
    if cfg.fused_ir and not cfg.fused_bn:
        # The fused-IR kernel computes the FusedBNAct epilogue math, so
        # it only engages on the fused_bn path — warn loudly rather
        # than let an A/B record claim a lever that never ran.
        import warnings
        warnings.warn("fused_ir=True has no effect with fused_bn=False "
                      "(the fused kernel computes the fused-BN epilogue); "
                      "running the plain path", stacklevel=2)
    return MobileNetV2(
        num_classes=cfg.num_classes,
        width_mult=cfg.width_mult,
        dropout_rate=cfg.dropout_rate,
        use_pallas=cfg.use_pallas_depthwise,
        fused_bn=cfg.fused_bn,
        fused_ir=cfg.fused_ir,
        block_remat=cfg.block_remat,
        dtype=jnp.dtype(cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype),
    )


