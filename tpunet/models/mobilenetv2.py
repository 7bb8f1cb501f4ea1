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

from typing import Any, Tuple

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

# BatchNorm hyperparameters (torch momentum 0.1 == flax decay 0.9).
BN_MOMENTUM = 0.9
BN_EPSILON = 1e-5


def _make_divisible(v: float, divisor: int = 8) -> int:
    """Round channel counts like torchvision does for width multipliers."""
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


class ConvBN(nn.Module):
    """Conv + BatchNorm (+ optional ReLU6), the MobileNetV2 building unit.

    ``nn.Conv`` without bias (explicit torch padding, ``groups`` for the
    depthwise 3x3) -> ``nn.BatchNorm`` (batch statistics in training,
    the running ``batch_stats`` in evaluation) -> ReLU6 where ``act``.
    The TPU compiler fuses the statistics, the normalisation and the
    clamp into its convolutions.
    """

    features: int
    kernel: int = 3
    stride: int = 1
    groups: int = 1
    act: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        pad = (self.kernel - 1) // 2
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
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        in_features = x.shape[-1]
        hidden = in_features * self.expand_ratio
        y = x
        if self.expand_ratio != 1:
            y = ConvBN(hidden, kernel=1, dtype=self.dtype,
                       param_dtype=self.param_dtype, name="expand")(y, train)
        y = ConvBN(hidden, kernel=3, stride=self.stride, groups=hidden,
                   dtype=self.dtype, param_dtype=self.param_dtype,
                   name="depthwise")(y, train)
        y = ConvBN(self.features, kernel=1, act=False, dtype=self.dtype,
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
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        stem_ch = _make_divisible(32 * self.width_mult)
        x = ConvBN(stem_ch, kernel=3, stride=2, dtype=self.dtype,
                   param_dtype=self.param_dtype, name="stem")(x, train)
        idx = 0
        for t, c, n, s in INVERTED_RESIDUAL_SETTINGS:
            out_ch = _make_divisible(c * self.width_mult)
            for i in range(n):
                x = InvertedResidual(
                    out_ch, stride=s if i == 0 else 1, expand_ratio=t,
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name=f"block{idx:02d}")(x, train)
                idx += 1
        head_ch = _make_divisible(1280 * max(1.0, self.width_mult))
        x = ConvBN(head_ch, kernel=1, dtype=self.dtype,
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
    return MobileNetV2(
        num_classes=cfg.num_classes,
        width_mult=cfg.width_mult,
        dropout_rate=cfg.dropout_rate,
        dtype=jnp.dtype(cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype),
    )
