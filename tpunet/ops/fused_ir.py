"""Fused inverted-residual 1x1 Pallas kernel pair (conv + BN-stats + ReLU6).

The MobileNetV2 train step is HBM-bound (docs/performance.md): round 5
decomposed the remaining 2x roofline gap into ~1.55x excess traffic,
naming the training-BN second pass and the backward's activation
re-reads as the sources. This module attacks both for the expand and
project 1x1 convolutions that bracket the depthwise kernel
(tpunet/ops/depthwise.py) in every inverted-residual block:

- **Forward** (``_fwd_kernel``): one VMEM pass computes the 1x1 conv
  (an MXU matmul over channels — no halo, no padding) AND the per-image
  batch-statistic partials (sum and sum-of-squares per channel, reduced
  from the *cast* conv output so statistics match the unfused path's
  bf16-resident input). The training-BN statistics pass — a full HBM
  read of the conv output in the unfused schedule — never happens; XLA
  finishes the (C,)-sized cross-image reduction and applies the
  normalize/scale/shift/clamp epilogue in one further fused
  read+write. Net: one whole activation read removed per 1x1 conv.
- **Backward** (``_bwd_kernel``): the cheap elementwise epilogue
  (ReLU6 mask, y-hat, the BN-backward recombination) is *recomputed in
  VMEM* from the saved conv output instead of materializing the
  conv-input cotangent to HBM: one stripe pass reads (g, y, x), builds
  t = d(loss)/d(conv_out) on-chip, computes dx = t @ w^T on the MXU,
  and reduces the per-image dw partial [Cin, Cout] in f32 in the same
  pass. The unfused schedule's materialized cotangent (one write + two
  conv-backward reads) never exists in HBM. dw partials are summed
  over batch OUTSIDE the kernel so data-parallel batch partitioning
  stays a plain psum XLA inserts from shardings (the same contract as
  the depthwise backward). The (C,)-sized BN-backward reductions
  (sum g*mask, sum g*mask*y_hat) are a cheap XLA prelude — they must
  complete over the whole batch before any stripe's t is computable,
  so they cannot live inside the sequential grid.

Per-shape dispatch (``_kernel_pays``): the chip's verdict, taken IN THE
STEP on a TPU v5e (2026-10-03, PERF.md section 6, PR 44) — and no shape
of the 224px model engages the pair. The byte model this module was
written to (the per-image dw partial's ``Cin*Cout*4`` bytes against
``~3*H*W*Cout*2`` of saved epilogue traffic, so ``Cin < H*W``: 20 of
the 33 expand/project convs) counted the kernels' own traffic only.
What it left out decides: the compiler lays a ``[128, H, W, C]``
activation out batch-minor (128 images fill the 128 lanes whatever C
is), a Mosaic call takes its operands channel-minor, so every engaged
call is bracketed by whole-activation layout copies in HBM and, inside,
C = 16..192 fills an eighth to three quarters of a lane tile. Off-TPU
the reference runs (the interpreter is far too slow for a hot path);
``interpret=True`` exercises both kernels in tests whatever the
dispatch says; ``TPUNET_FUSED_IR_REF=1`` is the escape hatch back to
the XLA reference on TPU without touching checkpoints or configs.

The reference path (``conv1x1_bn_act_reference``) mirrors
``models.mobilenetv2.FusedBNAct`` op for op, so flipping
``ModelConfig.fused_ir`` changes nothing numerically on backends where
the kernels don't engage, and eval mode (which never calls this
module) stays bit-identical by construction.

Contract notes: the ``(out, mean, var)`` outputs' ``mean``/``var`` are
auxiliary (they feed the module's running-stat update, which flax does
not differentiate); the custom backward treats their cotangents as
zero. Parity is property-tested against ``jax.vjp`` of the reference
composition in interpret mode on CPU (tests/test_fused_ir.py).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from tpunet.ops.partition import sharded


# ---------------------------------------------------------------------------
# Reference (XLA) path: op-for-op the nn.Conv(1x1) -> FusedBNAct schedule
# of models/mobilenetv2.py, so fused_ir on/off is numerically identical
# wherever the kernels don't engage.
# ---------------------------------------------------------------------------


def conv1x1_reference(x: jax.Array, w: jax.Array) -> jax.Array:
    """x [N,H,W,Ci] @ w [Ci,Co] as the conv nn.Conv emits (bit-compatible
    with the unfused module path)."""
    return jax.lax.conv_general_dilated(
        x, w[None, None], window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def conv1x1_bn_act_reference(x: jax.Array, w: jax.Array, scale: jax.Array,
                             bias: jax.Array, act: bool,
                             eps: float) -> Tuple[jax.Array, jax.Array,
                                                  jax.Array]:
    """-> (out, batch_mean, batch_var); the exact FusedBNAct train math."""
    y = conv1x1_reference(x, w)
    y = checkpoint_name(y, "tpunet_convout")
    axes = tuple(range(y.ndim - 1))
    yf = y.astype(jnp.float32)
    mean = jnp.mean(yf, axes)
    var = jnp.maximum(0.0, jnp.mean(yf * yf, axes) - mean * mean)
    # Named for the block-remat saved-residual policy (same contract
    # as FusedBNAct): the (C,)-sized stats are kept so the replay
    # never re-reduces the full conv output.
    mean = checkpoint_name(mean, "tpunet_bn_stats")
    var = checkpoint_name(var, "tpunet_bn_stats")
    inv = jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    shift = bias.astype(jnp.float32) - mean * inv
    o = yf * inv + shift
    if act:
        o = jnp.minimum(jnp.maximum(o, 0.0), 6.0)  # ReLU6
    return o.astype(y.dtype), mean, var


# ---------------------------------------------------------------------------
# Forward kernel: y = x @ w and the per-image (sum, sum-of-squares)
# stat partials in one stripe pass.
# ---------------------------------------------------------------------------


def _pick_rows(h: int, w: int, ci: int, co: int, bufs_ci: int,
               bufs_co: int) -> int:
    """Largest divisor of ``h`` whose stripe temporaries (f32-equivalent
    buffer counts per element: ``bufs_ci`` input-channel-sized,
    ``bufs_co`` output-channel-sized) stay within a ~4 MB budget —
    the same scoped-vmem discipline as the depthwise kernel's
    ``_pick_rows`` (whole-image programs overflow the 16 MB stack at
    the 112px layers)."""
    budget = 4 * 1024 * 1024
    for rows in range(h, 0, -1):
        if h % rows == 0 and \
                rows * w * (bufs_ci * ci + bufs_co * co) * 4 <= budget:
            return rows
    return 1


def _fwd_kernel(x_ref, w_ref, y_ref, p_ref):
    """One output-row stripe per grid step. The stat partials reduce
    the *cast* conv output (matching the unfused path, whose BN reads
    the bf16-resident activation) and accumulate into the per-image
    (2, Co) block across stripes (j == 0 initializes — the standard
    TPU revisiting pattern; the grid is sequential per image)."""
    xs = x_ref[0]                                   # (rows, W, Ci)
    rows, wdt, _ = xs.shape
    yf = jnp.dot(xs.reshape(rows * wdt, -1), w_ref[:],
                 preferred_element_type=jnp.float32)
    yc = yf.astype(y_ref.dtype)
    y_ref[0] = yc.reshape(rows, wdt, -1)
    yb = yc.astype(jnp.float32)
    part = jnp.stack([jnp.sum(yb, axis=0),
                      jnp.sum(yb * yb, axis=0)])    # (2, Co)

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        p_ref[0] = part

    @pl.when(j > 0)
    def _accum():
        p_ref[0] = p_ref[0] + part


def _pallas_forward(x: jax.Array, w: jax.Array, interpret: bool):
    """(x [N,H,W,Ci], w [Ci,Co]) -> (y [N,H,W,Co] x.dtype,
    partials [N,2,Co] f32)."""
    n, h, wdt, ci = x.shape
    co = w.shape[-1]
    rows = _pick_rows(h, wdt, ci, co, bufs_ci=2, bufs_co=6)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(n, h // rows),
        in_specs=[
            pl.BlockSpec((1, rows, wdt, ci), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((ci, co), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, wdt, co), lambda i, j: (i, j, 0, 0)),
            # Constant over j: resident, accumulates across stripes.
            pl.BlockSpec((1, 2, co), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, wdt, co), x.dtype),
            jax.ShapeDtypeStruct((n, 2, co), jnp.float32),
        ],
        interpret=interpret,
    )(x, w)


# SPMD: the op is trivially parallel over batch (the kernel grids over
# N); H/W/channels stay replicated (Ci is contracted, Co would need w
# sharded). Split over the step's mesh (tpunet/ops/partition.py);
# without it the partitioner would all-gather the batch.

_B4 = P("data", None, None, None)
_B3 = P("data", None, None)
_R2 = P(None, None)

_partitioned_fwd = sharded(_pallas_forward, (_B4, _R2), (_B4, _B3))


# ---------------------------------------------------------------------------
# Backward kernel: recompute the elementwise epilogue in VMEM, fuse
# dx = t @ w^T and the per-image dw partial into the same stripe pass.
#
# Math (per channel, n = N*H*W, r = rsqrt(var+eps), yh = (y-mean)*r,
# inv = r*scale, shift = bias - mean*inv, gm = g * relu6_mask):
#   t  = inv * (gm - sum(gm)/n - yh * sum(gm*yh)/n)   # d loss / d y
#   dx = t @ w^T          dw = sum_n x^T t
#   dscale = sum(gm*yh)   dbias = sum(gm)
# The two batch reductions are the XLA prelude; everything per-element
# lives in the kernel, and t never hits HBM.
# ---------------------------------------------------------------------------


def _bwd_kernel(x_ref, g_ref, y_ref, w_ref, c_ref, dx_ref, dwp_ref, *,
                act: bool):
    xs = x_ref[0]                                   # (rows, W, Ci)
    gs = g_ref[0].astype(jnp.float32)               # (rows, W, Co)
    ys = y_ref[0].astype(jnp.float32)
    rows, wdt, ci = xs.shape
    co = gs.shape[-1]
    cf = c_ref[:]                                   # (6, Co) f32
    inv, shift, r, mr, e, f = (cf[0], cf[1], cf[2], cf[3], cf[4], cf[5])
    if act:
        yn = ys * inv + shift                       # pre-clamp activation
        gm = gs * ((yn > 0.0) & (yn < 6.0)).astype(jnp.float32)
    else:
        gm = gs
    yh = ys * r - mr                                # y-hat
    t = (inv * (gm - e - yh * f)).reshape(rows * wdt, co)
    dxs = jax.lax.dot_general(
        t, w_ref[:].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    dx_ref[0] = dxs.reshape(rows, wdt, ci).astype(dx_ref.dtype)
    part = jax.lax.dot_general(
        xs.reshape(rows * wdt, ci).astype(jnp.float32), t,
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dwp_ref[0] = part

    @pl.when(j > 0)
    def _accum():
        dwp_ref[0] = dwp_ref[0] + part


def _pallas_backward(x: jax.Array, g: jax.Array, y: jax.Array,
                     w: jax.Array, chan: jax.Array, act: bool,
                     interpret: bool):
    """-> (dx [N,H,W,Ci] x.dtype, per-image dw partials [N,Ci,Co] f32)."""
    n, h, wdt, ci = x.shape
    co = w.shape[-1]
    rows = _pick_rows(h, wdt, ci, co, bufs_ci=3, bufs_co=8)
    kern = functools.partial(_bwd_kernel, act=act)
    return pl.pallas_call(
        kern,
        grid=(n, h // rows),
        in_specs=[
            pl.BlockSpec((1, rows, wdt, ci), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, rows, wdt, co), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, rows, wdt, co), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((ci, co), lambda i, j: (0, 0)),
            pl.BlockSpec((6, co), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, wdt, ci), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, ci, co), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, wdt, ci), x.dtype),
            jax.ShapeDtypeStruct((n, ci, co), jnp.float32),
        ],
        interpret=interpret,
    )(x, g, y, w, chan)


_partitioned_bwd = sharded(_pallas_backward,
                           (_B4, _B4, _B4, _R2, _R2), (_B4, _B3))


# ---------------------------------------------------------------------------
# custom_vjp over the kernel path. Only shapes the kernel pays for enter
# this function (dispatch below), so the backward never needs a
# re-run-the-forward reference fallback.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused(x, w, scale, bias, act, eps, interpret):
    out, _mean, _var, _y, *_ = _fused_fwd_impl(x, w, scale, bias, act,
                                               eps, interpret)
    return out, _mean, _var


def _fused_fwd_impl(x, w, scale, bias, act, eps, interpret):
    with jax.named_scope("tpunet_fused_ir_fwd"):
        y, part = _partitioned_fwd(x, w, interpret)
    # The conv output is the residual the backward reads — name it for
    # the block-remat saved-residual policy (models/mobilenetv2.py).
    y = checkpoint_name(y, "tpunet_convout")
    n = x.shape[0] * x.shape[1] * x.shape[2]
    s = jnp.sum(part, axis=0)           # plain psum under batch sharding
    mean = s[0] / n
    var = jnp.maximum(0.0, s[1] / n - mean * mean)
    # Saved-residual names survive the custom_vjp boundary, so the
    # block-remat policy keeps the (C,)-sized stats here too.
    mean = checkpoint_name(mean, "tpunet_bn_stats")
    var = checkpoint_name(var, "tpunet_bn_stats")
    r = jax.lax.rsqrt(var + eps)
    inv = r * scale.astype(jnp.float32)
    shift = bias.astype(jnp.float32) - mean * inv
    o = y.astype(jnp.float32) * inv + shift
    if act:
        o = jnp.minimum(jnp.maximum(o, 0.0), 6.0)
    return o.astype(y.dtype), mean, var, y, inv, shift, r, mean * r


def _fused_fwd(x, w, scale, bias, act, eps, interpret):
    out, mean, var, y, inv, shift, r, mr = _fused_fwd_impl(
        x, w, scale, bias, act, eps, interpret)
    res = (x, w, scale, bias, y, inv, shift, r, mr)
    return (out, mean, var), res


def _fused_bwd(act, eps, interpret, res, cts):
    # cts = (g_out, g_mean, g_var); the stats outputs feed only the
    # (non-differentiated) running-stat update, so their cotangents are
    # treated as zero — the documented contract of this op.
    #
    # The ENTIRE body sits under the tpunet_fused_ir_bwd scope: a
    # custom_vjp backward carries no ``transpose(`` marker, so the
    # scope is what keeps the prelude's full-tensor g/y reads and the
    # dw batch-sum attributed to the backward phase / conv_bwd bucket
    # (tpunet/obs/hlo_bytes.py) instead of leaking into fwd.
    with jax.named_scope("tpunet_fused_ir_bwd"):
        x, w, scale, bias, y, inv, shift, r, mr = res
        g = cts[0]
        n = x.shape[0] * x.shape[1] * x.shape[2]
        axes = tuple(range(y.ndim - 1))
        yf = y.astype(jnp.float32)
        if act:
            yn = yf * inv + shift
            gm = g.astype(jnp.float32) * ((yn > 0.0) & (yn < 6.0)
                                          ).astype(jnp.float32)
        else:
            gm = g.astype(jnp.float32)
        yh = yf * r - mr
        r1 = jnp.sum(gm, axes)              # = dbias
        r2 = jnp.sum(gm * yh, axes)         # = dscale
        chan = jnp.stack([inv, shift, r, mr, r1 / n, r2 / n])
        dx, dwp = _partitioned_bwd(x, g, y, w, chan, act, interpret)
        dw = jnp.sum(dwp, axis=0).astype(w.dtype)   # psum stays in XLA
        return dx, dw, r2.astype(scale.dtype), r1.astype(bias.dtype)


_fused.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# Dispatch.
# ---------------------------------------------------------------------------


def _kernel_pays(shape) -> bool:
    """The chip's verdict for an input shape ``(N, H, W, Ci)``: does the
    Pallas pair beat the compiler's convolution IN THE STEP, layout
    copies around the call included? Not for any shape of the 224px
    model. One TPU v5 lite, 2026-10-03, batch 128, the benchmark's
    ``mnv2-224.train-b128`` cell traced (``fwd_bwd_ms.train``, 64-160
    steps, seed 1618033988; PERF.md section 6, PR 44), the pair engaged
    at ONE resolution and the reference everywhere else:

    ====== ================================== ========= ============
    H = W  engaged input shapes (Ci)          with pair without (B)
    ====== ================================== ========= ============
    112    32, 16                             50.21 ms  25.36 ms
    56     96, 24, 144, 24                    35.99 ms  25.36 ms
    28     144, 32 x3, 192 x2                 31.27 ms  25.36 ms
    14     192, 64 x4, 96 x3                  30.60 ms  25.36 ms
    14, 7  Ci >= H*W (the old rule's 13 off)  never ran the pair
    ====== ================================== ========= ============

    All 20 at once (the old rule ``Ci < H*W``): 71.67 ms and 1,764
    img/s against 25.36 ms and 4,848 img/s untraced (three seeds a
    side). A resolution had to win by 1% to engage; the closest loses
    by 21%, so batch 512 (not measured) was not needed to decide. Of
    the 72.2 ms of operations with the 20 engaged, the kernels are
    16.4, the layout copies around them 15.7 and the neighbouring
    elementwise fusions, run channel-minor on lane-padded C, 37.7 (the
    module docstring says why)."""
    del shape
    return False


def use_fused_ir_kernel(shape) -> bool:
    """Would ``conv1x1_bn_act`` run the Pallas pair for this input
    shape on the current backend? (Factored out for tests and for the
    docs' per-shape table.)"""
    if jax.default_backend() != "tpu":
        return False
    if os.environ.get("TPUNET_FUSED_IR_REF"):
        return False
    return _kernel_pays(shape)


def conv1x1_bn_act(x: jax.Array, w: jax.Array, scale: jax.Array,
                   bias: jax.Array, act: bool = True, eps: float = 1e-5,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused train-mode 1x1-conv + BatchNorm-stats + scale/shift
    (+ReLU6): -> (out, batch_mean, batch_var).

    ``x`` [N,H,W,Ci], ``w`` [Ci,Co]; ``scale``/``bias`` are the BN
    affine params. On TPU, shapes passing ``_kernel_pays`` run the
    Pallas kernel pair under ``jax.custom_vjp``; everything else (and
    every other backend, and ``TPUNET_FUSED_IR_REF=1``) runs the XLA
    reference, whose ops mirror the unfused module path exactly — so
    the flag flips freely on existing checkpoints. ``interpret=True``
    forces the kernels through the Pallas interpreter (tests).

    The ``mean``/``var`` outputs are auxiliary (running-stat updates):
    their cotangents are treated as zero by the custom backward.
    """
    if interpret is None:
        if not use_fused_ir_kernel(x.shape):
            return conv1x1_bn_act_reference(x, w, scale, bias, act, eps)
        interpret = False
    return _fused(x, w, scale, bias, act, eps, interpret)
