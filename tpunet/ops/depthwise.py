"""Pallas TPU kernel: 3x3 depthwise convolution (NHWC, torch padding=1).

This is the VPU-bound hot op of MobileNetV2 (the reference consumes it
through cuDNN inside torchvision's ``mobilenet_v2``; here it is a
first-class kernel). Design:

- Input is pre-padded by one pixel (XLA fuses the pad), so the kernel
  body is 9 shifted multiply-adds over a VMEM-resident image — pure VPU
  work with no bounds logic. Channels ride the lane dimension (NHWC).
- Grid is (batch, row_stripes); each program computes one stripe of
  output rows, slicing its input rows (+2-row halo) from the resident
  padded image with ``pl.ds``. Whole-image programs would overflow the
  16 MB scoped-vmem stack: the 9 float32 tap temporaries at a 112x112
  layer alone are ~14 MB (stride 2's slice/reshape trick reads ~4x
  more, so the stripe height budget is stride-aware — ``_pick_rows``).
- Stride 2 is expressed as slice + reshape + take (no strided vector
  slices, which Mosaic handles poorly).
- Accumulation in float32 regardless of compute dtype; output cast back.
- ``jax.custom_vjp``: forward runs the Pallas kernel; backward runs
  IO-aware Pallas kernels with the same stripe/halo VMEM design
  (``_bwd_kernel``): dx is a stride-1 correlation with the flipped taps
  over the (for stride 2, zero-dilated IN VMEM) output gradient, and dw
  is reduced per image in float32 inside the same kernel — the
  transposed-conv lowering XLA emits for the reference (input-dilated
  gradient image, window-gathered weight reduction) never materializes
  its dilated/padded temporaries in HBM. Off-TPU (and for any caller
  that asks via ``interpret=None`` on a non-TPU backend) the backward
  stays the transpose of the XLA reference via ``jax.vjp``, exactly as
  before. Remaining known HBM amplification on the Pallas path: the
  host-side ``jnp.pad`` of x/g feeding the kernels (~(1 + 2/H)^2 of one
  activation each) — the kernel body itself reads each padded image
  once and writes dx/per-image dw partials once.

Numerically identical (up to dtype rounding) to
``depthwise_conv3x3_reference`` — property-tested (forward AND both
backward kernels, stride 1 and 2, odd sizes, off-lane-multiple
channels) in interpret mode on CPU (tests/test_ops.py).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def depthwise_conv3x3_reference(x: jax.Array, w: jax.Array,
                                stride: int = 1) -> jax.Array:
    """XLA reference: x [N,H,W,C], w [3,3,C] -> [N,Ho,Wo,C], padding=1."""
    return jax.lax.conv_general_dilated(
        x, w[:, :, None, :],
        window_strides=(stride, stride),
        padding=((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1],
    )


def _tap(x, dy: int, dx: int, ho: int, wo: int, stride: int):
    """x [Hp, Wp, C] -> the (ho, wo, C) input samples for kernel tap
    (dy, dx): rows dy, dy+stride, ...; cols dx, dx+stride, ..."""
    if stride == 1:
        return x[dy:dy + ho, dx:dx + wo]
    v = x[dy:dy + stride * ho, dx:dx + stride * wo]
    c = v.shape[-1]
    v = v.reshape(ho, stride, stride * wo, c)[:, 0]
    return v.reshape(ho, wo, stride, c)[:, :, 0]


def _kernel(x_ref, w_ref, o_ref, *, wo: int, stride: int, rows: int):
    """Compute one ``rows``-high output stripe per grid step. The 9
    float32 tap temporaries are stripe-sized, not image-sized —
    computing the whole image in one program overflows the 16 MB
    scoped-vmem stack at the 224px workload's 112x112 layers (9 taps x
    112x112xC x 4B; an in-kernel loop doesn't help because Mosaic's
    stack allocator sums the iterations' temporaries)."""
    w = w_ref[:]                       # (3, 3, C)
    c = x_ref.shape[-1]
    bh = stride * rows + 2             # input rows feeding one stripe
                                       # (max tap offset dy=2 + stride*rows)
    r0 = pl.program_id(1) * rows
    xs = x_ref[0, pl.ds(r0 * stride, bh)]   # (bh, Wp, C) stripe
    acc = jnp.zeros((rows, wo, c), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            t = _tap(xs, dy, dx, rows, wo, stride).astype(jnp.float32)
            acc = acc + t * w[dy, dx].astype(jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype)


def _pick_rows(ho: int, wo: int, c: int, stride: int,
               bufs: int = 12) -> int:
    """Largest divisor of ho whose stripe temporaries (~``bufs`` f32
    buffers: 9 taps + accumulator + slack; stride 2's slice/reshape
    trick reads ~stride^2 x more elements per tap) stay within a ~4 MB
    budget. The backward kernel passes a larger ``bufs`` (its stripes
    carry dx taps AND dw reduction temporaries)."""
    budget = 4 * 1024 * 1024
    for rows in range(ho, 0, -1):
        if ho % rows == 0 and rows * wo * c * 4 * bufs * stride**2 <= budget:
            return rows
    return 1


def _pallas_forward(x: jax.Array, w: jax.Array, stride: int,
                    interpret: bool) -> jax.Array:
    n, h, w_in, c = x.shape
    ho = (h - 1) // stride + 1
    wo = (w_in - 1) // stride + 1
    # Pad so every tap's full slice (stride*ho rows from offset <=2, for
    # the stride>1 reshape trick) stays in bounds; the extra zero rows
    # land only in discarded reshape positions. The last stripe's
    # dynamic_slice ends exactly at hp = stride*ho + 2, in bounds.
    pad_b = stride * ho + 1 - h
    pad_r = stride * wo + 1 - w_in
    xp = jnp.pad(x, ((0, 0), (1, pad_b), (1, pad_r), (0, 0)))
    hp, wp = xp.shape[1], xp.shape[2]

    rows = _pick_rows(ho, wo, c, stride)
    kern = functools.partial(_kernel, wo=wo, stride=stride, rows=rows)
    return pl.pallas_call(
        kern,
        grid=(n, ho // rows),
        in_specs=[
            # Whole padded image per program (same block for every
            # stripe index — Pallas keeps it resident); the kernel
            # slices its stripe (+halo) out with pl.ds.
            pl.BlockSpec((1, hp, wp, c), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, c), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, wo, c), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, c), x.dtype),
        interpret=interpret,
    )(xp, w)


# ---------------------------------------------------------------------------
# SPMD partitioning: a pallas_call is opaque to GSPMD, so without help the
# partitioner would all-gather the batch onto every device. The op is
# trivially parallel over batch (the kernel grids over N), so it is split
# over the step's mesh by batch (tpunet/ops/partition.py) and each
# device runs a per-shard pallas call. H/W/channels stay replicated.
# ---------------------------------------------------------------------------

from jax.sharding import PartitionSpec as P

from tpunet.ops.partition import sharded

_B4 = P("data", None, None, None)
_R3 = P(None, None, None)

_partitioned = sharded(_pallas_forward, (_B4, _R3), _B4)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def depthwise_conv3x3(x: jax.Array, w: jax.Array, stride: int = 1,
                      interpret: Optional[bool] = None) -> jax.Array:
    """3x3 depthwise conv, NHWC, padding=1 (torch semantics).

    ``x`` [N,H,W,C], ``w`` [3,3,C]. Forward runs the Pallas kernel on
    TPU; off-TPU the default is the XLA reference (the Pallas
    interpreter is far too slow for a hot path — pass ``interpret=True``
    explicitly to exercise the kernel in tests). Under SPMD jit it
    partitions over batch/channels via the registered rule. Gradients
    are exactly the XLA reference's.
    """
    if interpret is None:
        if jax.default_backend() != "tpu":
            return depthwise_conv3x3_reference(x, w, stride)
        interpret = False
    # Named for byte/phase attribution (tpunet/obs/hlo_bytes.py): the
    # kernel lowers to a custom call, not a convolution opcode, so the
    # scope is what keeps it in the conv_fwd bucket.
    with jax.named_scope("tpunet_depthwise_fwd"):
        return _partitioned(x, w, stride, interpret)


# ---------------------------------------------------------------------------
# IO-aware backward kernels. The math: with xp = pad(x, 1) and
# out[i,j] = sum_{dy,dx} xp[s*i+dy, s*j+dx] * w[dy,dx],
#
#   dw[dy,dx,c] = sum_{n,i,j} xp[n, s*i+dy, s*j+dx, c] * g[n,i,j,c]
#   dx[p,q,c]   = sum_{dy',dx'} G[p+dy', q+dx', c] * w[2-dy', 2-dx', c]
#
# where G is the gradient image zero-DILATED by the stride and shifted
# by the forward padding: G[a,b] = g[(a-1)/s, (b-1)/s] when both are
# whole in-range numbers, else 0. I.e. dx is a plain stride-1
# correlation with the flipped taps over the dilated gradient — the
# dilation is built IN VMEM per stripe (zero-interleaving via
# stack+reshape, the same no-strided-vector-ops discipline as the
# forward's stride trick), so the 4x-elements dilated image the XLA
# transposed conv materializes never exists in HBM. dw partials are
# reduced per image in float32 inside the same kernel and summed over
# batch OUTSIDE the pallas_call: the (N, 3, 3, C) partial is tiny, and
# summing it in XLA keeps the op trivially batch-partitionable (the
# data-parallel gradient all-reduce stays a plain psum XLA inserts from
# shardings, instead of a collective the kernel would have to own).
# ---------------------------------------------------------------------------


def _bwd_kernel(xp_ref, gp_ref, w_ref, dx_ref, dwp_ref, *,
                wo: int, stride: int, rows: int):
    """One output-row stripe per grid step, same stripe/halo design as
    the forward kernel: the stripe's dw partial accumulates into the
    per-image (3, 3, C) block across grid steps (j == 0 initializes),
    and the stride*rows dx rows this stripe's gradient reaches are
    computed once. All tap temporaries are stripe-sized f32 in VMEM."""
    w = w_ref[:]                        # (3, 3, C)
    c = xp_ref.shape[-1]
    j = pl.program_id(1)
    r0 = j * rows                       # first output (gradient) row

    # -- dw partial: sum over stripe of xp[s*i+dy, s*j+dx] * g[i, j] --
    bh = stride * rows + 2
    xs = xp_ref[0, pl.ds(r0 * stride, bh)]            # (bh, Wp, C)
    if stride == 1:
        # gp is pad(g, 1): the unpadded gradient is its interior.
        gs_dw = gp_ref[0, pl.ds(r0 + 1, rows)][:, 1:1 + wo]
    else:
        # gp is pad(g, (0,1),(0,1)): rows/cols [0, rows)/[0, wo).
        gs_dw = gp_ref[0, pl.ds(r0, rows)][:, :wo]
    gf = gs_dw.astype(jnp.float32)
    parts = []
    for dy in range(3):
        for dx in range(3):
            t = _tap(xs, dy, dx, rows, wo, stride).astype(jnp.float32)
            parts.append(jnp.sum(t * gf, axis=(0, 1)))  # (C,)
    part = jnp.stack(parts).reshape(3, 3, c)

    @pl.when(j == 0)
    def _init():
        dwp_ref[0] = part

    @pl.when(j > 0)
    def _accum():
        dwp_ref[0] = dwp_ref[0] + part

    # -- dx: stride-1 flipped-tap correlation over the dilated g ------
    rows_in = stride * rows
    if stride == 1:
        # No dilation: G rows [p0, p0+rows+2) are gp rows directly.
        G = gp_ref[0, pl.ds(r0, rows_in + 2)]         # (rows+2, W+2, C)
    else:
        # Zero-dilate in VMEM: G[t] = g[r0 + (t-1)/2] for odd t else 0
        # (p0 = stride*r0 is even, so stripe-local parity == global).
        # Dilated in float32: Mosaic refuses the unit-dim insert of the
        # stack on packed 16-bit rows ("unsupported shape cast",
        # jax 0.9.0 / libtpu 0.0.34), and the taps below are summed in
        # float32 anyway.
        gs = gp_ref[0, pl.ds(r0, rows + 1)].astype(jnp.float32)
        z = jnp.zeros_like(gs)                        # (rows+1, wo+1, C)
        G = jnp.stack([z, gs], axis=2).reshape(rows + 1, -1, c)
        G = jnp.stack([jnp.zeros_like(G), G], axis=1).reshape(
            rows_in + 2, -1, c)
    wout = stride * wo
    acc = jnp.zeros((rows_in, wout, c), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            t = G[dy:dy + rows_in, dx:dx + wout].astype(jnp.float32)
            acc = acc + t * w[2 - dy, 2 - dx].astype(jnp.float32)
    dx_ref[0] = acc.astype(dx_ref.dtype)


def _pallas_backward(x: jax.Array, w: jax.Array, g: jax.Array,
                     stride: int, interpret: bool):
    """(x, w, g) -> (dx, per-image dw partials [N, 3, 3, C] f32)."""
    n, h, w_in, c = x.shape
    ho = (h - 1) // stride + 1
    wo = (w_in - 1) // stride + 1
    pad_b = stride * ho + 1 - h
    pad_r = stride * wo + 1 - w_in
    xp = jnp.pad(x, ((0, 0), (1, pad_b), (1, pad_r), (0, 0)))
    if stride == 1:
        gp = jnp.pad(g, ((0, 0), (1, 1), (1, 1), (0, 0)))
    else:
        # Dilation supplies the leading zero row/col; one trailing
        # zero row/col keeps the last stripe's slices in bounds.
        gp = jnp.pad(g, ((0, 0), (0, 1), (0, 1), (0, 0)))
    hp, wp = xp.shape[1], xp.shape[2]
    gh, gw = gp.shape[1], gp.shape[2]

    rows = _pick_rows(ho, wo, c, stride, bufs=24)
    kern = functools.partial(_bwd_kernel, wo=wo, stride=stride, rows=rows)
    hout, wout = stride * ho, stride * wo
    dx_full, dwp = pl.pallas_call(
        kern,
        grid=(n, ho // rows),
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1, gh, gw, c), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, c), lambda i, j: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, stride * rows, wout, c),
                         lambda i, j: (i, j, 0, 0)),
            # Constant index map over j: the block stays resident and
            # accumulates across the image's stripes (standard TPU
            # revisiting pattern; the grid is sequential per image).
            pl.BlockSpec((1, 3, 3, c), lambda i, j: (i, 0, 0, 0)),
        ],
        out_shape=[
            # dx covers [0, stride*ho) rows; rows >= h (at most one
            # phantom row/col for odd sizes) are sliced off below.
            jax.ShapeDtypeStruct((n, hout, wout, c), x.dtype),
            jax.ShapeDtypeStruct((n, 3, 3, c), jnp.float32),
        ],
        interpret=interpret,
    )(xp, gp, w)
    return dx_full[:, :h, :w_in], dwp


# (dx [N,H,W,C], per-image dw partials [N,3,3,C]) — both batch-split.
_partitioned_bwd = sharded(_pallas_backward, (_B4, _R3, _B4),
                           (_B4, _B4))


def _reference_bwd(x, w, g, stride):
    _, vjp = jax.vjp(lambda xx, ww: depthwise_conv3x3_reference(
        xx, ww, stride), x, w)
    return vjp(g)


def _fwd(x, w, stride, interpret):
    # With nondiff_argnums, f_fwd takes the primal's full signature;
    # f_bwd gets the nondiff args first.
    return depthwise_conv3x3(x, w, stride, interpret), (x, w)


def _bwd(stride, interpret, res, g):
    # The whole body sits under the tpunet_depthwise_bwd scope: a
    # custom_vjp backward carries no ``transpose(`` marker, so the
    # scope is what keeps the kernel's custom call (and the reference
    # fallback's transposed conv, and the dw batch-sum) attributed to
    # the backward phase / conv_bwd bucket (tpunet/obs/hlo_bytes.py)
    # instead of leaking into fwd — the same contract as the fused-IR
    # pair's backward.
    with jax.named_scope("tpunet_depthwise_bwd"):
        x, w = res
        # Mirror the primal's dispatch: interpret=None means "Pallas on
        # TPU, XLA reference elsewhere" (the interpreter is too slow
        # for a hot path); interpret=True exercises the kernels in
        # tests. TPUNET_DEPTHWISE_REF_BWD=1 is the escape hatch back to
        # the reference-transpose backward (e.g. a Mosaic regression on
        # a new toolchain) without giving up the Pallas forward.
        if interpret is None:
            if jax.default_backend() != "tpu":
                return _reference_bwd(x, w, g, stride)
            interpret = False
        if os.environ.get("TPUNET_DEPTHWISE_REF_BWD"):
            return _reference_bwd(x, w, g, stride)
        dx, dwp = _partitioned_bwd(x, w, g, stride, interpret)
        return dx, jnp.sum(dwp, axis=0).astype(w.dtype)


depthwise_conv3x3.defvjp(_fwd, _bwd)
