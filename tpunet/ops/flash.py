"""Pallas TPU kernel: flash attention (fused online-softmax attention).

The pure-JAX attention family (tpunet/ops/attention.py) bounds MEMORY
via lax.scan online softmax, but XLA still materializes each [bq, Tk]
score block in HBM between the two einsums. This kernel fuses
scores -> online softmax -> weighted values into one VMEM-resident
program per (batch, head, q-block): scores never leave VMEM, the two
matmuls hit the MXU back-to-back, and the running (m, l, acc) state
lives in scratch that persists across the sequential k-block grid axis
(the standard TPU FlashAttention schedule).

Design notes:
- Grid (B, H, nq, nk); TPU iterates the LAST axis sequentially on one
  core, so VMEM scratch carries the online-softmax state across k
  blocks; @pl.when(k==0) initializes, @pl.when(k==nk-1) finalizes.
- m/l scratch is (bq, 128): Mosaic wants the lane dim, values are
  broadcast across it and read back as [:, :1].
- Causal masking uses the same "explicitly zero masked probabilities"
  convention as tpunet/ops/attention.py (fully-masked rows emit zeros,
  not uniform attention).
- float32 accumulation regardless of compute dtype (MXU-native bf16 in,
  f32 out of the dot).
- Backward: two more Pallas kernels (the standard flash backward) —
  probabilities are recomputed per block from the saved log-sum-exp, so
  nothing O(Tq x Tk) touches HBM in either direction. dQ accumulates
  over k blocks on grid (B,H,nq,nk); dK/dV accumulate over q blocks on
  the transposed grid (B,H,nk,nq); delta = rowsum(dO * O) is plain XLA.
- Off-TPU the public entry falls back to dense_attention (the Pallas
  interpreter is far too slow for a hot path); tests exercise the real
  kernel body on CPU with interpret=True, the same scheme as
  tpunet/ops/depthwise.py.
- ``flash_prefill`` (the serve engine's row prefill of a grouped-query
  model, and its training forward): K and V keep their own, fewer heads
  — the K/V index maps send query head h to KV head ``h // group``, so
  nothing is repeated in HBM — and a sliding ``window`` walks a BAND of
  the grid: per query block only the k blocks that hold a key in sight,
  the dead leading steps of the first rows clamped to block 0 (no copy,
  no product). Its backward: dQ on the same band; dK/dV one grid row a
  KV head and k block, accumulated over the group's query heads and
  the q blocks ``j .. j + band - 1`` that see block ``j``.

Measured on a real TPU v5e chip (B=4, T=4096, H=8, D=64, causal,
bfloat16; synchronized by fetching a data-dependent output element;
scripts/bench_flash.py):

  round 1 (rectangular causal grid + @pl.when skip):
    fwd: flash 10.7 ms vs dense 25.6 ms vs blockwise 17.1 ms
  round 2 (fused TRIANGULAR causal grids — fwd, dQ, AND dK/dV (upper
  triangle via point reflection of the same inversion) — dead copies
  elided on the remaining rectangular cross-length paths):
    fwd: flash 8.2-8.6 ms (-20% vs round 1; ~2.5x dense's 20.8 ms)
    fwd+bwd: flash 12.8 ms vs dense 39.8 ms (3.1x) vs blockwise 50.7 ms
    segments (4 packed docs): 8.0 ms fwd — masking costs ~nothing

End-to-end LM training (fwd + bwd + Adam, the numbers that matter):
357k tok/s at T=2048 vs 157k dense (2.3x; was 339k with the
rectangular grid), and 135k tok/s at T=8192+remat vs 28k blockwise —
the flash backward kernels remove the O(T²) HBM traffic that binds the
dense backward (scripts/bench_lm.py; full table in README.md).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpunet.ops.attention import (_NEG_INF, _divisor_block,
                                  dense_attention)


def _tri_qi_ki(t):
    """Invert the row-major lower-triangle linearization: step t ->
    (qi, ki) with ki <= qi; t = qi*(qi+1)/2 + ki. Float sqrt with an
    exact integer correction (sqrt rounding can be off by one at the
    triangular-number boundaries)."""
    qi = ((jnp.sqrt(8.0 * t.astype(jnp.float32) + 1.0) - 1.0) / 2.0
          ).astype(jnp.int32)
    qi = jnp.where(t < qi * (qi + 1) // 2, qi - 1, qi)
    qi = jnp.where(t >= (qi + 1) * (qi + 2) // 2, qi + 1, qi)
    return qi, t - qi * (qi + 1) // 2


def _tri_ki_qi_upper(t, nq: int):
    """Invert the row-major UPPER-triangle linearization used by the
    dK/dV grid: rows are k blocks, each accumulating q blocks
    qi = ki..nq-1. Reuses the tested lower-triangle inversion through a
    point reflection: enumerating the upper triangle forward equals
    enumerating the lower one backward with both coordinates flipped.
    """
    total = nq * (nq + 1) // 2
    lo_qi, lo_ki = _tri_qi_ki(total - 1 - t)
    return nq - 1 - lo_qi, nq - 1 - lo_ki      # (ki, qi)


def _use_tri(causal, tq, tk, bq, bk) -> bool:
    """Triangular-grid eligibility: causal SELF-attention with square
    blocks — every diagonal block is then partially valid and every
    sub-diagonal block fully valid, so the lower triangle enumerates
    exactly the needed (qi, ki) pairs. The sqrt inversion in
    _tri_qi_ki runs in float32: its ~2^-24 relative error keeps the
    qi estimate within reach of the ±1 integer correction only while
    the triangle size stays under 2**23 (verified exhaustively at
    nq=4095); beyond that (tiny blocks on a very long sequence) fall
    back to the rectangular grid rather than risk silently enumerating
    wrong pairs."""
    if not (causal and tq == tk and bq == bk):
        return False
    nq = -(-tq // bq)
    return nq * (nq + 1) // 2 < 2 ** 23


def _seg_mask(qseg_ref, kseg_ref):
    """[bq, bk] same-segment mask from the lane-broadcast q segment ids
    ([bq, 128], read [:, :1]) and sublane-broadcast kv segment ids
    ([8, bk], read [:1, :]) — the stock TPU flash kernel's layouts."""
    return qseg_ref[0, :, :1] == kseg_ref[0, :1, :]


def _kernel(q_ref, k_ref, v_ref, *refs,
            scale: float, causal: bool, bq: int, bk: int, nk: int,
            tq: int, tk: int, with_lse: bool, tri: bool,
            with_segments: bool, window: int = 0, band: int = 0):
    # Optional operands/outputs resolved by arity: segment-id inputs
    # come after v; the lse output exists only on the residual
    # (training-forward) variant — the forward-only path skips its HBM
    # writes entirely.
    if with_segments:
        qseg_ref, kseg_ref, *refs = refs
    o_ref, *refs = refs
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = refs
    else:
        m_ref, l_ref, acc_ref = refs
    if band:
        # Banded grid (a sliding window over a causal self-attention,
        # square blocks): step j of query block qi is k block
        # qi - (band - 1) + j; the blocks before the sequence are dead.
        qi = pl.program_id(2)
        ki = qi - (band - 1) + pl.program_id(3)
        first, last, needed = pl.program_id(3) == 0, ki == qi, ki >= 0
    elif tri:
        # Fused lower-triangular grid: only needed (qi, ki) pairs exist,
        # no dead steps at all (VERDICT r1 item 5).
        qi, ki = _tri_qi_ki(pl.program_id(2))
        first, last, needed = ki == 0, ki == qi, True
    else:
        qi = pl.program_id(2)  # program ids are hoisted out of the
        ki = pl.program_id(3)  # pl.when bodies (cond sub-traces cannot
                               # bind pallas primitives in interpret mode)
        first, last = ki == 0, ki == nk - 1
        # Causal (cross-length rectangular grid): skip BOTH MXU dots for
        # k blocks entirely in this q block's future; their k/v copies
        # are also elided via the clamped index maps in _forward_impl.
        needed = ((qi + 1) * bq - 1 + (tk - tq) >= ki * bk) if causal \
            else True

    @pl.when(first)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(needed)
    def _compute():
        # Dots run in the INPUT dtype with f32 accumulation (bf16 MXU
        # throughput; attention.py's einsums use the same convention).
        q = q_ref[0, 0]                            # [bq, D]
        k = k_ref[0, 0]                            # [bk, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = None
        if causal:
            # Global positions; the tk - tq offset matches
            # dense_attention's convention for decode windows.
            qpos = (qi * bq
                    + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            kpos = (ki * bk
                    + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
            mask = qpos + (tk - tq) >= kpos
            if window:               # the query itself counts
                mask = mask & (kpos > qpos + (tk - tq) - window)
        if with_segments:
            seg = _seg_mask(qseg_ref, kseg_ref)
            mask = seg if mask is None else mask & seg
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]                      # [bq, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                     # [bq, bk]
        if mask is not None:
            # Fully-masked ROWS keep m at the init floor; exp(s - m)
            # there is 1, so zero the masked probabilities explicitly
            # (same convention as attention.py's _block_update).
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)             # [bq, 1]
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(last)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        if with_lse:
            # Log-sum-exp residual for the backward kernels: p can then
            # be recomputed per block as exp(s - lse) without the
            # running (m, l) state. Fully-masked rows keep the _NEG_INF
            # floor. Broadcast across the 128-lane dim (Mosaic block
            # constraint — the scheme of jax's stock TPU flash kernel).
            lse = jnp.where(l == 0.0, _NEG_INF,
                            m_ref[:, :1] + jnp.log(l_safe))
            lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _grid_and_maps(causal, bq, bk, nq, nk, tq, tk, b, h,
                   transposed: bool = False):
    """(grid, qmap, kvmap, qsegmap, ksegmap) for the flash pallas_calls.

    Default: the forward/dQ iteration order (q rows, k accumulated) —
    triangular when eligible (no dead steps at all), else rectangular
    with the k/v index maps CLAMPED for causal so dead blocks
    re-reference the previous block and Mosaic elides their copies
    (same-index revisiting).

    ``transposed``: the dK/dV order (k rows, q accumulated) — the upper
    triangle when eligible, else rectangular with the q-side maps
    clamped to the first needed q block of each k row (dead LEADING
    steps elided the same way).
    """
    if _use_tri(causal, tq, tk, bq, bk):
        if transposed:
            qb = lambda t: _tri_ki_qi_upper(t, nq)[1]
            kb = lambda t: _tri_ki_qi_upper(t, nq)[0]
        else:
            qb = lambda t: _tri_qi_ki(t)[0]
            kb = lambda t: _tri_qi_ki(t)[1]
        return ((b, h, nq * (nq + 1) // 2),
                lambda b, h, t: (b, h, qb(t), 0),
                lambda b, h, t: (b, h, kb(t), 0),
                lambda b, h, t: (b, qb(t), 0),
                lambda b, h, t: (b, 0, kb(t)))
    if transposed:
        if causal:
            qmin = lambda j: jnp.clip((j * bk - (tk - tq)) // bq,
                                      0, nq - 1)
            i_eff = lambda j, i: jnp.maximum(i, qmin(j))
        else:
            i_eff = lambda j, i: i
        return ((b, h, nk, nq),
                lambda b, h, j, i: (b, h, i_eff(j, i), 0),
                lambda b, h, j, i: (b, h, j, 0),
                lambda b, h, j, i: (b, i_eff(j, i), 0),
                lambda b, h, j, i: (b, 0, j))
    if causal:
        kmax = lambda i: jnp.clip(((i + 1) * bq - 1 + (tk - tq)) // bk,
                                  0, nk - 1)
        j_eff = lambda i, j: jnp.minimum(j, kmax(i))
    else:
        j_eff = lambda i, j: j
    return ((b, h, nq, nk),
            lambda b, h, i, j: (b, h, i, 0),
            lambda b, h, i, j: (b, h, j_eff(i, j), 0),
            lambda b, h, i, j: (b, i, 0),
            lambda b, h, i, j: (b, 0, j_eff(i, j)))


def _seg_operands(segment_ids, b, tq, tk):
    """(q_seg [B,Tq,128] lane-broadcast, kv_seg [B,8,Tk] sublane-
    broadcast) int32 — Mosaic-friendly layouts for 1-D per-token ids."""
    q_seg, kv_seg = segment_ids
    q_seg = jnp.asarray(q_seg, jnp.int32)
    kv_seg = jnp.asarray(kv_seg, jnp.int32)
    if q_seg.shape != (b, tq) or kv_seg.shape != (b, tk):
        raise ValueError(
            f"segment_ids shapes {q_seg.shape}/{kv_seg.shape} != "
            f"({(b, tq)}/{(b, tk)})")
    return (jnp.broadcast_to(q_seg[:, :, None], (b, tq, 128)),
            jnp.broadcast_to(kv_seg[:, None, :], (b, 8, tk)))


def _band_blocks(window: int, block: int, nq: int) -> int:
    """k blocks a query block of a windowed causal self-attention can
    see (square blocks): its own and those that hold one of the
    ``window - 1`` keys before its first query."""
    return min(nq, -(-(window - 1) // block) + 1)


def _rows_grid(grid, qmap, kvmap, b, h, nq, band: int, group: int):
    """The forward's and dQ's ``(grid, qmap, kvmap)`` under a band
    (step j of query block i is k block ``i - (band - 1) + j``, the
    dead leading steps clamped to block 0: no copy) and with K/V read
    by head group (query head h on KV head ``h // group``)."""
    if band:
        grid = (b, h, nq, band)
        qmap = lambda b, h, i, j: (b, h, i, 0)          # noqa: E731
        kvmap = lambda b, h, i, j: (                    # noqa: E731
            b, h, jnp.maximum(i - (band - 1) + j, 0), 0)
    if group > 1:
        per_head = kvmap
        kvmap = lambda b, h, *at: per_head(b, h // group, *at)  # noqa: E731
    return grid, qmap, kvmap


def _forward_impl(q, k, v, causal, scale, block_q, block_k, interpret,
                  with_lse: bool, segment_ids=None, window: int = 0):
    """``k`` / ``v`` may hold fewer heads than ``q`` (a whole group of
    query heads a KV head); ``window`` > 0 needs causal self-attention
    with square blocks (``flash_prefill``, whose backward is
    ``_pallas_backward`` under the same two)."""
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, d = q.shape
    tk = k.shape[1]
    bq = _divisor_block(tq, block_q)
    bk = _divisor_block(tk, block_k)
    nq, nk = tq // bq, tk // bk
    tri = _use_tri(causal, tq, tk, bq, bk)
    with_seg = segment_ids is not None
    group = h // k.shape[2]
    band = 0
    if window:
        if not (causal and tq == tk and bq == bk) or with_seg:
            raise ValueError("a window is built for causal self-attention "
                             "with square blocks, without segments")
        band = _band_blocks(window, bq, nq)

    qt = q.swapaxes(1, 2)                          # [B, H, Tq, D]
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, nk=nk, tq=tq, tk=tk,
                             with_lse=with_lse, tri=tri,
                             with_segments=with_seg, window=window,
                             band=band)
    grid, qmap, kvmap, qsegmap, ksegmap = _grid_and_maps(
        causal, bq, bk, nq, nk, tq, tk, b, h)
    grid, qmap, kvmap = _rows_grid(grid, qmap, kvmap, b, h, nq, band, group)

    in_specs = [
        pl.BlockSpec((1, 1, bq, d), qmap),
        pl.BlockSpec((1, 1, bk, d), kvmap),
        pl.BlockSpec((1, 1, bk, d), kvmap),
    ]
    args = [qt, kt, vt]
    if with_seg:
        qs, ks = _seg_operands(segment_ids, b, tq, tk)
        in_specs += [pl.BlockSpec((1, bq, 128), qsegmap),
                     pl.BlockSpec((1, 8, bk), ksegmap)]
        args += [qs, ks]

    o_spec = pl.BlockSpec((1, 1, bq, d), qmap)
    o_shape = jax.ShapeDtypeStruct((b, h, tq, d), q.dtype)
    lse_spec = pl.BlockSpec((1, 1, bq, 128), qmap)
    lse_shape = jax.ShapeDtypeStruct((b, h, tq, 128), jnp.float32)
    # Named for byte/phase attribution (tpunet/obs/hlo_bytes.py
    # KERNEL_SCOPES): the kernel lowers to a custom call, not a dot
    # opcode, so the scope is what keeps it in the matmul bucket.
    with jax.named_scope("tpunet_flash_fwd"):
        res = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=in_specs,
            out_specs=[o_spec, lse_spec] if with_lse else o_spec,
            out_shape=[o_shape, lse_shape] if with_lse else o_shape,
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),    # running max m
                pltpu.VMEM((bq, 128), jnp.float32),    # running normalizer l
                pltpu.VMEM((bq, d), jnp.float32),      # un-normalized acc
            ],
            interpret=interpret,
        )(*args)
    if with_lse:
        out, lse = res
        # out back to BTHD; lse squeezed to [B, H, Tq] (the kernel
        # wrote identical values across the 128-lane dim).
        return out.swapaxes(1, 2), lse[..., 0]
    return res.swapaxes(1, 2)


def _pallas_forward_res(q, k, v, causal, scale, block_q, block_k,
                        interpret):
    """-> (out [B,Tq,H,D], lse [B,H,Tq]) — the training forward.

    FIXED ARITY: split over the mesh by operand position
    (tpunet/ops/partition.py) and wrapped in custom_vjp, where a
    trailing default parameter would count as an operand slot — the
    segmented variants below are separate functions for that reason.
    """
    return _forward_impl(q, k, v, causal, scale, block_q, block_k,
                         interpret, with_lse=True)


def _pallas_forward(q, k, v, causal, scale, block_q, block_k, interpret):
    """-> out only; no lse HBM writes (the inference/eval forward)."""
    return _forward_impl(q, k, v, causal, scale, block_q, block_k,
                         interpret, with_lse=False)


# ---------------------------------------------------------------------------
# Backward kernels (the standard two-pass flash backward): probabilities
# are recomputed per block from the saved log-sum-exp, so nothing
# O(Tq x Tk) ever touches HBM. delta = rowsum(dO * O) is plain XLA.
#   dQ:    grid (B, H, nq, nk), accumulate over k blocks
#   dK/dV: grid (B, H, nk, nq), accumulate over q blocks
# ---------------------------------------------------------------------------


def _recompute_p_ds(q, k, v, do, lse, delta, glse, scale, causal,
                    qi, ki, bq, bk, tq, tk, seg=None, window: int = 0):
    """Shared block math: p = exp(s - lse) (masked), dp = dO Vᵀ,
    ds = p * (dp - delta + glse) * scale. All f32; lse/delta/glse are
    [bq, 1]. ``glse`` is the cotangent of the lse OUTPUT (d lse/d s is
    exactly p, so it adds inside the parenthesis); zero for plain
    attention, nonzero when attention-state merging consumed the lse
    (the ring). ``seg`` is the optional [bq, bk] same-segment mask;
    ``window`` the forward's (the query itself counts)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = None
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = qpos + (tk - tq) >= kpos
        if window:
            mask = mask & (kpos > qpos + (tk - tq) - window)
    if seg is not None:
        mask = seg if mask is None else mask & seg
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta + glse) * scale
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
               scale, causal, bq, bk, nk, tq, tk, with_glse, tri,
               with_segments, window: int = 0, band: int = 0):
    # glse is an input only when the lse output's cotangent is nonzero
    # (the ring's state merging); plain attention skips its HBM reads.
    if with_glse:
        glse_ref, *refs = refs
        glse = glse_ref[0, 0, :, :1]
    else:
        glse = 0.0
    if with_segments:
        qseg_ref, kseg_ref, *refs = refs
    dq_ref, dq_scr = refs
    if band:
        # the forward's band: step j of query block qi is k block
        # qi - (band - 1) + j, the blocks before the sequence are dead
        qi = pl.program_id(2)
        ki = qi - (band - 1) + pl.program_id(3)
        first, last, needed = pl.program_id(3) == 0, ki == qi, ki >= 0
    elif tri:
        qi, ki = _tri_qi_ki(pl.program_id(2))
        first, last, needed = ki == 0, ki == qi, True
    else:
        qi, ki = pl.program_id(2), pl.program_id(3)
        first, last = ki == 0, ki == nk - 1
        needed = ((qi + 1) * bq - 1 + (tk - tq) >= ki * bk) if causal \
            else True

    @pl.when(first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(needed)
    def _compute():
        k = k_ref[0, 0]
        seg = _seg_mask(qseg_ref, kseg_ref) if with_segments else None
        _, ds = _recompute_p_ds(q_ref[0, 0], k, v_ref[0, 0], do_ref[0, 0],
                                lse_ref[0, 0, :, :1], delta_ref[0, 0, :, :1],
                                glse,
                                scale, causal, qi, ki, bq, bk, tq, tk,
                                seg=seg, window=window)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                scale, causal, bq, bk, nq, tq, tk, with_glse,
                with_segments, tri, window: int = 0, group: int = 1,
                steps: int = 0):
    if with_glse:
        glse_ref, *refs = refs
        glse = glse_ref[0, 0, :, :1]
    else:
        glse = 0.0
    if with_segments:
        qseg_ref, kseg_ref, *refs = refs
    dk_ref, dv_ref, dk_scr, dv_scr = refs
    if steps:
        # By KV head and under a band: grid (B, Hkv, nk, group, steps).
        # Row ki accumulates, for each of its group's query heads in
        # turn, the q blocks ki .. ki + steps - 1 that see it (the
        # forward's band transposed; every later block without a
        # window); the blocks past the sequence are dead.
        ki = pl.program_id(2)
        qi = ki + pl.program_id(4)
        first = (pl.program_id(3) == 0) & (pl.program_id(4) == 0)
        last = (pl.program_id(3) == group - 1) & (pl.program_id(4)
                                                  == steps - 1)
        needed = qi <= nq - 1
    elif tri:
        # Fused upper-triangular grid: row ki accumulates qi = ki..nq-1,
        # exactly the blocks a causal self-attention needs.
        ki, qi = _tri_ki_qi_upper(pl.program_id(2), nq)
        first, last, needed = qi == ki, qi == nq - 1, True
    else:
        ki, qi = pl.program_id(2), pl.program_id(3)  # k outer, q inner
        first, last = qi == 0, qi == nq - 1
        needed = ((qi + 1) * bq - 1 + (tk - tq) >= ki * bk) if causal \
            else True

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        seg = _seg_mask(qseg_ref, kseg_ref) if with_segments else None
        p, ds = _recompute_p_ds(q, k_ref[0, 0], v_ref[0, 0], do,
                                lse_ref[0, 0, :, :1], delta_ref[0, 0, :, :1],
                                glse,
                                scale, causal, qi, ki, bq, bk, tq, tk,
                                seg=seg, window=window)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _pallas_backward(q, k, v, out, lse, do,
                     causal: bool, scale: float,
                     block_q: int, block_k: int, interpret: bool,
                     glse=None, segment_ids=None, window: int = 0):
    """-> (dq, dk, dv), all in their input layouts/dtypes. ``glse``
    [B,H,Tq] is the lse output's cotangent — None (plain attention)
    compiles kernels without the extra input. ``segment_ids``:
    (q_seg [B,Tq], kv_seg [B,Tk]) for packed-sequence masking.
    ``k`` / ``v`` with fewer heads than ``q`` (whole groups of query
    heads a KV head) and a ``window`` are the forward's
    (``_forward_impl``): causal self-attention with square blocks. dQ
    then walks the forward's band, K/V read by head group; dK/dV take
    one grid row a KV head and k block and accumulate, in float32,
    over the group's query heads and the q blocks that see the block
    (the band transposed)."""
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    bq = _divisor_block(tq, block_q)
    bk = _divisor_block(tk, block_k)
    nq, nk = tq // bq, tk // bk
    with_glse = glse is not None
    with_seg = segment_ids is not None
    tri = _use_tri(causal, tq, tk, bq, bk)
    group = h // hkv
    band = steps = 0
    if window or group > 1:
        if not (causal and tq == tk and bq == bk) or with_seg:
            raise ValueError("head groups and a window are built for "
                             "causal self-attention with square blocks, "
                             "without segments")
        band = _band_blocks(window, bq, nq) if window else 0
        steps = band or nq

    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    dot_ = do.swapaxes(1, 2)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1).swapaxes(1, 2)        # [B, H, Tq]
    # Row vectors carry a 128-lane dim for Mosaic's block constraint
    # (values identical across lanes; kernels read [:, :1]).
    lane = lambda x: jnp.broadcast_to(x.astype(jnp.float32)[..., None],
                                      x.shape + (128,))
    rows = [lane(lse), lane(delta)] + ([lane(glse)] if with_glse else [])
    segs = list(_seg_operands(segment_ids, b, tq, tk)) if with_seg else []

    # dQ: same grid/order as the forward — the band under a window,
    # triangular when eligible, else rectangular with clamped k/v maps
    # (dead copies elided).
    grid_dq, qmap, kvmap, qsegmap, ksegmap = _grid_and_maps(
        causal, bq, bk, nq, nk, tq, tk, b, h)
    grid_dq, qmap, kvmap = _rows_grid(grid_dq, qmap, kvmap, b, h, nq, band,
                                      group)
    q_spec = pl.BlockSpec((1, 1, bq, d), qmap)
    row_spec = pl.BlockSpec((1, 1, bq, 128), qmap)
    kv_spec = pl.BlockSpec((1, 1, bk, d), kvmap)
    seg_specs = [pl.BlockSpec((1, bq, 128), qsegmap),
                 pl.BlockSpec((1, 8, bk), ksegmap)] if with_seg else []
    # Scoped like the fused-IR/depthwise backwards: a custom_vjp
    # backward carries no transpose( marker, so the tpunet_flash_bwd
    # scope is what keeps these kernels in the bwd phase.
    with jax.named_scope("tpunet_flash_bwd"):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, causal=causal,
                              bq=bq, bk=bk, nk=nk, tq=tq, tk=tk,
                              with_glse=with_glse, tri=tri,
                              with_segments=with_seg, window=window,
                              band=band),
            grid=grid_dq,
            in_specs=[q_spec, kv_spec, kv_spec, q_spec]
            + [row_spec] * len(rows) + seg_specs,
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=interpret,
        )(qt, kt, vt, dot_, *rows, *segs)

    # dK/dV: same block roles, transposed order — k block index is the
    # grid row, q block the accumulated axis (the upper triangle when
    # eligible).
    grid_dkv, qmap_t, kvmap_t, qsegmap_t, ksegmap_t = _grid_and_maps(
        causal, bq, bk, nq, nk, tq, tk, b, h, transposed=True)
    if steps:
        # (a dead trailing step re-references the last q block: no copy)
        grid_dkv = (b, hkv, nk, group, steps)
        qmap_t = lambda b, n, j, g, s: (                # noqa: E731
            b, n * group + g, jnp.minimum(j + s, nq - 1), 0)
        kvmap_t = lambda b, n, j, g, s: (b, n, j, 0)    # noqa: E731
    qi_spec = pl.BlockSpec((1, 1, bq, d), qmap_t)
    rowi_spec = pl.BlockSpec((1, 1, bq, 128), qmap_t)
    kvj_spec = pl.BlockSpec((1, 1, bk, d), kvmap_t)
    segi_specs = [pl.BlockSpec((1, bq, 128), qsegmap_t),
                  pl.BlockSpec((1, 8, bk), ksegmap_t)] if with_seg else []
    with jax.named_scope("tpunet_flash_bwd"):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale, causal=causal,
                              bq=bq, bk=bk, nq=nq, tq=tq, tk=tk,
                              with_glse=with_glse, with_segments=with_seg,
                              tri=tri, window=window, group=group,
                              steps=steps),
            grid=grid_dkv,
            in_specs=[qi_spec, kvj_spec, kvj_spec, qi_spec]
            + [rowi_spec] * len(rows) + segi_specs,
            out_specs=[kvj_spec, kvj_spec],
            out_shape=[jax.ShapeDtypeStruct((b, hkv, tk, d), k.dtype),
                       jax.ShapeDtypeStruct((b, hkv, tk, d), v.dtype)],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32)],
            interpret=interpret,
        )(qt, kt, vt, dot_, *rows, *segs)
    return (dq.swapaxes(1, 2), dk.swapaxes(1, 2), dv.swapaxes(1, 2))


# ---------------------------------------------------------------------------
# SPMD partitioning: a pallas_call is opaque to GSPMD, so left alone
# the partitioner would all-gather the sharded batch onto every device
# (the same issue tpunet/ops/depthwise.py solves). Flash attention is
# trivially parallel over batch and heads (the grid's first two axes);
# seq and head_dim must stay replicated per shard. The split is a
# shard_map over the step's mesh (tpunet/ops/partition.py).
# ---------------------------------------------------------------------------

from jax.sharding import PartitionSpec as P

from tpunet.ops.partition import sharded

_S4 = P("data", None, "model", None)      # q/k/v/out/do: batch, heads
_S3 = P("data", "model", None)            # lse [B, H, Tq]
_SSEG = P("data", None)                   # per-token segment ids

_partitioned = sharded(_pallas_forward, (_S4,) * 3, _S4)
_partitioned_res = sharded(_pallas_forward_res, (_S4,) * 3, (_S4, _S3))
_partitioned_bwd = sharded(_pallas_backward,
                           (_S4, _S4, _S4, _S4, _S3, _S4), (_S4,) * 3)


def _make_flash(fwd_prim, res_prim, bwd_prim):
    """custom_vjp wiring shared by the partitioned (top-level jit) and
    shard-local (inside shard_map, where GSPMD has nothing left to
    partition — the Ulysses core) variants: the flash forward saves
    (q, k, v, out, lse) and the backward runs the two flash backward
    kernels (dQ; dK/dV) — nothing O(Tq x Tk) in HBM either direction."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
    def f(q, k, v, causal, scale, block_q, block_k, interpret):
        return fwd_prim(q, k, v, causal, scale, block_q, block_k,
                        interpret)

    def fwd(q, k, v, causal, scale, block_q, block_k, interpret):
        # Scopes on the custom_vjp bodies keep EVERYTHING they emit
        # (lane broadcasts, delta reductions, swapaxes copies — not
        # just the kernels) attributed to the right phase/bucket
        # (tpunet/obs/hlo_bytes.py KERNEL_SCOPES).
        with jax.named_scope("tpunet_flash_fwd"):
            out, lse = res_prim(q, k, v, causal, scale, block_q,
                                block_k, interpret)
        return out, (q, k, v, out, lse)

    def bwd(causal, scale, block_q, block_k, interpret, res, g):
        q, k, v, out, lse = res
        # Plain attention exposes no lse downstream: no glse operand.
        with jax.named_scope("tpunet_flash_bwd"):
            return bwd_prim(q, k, v, out, lse, g, causal, scale,
                            block_q, block_k, interpret)

    f.defvjp(fwd, bwd)
    return f


_flash = _make_flash(_partitioned, _partitioned_res, _partitioned_bwd)
_flash_local = _make_flash(_pallas_forward, _pallas_forward_res,
                           _pallas_backward)


# ---------------------------------------------------------------------------
# Segmented (packed-sequence) variants: separate FIXED-ARITY primitives
# — segment ids are real operands, and both the mesh split and
# custom_vjp count every non-static parameter as an operand slot, so
# the plain primitives cannot grow an optional argument.
# ---------------------------------------------------------------------------


def _pallas_forward_seg(q, k, v, qseg, kseg, causal, scale, block_q,
                        block_k, interpret):
    return _forward_impl(q, k, v, causal, scale, block_q, block_k,
                         interpret, with_lse=False,
                         segment_ids=(qseg, kseg))


def _pallas_forward_res_seg(q, k, v, qseg, kseg, causal, scale, block_q,
                            block_k, interpret):
    return _forward_impl(q, k, v, causal, scale, block_q, block_k,
                         interpret, with_lse=True,
                         segment_ids=(qseg, kseg))


def _pallas_backward_seg(q, k, v, qseg, kseg, out, lse, do, causal,
                         scale, block_q, block_k, interpret):
    return _pallas_backward(q, k, v, out, lse, do, causal, scale,
                            block_q, block_k, interpret,
                            segment_ids=(qseg, kseg))


_partitioned_seg = sharded(_pallas_forward_seg,
                           (_S4, _S4, _S4, _SSEG, _SSEG), _S4)
_partitioned_res_seg = sharded(_pallas_forward_res_seg,
                               (_S4, _S4, _S4, _SSEG, _SSEG), (_S4, _S3))
_partitioned_bwd_seg = sharded(
    _pallas_backward_seg,
    (_S4, _S4, _S4, _SSEG, _SSEG, _S4, _S3, _S4), (_S4,) * 3)


def _make_flash_seg(fwd_prim, res_prim, bwd_prim):
    """custom_vjp wiring for the segmented variants; segment ids are
    integer operands whose cotangents are symbolic-zero float0."""
    import numpy as np

    @functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
    def f(q, k, v, qseg, kseg, causal, scale, block_q, block_k,
          interpret):
        return fwd_prim(q, k, v, qseg, kseg, causal, scale, block_q,
                        block_k, interpret)

    def fwd(q, k, v, qseg, kseg, causal, scale, block_q, block_k,
            interpret):
        with jax.named_scope("tpunet_flash_fwd"):
            out, lse = res_prim(q, k, v, qseg, kseg, causal, scale,
                                block_q, block_k, interpret)
        return out, (q, k, v, qseg, kseg, out, lse)

    def bwd(causal, scale, block_q, block_k, interpret, res, g):
        q, k, v, qseg, kseg, out, lse = res
        with jax.named_scope("tpunet_flash_bwd"):
            dq, dk, dv = bwd_prim(q, k, v, qseg, kseg, out, lse, g,
                                  causal, scale, block_q, block_k,
                                  interpret)
        z = lambda a: np.zeros(a.shape, jax.dtypes.float0)
        return dq, dk, dv, z(qseg), z(kseg)

    f.defvjp(fwd, bwd)
    return f


_flash_seg = _make_flash_seg(_partitioned_seg, _partitioned_res_seg,
                             _partitioned_bwd_seg)
_flash_seg_local = _make_flash_seg(_pallas_forward_seg,
                                   _pallas_forward_res_seg,
                                   _pallas_backward_seg)


def local_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          causal: bool = False,
                          scale: Optional[float] = None,
                          block_q: int = 512,
                          block_k: int = 512,
                          interpret: Optional[bool] = None,
                          segment_ids=None) -> jax.Array:
    """flash_attention for use INSIDE shard_map bodies: per-shard
    arrays, no mesh split of its own. Same fallbacks (dense for
    degenerate lengths; dense off-TPU unless interpret=True) and the
    same optional packed-sequence ``segment_ids``."""
    return _entry(_flash_local, _flash_seg_local, q, k, v, causal, scale,
                  block_q, block_k, interpret, segment_ids=segment_ids)


# Attention-STATE variant for the ring: returns (out, lse) so partial
# results over different K/V blocks can be merged exactly
# (merge_attention_states). Differentiable: built from the same
# primitives, so the flash backward kernels serve it too.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_local_state(q, k, v, causal, scale, block_q, block_k,
                       interpret):
    return _pallas_forward_res(q, k, v, causal, scale, block_q, block_k,
                               interpret)


def _fwd_local_state(q, k, v, causal, scale, block_q, block_k, interpret):
    with jax.named_scope("tpunet_flash_fwd"):
        out, lse = _pallas_forward_res(q, k, v, causal, scale, block_q,
                                       block_k, interpret)
    return (out, lse), (q, k, v, out, lse)


def _bwd_local_state(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    go, glse = g
    # The lse output IS consumed downstream (the ring's state-merge
    # weights depend on it), so its cotangent carries real gradient:
    # d lse / d s = p, folded into ds inside the kernels.
    with jax.named_scope("tpunet_flash_bwd"):
        return _pallas_backward(q, k, v, out, lse, go, causal, scale,
                                block_q, block_k, interpret, glse=glse)


_flash_local_state.defvjp(_fwd_local_state, _bwd_local_state)


def local_flash_attention_state(q, k, v, *, causal=False, scale=None,
                                block_q: int = 512, block_k: int = 512,
                                interpret: Optional[bool] = None):
    """(out [B,Tq,H,D], lse [B,H,Tq]) over ONE K/V block — the ring
    core. No dense fallback here: the ring needs the lse state, and a
    shard's K/V block length is mesh-controlled (divisible), not
    user-degenerate. Off-TPU runs in interpret mode."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash_local_state(q, k, v, causal, scale, block_q, block_k,
                              interpret)


def merge_attention_states(state_a, state_b):
    """Exactly combine two partial attention results computed over
    disjoint K/V blocks: each state is (out [B,Tq,H,D] normalized,
    lse [B,H,Tq]). With m = max(lse_a, lse_b) and w_x = exp(lse_x - m):
    out = (w_a*out_a + w_b*out_b) / (w_a + w_b), lse = m + log(w_a+w_b)
    — associative, so it carries through a lax.scan (the ring).
    Fully-masked blocks arrive with lse = _NEG_INF and weight 0; rows
    masked in BOTH emit zeros (the l == 0 convention of
    tpunet/ops/attention.py)."""
    oa, la = state_a
    ob, lb = state_b
    m = jnp.maximum(la, lb)                        # [B, H, Tq]
    # Guard exp(_NEG_INF - _NEG_INF) = 1 on rows masked in both.
    both_dead = m <= _NEG_INF
    wa = jnp.where(both_dead, 0.0, jnp.exp(la - m))
    wb = jnp.where(both_dead, 0.0, jnp.exp(lb - m))
    denom = wa + wb
    safe = jnp.where(denom == 0.0, 1.0, denom)
    to_bthd = lambda w: w.transpose(0, 2, 1)[..., None]  # [B,Tq,H,1]
    out = (to_bthd(wa) * oa.astype(jnp.float32)
           + to_bthd(wb) * ob.astype(jnp.float32)) / to_bthd(safe)
    lse = jnp.where(denom == 0.0, _NEG_INF, m + jnp.log(safe))
    return out.astype(oa.dtype), lse


def _entry(prim, seg_prim, q, k, v, causal, scale, block_q, block_k,
           interpret, segment_ids=None):
    """Shared entry prologue for both public wrappers: scale default,
    degenerate-length dense fallback, off-TPU/interpret resolution,
    and routing to the fixed-arity segmented primitive when packed-
    sequence segment ids are given."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    tq, tk = q.shape[1], k.shape[1]
    bq = _divisor_block(tq, block_q)
    bk = _divisor_block(tk, block_k)
    dense = functools.partial(dense_attention, q, k, v, causal=causal,
                              scale=scale, segment_ids=segment_ids)
    if (bq < 64 and bq < min(block_q, tq)) or \
            (bk < 64 and bk < min(block_k, tk)):
        # Degenerate lengths (primes etc.) whose only divisors are tiny:
        # a grid of near-1-row blocks would serialize the contraction —
        # fall back to one dense pass instead, the same policy as
        # attention.py's _auto_block. (An explicitly requested small
        # block is honored: tests drive the kernel with block 16/32.)
        return dense()
    if interpret is None:
        if os.environ.get("TPUNET_FLASH_INTERPRET",
                          "").lower() not in ("", "0", "false"):
            # Force the Pallas interpreter off-TPU (driver dryrun/tests:
            # exercises the real kernel body, not the dense fallback).
            interpret = True
        elif jax.default_backend() != "tpu":
            return dense()
        else:
            interpret = False
    if segment_ids is not None:
        qseg = jnp.asarray(segment_ids[0], jnp.int32)
        kseg = jnp.asarray(segment_ids[1], jnp.int32)
        return seg_prim(q, k, v, qseg, kseg, causal, scale, block_q,
                        block_k, interpret)
    return prim(q, k, v, causal, scale, block_q, block_k, interpret)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 512,
                    block_k: int = 512,
                    interpret: Optional[bool] = None,
                    segment_ids=None) -> jax.Array:
    """Fused flash attention, BTHD layout, drop-in for dense_attention.

    On TPU the Pallas kernel runs; off-TPU the default is the XLA dense
    reference (pass ``interpret=True`` to exercise the kernel in tests).
    Blocks clamp to the largest divisor of the sequence length <= the
    requested size, so any length works (degenerate lengths fall back
    to a dense pass). ``segment_ids``: optional (q_seg [B,Tq],
    kv_seg [B,Tk]) int pair for packed-sequence masking — a query
    attends only to keys with the same segment id (compose with
    ``causal`` for packed causal LM training; padding gets a dedicated
    id so real tokens never attend to it).
    """
    return _entry(_flash, _flash_seg, q, k, v, causal, scale, block_q,
                  block_k, interpret, segment_ids=segment_ids)


def grouped_window_attention(q, k, v, *, scale: float, window: int = 0):
    """What ``flash_prefill`` computes, dense in ``jax.numpy``: ``q``
    [B, T, H, D] over ``k`` / ``v`` [B, T, Hkv, D], query head h on KV
    head ``h // (H / Hkv)``, query t on keys ``t - window < s <= t``
    (every ``s <= t`` without a window); softmax in float32."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    s = jnp.einsum("bqngd,bknd->bngqk", q.reshape(b, t, hkv, h // hkv, d),
                   k, preferred_element_type=jnp.float32) * scale
    at = jnp.arange(t)
    keep = at[None, :] <= at[:, None]
    if window:
        keep = keep & (at[None, :] > at[:, None] - window)
    p = jax.nn.softmax(jnp.where(keep, s, _NEG_INF), -1)
    o = jnp.einsum("bngqk,bknd->bqngd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, t, h, d).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_rows(q, k, v, scale, window, block, interpret):
    """``flash_prefill``'s kernel call; differentiated, the forward
    keeps its log-sum-exp and the backward is ``_pallas_backward`` by
    head group and under the band."""
    return _forward_impl(q, k, v, True, scale, block, block, interpret,
                         with_lse=False, window=window)


def _flash_rows_fwd(q, k, v, scale, window, block, interpret):
    with jax.named_scope("tpunet_flash_fwd"):
        out, lse = _forward_impl(q, k, v, True, scale, block, block,
                                 interpret, with_lse=True, window=window)
    return out, (q, k, v, out, lse)


def _flash_rows_bwd(scale, window, block, interpret, res, g):
    q, k, v, out, lse = res
    with jax.named_scope("tpunet_flash_bwd"):
        return _pallas_backward(q, k, v, out, lse, g, True, scale, block,
                                block, interpret, window=window)


_flash_rows.defvjp(_flash_rows_fwd, _flash_rows_bwd)


def causal_blocks_visited(t: int, window: int, block: int = 512):
    """``(visited, causal)``: the (query block, key block) pairs the
    grids of ``flash_prefill`` and of its backward visit for rows of
    ``t`` tokens under ``window`` (0: none), of the pairs on or under
    the diagonal. What a trainer's gauge says of a windowed layer."""
    bs = _divisor_block(t, block)
    nq = t // bs
    band = _band_blocks(window, bs, nq) if 0 < window < t else nq
    return sum(min(i + 1, band) for i in range(nq)), nq * (nq + 1) // 2


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  scale: Optional[float] = None,
                  window: Optional[int] = None, block: int = 512,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Causal self-attention of rows that start at position 0 (a
    prefill, a training batch): ``q`` [B, T, H, D] over ``k`` / ``v``
    [B, T, Hkv, D] with ``Hkv`` dividing ``H`` (grouped-query
    attention: K and V are read by head group, never repeated) and,
    with ``window``, each query on its last ``window`` keys (itself
    counted). Differentiable in ``q``, ``k`` and ``v``. The flash
    kernels on the TPU, ``grouped_window_attention`` off it
    (``interpret=True`` drives the kernels' bodies in tests) and for
    lengths whose only divisors are tiny."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    t, window = q.shape[1], int(window or 0)
    if q.shape[2] % k.shape[2] or k.shape != v.shape or k.shape[1] != t:
        raise ValueError(f"q {q.shape} over k {k.shape} / v {v.shape}: one "
                         "length, and whole groups of query heads")
    if window >= t:                      # the whole row is in sight
        window = 0
    bs = _divisor_block(t, block)
    if (interpret is None and jax.default_backend() != "tpu") \
            or (bs < 64 and bs < min(block, t)):
        return grouped_window_attention(q, k, v, scale=scale, window=window)
    return _flash_rows(q, k, v, scale, window, bs, bool(interpret))
