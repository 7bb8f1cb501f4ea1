"""Attention ops: dense, blockwise, ring and Ulysses (sequence-parallel).

The reference workload is a CNN with no attention anywhere (SURVEY.md
section 2b), but tpunet treats long-context support as first-class: these
ops are the sequence/context-parallel layer that the attention-based
model families (tpunet/models/) build on.

Design (TPU-first):

- All variants share one *online-softmax* block update (the math of
  FlashAttention / Rabe-Staats): running max ``m``, normalizer ``l`` and
  un-normalized accumulator ``acc`` are carried across key/value blocks,
  so the full [Tq, Tk] score matrix never materializes. Accumulation is
  float32 regardless of compute dtype.
- ``blockwise_attention`` scans the *local* K/V in chunks — bounded
  memory for long sequences on one chip.
- ``ring_attention`` is the sequence-parallel form (Liu et al., "Ring
  Attention with Blockwise Transformers"): Q stays put, K/V shards
  rotate around the mesh axis via ``lax.ppermute`` (one ICI hop per
  step), each arrival folded in with the same online-softmax update.
  It is written against a shard_map axis name; ``ring_self_attention``
  wraps it in ``jax.shard_map`` over a mesh.
- ``ulysses_attention`` is the all-to-all sequence-parallel form
  (DeepSpeed-Ulysses): two ``lax.all_to_all``s trade the seq sharding
  for head sharding around a locally-dense full-sequence attention.
  Fewer collectives than the ring; memory O(T) per head group.
- Layout is [batch, seq, heads, head_dim] (BTHD) throughout.
- Causal masking uses *global* positions reconstructed from the axis
  index, so causality is exact under sequence sharding.

Differentiable end-to-end (the ring rotation is a ``lax.scan``; JAX
reverse-differentiates through the ppermutes).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/grads NaN-free


# ---------------------------------------------------------------------------
# Shared online-softmax block update
# ---------------------------------------------------------------------------

def _block_update(carry: Tuple[jax.Array, jax.Array, jax.Array],
                  q: jax.Array, k: jax.Array, v: jax.Array,
                  scale: float,
                  mask: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array,
                                                      jax.Array]:
    """Fold one K/V block into the (m, l, acc) running softmax state.

    q [B,Tq,H,D]; k,v [B,Tk,H,D]; mask [Tq,Tk] or — per-example
    (packed-segment) masks — [B,Tq,Tk] bool (True = attend) or None.
    m,l [B,H,Tq]; acc [B,Tq,H,D]. All state float32.
    """
    m, l, acc = carry
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None]
        mask = mask[:, None]                       # broadcast over heads
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # Rows with nothing to attend to yet keep m at the initial floor;
    # exp(s - floor) would overflow, so shift defensively.
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                    preferred_element_type=jnp.float32)
    acc = acc * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l, acc


def _init_carry(q: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, tq, h, d = q.shape
    m = jnp.full((b, h, tq), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, tq), jnp.float32)
    acc = jnp.zeros((b, tq, h, d), jnp.float32)
    return m, l, acc


def _finalize(m, l, acc, dtype) -> jax.Array:
    # l == 0 only for rows masked out of every block; emit zeros there.
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# Dense reference
# ---------------------------------------------------------------------------

def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    segment_ids=None) -> jax.Array:
    """Plain softmax attention, float32 accumulation. BTHD layout.

    ``segment_ids``: optional (q_seg [B,Tq], kv_seg [B,Tk]) int pair for
    packed sequences — a query attends only to same-segment keys."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = None                                    # [B, Tq, Tk] or None
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)[None]
    if segment_ids is not None:
        q_seg, kv_seg = segment_ids
        seg = q_seg[:, :, None] == kv_seg[:, None, :]
        mask = seg if mask is None else mask & seg
    if mask is not None:
        s = jnp.where(mask[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if mask is not None:
        # Rows with no valid key (tq > tk top rows, orphan segments) get
        # zeros, matching the l == 0 convention of the blockwise/ring
        # variants — softmax alone would attend uniformly, leaking
        # masked values.
        p = jnp.where(mask.any(-1)[:, None, :, None], p, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Blockwise attention (single device, chunked K/V)
# ---------------------------------------------------------------------------

def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        block_size: int = 512,
                        causal: bool = False,
                        scale: Optional[float] = None,
                        segment_ids=None) -> jax.Array:
    """Online-softmax attention over K/V chunks of ``block_size``.

    Memory is O(Tq * block_size) instead of O(Tq * Tk); exact same
    result as ``dense_attention``. ``segment_ids``: optional
    (q_seg [B,Tq], kv_seg [B,Tk]) pair for packed sequences — the
    kv-block slice of the mask rides the scan, keeping the
    O(Tq * block_size) bound."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    tq, tk = q.shape[1], k.shape[1]
    block_size = min(block_size, tk)
    if tk % block_size != 0:
        raise ValueError(f"seq len {tk} not divisible by block {block_size}")
    n_blocks = tk // block_size
    b = k.shape[0]
    kb = k.reshape(b, n_blocks, block_size, *k.shape[2:])
    vb = v.reshape(b, n_blocks, block_size, *v.shape[2:])
    q_pos = jnp.arange(tq)
    if segment_ids is not None:
        q_seg, kv_seg = segment_ids
        sb = kv_seg.reshape(b, n_blocks, block_size).swapaxes(0, 1)
    else:
        q_seg = None
        sb = jnp.zeros((n_blocks, 0), jnp.int32)   # scan arity filler

    def body(carry, xs):
        j, k_j, v_j, s_j = xs
        mask = None
        if causal:
            k_pos = j * block_size + jnp.arange(block_size)
            mask = q_pos[:, None] + (tk - tq) >= k_pos[None, :]
        if q_seg is not None:
            seg = q_seg[:, :, None] == s_j[:, None, :]  # [B, Tq, bs]
            mask = seg if mask is None else mask[None] & seg
        return _block_update(carry, q, k_j, v_j, scale, mask), None

    (m, l, acc), _ = jax.lax.scan(
        body, _init_carry(q),
        (jnp.arange(n_blocks), kb.swapaxes(0, 1), vb.swapaxes(0, 1), sb))
    return _finalize(m, l, acc, q.dtype)


# ---------------------------------------------------------------------------
# Ring attention (sequence-parallel, shard_map body)
# ---------------------------------------------------------------------------

def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, *,
                   causal: bool = False,
                   scale: Optional[float] = None,
                   core: Optional[str] = None) -> jax.Array:
    """Sequence-parallel attention over shard_map axis ``axis_name``.

    Call inside ``shard_map`` with q/k/v sharded on their seq dim over
    ``axis_name``. K/V shards rotate around the ring (``lax.ppermute``,
    one neighbor hop per step — ICI-friendly); each arriving block is
    folded in. Exactly matches ``dense_attention`` on the gathered
    arrays.

    ``core`` (like Ulysses'): None = the flash kernel on TPU, the
    pure-JAX online-softmax update elsewhere; "flash"/"blockwise"
    force. The flash core computes each arriving block with the fused
    kernel and folds it in by exact attention-state merging
    (tpunet/ops/flash.py merge_attention_states); a ring step is one
    of three static cases per source shard — fully past (unmasked
    flash), the diagonal (causal flash), fully future (skip) — selected
    with lax.cond on the rotating source index.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    tq = q.shape[1]
    tk = k.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]

    auto = core is None
    if auto:
        core = "flash" if jax.default_backend() == "tpu" else "blockwise"
    if core not in ("flash", "blockwise"):
        raise ValueError(f"unknown attention core {core!r}")
    if core == "flash":
        if not causal or tq == tk:
            return _ring_flash(q, k, v, axis_name, causal, scale, n, my,
                               perm)
        if not auto:
            raise ValueError(
                f"core='flash' does not support causal cross-length "
                f"rings (tq={tq} != tk={tk}: per-step masks are "
                "arbitrary); use core='blockwise'")
    # core == "blockwise" (the pure-JAX path), or auto-selected flash
    # downgraded for a causal cross-length ring.

    q_pos = my * tq + jnp.arange(tq)

    def block_mask(step):
        # k block held at `step` originated on device (my - step) mod n.
        if not causal:
            return None
        k_pos = ((my - step) % n) * tk + jnp.arange(tk)
        return q_pos[:, None] >= k_pos[None, :]

    def body(carry, step):
        state, k_cur, v_cur = carry
        state = _block_update(state, q, k_cur, v_cur, scale,
                              block_mask(step))
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (state, k_nxt, v_nxt), None

    # n-1 update+rotate steps, then a final update with no rotation (the
    # last ppermute's result would be discarded, but XLA cannot DCE a
    # collective inside the scan — one wasted ICI hop per layer per step).
    state, k_last, v_last = _init_carry(q), k, v
    if n > 1:
        (state, k_last, v_last), _ = jax.lax.scan(
            body, (state, k, v), jnp.arange(n - 1))
    m, l, acc = _block_update(state, q, k_last, v_last, scale,
                              block_mask(n - 1))
    return _finalize(m, l, acc, q.dtype)


def _ring_flash(q, k, v, axis_name, causal, scale, n, my, perm):
    """Flash-core ring body (see ring_attention): fused-kernel local
    attention per arriving K/V shard + exact state merging."""
    from tpunet.ops.flash import (local_flash_attention_state,
                                  merge_attention_states)
    b, tq, h, d = q.shape

    def block_state(k_cur, v_cur, blk_causal: bool):
        return local_flash_attention_state(q, k_cur, v_cur,
                                           causal=blk_causal, scale=scale)

    def fold(state, k_cur, v_cur, step):
        if not causal:
            return merge_attention_states(
                state, block_state(k_cur, v_cur, False))
        src = (my - step) % n
        return jax.lax.cond(
            src < my,
            lambda args: merge_attention_states(
                state, block_state(args[0], args[1], False)),
            lambda args: jax.lax.cond(
                src == my,
                lambda a: merge_attention_states(
                    state, block_state(a[0], a[1], True)),
                lambda a: state,          # fully future: skip
                args),
            (k_cur, v_cur))

    def body(carry, step):
        state, k_cur, v_cur = carry
        state = fold(state, k_cur, v_cur, step)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (state, k_nxt, v_nxt), None

    # The merged-output accumulator stays float32 across all n folds
    # (merge_attention_states keeps the carry's dtype) — one cast at
    # the end, like the pure-JAX path's _finalize; a q.dtype carry
    # would re-round bf16 at every ring step.
    state = (jnp.zeros((b, tq, h, d), jnp.float32),
             jnp.full((b, h, tq), _NEG_INF, jnp.float32))
    k_last, v_last = k, v
    if n > 1:
        (state, k_last, v_last), _ = jax.lax.scan(
            body, (state, k, v), jnp.arange(n - 1))
    out, _ = fold(state, k_last, v_last, n - 1)
    return out.astype(q.dtype)


def _resolve_head_axis(mesh: Mesh, head_axis: Optional[str], heads: int,
                       local_divisor: int = 1) -> Optional[str]:
    """Head-dim mesh axis for the shard_map wrappers, or None to
    replicate: the axis must exist, be >1, and divide the head count
    (with the per-shard head count further divisible by
    ``local_divisor`` — Ulysses needs local heads to divide the seq
    axis)."""
    if not head_axis or head_axis not in mesh.shape:
        return None
    size = mesh.shape[head_axis]
    if size <= 1 or heads % size or (heads // size) % local_divisor:
        return None
    return head_axis


def _divisor_block(t: int, cap: int) -> int:
    """Largest divisor of ``t`` that is <= cap — honors explicitly tiny
    caps (used when the caller chose the block size deliberately; the
    flash kernel shares this)."""
    return next(b for b in range(min(cap, t), 0, -1) if t % b == 0)


def _auto_block(t: int, cap: int = 512) -> int:
    """Block size for a length-``t`` blockwise pass: the largest divisor
    of t that is <= ``cap``, bounding score memory to O(t x cap).
    Lengths whose only small divisors are degenerate (< 64, e.g. primes
    — a t-step scan of 1-wide blocks) fall back to one dense pass
    instead; that trades memory for not serializing the contraction."""
    if t <= cap:
        return t
    b = next(b for b in range(cap, 0, -1) if t % b == 0)
    return b if b >= 64 else t


def _local_full_attention(q, k, v, causal, scale, core: Optional[str],
                          block: Optional[int] = None,
                          segment_ids=None):
    """The locally-dense full-sequence core used inside Ulysses.

    ``core`` None resolves to the Pallas flash kernel on TPU (measured
    1.31x the blockwise scan, tpunet/ops/flash.py) and the blockwise
    scan elsewhere; "flash"/"blockwise" force a choice ("flash" off-TPU
    runs the kernel in interpret mode — test use only). ``block``
    overrides the kernel/scan block size (cfg.attention_block).
    ``segment_ids``: optional (q_seg, kv_seg) pair — both cores are
    segment-capable (packed x SP)."""
    if core is None:
        core = "flash" if jax.default_backend() == "tpu" else "blockwise"
    if core == "flash":
        from tpunet.ops.flash import local_flash_attention
        interpret = True if jax.default_backend() != "tpu" else None
        b = block or 512
        return local_flash_attention(q, k, v, causal=causal, scale=scale,
                                     block_q=b, block_k=b,
                                     interpret=interpret,
                                     segment_ids=segment_ids)
    if core == "blockwise":
        # ``block`` is a CAP clamped to a divisor of the local length.
        # An EXPLICIT cap is honored even below _auto_block's 64 floor
        # (the user chose it to bound memory); only auto-selection
        # applies the degenerate-length dense fallback.
        bs = (_divisor_block(q.shape[1], block) if block
              else _auto_block(q.shape[1]))
        return blockwise_attention(q, k, v, block_size=bs,
                                   causal=causal, scale=scale,
                                   segment_ids=segment_ids)
    raise ValueError(f"unknown attention core {core!r}")


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str, *,
                      causal: bool = False,
                      scale: Optional[float] = None,
                      core: Optional[str] = None,
                      block: Optional[int] = None,
                      segment_ids=None) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style),
    shard_map body: inputs arrive seq-sharded [B, T/s, H, D]; one
    all-to-all (q/k/v stacked, so it is a single collective) re-shards
    heads instead ([B, T, H/s, D]), attention runs over the FULL
    sequence per head group (the flash kernel on TPU, the blockwise
    scan elsewhere — ``core``), and a second all-to-all restores seq
    sharding. Two collectives total per call — fewer than the ring's
    per-step hops when heads divide the axis — at the cost of holding
    full-T activations per head group (the scores themselves stay in
    VMEM / O(T x block)).

    ``segment_ids`` (packed x SP): a (q_seg, kv_seg) pair of
    seq-SHARDED [B, T/s] int arrays (equal for self-attention). The
    local core sees the full sequence per head group, so segment
    masking is exact under sharding: one [B, T/s] -> [B, T]
    ``all_gather`` (int32 metadata, negligible next to the qkv
    all-to-all) rebuilds the global ids the core masks with."""
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(
            f"ulysses_attention is self-attention only (q {q.shape}, "
            f"k {k.shape}, v {v.shape}); use ring_attention for "
            "cross-length attention")
    n = jax.lax.psum(1, axis_name)
    if q.shape[2] % n:
        raise ValueError(
            f"{q.shape[2]} heads not divisible by sequence axis {n}")
    seg = None
    if segment_ids is not None:
        ids = segment_ids[0]     # self-attention: q_seg is kv_seg
        if n > 1:
            ids = jax.lax.all_gather(ids, axis_name, axis=1, tiled=True)
        seg = (ids, ids)
    if n == 1:
        return _local_full_attention(q, k, v, causal, scale, core, block,
                                     segment_ids=seg)
    # [3, B, T/s, H, D] -> [3, B, T, H/s, D]: split heads, concat seq.
    qkv = jax.lax.all_to_all(jnp.stack([q, k, v]), axis_name,
                             split_axis=3, concat_axis=2, tiled=True)
    out = _local_full_attention(qkv[0], qkv[1], qkv[2], causal, scale,
                                core, block, segment_ids=seg)
    # [B, T, H/s, D] -> [B, T/s, H, D]: split seq, concat heads.
    return jax.lax.all_to_all(out, axis_name, split_axis=1,
                              concat_axis=2, tiled=True)


def ulysses_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh: Mesh, *,
                           seq_axis: str = "seq",
                           batch_axis: str = "data",
                           head_axis: Optional[str] = "model",
                           causal: bool = False,
                           scale: Optional[float] = None,
                           core: Optional[str] = None,
                           block: Optional[int] = None,
                           segment_ids=None) -> jax.Array:
    """shard_map wrapper for ``ulysses_attention`` (mirror of
    ``ring_self_attention``, including pass-through tensor-parallel
    head sharding — local heads must still divide the seq axis).
    ``segment_ids``: optional (q_seg, kv_seg) [B, T] pair (packed
    sequences) — sharded over ``seq_axis`` into the body, where the
    gather-and-mask happens."""
    h_ax = _resolve_head_axis(mesh, head_axis, q.shape[2],
                              local_divisor=mesh.shape[seq_axis])
    spec = P(batch_axis, seq_axis, h_ax, None)
    if segment_ids is None:
        fn = shard_map(
            functools.partial(ulysses_attention, axis_name=seq_axis,
                              causal=causal, scale=scale, core=core,
                              block=block),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return fn(q, k, v)
    s_spec = P(batch_axis, seq_axis)

    def body(q, k, v, q_seg, kv_seg):
        return ulysses_attention(q, k, v, axis_name=seq_axis,
                                 causal=causal, scale=scale, core=core,
                                 block=block,
                                 segment_ids=(q_seg, kv_seg))

    fn = shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec, s_spec, s_spec),
        out_specs=spec, check_vma=False)
    return fn(q, k, v, *segment_ids)


def ring_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        mesh: Mesh, *,
                        seq_axis: str = "seq",
                        batch_axis: str = "data",
                        head_axis: Optional[str] = "model",
                        causal: bool = False,
                        scale: Optional[float] = None,
                        core: Optional[str] = None) -> jax.Array:
    """shard_map wrapper: global BTHD arrays in, ring attention inside.

    Batch dim sharded over ``batch_axis``, seq dim over ``seq_axis``.
    When ``head_axis`` names a mesh axis that divides the head count,
    the head dim stays sharded over it too (attention is elementwise in
    heads), so tensor-parallel activations flow through without the
    all-gather an unmentioned axis would force.
    """
    h_ax = _resolve_head_axis(mesh, head_axis, q.shape[2])
    spec = P(batch_axis, seq_axis, h_ax, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=seq_axis,
                          causal=causal, scale=scale, core=core),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
