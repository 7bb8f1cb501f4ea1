"""Pallas TPU kernel: width-1 decode attention over the paged KV pool.

The serve engine's decode step hands every layer one query token per
slot and a page table into the layer's shared K/V pool
(tpunet/models/vit.py ``Attention._paged_decode_attend``). The dense
path gathers ``pages_per_slot * page_tokens`` rows for every slot out
of the pool, scores all of them and masks by position — at GPT-2 XL's
serve geometry one live key row in six (PERF.md section 6, PR 26). This
kernel reads K/V from the pool where it lies, page by page through the
page table, up to each row's own live length, with an online softmax:
no gathered copy, no ``[B, H, 1, K]`` score tensor, one program for any
length.

Design notes:
- The pool is ``[pool_rows, W]`` with ``W`` = heads * head_dim rounded
  up to the 128-lane tile (``pool_width``): a page is ``page_tokens``
  consecutive rows, one lane-dense block, so a page DMA is one
  contiguous copy. The rounding is what makes the TPU's compact device
  layout of the buffer row-major: ``[16400, 1600]`` bfloat16 is laid out
  rows-minor (less tile padding that way) and every program that
  scatters or gathers rows then converts the whole pool in and out — the
  ~38 ms of ``copy`` operations per GPT-2 XL decode step before this
  kernel.
- Scalar-prefetched: the flattened page table and the per-row live
  length (0 for an inactive row: nothing of it is read, its output is
  zeros). The kernel is one invocation. It first lists its work in SMEM
  — (row, chunk) for every chunk of ``pages_per_chunk`` pages that
  holds a live key — then walks the list with the next chunk's page
  DMAs in flight while the current one is computed (double buffer
  across rows as well as within one).
- Heads without lane slicing: ``head_dim`` 64 puts two heads in one
  128-lane tile, and 25 heads fill 12.5 tiles. The step is bound by
  bytes, not FLOPs, so all heads go through the MXU at once against a
  block-diagonal expansion of ``q``: ``[Hp, W] x [W, tokens]`` gives
  every head's scores (the off-diagonal zeros add exact zeros), and
  ``[Hp, tokens] x [tokens, W]`` gives every head's weighted values in
  its own column block, which a mask picks out at the end.
- Precision: scores, running max, running sum and the accumulator are
  float32. K and V enter the MXU in the pool's stored dtype (converted
  up to ``q``'s dtype when that is wider). The probabilities stay
  float32: against a bfloat16 pool they are split into a bfloat16 head
  and a bfloat16 remainder, both multiplied in one product of twice the
  rows, so nothing is rounded to 8 bits that the dense path keeps.
- Rows of a chunk past the live length are never fetched; what the
  buffer holds there is stale VMEM, so K's scores are masked and V's
  rows are zeroed before the product (0 * NaN is NaN).
- ``window`` (static; a sliding-attention layer): a row attends to its
  last ``window`` keys only (the newest counts). Its work list then
  STARTS at the chunk that holds key ``live - window``: nothing before
  that chunk is fetched, whatever the row's length, and the keys of
  that chunk that lie before the window are masked (they are pool rows
  the row wrote earlier, so their V rows are finite and a zero
  probability drops them). Without a window the program is what it was.
- Off-TPU the caller keeps the dense path (the Pallas interpreter is
  far too slow for an engine step); tests drive this kernel body on the
  CPU with ``interpret=True``, the scheme of flash.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpunet.ops.attention import _NEG_INF

_LANES = 128
_CHUNK_TOKENS = 128   # keys per compute block: one lane tile of scores


def pool_width(heads: int, head_dim: int) -> int:
    """Columns of a pool row: heads * head_dim, rounded up to the lane
    tile (the padding columns are written as zeros)."""
    return -(-heads * head_dim // _LANES) * _LANES


def _on_tpu() -> bool:
    """The dispatch's view of the backend (tests and the chip smoke
    replace it to take one path or the other on one pool)."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Off the TPU the kernel's body runs in the Pallas interpreter
    (a compile for a described chip replaces this with False)."""
    return jax.default_backend() != "tpu"


def kernel_applies(paged_kv, width: int, pool_dtype) -> bool:
    """Whether a decode program of token width ``width`` over this pool
    attends through the kernel — decided from what the trace can see,
    never from an option: one token per row, an unquantized pool on one
    device, pages that are whole sublane tiles of the pool's dtype (a
    page DMA must not split a packed tile), and a TPU backend."""
    itemsize = jnp.dtype(pool_dtype).itemsize
    return (width == 1 and not paged_kv.quantized
            and not paged_kv.mesh_sharded
            and itemsize in (2, 4)
            and paged_kv.page_tokens % (32 // itemsize) == 0
            and _on_tpu())


def _kernel(lengths_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, work_row, work_chunk, qbd_ref, m_ref,
            l_ref, acc_ref, *, slots: int, pages_per_slot: int,
            page_tokens: int, pages_per_chunk: int, heads: int,
            head_dim: int, scale: float, split_p: bool, group: int = 1,
            window: int = 0):
    pt, ppc = page_tokens, pages_per_chunk
    ct = pt * ppc                          # keys per chunk
    hp, w = acc_ref.shape

    def first_chunk(length):
        """The chunk that holds a row's oldest key in sight."""
        if not window:
            return 0
        return jnp.maximum(length - window, 0) // ct

    # -- the work list: every (row, chunk) that holds a key in sight ---
    def list_row(b, n):
        def list_chunk(c, n):
            work_row[n] = b
            work_chunk[n] = c
            return n + 1
        return lax.fori_loop(first_chunk(lengths_ref[b]),
                             pl.cdiv(lengths_ref[b], ct), list_chunk, n)
    n_work = lax.fori_loop(0, slots, list_row, jnp.int32(0))

    def live_copies(i, slot, act: str) -> None:
        """Start, or wait for, the K and V copies of every page of work
        item ``i`` that holds a live key, into buffer ``slot``."""
        b, c = work_row[i], work_chunk[i]
        for j in range(ppc):
            p = c * ppc + j
            # A dead page's table entry is never read as an address.
            page = table_ref[b * pages_per_slot
                             + jnp.minimum(p, pages_per_slot - 1)]
            src = pl.ds(pl.multiple_of(page * pt, pt), pt)
            dst = pl.ds(j * pt, pt)

            @pl.when(p * pt < lengths_ref[b])
            def _():
                for hbm, buf, sem in ((k_hbm, kbuf, sems.at[0, slot]),
                                      (v_hbm, vbuf, sems.at[1, slot])):
                    getattr(pltpu.make_async_copy(
                        hbm.at[src], buf.at[slot, dst], sem), act)()

    o_ref[...] = jnp.zeros_like(o_ref)
    # Head h owns columns [h * head_dim, (h + 1) * head_dim); grouped,
    # query head h reads the columns of KV head h // group.
    row = lax.broadcasted_iota(jnp.int32, (hp, w), 0)
    col = lax.broadcasted_iota(jnp.int32, (hp, w), 1)
    if group == 1:
        own = (col >= row * head_dim) & (col < (row + 1) * head_dim) \
            & (row < heads)
    else:
        own = row < 0
        for n in range(heads // group):     # comparisons only: no vector
            own = own | ((row >= n * group) & (row < (n + 1) * group)  # div
                         & (col >= n * head_dim) & (col < (n + 1) * head_dim))

    @pl.when(n_work > 0)
    def _():
        live_copies(0, 0, "start")

    def step(i, carry):
        slot = lax.rem(i, 2)
        b, c = work_row[i], work_chunk[i]
        length = lengths_ref[b]

        @pl.when(i + 1 < n_work)
        def _():
            live_copies(i + 1, 1 - slot, "start")

        @pl.when(c == first_chunk(length))
        def _():
            # Selects run on 32-bit lanes (Mosaic cannot carry a
            # mask between the 32-bit and the packed 16-bit tiling).
            q = q_ref[b].astype(jnp.float32)      # [1, W]; grouped [hp, D]
            if group == 1:
                q = jnp.broadcast_to(q, (hp, w))
            else:                # every head's q under every KV block
                q = jnp.concatenate([q] * (heads // group), axis=1)
            qbd_ref[...] = jnp.where(own, q, 0.0).astype(qbd_ref.dtype)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        live_copies(i, slot, "wait")
        # A row's last chunk: zero V's rows past the live length (a
        # page that was not fetched holds stale VMEM, a fetched one the
        # pool's rows beyond the row's own; 0 * NaN is NaN).
        for j in range(ppc):
            left = length - c * ct - j * pt       # live rows of page j

            @pl.when(left < pt)
            def _():
                rows = pl.ds(j * pt, pt)
                page = vbuf[slot, rows].astype(jnp.float32)
                keep = lax.broadcasted_iota(jnp.int32, (pt, w), 0) < left
                vbuf[slot, rows] = jnp.where(keep, page,
                                             0.0).astype(vbuf.dtype)
        qbd = qbd_ref[...]
        k = kbuf[slot].astype(qbd.dtype)                     # [ct, W]
        v = vbuf[slot]
        s = lax.dot_general(qbd, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        kpos = c * ct + lax.broadcasted_iota(jnp.int32, (hp, ct), 1)
        live = kpos < length
        if window:
            live = live & (kpos >= length - window)
        s = jnp.where(live, s, _NEG_INF)                     # [hp, ct]
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # every listed chunk holds a key in sight, so m_new is a real
        # score and a masked one's exp is exactly 0
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        if split_p:
            # float32 probabilities against a narrower V: head and
            # remainder in V's dtype, one product of twice the rows.
            p_hi = p.astype(v.dtype)
            p_lo = (p - p_hi.astype(jnp.float32)).astype(v.dtype)
            pv = lax.dot_general(
                jnp.concatenate([p_hi, p_lo], axis=0), v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            pv = pv[:hp] + pv[hp:]
        else:
            pv = lax.dot_general(p, v.astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when((c + 1) * ct >= length)
        def _():
            out = jnp.where(own, acc_ref[...] / l_ref[:, :1], 0.0)
            if group == 1:
                o_ref[b] = jnp.sum(out, axis=0,
                                   keepdims=True).astype(o_ref.dtype)
            else:                # row h's values lie in its KV block
                o_ref[b] = sum(
                    out[:, n * head_dim:(n + 1) * head_dim]
                    for n in range(heads // group)).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, n_work, step, 0)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *, page_tokens: int,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           kv_heads: Optional[int] = None,
                           window: Optional[int] = None) -> jax.Array:
    """One query token per row against its own pages of the pool.

    ``q`` [B, H, D]; ``k_pool`` / ``v_pool`` [pool_rows, pool_width(H, D)]
    (a page = ``page_tokens`` consecutive rows, columns past H * D zero);
    with ``kv_heads`` < H (grouped-query attention: query head h reads
    KV head ``h // (H / kv_heads)``) the pools are
    ``pool_width(kv_heads, D)`` wide and ``D`` a multiple of the lane
    tile;
    ``page_table`` [B, pages_per_slot] int32 page ids; ``lengths`` [B]
    int32 live keys per row (0 = inactive: nothing read, zeros out).
    Returns [B, H, D] in ``q``'s dtype — softmax(q k^T * scale) v over
    keys ``0 .. lengths[b] - 1`` of row b (with ``window``: over the
    last ``window`` of them), float32 inside.
    ``interpret`` defaults to "off the TPU"."""
    if interpret is None:
        interpret = _interpret()
    if scale is None:
        scale = q.shape[-1] ** -0.5
    heads = q.shape[1]
    kv_heads = heads if kv_heads is None else int(kv_heads)
    if heads % kv_heads or (kv_heads != heads and q.shape[-1] % _LANES):
        raise ValueError(f"{heads} query heads of {q.shape[-1]} over "
                         f"{kv_heads} KV heads: the group must be whole and "
                         f"a grouped head a multiple of {_LANES} lanes")
    if window is not None and window < 1:
        raise ValueError(f"window {window}: a row sees itself at least")
    # (group 1 without a window traces the program as it always was)
    return _attend(q, k_pool, v_pool, page_table, lengths,
                   page_tokens=page_tokens, scale=float(scale),
                   interpret=bool(interpret), group=heads // kv_heads,
                   window=int(window or 0))


# jit: a model calls this once per layer with one signature, and an
# inner jit is traced and lowered to Mosaic once for all of them (48
# separate lowerings of the kernel body cost a GPT-2 XL engine 74 s of
# set-up in every process, compile cache or not; my chip runs, PR 26).
@functools.partial(jax.jit, static_argnames=("page_tokens", "scale",
                                             "interpret", "group",
                                             "window"))
def _attend(q, k_pool, v_pool, page_table, lengths, *, page_tokens,
            scale, interpret, group=1, window=0):
    b, heads, head_dim = q.shape
    w = pool_width(heads // group, head_dim)
    if k_pool.shape[1] != w or v_pool.shape != k_pool.shape:
        raise ValueError(f"pool {k_pool.shape} / {v_pool.shape} is not "
                         f"[rows, {w}] for {heads // group} heads of "
                         f"{head_dim}")
    pages_per_slot = page_table.shape[1]
    # K and V chunks, double-buffered, stay inside half of the 16 MiB
    # of VMEM a kernel may scope (a very wide pool takes fewer pages).
    page_bytes = page_tokens * w * jnp.dtype(k_pool.dtype).itemsize
    ppc = max(1, min(_CHUNK_TOKENS // page_tokens, pages_per_slot,
                     (8 << 20) // (4 * page_bytes)))
    ct = ppc * page_tokens
    hp = -(-heads // 16) * 16            # a sublane tile of any dtype
    split_p = (jnp.dtype(v_pool.dtype).itemsize < 4)
    if group == 1:
        qf = jnp.pad(q.reshape(b, 1, heads * head_dim),
                     ((0, 0), (0, 0), (0, w - heads * head_dim)))
    else:                    # a row per query head, padded to the tile
        qf = jnp.pad(q, ((0, 0), (0, hp - heads), (0, 0)))
    io = qf.shape[1:]        # [1, W], or grouped [hp, D]
    kern = functools.partial(
        _kernel, slots=b, pages_per_slot=pages_per_slot,
        page_tokens=page_tokens, pages_per_chunk=ppc, heads=heads,
        head_dim=head_dim, scale=scale, split_p=split_p, group=group,
        window=window)
    max_work = b * (-(-pages_per_slot // ppc))
    whole = lambda i, *_: (0, 0, 0)      # noqa: E731 — one invocation
    # Scope: hlo_bytes.KERNEL_SCOPES' convention (<prefix>_fwd); the
    # device trace names the operations by the call's own name.
    with jax.named_scope("tpunet_paged_decode_fwd"):
        out = pl.pallas_call(
            kern,
            name="tpunet_paged_decode",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(1,),
                in_specs=[pl.BlockSpec((b, *io), whole),
                          pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((b, *io), whole),
                scratch_shapes=[
                    pltpu.VMEM((2, ct, w), k_pool.dtype),
                    pltpu.VMEM((2, ct, w), v_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.SMEM((max_work,), jnp.int32),   # work: row
                    pltpu.SMEM((max_work,), jnp.int32),   # work: chunk
                    pltpu.VMEM((hp, w), q.dtype),         # block-diag q
                    pltpu.VMEM((hp, _LANES), jnp.float32),  # running max
                    pltpu.VMEM((hp, _LANES), jnp.float32),  # running sum
                    pltpu.VMEM((hp, w), jnp.float32),     # accumulator
                ]),
            out_shape=jax.ShapeDtypeStruct((b, *io), q.dtype),
            interpret=interpret,
        )(lengths.astype(jnp.int32),
          page_table.reshape(-1).astype(jnp.int32), qf, k_pool, v_pool)
    if group > 1:
        return out[:, :heads]
    return out[:, 0, :heads * head_dim].reshape(b, heads, head_dim)
