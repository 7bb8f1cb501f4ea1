"""Vision Transformer in Flax — tpunet's attention-based model family.

The reference has exactly one model (torchvision MobileNetV2,
cifar10_mpi_mobilenet_224.py:137-139). tpunet adds a ViT family because
a TPU framework's parallelism surface is defined by attention: sequence/
context parallelism (ring attention over a 'seq' mesh axis), tensor
parallelism (heads/MLP over the 'model' axis) and expert parallelism all
need a transformer to exercise them end-to-end on the same CIFAR-10
workload, trainer, checkpointing and serving stack as the CNN.

TPU-first choices:

- Pre-LN encoder, mean-pooled tokens (no CLS token: the sequence stays
  exactly ``(image/patch)**2`` long, so it divides evenly over a
  sequence-parallel mesh axis).
- bfloat16 compute / float32 params; logits float32.
- The attention implementation is injected (``attn_fn``): dense or
  blockwise for a single chip, ``ring_self_attention`` over the 'seq'
  mesh axis for sequence parallelism (tpunet/ops/attention.py). The
  module itself stays mesh-agnostic.
- QKV / output / MLP projections are single fused Dense ops — large
  matmuls for the MXU; tensor-parallel sharding of their parameters is
  applied from outside via path rules (tpunet/parallel/tp.py), not
  baked into the module.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpunet.config import ModelConfig
from tpunet.ops import blockwise_attention, dense_attention

AttnFn = Callable[..., jax.Array]  # (q, k, v) BTHD -> BTHD


@dataclasses.dataclass(frozen=True)
class PagedKV:
    """Paged KV-cache geometry (tpunet/serve continuous batching).

    The serve engine's cache is a SHARED page pool: K/V live
    in ``pages`` fixed-size pages of ``page_tokens`` tokens each, and
    every batch row addresses its tokens through a per-row page table
    (``page_table`` [B, ceil(max_seq_len/page_tokens)] int32 page
    ids). A slot then costs HBM proportional to its prompt+generated
    length, not ``max_seq_len`` — the engine (tpunet/serve/engine.py)
    owns allocation (allocate-on-advance, free-on-finish, recycling).

    Page 0 is RESERVED as the garbage page: inactive rows and the
    padded tail of a bucketed prefill scatter their writes there, and
    the host allocator never hands it to a slot — the write gate is an
    index redirect, not a select over the whole pool.

    ``dtype`` selects the page payload: "auto" stores at the compute
    dtype, "bfloat16" halves float32 payloads, "int8" quantizes each
    written token row against its own absmax with the float32 scale
    stored alongside the page (per page-row scale — a single scalar
    per page cannot absorb incremental writes without rescaling the
    whole page) and dequantizes on gather.

    ``mesh_sharded`` is what the pool's owner knows and a trace cannot
    see: the engine sets it when it serves over a mesh (the pool is
    then split over the 'model' axis and GSPMD partitions the attend);
    a pool on one device may be read in place by the width-1 decode
    kernel (tpunet/ops/paged_decode.py ``kernel_applies``).
    """

    pages: int            # total pages INCLUDING the reserved page 0
    page_tokens: int      # tokens per page
    dtype: str = "auto"   # auto | bfloat16 | int8
    mesh_sharded: bool = False

    def store_dtype(self, compute_dtype):
        if self.dtype == "auto":
            return compute_dtype
        if self.dtype in ("bfloat16", "bf16"):
            return jnp.bfloat16
        if self.dtype == "int8":
            return jnp.int8
        raise ValueError(f"unknown kv dtype {self.dtype!r}")

    @property
    def quantized(self) -> bool:
        return self.dtype == "int8"


def _quantize_kv_rows(x):
    """Symmetric int8 per-row quantization of ``x`` [N, W]: each
    token row is scaled by its own absmax over its columns so one
    outlier token cannot crush every other row's resolution. Returns
    (int8 rows, float32 scale [N])."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[:, None]), -127, 127)
    return q.astype(jnp.int8), scale


class Attention(nn.Module):
    """Multi-head self-attention with an injected core attention op.

    ``decode=True`` switches to incremental decoding against a KV cache
    (flax 'cache' collection): the call processes one new token, writes
    its K/V at the cache index, and attends over the cached prefix —
    O(T) per step instead of O(T^2) recompute. The cache buffers are
    created (sized by the input length) when the module is initialized
    with ``decode=True``; the injected attn_fn is bypassed in this mode
    (single-query attention is computed inline).

    That module-clocked cache is solo ``models.lm.generate``'s (the
    tests' reference). Serving (tpunet/serve continuous batching)
    passes ``paged_kv`` + ``page_table`` and decodes against a shared
    page pool instead (``_paged_decode_attend``): ``positions`` [B]
    int32 gives each batch row its OWN write index (rows advance
    independently — the engine keeps requests at different depths in
    one batch) and generalizes the call to T >= 1 queries per row
    (chunked prefill: K/V for positions ``positions[b] ..
    positions[b]+T-1`` are written in one pass, causally masked);
    ``active`` [B] bool gates the write per row. The module's own
    ``cache_index`` is then neither created nor read: the engine owns
    the clock."""

    heads: int
    attn_fn: AttnFn = dense_attention
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False, decode: bool = False,
                 segment_ids=None, positions=None, active=None,
                 paged_kv=None, page_table=None):
        b, t, c = x.shape
        if c % self.heads:
            raise ValueError(
                f"hidden dim {c} not divisible by {self.heads} heads")
        head_dim = c // self.heads
        qkv = nn.Dense(3 * c, use_bias=True, dtype=self.dtype,
                       param_dtype=self.param_dtype, name="qkv")(x)
        qkv = qkv.reshape(b, t, 3, self.heads, head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if decode:
            y = self._decode_attend(q, k, v, positions, active,
                                    paged_kv, page_table)
        elif segment_ids is not None:
            # Packed sequences: same-segment masking in the core. The
            # dense/flash cores and Ulysses SP take the kwarg (packed
            # x SP composes, tpunet/ops/attention.py); ring's
            # state-merging core doesn't — config validation rejects
            # that combination up front and a TypeError backstops it.
            y = self.attn_fn(q, k, v,
                             segment_ids=(segment_ids, segment_ids))
        else:
            y = self.attn_fn(q, k, v)
        y = y.reshape(b, t, c)
        y = nn.Dense(c, dtype=self.dtype, param_dtype=self.param_dtype,
                     name="out")(y)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        return y

    def _decode_attend(self, q, k, v, positions=None, active=None,
                       paged_kv=None, page_table=None):
        if paged_kv is not None:
            return self._paged_decode_attend(q, k, v, positions, active,
                                             paged_kv, page_table)
        is_init = not self.has_variable("cache", "cached_k")
        ck = self.variable("cache", "cached_k", jnp.zeros, k.shape, k.dtype)
        cv = self.variable("cache", "cached_v", jnp.zeros, v.shape, v.dtype)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((), jnp.int32))
        if is_init:
            # init pass (full-length dummy): the buffers are sized from
            # k/v; skip the attention core entirely (it has no params,
            # and sharded cores would impose mesh divisibility on the
            # dummy shape — decode steps never call it).
            return jnp.zeros_like(q)
        # Solo decode (models.lm.generate): one clock for the whole
        # batch, one token per call, module-owned advance.
        if positions is not None or active is not None:
            raise ValueError(
                "per-row positions and an active mask address a paged "
                "pool: pass paged_kv and a page_table (TransformerLM "
                "passes them only with one)")
        if q.shape[1] != 1:
            raise ValueError(
                f"decode processes one token per call, got {q.shape[1]}")
        ck.value = jax.lax.dynamic_update_slice(
            ck.value, k, (0, ci.value, 0, 0))
        cv.value = jax.lax.dynamic_update_slice(
            cv.value, v, (0, ci.value, 0, 0))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, ck.value,
                       preferred_element_type=jnp.float32)
        s = s * (q.shape[-1] ** -0.5)
        # only cache entries at or before the clock are real
        from tpunet.ops.attention import _NEG_INF
        valid = jnp.arange(ck.value.shape[1]) <= ci.value          # [K]
        s = jnp.where(valid[None, None, None, :], s, _NEG_INF)
        ci.value = ci.value + 1
        p = jax.nn.softmax(s, axis=-1)
        y = jnp.einsum("bhqk,bkhd->bqhd", p, cv.value,
                       preferred_element_type=jnp.float32)
        return y.astype(q.dtype)

    def _paged_decode_attend(self, q, k, v, positions, active,
                             paged_kv, page_table):
        """Paged decode: K/V live in a SHARED flat page pool
        ``[pages * page_tokens, W]`` per layer, a token's heads side by
        side in one row and ``W`` = H * D rounded up to the 128-lane
        tile (``paged_decode.pool_width``; the padding columns hold
        zeros — the rounding keeps the TPU's device layout of the
        buffer row-major, so a program that scatters rows into it does
        not convert the whole pool in and out). Each row's logical
        position p maps to flat row
        ``page_table[b, p // page_tokens] * page_tokens + p %
        page_tokens``. Writes are one scatter over the new rows
        (inactive rows and unallocated positions are redirected into
        the reserved garbage page 0), for every caller alike.

        Two attend paths, chosen at trace time from what the trace
        sees (``paged_decode.kernel_applies``: one token per row, an
        unquantized pool on one device, a TPU backend): the Pallas
        kernel ``tpunet_paged_decode`` walks each row's own pages in
        the pool up to the row's live length; everything else — the
        bucket-wide prefill programs, the spec verify and draft
        programs, int8 pools, a mesh, the CPU — gathers the row's
        pages back into position order and runs the exact dense masked
        attention math over them. Causality (j <= qpos per row) makes
        garbage beyond each row's own written prefix invisible on
        both.

        int8 pages carry a float32 scale per page row (written in the
        same scatter) and dequantize on gather. The engine owns page
        allocation; this method never sees a free list.

        Prefix-cache safety contract (PR 18): the scatter only ever
        touches rows for the NEW tokens of this step — flat indices
        derived from ``positions + arange(t)``, i.e. positions >= the
        row's prefill start. Pages the engine pinned from the prefix
        cache cover positions strictly BELOW start, so shared
        refcounted pages are bitwise-frozen by construction; the
        engine enforces copy-on-write before any position inside a
        shared page could land in the scatter."""
        b, t = q.shape[0], q.shape[1]
        heads, head_dim = k.shape[2], k.shape[3]
        pt = paged_kv.page_tokens
        flat_rows = paged_kv.pages * pt
        store_dtype = paged_kv.store_dtype(k.dtype)
        is_init = not self.has_variable("cache", "cached_k")
        from tpunet.ops import paged_decode
        hd = heads * head_dim
        width = paged_decode.pool_width(heads, head_dim)
        ck = self.variable("cache", "cached_k", jnp.zeros,
                           (flat_rows, width), store_dtype)
        cv = self.variable("cache", "cached_v", jnp.zeros,
                           (flat_rows, width), store_dtype)
        if paged_kv.quantized:
            sk = self.variable("cache", "scale_k", jnp.zeros,
                               (flat_rows,), jnp.float32)
            sv = self.variable("cache", "scale_v", jnp.zeros,
                               (flat_rows,), jnp.float32)
        if is_init:
            # Cache-creation pass (positions legitimately absent):
            # buffers sized above, attention skipped like the solo
            # init path.
            return jnp.zeros_like(q)
        if positions is None or page_table is None:
            raise ValueError("paged decode requires engine-owned "
                             "per-row positions and a page table")

        # -- write: new K/V rows scattered to their flat page rows ----
        pos_t = positions[:, None] + jnp.arange(t)[None, :]     # [B, T]
        page_slot = jnp.clip(pos_t // pt, 0, page_table.shape[1] - 1)
        page_ids = jnp.take_along_axis(page_table, page_slot, axis=1)
        flat_idx = page_ids * pt + pos_t % pt                   # [B, T]
        if active is not None:
            # Inactive rows write into the garbage page instead of
            # being where()-gated over the whole pool.
            flat_idx = jnp.where(active[:, None], flat_idx, 0)
        flat_idx = flat_idx.reshape(-1)
        pad = ((0, 0), (0, width - hd))
        k_rows = jnp.pad(k.reshape(b * t, hd), pad)
        v_rows = jnp.pad(v.reshape(b * t, hd), pad)
        if paged_kv.quantized:
            k_q, k_s = _quantize_kv_rows(k_rows)
            v_q, v_s = _quantize_kv_rows(v_rows)
            ck.value = ck.value.at[flat_idx].set(k_q)
            cv.value = cv.value.at[flat_idx].set(v_q)
            sk.value = sk.value.at[flat_idx].set(k_s)
            sv.value = sv.value.at[flat_idx].set(v_s)
        else:
            ck.value = ck.value.at[flat_idx].set(
                k_rows.astype(store_dtype))
            cv.value = cv.value.at[flat_idx].set(
                v_rows.astype(store_dtype))

        if paged_decode.kernel_applies(paged_kv, t, store_dtype):
            # -- in place: each row's own pages, up to its length ------
            lengths = positions + 1
            if active is not None:
                lengths = jnp.where(active, lengths, 0)
            y = paged_decode.paged_decode_attention(
                q[:, 0], ck.value, cv.value, page_table, lengths,
                page_tokens=pt)
            return y[:, None]

        # -- gather: each row's pages back into position order --------
        n_page_slots = page_table.shape[1]
        rows = (page_table[:, :, None] * pt
                + jnp.arange(pt)[None, None, :]).reshape(b, -1)  # [B, K]
        kf = jnp.take(ck.value, rows, axis=0)
        vf = jnp.take(cv.value, rows, axis=0)
        if paged_kv.quantized:
            kf = kf.astype(jnp.float32) \
                * jnp.take(sk.value, rows, axis=0)[..., None]
            vf = vf.astype(jnp.float32) \
                * jnp.take(sv.value, rows, axis=0)[..., None]
        kf = kf[..., :hd].astype(q.dtype).reshape(b, -1, heads, head_dim)
        vf = vf[..., :hd].astype(q.dtype).reshape(b, -1, heads, head_dim)

        s = jnp.einsum("bqhd,bkhd->bhqk", q, kf,
                       preferred_element_type=jnp.float32)
        s = s * (q.shape[-1] ** -0.5)
        from tpunet.ops.attention import _NEG_INF
        qpos = pos_t                                            # [B, T]
        valid = (jnp.arange(n_page_slots * pt)[None, None, :]
                 <= qpos[:, :, None])                           # [B,T,K]
        s = jnp.where(valid[:, None, :, :], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        y = jnp.einsum("bhqk,bkhd->bqhd", p, vf,
                       preferred_element_type=jnp.float32)
        return y.astype(q.dtype)


class MlpBlock(nn.Module):
    """Transformer MLP: Dense -> GELU -> Dense."""

    mlp_dim: int
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = x.shape[-1]
        y = nn.Dense(self.mlp_dim, dtype=self.dtype,
                     param_dtype=self.param_dtype, name="fc1")(x)
        y = nn.gelu(y)
        y = nn.Dense(c, dtype=self.dtype, param_dtype=self.param_dtype,
                     name="fc2")(y)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        return y


class EncoderBlock(nn.Module):
    """Pre-LN block: x + Attn(LN(x)); x + Mlp(LN(x)).

    With ``moe_experts > 0`` the dense MLP is replaced by a top-k routed
    MoE MLP (tpunet/models/moe.py) — expert-parallel over the mesh
    'model' axis via the TP path rules."""

    heads: int
    mlp_dim: int
    attn_fn: AttnFn = dense_attention
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_dispatch: str = "auto"        # EP lowering (moe.py docstring)
    moe_mesh: Any = None              # mesh for the a2a EP lowering
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False, decode: bool = False,
                 segment_ids=None, positions=None, active=None,
                 paged_kv=None, page_table=None):
        y = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype,
                         name="ln1")(x)
        x = x + Attention(self.heads, attn_fn=self.attn_fn,
                          dropout_rate=self.dropout_rate, dtype=self.dtype,
                          param_dtype=self.param_dtype,
                          name="attn")(y, train, decode, segment_ids,
                                       positions, active, paged_kv,
                                       page_table)
        y = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype,
                         name="ln2")(x)
        if self.moe_experts > 0:
            from tpunet.models.moe import MoeMlp
            mlp_out = MoeMlp(self.moe_experts, self.mlp_dim,
                             top_k=self.moe_top_k,
                             capacity_factor=self.moe_capacity_factor,
                             dispatch=self.moe_dispatch,
                             mesh=self.moe_mesh,
                             dropout_rate=self.dropout_rate,
                             dtype=self.dtype, param_dtype=self.param_dtype,
                             name="moe")(y, train)
        else:
            mlp_out = MlpBlock(self.mlp_dim, dropout_rate=self.dropout_rate,
                               dtype=self.dtype, param_dtype=self.param_dtype,
                               name="mlp")(y, train)
        return x + mlp_out


class ViT(nn.Module):
    """ViT backbone + linear head; same call signature as MobileNetV2
    (NHWC normalized images in, float32 logits out) so the trainer,
    checkpointing and serving stack are model-agnostic."""

    num_classes: int = 10
    patch_size: int = 16
    hidden: int = 192
    depth: int = 6
    heads: int = 3
    mlp_ratio: float = 4.0
    dropout_rate: float = 0.0
    attn_fn: AttnFn = dense_attention
    moe_experts: int = 0              # 0 = dense MLP everywhere
    moe_every: int = 2                # MoE in every moe_every-th block
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_dispatch: str = "auto"
    moe_mesh: Any = None
    remat: bool = False               # jax.checkpoint each block
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.moe_experts > 0 and self.moe_every < 1:
            raise ValueError(f"moe_every must be >= 1, got {self.moe_every}")
        p = self.patch_size
        if x.shape[1] % p or x.shape[2] % p:
            raise ValueError(
                f"image {x.shape[1]}x{x.shape[2]} not divisible by "
                f"patch {p}")
        x = x.astype(self.dtype)
        x = nn.Conv(self.hidden, (p, p), strides=(p, p), padding="VALID",
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name="patch_embed")(x)
        b, h, w, c = x.shape
        x = x.reshape(b, h * w, c)
        pos = self.param("pos_embed", nn.initializers.normal(stddev=0.02),
                         (1, h * w, c), self.param_dtype)
        x = x + pos.astype(self.dtype)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        # Remat: recompute each block's activations in the backward pass
        # (jax.checkpoint) — O(depth) less live memory for long contexts.
        Block = (nn.remat(EncoderBlock, static_argnums=(2,))
                 if self.remat else EncoderBlock)
        for i in range(self.depth):
            # ViT-MoE placement: sparse MLP in every moe_every-th block
            # (the later block of each pair), dense elsewhere.
            moe_here = (self.moe_experts > 0
                        and i % self.moe_every == self.moe_every - 1)
            x = Block(self.heads, int(self.hidden * self.mlp_ratio),
                             attn_fn=self.attn_fn,
                             moe_experts=self.moe_experts if moe_here else 0,
                             moe_top_k=self.moe_top_k,
                             moe_capacity_factor=self.moe_capacity_factor,
                             moe_dispatch=self.moe_dispatch,
                             moe_mesh=self.moe_mesh,
                             dropout_rate=self.dropout_rate,
                             dtype=self.dtype, param_dtype=self.param_dtype,
                             name=f"block{i:02d}")(x, train)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype,
                         name="ln")(x)
        x = jnp.mean(x, axis=1)  # mean pool over tokens
        x = nn.Dense(self.num_classes,
                     kernel_init=nn.initializers.zeros_init(),
                     dtype=self.dtype, param_dtype=self.param_dtype,
                     name="classifier")(x)
        return x.astype(jnp.float32)


# Name -> (patch, hidden, depth, heads). "vit" uses the ModelConfig's
# vit_* fields directly.
VIT_PRESETS = {
    "vit_tiny": (16, 192, 12, 3),
    "vit_small": (16, 384, 12, 6),
    "vit_base": (16, 768, 12, 12),
}


def make_attn_fn(cfg: ModelConfig, mesh=None, causal: bool = False) -> AttnFn:
    """Resolve the configured attention implementation.

    'ring' needs the mesh (sequence-parallel shard_map over its 'seq'
    axis); 'dense'/'blockwise' are mesh-free. ``causal`` is exact under
    sequence sharding (global positions, tpunet/ops/attention.py).
    """
    import functools
    if cfg.attention == "auto":
        # Measured policy (README long-context table): the flash kernel
        # wins every regime on TPU; elsewhere flash_attention itself
        # falls back to dense, so 'auto' == flash with dense semantics
        # off-TPU. Resolved at model build time.
        cfg = dataclasses.replace(
            cfg, attention=("flash" if jax.default_backend() == "tpu"
                            else "dense"))
    if cfg.attention == "dense":
        return functools.partial(dense_attention, causal=causal)
    if cfg.attention == "blockwise":
        return functools.partial(blockwise_attention,
                                 block_size=cfg.attention_block,
                                 causal=causal)
    if cfg.attention == "flash":
        from tpunet.ops.flash import flash_attention
        return functools.partial(flash_attention,
                                 block_q=cfg.attention_block,
                                 block_k=cfg.attention_block,
                                 causal=causal)
    if cfg.attention == "ring":
        if mesh is None:
            raise ValueError("attention='ring' requires a mesh")
        from tpunet.ops import ring_self_attention
        core = None if cfg.attention_core == "auto" else cfg.attention_core
        return functools.partial(ring_self_attention, mesh=mesh,
                                 causal=causal, core=core)
    if cfg.attention == "ulysses":
        if mesh is None:
            raise ValueError("attention='ulysses' requires a mesh")
        from tpunet.ops import ulysses_self_attention
        core = None if cfg.attention_core == "auto" else cfg.attention_core
        return functools.partial(ulysses_self_attention, mesh=mesh,
                                 causal=causal, core=core,
                                 block=cfg.attention_block)
    raise ValueError(f"unknown attention {cfg.attention!r}")


def create_model(cfg: ModelConfig, mesh=None) -> ViT:
    if cfg.name in VIT_PRESETS:
        patch, hidden, depth, heads = VIT_PRESETS[cfg.name]
    elif cfg.name == "vit":
        patch, hidden, depth, heads = (cfg.vit_patch, cfg.vit_hidden,
                                       cfg.vit_depth, cfg.vit_heads)
    else:
        raise ValueError(f"unknown ViT model {cfg.name!r}")
    return ViT(
        num_classes=cfg.num_classes,
        patch_size=patch,
        hidden=hidden,
        depth=depth,
        heads=heads,
        mlp_ratio=cfg.vit_mlp_ratio,
        dropout_rate=cfg.dropout_rate,
        attn_fn=make_attn_fn(cfg, mesh),
        moe_experts=cfg.moe_experts,
        moe_every=cfg.moe_every,
        moe_top_k=cfg.moe_top_k,
        moe_capacity_factor=cfg.moe_capacity_factor,
        moe_dispatch=cfg.moe_dispatch,
        moe_mesh=mesh,
        remat=cfg.remat,
        dtype=jnp.dtype(cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype),
    )
