"""Pipeline-parallel ViT ("vit_pp").

Same architecture as tpunet/models/vit.py (pre-LN encoder, mean-pooled
tokens, linear head) but the encoder blocks are expressed as *stacked
functional parameters* — every weight has a leading ``depth`` dim — so
pipeline parallelism is just a sharding: the leading dim is split over
the mesh 'pipe' axis (path rule in tpunet/parallel/tp.py) and the GPipe
executor (tpunet/parallel/pp.py) streams microbatches through the
stages with ppermute hops.

With pipe == 1 (or mesh=None, e.g. single-chip serving) the same
stacked params run as a plain ``lax.scan`` over layers — bitwise the
same math, which is exactly what the parity tests assert.

Patch embed, final LN and the classifier head are tiny; they run
replicated on every pipe stage rather than being assigned to first/last
stages (standard trick — keeps the pipeline body uniform).

Differences from the dense ViT (documented, deliberate): the attention
core is dense, flash, or auto only — sequence parallelism lives in the
LM family (tpunet/models/lm_pp.py ulysses|ring), where sequences are
long enough to shard; flash picks the kernel variant by
context — see resolve_block_cores. Dropout IS supported: a PRNG key
threads through the GPipe executor, folded per (tick, stage, layer) —
see block_apply.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpunet.config import ModelConfig
from tpunet.ops import dense_attention
from tpunet.ops.flash import flash_attention, local_flash_attention
from tpunet.parallel.pp import gpipe, interleaved, onef1b


def resolve_block_cores(attention: str, block: int = 512):
    """(sequential_core, pipelined_core) for a pipeline model's blocks.

    'dense' honors the explicit request everywhere. 'blockwise' is the
    pure-JAX chunked scan (O(T x block) score memory — the bounded-
    memory core on any backend; it is mesh-free, so the same fn serves
    both contexts). 'flash'/'auto' use the fused kernel — but the
    VARIANT matters: inside the pipeline's shard_map the per-shard
    local kernel is correct (GSPMD is already done), while the
    sequential pipe==1 path runs under the top-level jit where only
    the mesh-split entry (tpunet/ops/partition.py) keeps a batch-sharded mesh
    from all-gathering q/k/v at every layer (the failure mode
    tpunet/ops/flash.py's partitioning section documents). Both fall
    back to dense off-TPU.
    """
    if attention == "dense":
        return dense_attention, dense_attention
    if attention == "blockwise":
        import functools

        from tpunet.ops import blockwise_attention
        core = functools.partial(blockwise_attention, block_size=block)
        return core, core
    return flash_attention, local_flash_attention


def _stacked_lecun_normal(key, shape, dtype=jnp.float32):
    """lecun_normal per layer for stacked [depth, fan_in, fan_out]
    kernels: fan_in is shape[-2] only — flax's variance_scaling would
    fold the stacked depth dim into the fan, and nn.Dense in the dense
    ViT uses lecun_normal, which this matches exactly (truncated normal,
    stddev correction 1/.87962566)."""
    fan_in = shape[-2]
    std = (1.0 / fan_in) ** 0.5 / 0.87962566103423978
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def _layer_norm(x, scale, bias, eps=1e-6):
    # Statistics in float32 regardless of compute dtype, matching flax
    # nn.LayerNorm's upcast behavior in the dense ViT.
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _dropout(x, rate, key):
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))


def attn_half_apply(p, x, *, heads, causal=False, dropout_rate=0.0,
                    key=None, attn=dense_attention, segment_ids=None):
    """The attention half of a pre-LN block: ln1 -> qkv -> ``attn`` ->
    out-projection -> dropout -> residual, then ln2. Returns
    ``(x_resid, y_ln2, mlp_key)`` — the post-residual activations, the
    ln2 output feeding whichever MLP follows (dense fc pair or the MoE
    core), and the second half of the dropout key split (None when
    dropout is off), so both block kinds share one dropout placement
    and key-split convention. ``segment_ids`` (packed sequences): a
    (q_seg, kv_seg) pair forwarded to segment-capable cores only when
    given, so SP closures without the kwarg stay usable."""
    mb, t, c = x.shape
    y = _layer_norm(x, p["ln1s"], p["ln1b"])
    qkv = y @ p["qkv_k"] + p["qkv_b"]
    qkv = qkv.reshape(mb, t, 3, heads, c // heads)
    if segment_ids is None:
        a = attn(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                 causal=causal)
    else:
        a = attn(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                 causal=causal, segment_ids=segment_ids)
    a = a.reshape(mb, t, c) @ p["out_k"] + p["out_b"]
    km = None
    if dropout_rate > 0.0 and key is not None:
        ka, km = jax.random.split(key)
        a = _dropout(a, dropout_rate, ka)
    x = x + a
    return x, _layer_norm(x, p["ln2s"], p["ln2b"]), km


def block_apply(p, x, *, heads, causal=False, dropout_rate=0.0, key=None,
                attn=dense_attention, segment_ids=None):
    """One pre-LN encoder block from a dict of per-layer params.

    Mirrors tpunet/models/vit.py's EncoderBlock: dropout (when
    ``dropout_rate > 0`` and ``key`` is given) applies after the
    attention out-projection and after the MLP's second dense, exactly
    the flax module's placements; ``causal=True`` is the LM family's
    autoregressive mask. ``attn`` is the core from
    :func:`resolve_block_cores` (dense, or the flash kernel variant
    matching the calling context)."""
    x, y, km = attn_half_apply(p, x, heads=heads, causal=causal,
                               dropout_rate=dropout_rate, key=key,
                               attn=attn, segment_ids=segment_ids)
    h = nn.gelu(y @ p["fc1_k"] + p["fc1_b"])
    h = h @ p["fc2_k"] + p["fc2_b"]
    if dropout_rate > 0.0 and km is not None:
        h = _dropout(h, dropout_rate, km)
    return x + h


class PipelinedViT(nn.Module):
    """ViT with stacked encoder params, pipelined over 'pipe'."""

    num_classes: int = 10
    patch_size: int = 4
    hidden: int = 64
    depth: int = 4
    heads: int = 4
    mlp_ratio: float = 4.0
    n_micro: int = 4
    dropout_rate: float = 0.0
    attention: str = "dense"           # dense | flash | auto
    schedule: str = "gpipe"    # gpipe | 1f1b | interleaved (pp.py)
    virtual: int = 2                   # chunks/device for interleaved
    mesh: Any = None                   # jax.sharding.Mesh or None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.hidden % self.heads:
            raise ValueError(f"hidden {self.hidden} not divisible by "
                             f"{self.heads} heads")
        p = self.patch_size
        if x.shape[1] % p or x.shape[2] % p:
            raise ValueError(f"image {x.shape[1]}x{x.shape[2]} not "
                             f"divisible by patch {p}")
        x = x.astype(self.dtype)
        x = nn.Conv(self.hidden, (p, p), strides=(p, p), padding="VALID",
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name="patch_embed")(x)
        b, h, w, c = x.shape
        x = x.reshape(b, h * w, c)
        pos = self.param("pos_embed", nn.initializers.normal(stddev=0.02),
                         (1, h * w, c), self.param_dtype)
        x = x + pos.astype(self.dtype)

        ln_ones = nn.initializers.ones
        zeros = nn.initializers.zeros
        winit = _stacked_lecun_normal
        L, C, H = self.depth, c, int(self.hidden * self.mlp_ratio)
        blocks = {
            "ln1s": self.param("blocks_ln1s", ln_ones, (L, C),
                               self.param_dtype),
            "ln1b": self.param("blocks_ln1b", zeros, (L, C),
                               self.param_dtype),
            "qkv_k": self.param("blocks_qkv_k", winit, (L, C, 3 * C),
                                self.param_dtype),
            "qkv_b": self.param("blocks_qkv_b", zeros, (L, 3 * C),
                                self.param_dtype),
            "out_k": self.param("blocks_out_k", winit, (L, C, C),
                                self.param_dtype),
            "out_b": self.param("blocks_out_b", zeros, (L, C),
                                self.param_dtype),
            "ln2s": self.param("blocks_ln2s", ln_ones, (L, C),
                               self.param_dtype),
            "ln2b": self.param("blocks_ln2b", zeros, (L, C),
                               self.param_dtype),
            "fc1_k": self.param("blocks_fc1_k", winit, (L, C, H),
                                self.param_dtype),
            "fc1_b": self.param("blocks_fc1_b", zeros, (L, H),
                                self.param_dtype),
            "fc2_k": self.param("blocks_fc2_k", winit, (L, H, C),
                                self.param_dtype),
            "fc2_b": self.param("blocks_fc2_b", zeros, (L, C),
                                self.param_dtype),
        }
        blocks = jax.tree_util.tree_map(
            lambda a: a.astype(self.dtype), blocks)
        heads = self.heads
        rate = self.dropout_rate if train else 0.0
        key = self.make_rng("dropout") if rate > 0.0 else None
        if key is not None:
            x = _dropout(x, rate, self.make_rng("dropout"))

        seq_core, pipe_core = resolve_block_cores(self.attention)
        pipelined = (self.mesh is not None
                     and self.mesh.shape.get("pipe", 1) > 1)
        attn = pipe_core if pipelined else seq_core

        def stage_apply(params, xs, k=None):
            def body(carry, inp):
                pl, i = inp
                lk = (jax.random.fold_in(k, i) if k is not None else None)
                return block_apply(pl, carry, heads=heads,
                                   dropout_rate=rate, key=lk,
                                   attn=attn), None
            idx = jnp.arange(jax.tree_util.tree_leaves(params)[0].shape[0])
            out, _ = jax.lax.scan(body, xs, (params, idx))
            return out

        if pipelined and self.schedule == "interleaved":
            # Virtual stages (chunk-permuted 'pipe' storage — see
            # tpunet/parallel/pp.py interleaved / lm_pp's note).
            x = interleaved(stage_apply, blocks, x, mesh=self.mesh,
                            n_micro=self.n_micro,
                            n_virtual=self.virtual, key=key)
        elif pipelined:
            executor = onef1b if self.schedule == "1f1b" else gpipe
            x = executor(stage_apply, blocks, x, mesh=self.mesh,
                         n_micro=self.n_micro, key=key)
        else:
            x = (stage_apply(blocks, x) if key is None
                 else stage_apply(blocks, x, key))

        x = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype,
                         name="ln")(x)
        x = jnp.mean(x, axis=1)
        x = nn.Dense(self.num_classes,
                     kernel_init=nn.initializers.zeros_init(),
                     dtype=self.dtype, param_dtype=self.param_dtype,
                     name="classifier")(x)
        return x.astype(jnp.float32)


def create_model(cfg: ModelConfig, mesh=None) -> PipelinedViT:
    """Build a PipelinedViT. Unsupported 'vit' features fail loudly."""
    if cfg.attention not in ("dense", "flash", "auto"):
        raise ValueError(
            f"vit_pp supports dense/flash/auto attention (got "
            f"{cfg.attention!r}); sequence parallelism is the LM "
            "family's (lm/lm_pp ulysses|ring) — a 64-token patch grid "
            "has nothing to shard")
    if cfg.moe_experts > 0:
        raise ValueError("vit_pp does not support MoE blocks (the "
                         "MoE x PP composition lives in the LM "
                         "family: --model lm_pp --moe-experts N)")
    if cfg.pp_schedule not in ("gpipe", "1f1b", "interleaved"):
        raise ValueError(f"unknown pp_schedule {cfg.pp_schedule!r}; "
                         "expected gpipe|1f1b|interleaved")
    if cfg.pp_schedule == "interleaved":
        stages = mesh.shape.get("pipe", 1) if mesh is not None else 1
        if stages < 2:
            raise ValueError(
                "pp_schedule='interleaved' needs a mesh 'pipe' axis "
                "> 1 (use gpipe/1f1b at pipe=1)")
        if cfg.pp_virtual < 2:
            raise ValueError(f"--pp-virtual must be >= 2 (got "
                             f"{cfg.pp_virtual})")
        if cfg.vit_depth % (stages * cfg.pp_virtual):
            raise ValueError(
                f"vit_depth {cfg.vit_depth} not divisible by "
                f"{stages} stages x {cfg.pp_virtual} virtual chunks")
        if cfg.pp_microbatches % stages:
            raise ValueError(
                f"--pp-microbatches {cfg.pp_microbatches} not "
                f"divisible by the pipe axis ({stages})")
    if cfg.remat:
        # Same contract as lm_pp: a silently-ignored memory flag is a
        # trap — the pipeline already bounds activation memory per
        # stage (use --pp-schedule 1f1b when the backward binds).
        raise ValueError("vit_pp does not support --remat (the "
                         "pipeline scan already bounds activation "
                         "memory per stage; --pp-schedule 1f1b bounds "
                         "the backward)")
    if mesh is not None:
        stages = mesh.shape.get("pipe", 1)
        if stages > 1 and cfg.vit_depth % stages:
            raise ValueError(f"vit_depth {cfg.vit_depth} not divisible by "
                             f"{stages} pipeline stages")
    return PipelinedViT(
        num_classes=cfg.num_classes,
        patch_size=cfg.vit_patch,
        hidden=cfg.vit_hidden,
        depth=cfg.vit_depth,
        heads=cfg.vit_heads,
        mlp_ratio=cfg.vit_mlp_ratio,
        n_micro=cfg.pp_microbatches,
        dropout_rate=cfg.dropout_rate,
        attention=cfg.attention,
        schedule=cfg.pp_schedule,
        virtual=cfg.pp_virtual,
        mesh=mesh,
        dtype=jnp.dtype(cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype),
    )
