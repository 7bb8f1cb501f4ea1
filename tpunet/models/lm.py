"""Decoder-only transformer LM — tpunet's long-context model family.

The reference is a fixed-224px vision CNN with no sequence axis at all
(SURVEY.md section 5, "long-context: absent entirely"); tpunet treats
long context as first-class, and this model is where it is exercised
end-to-end: causal attention over sequences whose length scales with
the mesh 'seq' axis (ring attention, exact causality under sharding via
global positions) or with bounded memory on one chip (blockwise).

Architecture: token embedding + learned positions -> the same pre-LN
encoder blocks as the ViT family (tpunet/models/vit.py, with a causal
attention core) -> final LN -> logits against the embedding transpose
(weight tying — halves the head params and is standard for small LMs).

Reuses the whole tpunet stack: Trainer epoch loop, psum metrics, Orbax
checkpointing, TP path rules (the block param names match the ViT
rules), MoE blocks, and the dense/blockwise/ring/ulysses attention
cores.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpunet.config import ModelConfig
from tpunet.models.vit import EncoderBlock, make_attn_fn


class TransformerLM(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32."""

    vocab_size: int = 256
    hidden: int = 192
    depth: int = 6
    heads: int = 3
    mlp_ratio: float = 4.0
    max_len: int = 1024
    dropout_rate: float = 0.0
    attn_fn: Any = None
    moe_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_dispatch: str = "auto"
    moe_mesh: Any = None
    remat: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    input_kind = "tokens"              # init_variables dispatch

    @nn.compact
    def __call__(self, tokens, train: bool = False, decode: bool = False,
                 pos_offset=0, segment_ids=None,
                 return_hidden: bool = False, decode_active=None,
                 paged_kv=None, page_table=None):
        """``decode=True``: incremental step against the KV cache (one
        token per call after cache init); ``pos_offset`` is the absolute
        position of ``tokens[:, 0]`` in the sequence — a scalar (solo
        ``generate``), or with a page table an int32 [B] array giving
        each batch row its OWN position (the tpunet/serve engine: rows
        are independent requests at different depths; T > 1 then runs
        a chunked causal prefill that writes K/V for all T positions
        in one pass) and ``decode_active`` [B] bool gating per-row
        cache writes. ``segment_ids`` [B, T] enables packed-sequence
        training: attention is masked to same-segment tokens (composed
        with causality in the core). ``return_hidden=True`` returns the
        final-LN hidden states [B, T, C] float32 instead of logits —
        the vocab-sharded CE hook (tpunet/ops/vocab_ce.py): the caller
        computes the loss against the tied embedding without ever
        materializing the [B, T, V] logits. ``paged_kv`` (a
        ``models.vit.PagedKV``) + ``page_table`` [B, pages-per-row]
        int32 switch the decode KV cache to the shared page pool
        (tpunet/serve continuous batching; needs per-row
        ``pos_offset``, and per-row ``pos_offset`` needs it: the
        attention raises on one without the other)."""
        b, t = tokens.shape
        if t > self.max_len:
            raise ValueError(f"sequence {t} exceeds max_len {self.max_len}")
        embed = nn.Embed(self.vocab_size, self.hidden,
                         embedding_init=nn.initializers.normal(stddev=0.02),
                         param_dtype=self.param_dtype, name="embed")
        x = embed(tokens).astype(self.dtype)
        pos = self.param("pos_embed", nn.initializers.normal(stddev=0.02),
                         (1, self.max_len, self.hidden), self.param_dtype)
        per_row = getattr(pos_offset, "ndim", 0) == 1
        if per_row:
            # Per-row positions (serve engine): gather each row's slice
            # of the position table; clip covers the padded tail of a
            # bucketed prefill (those K/V are overwritten before any
            # query can attend to them — engine invariant).
            idx = jnp.clip(pos_offset[:, None] + jnp.arange(t)[None, :],
                           0, self.max_len - 1)
            x = x + jnp.take(pos[0], idx, axis=0).astype(self.dtype)
        else:
            x = x + jax.lax.dynamic_slice_in_dim(
                pos, pos_offset, t, 1).astype(self.dtype)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        # remat only matters for training; never wrap the decode path.
        # (both flags are static: argnums count self as 0)
        Block = (nn.remat(EncoderBlock, static_argnums=(2, 3))
                 if self.remat and not decode else EncoderBlock)
        for i in range(self.depth):
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
                             name=f"block{i:02d}")(
                                 x, train, decode, segment_ids,
                                 pos_offset if per_row else None,
                                 decode_active, paged_kv, page_table)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype,
                         name="ln")(x)
        if return_hidden:
            return x.astype(jnp.float32)
        # Tied output head: logits against the embedding matrix.
        logits = embed.attend(x.astype(self.param_dtype))
        return logits.astype(jnp.float32)


def create_model(cfg: ModelConfig, mesh=None) -> TransformerLM:
    return TransformerLM(
        vocab_size=cfg.vocab_size,
        hidden=cfg.vit_hidden,
        depth=cfg.vit_depth,
        heads=cfg.vit_heads,
        mlp_ratio=cfg.vit_mlp_ratio,
        max_len=cfg.max_seq_len,
        dropout_rate=cfg.dropout_rate,
        attn_fn=make_attn_fn(cfg, mesh, causal=True),
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


def filter_logits(lg, *, top_k: int = 0, top_p: float = 0.0):
    """Truncate ``lg`` [..., V] for sampling: tokens outside the filters
    become -inf. Sequential HF-warper semantics: top-k first, then the
    nucleus over the RENORMALIZED post-top-k distribution (computing the
    nucleus on the raw distribution would admit a larger, more
    permissive nucleus whenever top-k removed tail mass)."""
    need_sort = (top_k > 0 and top_k < lg.shape[-1]) or 0.0 < top_p < 1.0
    if need_sort:
        srt = jnp.sort(lg, -1)[..., ::-1]  # one descending sort
    if top_k > 0 and top_k < lg.shape[-1]:
        lg = jnp.where(lg >= srt[..., top_k - 1:top_k], lg, -jnp.inf)
        srt = jnp.where(jnp.arange(srt.shape[-1]) < top_k, srt, -jnp.inf)
    if 0.0 < top_p < 1.0:
        # Keep the smallest prefix of the sorted distribution whose
        # mass reaches top_p (the top token always survives).
        probs = jax.nn.softmax(srt, -1)
        keep = jnp.cumsum(probs, -1) - probs < top_p
        cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), -1, keepdims=True)
        lg = jnp.where(lg >= cutoff, lg, -jnp.inf)
    return lg


def generate(model: TransformerLM, variables: dict, prompt, n_new: int,
             *, temperature: float = 0.0, top_k: int = 0,
             top_p: float = 0.0, rng=None,
             use_cache: bool = True, mesh=None):
    """Greedy (or sampled) autoregressive generation from ``prompt``
    [B, T0] int32. ``temperature`` 0 = greedy; > 0 samples
    softmax(logits/T), optionally truncated to the ``top_k``
    highest-probability tokens and/or the smallest ``top_p``
    cumulative-probability nucleus (both 0 = off).

    Default path: incremental decoding against the KV cache — O(L) work
    per token, one jitted single-token program compiled once, prompt
    prefilled through the same step. Works for every attention config
    (both cache init and decode steps bypass the injected core). For
    MoE models note the standard caveat: decode routes each step's
    tokens with per-step expert capacity, so when experts overflow, the
    drop set can differ from a full-prefix forward pass (exact equality
    holds whenever nothing is dropped, e.g. small batches).

    ``mesh`` (tensor-parallel serving): when the caller placed
    ``variables`` with TP shardings (tpunet/infer/generate.py load_lm
    --mesh-model), pass the mesh so the KV cache is created sharded to
    match — heads over 'model', the layout the attention's head-sharded
    Q/K/V writes produce. Without it GSPMD would reshard the cache
    every step. Same tokens out: sharding never changes the math
    (exactness test vs the unsharded path).

    ``use_cache=False`` falls back to full-prefix recompute: dense
    models reuse a fixed-size buffer (one compile; causality makes the
    unwritten tail irrelevant), MoE models grow the prefix because
    buffer padding would consume expert capacity."""
    prompt = jnp.asarray(prompt, jnp.int32)
    b, t0 = prompt.shape
    keys = jax.random.split(rng if rng is not None else jax.random.PRNGKey(0),
                            max(1, n_new))

    def pick(lg, key):
        if temperature <= 0:
            return jnp.argmax(lg, -1)
        lg = filter_logits(lg / temperature, top_k=top_k, top_p=top_p)
        return jax.random.categorical(key, lg, -1)

    if use_cache:
        total = t0 + n_new
        # Shapes only — no initializer FLOPs, no transient param copy.
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((b, total), jnp.int32),
                               decode=True))

        def cache_zeros(s):
            if mesh is not None:
                from jax.sharding import (NamedSharding,
                                          PartitionSpec as P)
                tp = mesh.shape.get("model", 1)
                spec = (P(None, None, "model", None)
                        if (s.ndim == 4 and tp > 1
                            and s.shape[2] % tp == 0) else P())
                return jnp.zeros(s.shape, s.dtype,
                                 device=NamedSharding(mesh, spec))
            return jnp.zeros(s.shape, s.dtype)

        cache = jax.tree_util.tree_map(cache_zeros, shapes["cache"])

        @jax.jit
        def step(cache, buf, i, key):
            tok = jax.lax.dynamic_slice(buf, (0, i), (b, 1))
            logits, mutated = model.apply(
                {**variables, "cache": cache}, tok, train=False,
                decode=True, pos_offset=i, mutable=["cache"])
            nxt = pick(logits[:, 0], key).astype(jnp.int32)
            # write the prediction at i+1 unless that slot holds prompt
            buf = jnp.where(
                jnp.arange(buf.shape[1])[None, :] == i + 1,
                jnp.where(i + 1 < t0, buf, nxt[:, None]), buf)
            return mutated["cache"], buf

        buf = jnp.zeros((b, total), jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
        for i in range(total - 1):
            cache, buf = step(cache, buf, jnp.int32(i),
                              keys[max(0, i - t0 + 1) % len(keys)])
        return buf

    if model.moe_experts > 0:
        tokens = prompt
        for i in range(n_new):
            lg = model.apply(variables, tokens, train=False)[:, -1]
            nxt = pick(lg, keys[i]).astype(jnp.int32)
            tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
        return tokens

    buf = jnp.zeros((b, t0 + n_new), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))

    @jax.jit
    def write_next(buf, cur, key):
        logits = model.apply(variables, buf, train=False)
        lg = jax.lax.dynamic_index_in_dim(logits, cur - 1, axis=1,
                                          keepdims=False)
        nxt = pick(lg, key)
        return jax.lax.dynamic_update_slice(
            buf, nxt[:, None].astype(jnp.int32), (0, cur))

    for i in range(n_new):
        buf = write_next(buf, jnp.int32(t0 + i), keys[i])
    return buf
