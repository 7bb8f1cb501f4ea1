"""Decoder-only LM with latent attention — the second decoder block.

Model name ``latent_lm`` (``ModelConfig.latent`` holds the published
``config.json`` keys; the benchmark's ``dots3-note-prev`` (served) and
``glm-4.7-flash`` (trained) are the worked configurations). Beside ``TransformerLM`` (learned positions, LayerNorm,
GELU, one kind of attention, tied head) this block has RMSNorm, rotary
positions with a base per layer kind, gated-SiLU MLPs, an untied head,
and per layer one of two attentions over a *latent* cache:

- ``full_attention``: multi-head latent attention (MLA). A token's
  cache row is ``(c_kv, k^r)`` — ``kv_lora_rank`` + ``qk_rope_head_dim``
  numbers, not ``2 * heads * head_dim`` — plus the 128-wide key ``k^I``
  of a learned sparse indexer that picks, per query, the ``index_topk``
  cached keys the heads attend to (all of them while the row is
  shorter).
- ``sliding_attention``: the same latent attention with its own ranks
  and head sizes, over the last ``sliding_window_size`` positions (the
  token itself counts), no indexer.

The indexer (``index_topk``), a sigmoid gate per head from the block's
normed input before the output projection (``attention_gate_type``) and
the latent rescale are properties of the published config: an
architecture that has none of them has none of their parameters, and a
full layer is then plain causal latent attention. Layer 0
(``first_k_dense_replace``) has a dense gated-SiLU MLP, every later
layer ``moe.RoutedShareMlp`` (sigmoid routing without capacity, one
shared expert, the experts ``held`` by this chip).
``num_nextn_predict_layers`` 1 adds a multi-token-prediction module
(``MtpModule``) that shares the trunk's embedding and head.

The block takes its mixer BY LAYER KIND (``mixer_class``), and each mixer
states what it keeps in the serve engine's cache (``cache_spec``: numbers
a token keeps in page pools, arrays a slot keeps whatever its length).
``model_type`` ``qwen3_next`` puts another family under this block and LM
shell (``models/hybrid_mixers.py``; the benchmark's
``qwen3-next-80b-a3b``, served): ``linear_attention`` layers by the gated
delta rule, whose per-slot state lives in the engine's state pool, beside
gated grouped-query ``full_attention`` layers; zero-centred norm weights;
a softmax router and a gated shared expert in ``RoutedShareMlp``.
``model_type`` ``cohere2_moe`` is the third (the benchmark's
``command-a-plus-05-2026``, served): a PARALLEL block — one norm a
layer, ``x + Attn(n) + FFN(n)`` from the same ``n``, so the two halves
have no data dependence — with a mean-subtracting LayerNorm that has a
weight and no bias (block and final), the head TIED to the embedding
(times ``logit_scale``), ``sliding_attention`` / ``full_attention``
layers that name the grouped-query mixer of ``hybrid_mixers.py`` over a
KEY/VALUE cache (sliding layers: interleaved rotary positions and a
``sliding_window``; full layers: no positions at all), and a sigmoid
router without a bias beside ``num_shared_experts`` averaged shared
experts. A wide call of this family takes the FFN ``_FFN_ROWS`` tokens
at a time: its experts are 4,096 wide, and a bucket's (token, expert)
pair tables would not fit beside the weights.

``model_type`` ``smallthinker`` is the fourth, and the second one
trained (the benchmark's ``smallthinker-21ba3b``): a sequential
pre-RMSNorm block whose ROUTER reads the block's input ``x`` itself,
before the attention norm — ``LatentBlock`` takes ``x W_r`` first and
hands the logits to the expert layer after attention — over the same
grouped-query mixer (``sliding_attention`` layers: rotate-half rotary
over the whole head and a ``sliding_window_size`` window;
``full_attention`` layers: no positions), softmax top-k over ReGLU
experts with no shared one, an untied head. It is read from its
published keys (``rope_layout`` / ``sliding_window_layout``, ``moe_*``).
Its training call takes the batch's rows through ``ops/flash.py
flash_prefill`` and that kernel's backward by head group and under the
window's band.

Three forms of one mathematics, chosen by the call:

- ``train=True`` (the ``Trainer``'s step): every row of the batch at
  once, UP-PROJECTED, through the flash kernel (``ops/flash.py``) — the
  shared rotary key rides beside each head's own, so queries, keys and
  values have one head size, no ``[heads, T, T]`` tensor exists and
  nothing past the diagonal is computed; the FFN takes every token of
  the batch in one call; blocks are recomputed in the backward where
  ``remat``. Built for full layers without an indexer whose
  ``qk_nope_head_dim + qk_rope_head_dim == v_head_dim``.
- one token per row against the cache (the engine's ``[slots, 1]``
  decode step): the ABSORBED form — ``W_uk`` is folded into the query
  and ``W_uv`` into the output, so scores and values are taken against
  the cached latents directly and no key or value is ever up-projected.
  A full layer scores the row's ``k^I`` pool, takes the top
  ``index_topk`` positions and gathers only those latent rows; a
  sliding layer gathers its last window.
- anything wider (a bucket-wide prefill, or a plain forward without a
  cache): the UP-PROJECTED form, one batch row at a time
  (``moe.by_row``: rows that are not being prefilled are skipped), the
  row's keys taken in position order — from the page pool through the
  row's page table after the new rows are written, or from the call's
  own tokens when there is no cache — and the queries in blocks, so
  that no ``[heads, T, K]`` score tensor exists. Selection there is a
  mask: keys whose index score is at least the row's
  ``index_topk``-th largest.

The cache is flat-row page pools ``[pages * page_tokens, W]`` per layer
behind the engine's one page table (``models.vit.PagedKV``), ``W``
rounded up to the 128-lane tile. A sliding layer writes every position
and reads its window through the same table (an allocator that frees
pages behind the window is later work).

Precision: products take bfloat16 operands and accumulate in float32;
the residual stream, the norms, softmax, the router and the WHOLE
indexer path (its projections from the float32 normed input, its keys
in the cache, its scores) are float32. The last is not a nicety: the
indexer's choice of 2,048 keys is discrete, its scores lie ~0.0007 of
their spread apart at the cut, and with bfloat16 inputs a few dozen keys
a query change sides — each a random vector in a sum of random vectors,
so the layer's output moves by the ROOT of the share flipped (PERF.md
section 6, PR 27: logits off by 1-5 against the float32 reference where
a dense layer is off by 0.3).

``rescale``: ``apply_mla_qkv_lora_rescale`` is read as a constant
``sqrt(hidden / rank)`` on each latent after its RMSNorm (the
benchmark's configuration file lists this reading under ``assumed``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from tpunet.config import ModelConfig
from tpunet.models.moe import (RoutedShareMlp, by_row, gated_silu,
                               router_logits)
from tpunet.ops.attention import _NEG_INF

_LANES = 128
_Q_BLOCK_FULL = 128      # queries per block, full layers (K = whole row)
_Q_BLOCK_WINDOW = 512    # queries per block, sliding layers (K = block + window)
_FFN_ROWS = 2048         # tokens of a wide row the parallel family's FFN takes


@dataclasses.dataclass(frozen=True)
class LatentArch:
    """The published sizes, under the published names."""

    hidden_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    intermediate_size: int
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-5
    # full layers
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: Optional[int] = None   # None: no indexer, plain causal
    # (the one property an absent key does not switch off: the
    # configuration that has the gate does not name it in its program
    # section, so a model without one says null)
    attention_gate_type: Optional[str] = "headwise"
    # sliding layers
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513
    swa_attention_gate_type: Optional[str] = "headwise"
    apply_mla_qkv_lora_rescale: bool = False
    # expert layers
    n_routed_experts: int = 256        # the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.0
    held_experts: Optional[Tuple[int, ...]] = None   # None = all
    # multi-token prediction modules after the trunk (0 or 1)
    num_nextn_predict_layers: int = 0
    # -- the hybrid family (``model_type`` "qwen3_next",
    # models/hybrid_mixers.py): ``linear_attention`` layers by the gated
    # delta rule beside gated grouped-query ``full_attention`` layers,
    # zero-centred norm weights (``1 + w``), a softmax router without
    # bias, the shared expert behind a sigmoid gate
    model_type: Optional[str] = None
    num_key_value_heads: Optional[int] = None
    head_dim: Optional[int] = None
    partial_rotary_factor: float = 1.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # -- the parallel family (``model_type`` "cohere2_moe"): one
    # LayerNorm a block feeds attention and FFN side by side; grouped-
    # query attention over K/V pages, ``sliding_attention`` layers with
    # interleaved rotary positions over ``sliding_window`` keys (the
    # token itself counts) and ``full_attention`` layers with no
    # positions; ``num_shared_experts`` averaged; the head tied to the
    # embedding and scaled. ``intermediate_size`` is its experts' width.
    layer_norm_eps: float = 1e-5
    sliding_window: Optional[int] = None
    rotary_pct: float = 1.0
    logit_scale: float = 1.0
    num_shared_experts: int = 1
    # -- what a family's key mapping sets, no configuration names: the
    # rotary pairs' layout under a window, the experts' activation
    rotary_layout: str = "interleaved"
    expert_act: str = "silu"

    @classmethod
    def from_mapping(cls, m) -> "LatentArch":
        known = {f.name for f in dataclasses.fields(cls)} | {"num_experts"}
        kw = dict(m)
        if set(kw) & _MAPPED:
            raise ValueError(f"latent_lm: {sorted(set(kw) & _MAPPED)} are "
                             "set by a family's key mapping, not named")
        if kw.get("model_type") == "smallthinker":
            kw = _early_router_keys(kw)
        elif kw.get("model_type") == "cohere2_moe":
            kw = _parallel_keys(kw)
        elif set(kw) & _PARALLEL_ONLY:
            raise ValueError("latent_lm: only cohere2_moe reads "
                             f"{sorted(set(kw) & _PARALLEL_ONLY)}")
        unknown = set(kw) - known
        if unknown:
            raise ValueError(f"latent_lm: unknown keys {sorted(unknown)}")
        if "num_experts" in kw:          # the later families' name for it
            kw["n_routed_experts"] = kw.pop("num_experts")
        for key in ("layer_types", "held_experts"):
            if kw.get(key) is not None:
                kw[key] = tuple(kw[key])
        arch = cls(**kw)
        if len(arch.layer_types) != arch.num_hidden_layers:
            raise ValueError("latent_lm: layer_types must name "
                             f"{arch.num_hidden_layers} layers")
        for gate in (arch.attention_gate_type, arch.swa_attention_gate_type):
            if gate not in (None, "headwise"):
                raise ValueError(f"latent_lm: unknown gate type {gate!r}")
        if arch.num_nextn_predict_layers not in (0, 1):
            raise ValueError("latent_lm builds one multi-token-prediction "
                             "module at most")
        if arch.model_type not in (None, "qwen3_next", "cohere2_moe",
                                   "smallthinker"):
            raise ValueError(f"latent_lm: unknown model_type "
                             f"{arch.model_type!r}")
        kinds = (("full_attention", "linear_attention") if arch.hybrid
                 else ("full_attention", "sliding_attention"))
        if set(arch.layer_types) - set(kinds):
            raise ValueError(f"latent_lm: layer_types of model_type "
                             f"{arch.model_type!r} are {kinds}")
        if (arch.hybrid or arch.parallel or arch.early_router) and (
                not arch.num_key_value_heads or not arch.head_dim
                or arch.num_attention_heads % arch.num_key_value_heads
                or arch.linear_num_value_heads % arch.linear_num_key_heads):
            raise ValueError(
                f"latent_lm: {arch.model_type} needs head_dim and a "
                "num_key_value_heads that divides num_attention_heads, and "
                "linear_num_key_heads dividing linear_num_value_heads")
        if arch.parallel and (
                arch.first_k_dense_replace or not arch.sliding_window
                or arch.sliding_window < 1 or arch.num_shared_experts < 1
                or int(arch.head_dim * arch.rotary_pct) % 2):
            raise ValueError(
                "latent_lm: cohere2_moe needs a sliding_window, shared "
                "experts, an even rotary share of head_dim, and "
                "first_k_dense_replace 0 (prefix dense layers are not "
                "built)")
        return arch

    @property
    def hybrid(self) -> bool:
        return self.model_type == "qwen3_next"

    @property
    def parallel(self) -> bool:
        return self.model_type == "cohere2_moe"

    @property
    def early_router(self) -> bool:
        """The router reads the block's input, before attention."""
        return self.model_type == "smallthinker"

    @property
    def norm_offset(self) -> float:
        """What a norm adds to its weight: the hybrid family's are
        zero-centred (``1 + w``, initial ``w`` 0)."""
        return 1.0 if self.hybrid else 0.0

    def gqa(self, kind: str) -> dict:
        """What the grouped-query mixer (``hybrid_mixers.py``) of a
        layer of ``kind`` has: a sigmoid ``gated`` output, ``qk_norm``
        per head, how many leading dims of a head are rotated
        (``rotary``, 0 = no positions) in which ``layout``, over how
        many keys it looks back (``window``, None = all), and the
        ``scope`` and page-cache name its work and rows go under."""
        d = self.head_dim
        if self.hybrid:
            return {"gated": True, "qk_norm": True, "window": None,
                    "rotary": int(d * self.partial_rotary_factor),
                    "layout": "rotate_half", "scope": "tpunet_gqa_full",
                    "cache": "kv"}
        sliding = kind == "sliding_attention"
        return {"gated": False, "qk_norm": False,
                "window": self.sliding_window if sliding else None,
                "rotary": int(d * self.rotary_pct) if sliding else 0,
                "layout": self.rotary_layout,
                "scope": "tpunet_gqa_window" if sliding
                else "tpunet_gqa_full",
                "cache": "kv_window" if sliding else "kv"}

    def layer(self, kind: str) -> dict:
        """Sizes of one attention kind: heads, latent ranks, head dims,
        rotary base, the two latent scales, whether its heads are gated
        and how many keys its indexer keeps (None: it has none)."""
        full = kind == "full_attention"
        p = "" if full else "swa_"
        g = lambda k: getattr(self, p + k)  # noqa: E731
        rq, rkv = g("q_lora_rank"), g("kv_lora_rank")
        scale = self.apply_mla_qkv_lora_rescale
        return {"heads": g("num_attention_heads"), "rq": rq, "rkv": rkv,
                "dn": g("qk_nope_head_dim"), "dr": g("qk_rope_head_dim"),
                "dv": g("v_head_dim"), "theta": float(g("rope_theta")),
                "gated": g("attention_gate_type") == "headwise",
                "topk": self.index_topk if full else None,
                "s_q": math.sqrt(self.hidden_size / rq) if scale else 1.0,
                "s_kv": math.sqrt(self.hidden_size / rkv) if scale else 1.0}


# What the parallel family's block IS: a published switch is taken only
# at the value this block builds, and then dropped.
_PARALLEL_BUILT = {
    "use_parallel_block": True, "use_qk_norm": False,
    "position_embedding_type": "rope_gptj", "tie_word_embeddings": True,
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "shared_expert_combination_strategy": "average",
    "use_gated_activation": True, "hidden_act": "silu",
    "attention_bias": False}
# The numbers it reads. Every other key ``LatentArch`` knows belongs to
# latent attention or the delta rule: a silent default there would be
# a size the configuration never reads.
_PARALLEL_KEYS = {
    "model_type", "hidden_size", "num_hidden_layers", "layer_types",
    "intermediate_size", "first_k_dense_replace", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "rotary_pct",
    "sliding_window", "layer_norm_eps", "logit_scale", "num_experts",
    "num_experts_per_tok", "num_shared_experts", "held_experts"}
_PARALLEL_ONLY = {"layer_norm_eps", "sliding_window", "rotary_pct",
                  "logit_scale", "num_shared_experts"}
_MAPPED = {"rotary_layout", "expert_act"}


def _as_built(kw: dict, family: str, built: dict, read: set) -> None:
    """A family's published switches are taken only at the value its
    block builds, and then dropped from ``kw``; what is left has to be
    a key the family reads."""
    for key, value in built.items():
        if key in kw and kw.pop(key) != value:
            raise ValueError(f"latent_lm: {family} is built with "
                             f"{key} = {value!r}")
    foreign = set(kw) - read
    if foreign:
        raise ValueError(f"latent_lm: {family} does not read "
                         f"{sorted(foreign)} (another family's or unknown "
                         "keys)")


def _parallel_keys(kw: dict) -> dict:
    """A ``cohere2_moe`` mapping as ``LatentArch`` holds it: the
    switches checked and dropped, ``intermediate_size`` (the width of
    one expert, routed or shared) under the expert width's name."""
    _as_built(kw, "cohere2_moe", _PARALLEL_BUILT, _PARALLEL_KEYS)
    if "intermediate_size" in kw:
        kw["moe_intermediate_size"] = kw["intermediate_size"]
    return kw


# The fourth family, under its published names: what its block IS
# (switches taken only at the value built) and the numbers it reads.
_EARLY_ROUTER_BUILT = {
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "tie_word_embeddings": False, "rope_scaling": None}
_EARLY_ROUTER_NAMES = {
    "moe_ffn_hidden_size": "moe_intermediate_size",
    "moe_num_active_primary_experts": "num_experts_per_tok",
    "moe_num_primary_experts": "n_routed_experts"}
_EARLY_ROUTER_KEYS = {
    "model_type", "hidden_size", "num_hidden_layers", "rope_layout",
    "sliding_window_layout", "sliding_window_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
    "held_experts", *_EARLY_ROUTER_NAMES}


def _early_router_keys(kw: dict) -> dict:
    """A ``smallthinker`` mapping as ``LatentArch`` holds it: the
    switches checked and dropped, the expert layer's sizes under the
    arch's names, the two layouts (1 = rotary positions, 1 = a window;
    they go together) as ``layer_types``, the window, the whole-head
    rotate-half rotary and the ReGLU experts as the fields ``gqa`` and
    ``LatentBlock`` read; no dense layer, no shared expert."""
    _as_built(kw, "smallthinker", _EARLY_ROUTER_BUILT, _EARLY_ROUTER_KEYS)
    for name, ours in _EARLY_ROUTER_NAMES.items():
        if name in kw:
            kw[ours] = kw.pop(name)
    rope_on = list(kw.pop("rope_layout", ()))
    if rope_on != list(kw.pop("sliding_window_layout", ())):
        raise ValueError("latent_lm: smallthinker's layers with rotary "
                         "positions are its windowed layers")
    kw["layer_types"] = ["sliding_attention" if on else "full_attention"
                         for on in rope_on]
    kw.update(intermediate_size=kw.get("moe_intermediate_size"),
              first_k_dense_replace=0, num_shared_experts=0,
              sliding_window=kw.pop("sliding_window_size", None),
              rotary_pct=1.0, rotary_layout="rotate_half", expert_act="relu")
    return kw


# -- arithmetic ---------------------------------------------------------------

def lane_rounded(width: int) -> int:
    """A pool row's columns: ``width`` rounded up to the 128-lane tile
    (``ops/paged_decode.py pool_width`` says why)."""
    return -(-width // _LANES) * _LANES


def rms_norm(x, scale, eps, mult: float = 1.0, dtype=None,
             offset: float = 0.0):
    """RMSNorm in float32, times the constant ``mult``; the result in
    ``dtype`` (``x``'s own by default). ``offset`` 1 is the zero-centred
    weight ``1 + scale``."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    scale = scale.astype(jnp.float32)
    if offset:
        scale = offset + scale
    return (y * (mult * scale)).astype(dtype or x.dtype)


def norm_param(module, name: str, n: int, arch: "LatentArch", dtype):
    """A block or final norm's weight: ones, or zeros where the weight
    is zero-centred."""
    init = nn.initializers.zeros if arch.norm_offset else nn.initializers.ones
    return module.param(name, init, (n,), dtype)


def layer_norm(x, scale, bias, eps, dtype=None):
    """LayerNorm in float32 (``bias`` None: a weight alone); the result
    in ``dtype`` (``x``'s own by default)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype or x.dtype)


def block_norm(arch: "LatentArch", v, scale, dtype):
    """A block's (or the final) norm of the residual stream, as the
    family has it: the parallel family's LayerNorm without bias, else
    RMSNorm (zero-centred where the family's weights are)."""
    if arch.parallel:
        return layer_norm(v, scale, None, arch.layer_norm_eps, dtype)
    return rms_norm(v, scale, arch.rms_norm_eps, dtype=dtype,
                    offset=arch.norm_offset)


def rope(x, pos, theta: float, interleaved: bool = False):
    """Rotary embedding of ``x`` [..., T, d] or [..., T, H, d] at
    integer positions ``pos`` [..., T]: pair j is ``(x_j, x_{j + d/2})``
    (rotate-half) or, ``interleaved``, ``(x_{2j}, x_{2j+1})``, rotated
    by ``pos * theta ** (-2j / d)``. The interleaved form stays in the
    lane layout: each number's partner is its neighbour, fetched by two
    shifts and a select, no ``[..., d/2, 2]`` view."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * freq           # [..., T, d/2]
    if x.ndim == ang.ndim + 1:
        ang = ang[..., None, :]                               # heads axis
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    if interleaved:
        cos, sin = jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1)
        even = jnp.arange(d) % 2 == 0
        partner = jnp.where(even, jnp.roll(xf, -1, axis=-1),
                            jnp.roll(xf, 1, axis=-1))
        return (xf * cos + partner * jnp.where(even, -sin, sin)).astype(
            x.dtype)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def kth_largest(x, k: int):
    """The ``k``-th largest of ``x`` [..., K] float32 along the last
    axis, exactly, by choosing the bits of its order-preserving integer
    key from the top (32 counting passes, no sort). With fewer than
    ``k`` entries above -inf the result is at most -inf's key, so
    ``x >= kth`` keeps them all."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def body(i, found):
        cand = found | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, found)

    found = lax.fori_loop(0, 32, body,
                          jnp.zeros(x.shape[:-1], jnp.uint32))
    back = jnp.where(found >> 31 == 1, found & jnp.uint32(0x7FFFFFFF), ~found)
    return lax.bitcast_convert_type(back, jnp.float32)


def top_k_mask(x, k: int):
    """Which entries of ``x`` [..., K] ``lax.top_k(x, k)`` would take:
    those above the ``k``-th largest, and of its equals the first by
    index that fill the count (the cut's index found by bisection too:
    a cumulative sum over K would cost more than the attention)."""
    kth = kth_largest(x, k)[..., None]
    above, equal = x > kth, x == kth
    room = k - jnp.sum(above, axis=-1)
    at = jnp.arange(x.shape[-1])

    def body(_, lo_hi):
        # the smallest index c with count(equal[..., :c + 1]) >= room
        lo, hi = lo_hi
        mid = (lo + hi) // 2
        enough = jnp.sum(equal & (at <= mid[..., None]), axis=-1) >= room
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    steps = max(1, (x.shape[-1] - 1).bit_length())
    zero = jnp.zeros(x.shape[:-1], jnp.int32)
    cut, _ = lax.fori_loop(0, steps, body, (zero, zero + x.shape[-1] - 1))
    return above | (equal & (at <= cut[..., None]))


def index_scores(qi, w, ki):
    """The indexer's score of every key for every query, float32
    throughout (see the module's text): ``qi`` [..., Q, Hi, Di], ``w`` [..., Q, Hi] (already
    over sqrt(Hi)), ``ki`` [..., K, Di] -> [..., Q, K]."""
    s = jnp.einsum("...qhd,...kd->...qhk", qi.astype(jnp.float32),
                   ki.astype(jnp.float32), precision=lax.Precision.HIGHEST)
    s = jnp.maximum(s, 0.0) * w.astype(jnp.float32)[..., None]
    return jnp.sum(s, axis=-2) * (qi.shape[-1] ** -0.5)


def _dot32(x, w):
    """A float32 (``highest``) product: the indexer's projections."""
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)


def _block(n: int, target: int) -> int:
    return target if n % target == 0 else n


def _softmax_values(scores, keep, v):
    """softmax over the kept keys, then the values: ``scores``
    [H, Q, K] float32, ``keep`` [Q, K], ``v`` [H, K, Dv] -> [Q, H, Dv]
    float32, normalised after the product. (The barrier keeps the row
    maximum a reduction of its own: left to fuse with the subtraction,
    the TPU compiler turns it into a ``reduce-window`` as wide as the
    row — 5.6 of a 3.7 s prefill call's 7.5 device seconds, PERF.md
    section 6, PR 27.)"""
    s = jnp.where(keep[None], scores, _NEG_INF)
    top = lax.optimization_barrier(jnp.max(s, axis=-1))          # [H, Q]
    p = jnp.exp(s - top[..., None])
    o = jnp.einsum("hqk,hkd->hqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return jnp.swapaxes(o / jnp.sum(p, axis=-1)[..., None], 0, 1)


def mixer_class(arch: LatentArch, kind: str):
    """The module that mixes positions in a layer of ``kind``. Every
    mixer takes the block's normed input and the same cache arguments,
    and states what it keeps with ``cache_spec(arch, kind, dtype)``:
    ``paged`` = ``{cache: (numbers a token keeps in that cache's page
    pools, lane-rounded rows as stored; their dtype)}``, ``state`` =
    ``{name: (shape, dtype)}`` of what a SLOT keeps whatever its
    length, ``decode_kernel`` = whether its one-token call attends
    through ``tpunet_paged_decode`` where that kernel applies, and —
    where the mixer reads only a row's newest positions — ``window`` =
    how many (absent or None: it reads them all; the pages wholly
    behind it are what an allocator by layer kind would free)."""
    if not (arch.hybrid or arch.parallel or arch.early_router):
        return LatentAttention
    from tpunet.models import hybrid_mixers
    return (hybrid_mixers.GatedDeltaNet if kind == "linear_attention"
            else hybrid_mixers.GroupedQueryAttention)


# -- the attention layer ------------------------------------------------------

class LatentAttention(nn.Module):
    """One latent-attention layer (``kind`` = ``full_attention`` or
    ``sliding_attention``) on the block's normed input ``u``
    [B, T, C] float32; see the module's text for the two forms."""

    arch: LatentArch
    kind: str
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @classmethod
    def cache_spec(cls, a: LatentArch, kind: str, dtype) -> dict:
        z = a.layer(kind)
        row = (lane_rounded(z["rkv"] + z["dr"]), dtype)
        if kind != "full_attention":
            paged = {"window": row}
        elif z["topk"] is None:
            paged = {"latent": row}
        else:
            paged = {"latent": row,
                     "index": (lane_rounded(a.index_head_dim), jnp.float32)}
        # (the absorbed decode reads latents, not heads: no kernel)
        return {"paged": paged, "state": {}, "decode_kernel": False}

    @nn.compact
    def __call__(self, u, decode: bool = False, positions=None,
                 active=None, paged_kv=None, page_table=None,
                 train: bool = False, state_rows=None, lengths=None):
        # (``state_rows`` / ``lengths`` address a per-slot state: this
        # mixer keeps none)
        a, z = self.arch, self.arch.layer(self.kind)
        full = self.kind == "full_attention"
        indexed, gated = z["topk"] is not None, z["gated"]
        u32, u = u, u.astype(self.dtype)     # float32 for the indexer alone
        b, t, c = u.shape
        h, dn, dr, dv = z["heads"], z["dn"], z["dr"], z["dv"]
        rq, rkv, eps, dt = z["rq"], z["rkv"], a.rms_norm_eps, self.dtype
        init = nn.initializers.normal(stddev=0.02)

        def w(name, *shape):
            return self.param(name, init, shape, self.param_dtype).astype(dt)

        def ones(name, n):
            return self.param(name, nn.initializers.ones, (n,),
                              self.param_dtype)

        dq, q_norm, uq = w("dq", c, rq), ones("q_norm", rq), \
            w("uq", rq, h * (dn + dr))
        dkv, kv_norm = w("dkv", c, rkv + dr), ones("kv_norm", rkv)
        ukv = w("ukv", rkv, h * (dn + dv)).reshape(rkv, h, dn + dv)
        gate = w("gate", c, h) if gated else None
        out = w("out", h * dv, c)
        if indexed:
            hi, di = a.index_n_heads, a.index_head_dim
            iq, ik, iw = w("iq", rq, hi * di), w("ik", c, di), w("iw", c, hi)
            ik_scale = ones("ik_norm_scale", di)
            ik_bias = self.param("ik_norm_bias", nn.initializers.zeros,
                                 (di,), self.param_dtype)
        scope = "tpunet_mla_full" if full else "tpunet_mla_window"
        scale = (dn + dr) ** -0.5
        window = a.sliding_window_size

        if positions is None:
            positions = jnp.zeros((b,), jnp.int32)
        pos_t = positions[:, None] + jnp.arange(t)[None, :]      # [B, T]

        # -- what a token leaves behind: its latent row (and index key)
        with jax.named_scope(scope):
            kv = jnp.dot(u, dkv)
            c_kv = rms_norm(kv[..., :rkv], kv_norm, eps, z["s_kv"])
            k_r = rope(kv[..., rkv:], pos_t, z["theta"])
            lat = jnp.concatenate([c_kv, k_r], -1)               # [B,T,rkv+dr]
        if indexed:
            with jax.named_scope("tpunet_indexer"):
                k_i = layer_norm(_dot32(u32, ik), ik_scale, ik_bias, 1e-5)
                k_i = jnp.concatenate(
                    [rope(k_i[..., :dr], pos_t, z["theta"]), k_i[..., dr:]],
                    -1)

        # -- the query side of one batch row (or of all of them) ------
        def queries(u_, pos_, u32_=None):
            c_q = rms_norm(jnp.dot(u_, dq), q_norm, eps, z["s_q"])
            q = jnp.dot(c_q, uq).reshape(*u_.shape[:-1], h, dn + dr)
            q_n, q_r = q[..., :dn], rope(q[..., dn:], pos_, z["theta"])
            g = (jax.nn.sigmoid(jnp.dot(u_, gate).astype(jnp.float32))
                 if gated else None)
            if not indexed:
                return q_n, q_r, g, None, None
            with jax.named_scope("tpunet_indexer"):
                c_q32 = rms_norm(_dot32(u32_, dq), q_norm, eps, z["s_q"])
                q_i = _dot32(c_q32, iq).reshape(*u_.shape[:-1], hi, di)
                q_i = jnp.concatenate(
                    [rope(q_i[..., :dr], pos_, z["theta"]), q_i[..., dr:]],
                    -1)
                w_i = _dot32(u32_, iw) * (hi ** -0.5)
            return q_n, q_r, g, q_i, w_i

        def project_out(o, g):
            o = (o if g is None else o * g[..., None]).astype(dt)
            return jnp.dot(o.reshape(*o.shape[:-2], h * dv), out)

        # -- training: every row at once, causal, through flash -------
        if train:
            if decode or indexed or not full or dn + dr != dv:
                raise ValueError(
                    "latent_lm trains full layers without an indexer whose "
                    "queries, keys and values share one head size (the "
                    "flash kernel's one D); sliding layers, the indexer's "
                    "selection and unequal head sizes have no backward here")
            from tpunet.ops.flash import flash_attention
            with jax.named_scope(scope):
                q_n, q_r, g, _, _ = queries(u, pos_t)
                kv_up = jnp.einsum("btr,rhd->bthd", c_kv, ukv)
                k = jnp.concatenate([kv_up[..., :dn], jnp.broadcast_to(
                    k_r[:, :, None, :], (b, t, h, dr))], -1)
                o = flash_attention(jnp.concatenate([q_n, q_r], -1), k,
                                    kv_up[..., dn:], causal=True,
                                    scale=scale)
                return project_out(o, g)

        # -- up-projected form, one row: keys in position order -------
        def row_attend(u_, start, lat_keys, index_keys, u32_=None):
            """``u_`` [T, C] at positions start..start+T-1; the row's
            keys ``lat_keys`` [K, >=rkv+dr] (key j is position j)."""
            with jax.named_scope(scope):
                qpos = start + jnp.arange(t)
                q_n, q_r, g, q_i, w_i = queries(u_, qpos, u32_)
                k_all = lat_keys.shape[0]
                bq = _block(t, _Q_BLOCK_FULL if full else _Q_BLOCK_WINDOW)
                nb = t // bq
                blocks = lambda x: x.reshape(nb, bq, *x.shape[1:])  # noqa: E731
                # heads lead every operand (the batch dimension of both
                # products), and the shared rotary key rides beside each
                # head's own: one product for the scores, no [K, H, D]
                # tensor to transpose
                q_all = jnp.swapaxes(jnp.concatenate([q_n, q_r], -1), 0, 1)

                def up_project(keys):
                    # (the weights are sliced, not the products: a slice
                    # of a [H, K, dn + dv] product is re-copied by every
                    # block of queries that reads it)
                    c = keys[:, :rkv]
                    k_up = jnp.einsum("kr,rhd->hkd", c, ukv[..., :dn])
                    k_rope = jnp.broadcast_to(
                        keys[None, :, rkv:rkv + dr], (h, keys.shape[0], dr))
                    return (jnp.concatenate([k_up, k_rope], -1),
                            jnp.einsum("kr,rhd->hkd", c, ukv[..., dn:]))

                def attend(q_b, k_b, v_b, keep):
                    s = jnp.einsum("hqd,hkd->hqk", q_b, k_b,
                                   preferred_element_type=jnp.float32)
                    return _softmax_values(s * scale, keep, v_b)

                q_blocks = q_all.reshape(h, nb, bq, dn + dr).swapaxes(0, 1)
                if full:
                    k_all_up, v_all = up_project(lat_keys)
                    kpos = jnp.arange(k_all)
                    topk = z["topk"]

                    def one(args):
                        q_b, qi_b, wi_b, qpos_b = args
                        keep = kpos[None, :] <= qpos_b[:, None]
                        if not indexed:
                            return attend(q_b, k_all_up, v_all, keep)
                        with jax.named_scope("tpunet_indexer"):
                            score = jnp.where(
                                keep, index_scores(qi_b, wi_b, index_keys),
                                -jnp.inf)
                        if topk < k_all:
                            with jax.named_scope("tpunet_kv_select"):
                                # (the barrier: one mask per block of
                                # queries, made before the heads share it,
                                # not once more inside each head's softmax)
                                keep = lax.optimization_barrier(
                                    keep & top_k_mask(score, topk))
                        return attend(q_b, k_all_up, v_all, keep)

                    o = lax.map(one, (q_blocks,) + tuple(
                        blocks(x) if indexed else None
                        for x in (q_i, w_i)) + (blocks(qpos),))
                else:
                    kb = min(k_all, bq + window - 1)

                    def one(args):
                        q_b, qpos_b = args
                        first = jnp.clip(qpos_b[0] - (window - 1), 0,
                                         k_all - kb)
                        k_b, v_b = up_project(
                            lax.dynamic_slice_in_dim(lat_keys, first, kb))
                        kpos = first + jnp.arange(kb)
                        keep = ((kpos[None, :] <= qpos_b[:, None])
                                & (kpos[None, :] > qpos_b[:, None] - window))
                        return attend(q_b, k_b, v_b, keep)

                    o = lax.map(one, (q_blocks, blocks(qpos)))
                return project_out(o.reshape(t, h, dv), g)

        if not decode:
            # plain forward: the call's own tokens are the keys
            if indexed:
                return by_row(row_attend, None, u, positions, lat, k_i, u32)
            return by_row(lambda u_, s_, l_: row_attend(u_, s_, l_, None),
                          None, u, positions, lat)

        # -- the paged cache ------------------------------------------
        if paged_kv is None:
            raise ValueError("latent_lm keeps its cache in pages: decode "
                             "needs paged_kv and a page table (the serve "
                             "engine's default)")
        pt = paged_kv.page_tokens
        flat_rows = paged_kv.pages * pt
        store = paged_kv.store_dtype(dt)
        if paged_kv.quantized:
            raise ValueError("latent_lm has no int8 page payload")
        is_init = not self.has_variable("cache", "latent")
        wide = lane_rounded(rkv + dr)
        pool = self.variable("cache", "latent", jnp.zeros,
                             (flat_rows, wide), store)
        if indexed:
            ipool = self.variable("cache", "index", jnp.zeros,
                                  (flat_rows, lane_rounded(di)),
                                  jnp.float32)
        if is_init:
            return jnp.zeros_like(u)
        if page_table is None:
            raise ValueError("paged decode requires engine-owned per-row "
                             "positions and a page table")

        def flat_of(pos):
            """positions [B, N] -> flat pool rows through the table."""
            page = jnp.take_along_axis(
                page_table, jnp.clip(pos // pt, 0, page_table.shape[1] - 1),
                axis=1)
            return page * pt + pos % pt

        new = flat_of(pos_t)
        if active is not None:
            new = jnp.where(active[:, None], new, 0)     # the garbage page
        new = new.reshape(-1)

        def put(var, rows):
            rows = rows.reshape(b * t, -1)
            rows = jnp.pad(rows, ((0, 0), (0, var.value.shape[1]
                                           - rows.shape[1])))
            var.value = var.value.at[new].set(rows.astype(var.value.dtype))

        put(pool, lat)
        if indexed:
            put(ipool, k_i)
        k_max = page_table.shape[1] * pt

        def rows_of(table):
            """Flat pool rows of positions 0..k_max-1 of one table."""
            return (table[..., None] * pt + jnp.arange(pt)).reshape(
                *table.shape[:-1], k_max)

        if t > 1:
            def cached_row(u_, start, table, u32_):
                rows = rows_of(table)
                keys = jnp.take(pool.value, rows, axis=0).astype(dt)
                ikeys = (jnp.take(ipool.value, rows, axis=0)[:, :di]
                         if indexed else None)
                return row_attend(u_, start, keys, ikeys, u32_)
            return by_row(cached_row, active, u, positions, page_table, u32)

        # -- absorbed form: one token per row -------------------------
        # scores q_n . (W_uk c) = (W_uk^T q_n) . c and values
        # W_uv (sum_s p_s c_s): the same mathematics as the up-projected
        # form, with the per-key products moved to the query and the
        # output.
        with jax.named_scope(scope):
            q_n, q_r, g, q_i, w_i = queries(u[:, 0], positions, u32[:, 0])
            if indexed:
                with jax.named_scope("tpunet_indexer"):
                    ikeys = jnp.take(ipool.value, rows_of(page_table),
                                     axis=0)[..., :di]
                    score = index_scores(q_i[:, None], w_i[:, None],
                                         ikeys)[:, 0]            # [B, K]
                    score = jnp.where(jnp.arange(k_max)[None, :]
                                      <= positions[:, None], score, -jnp.inf)
                with jax.named_scope("tpunet_kv_select"):
                    best, sel = lax.top_k(score, min(a.index_topk, k_max))
                    keep = best > -jnp.inf
            elif full:                   # every cached position, in order
                sel = jnp.broadcast_to(jnp.arange(k_max)[None, :], (b, k_max))
                keep = sel <= positions[:, None]
            else:
                sel = positions[:, None] - (window - 1) \
                    + jnp.arange(window)[None, :]
                keep = sel >= 0
                sel = jnp.maximum(sel, 0)
            # (an indexed layer's gather of its selected rows is the cost
            # of selection; a sliding layer's window stays under its own
            # scope, a plain full layer's rows under the layer's)
            with jax.named_scope("tpunet_kv_select" if indexed else
                                 "tpunet_window_gather" if not full else
                                 "tpunet_kv_gather"):
                keys = jnp.take(pool.value, flat_of(sel), axis=0).astype(dt)
            c_keys, r_keys = keys[..., :rkv], keys[..., rkv:rkv + dr]
            q_abs = jnp.einsum("bhd,rhd->bhr", q_n, ukv[..., :dn])
            s = (jnp.einsum("bhr,bkr->bhk", q_abs, c_keys,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bhd,bkd->bhk", q_r, r_keys,
                              preferred_element_type=jnp.float32)) * scale
            p = jax.nn.softmax(jnp.where(keep[:, None, :], s, _NEG_INF), -1)
            o_lat = jnp.einsum("bhk,bkr->bhr", p.astype(dt), c_keys)
            o = jnp.einsum("bhr,rhd->bhd", o_lat, ukv[..., dn:],
                           preferred_element_type=jnp.float32)
            return project_out(o, g)[:, None]


# -- the model ----------------------------------------------------------------

class LatentBlock(nn.Module):
    """``h = x + Attn(norm(x))``, ``y = h + FFN(norm(h))`` or, the
    parallel family, ``y = x + Attn(n) + FFN(n)`` with the one
    ``n = norm(x)``; the FFN dense (``dense`` True) or the expert layer.
    Where the family's router reads the block's input
    (``early_router``), ``x W_r`` is taken first, in float32, and the
    expert layer after attention routes by those logits.
    A wide call (T > 1) takes the FFN one batch row at a time, skipping
    the rows ``row_active`` marks idle (the parallel family:
    ``_FFN_ROWS`` tokens of a row at a time); a training call
    (``train``) takes it over every token of the batch at once."""

    arch: LatentArch
    kind: str
    dense: bool
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, decode, positions, active, paged_kv, page_table,
                 train: bool = False, state_rows=None, lengths=None):
        a = self.arch
        b, t, c = x.shape
        wide = t > 1 and not train
        row_active = active if (decode and wide) else None

        def norm(name, v):
            return block_norm(a, v, norm_param(self, name, c, a,
                                               self.param_dtype), jnp.float32)

        def mix(u):
            return mixer_class(a, self.kind)(
                a, self.kind, dtype=self.dtype, param_dtype=self.param_dtype,
                name="linear_attn" if self.kind == "linear_attention"
                else "attn")(
                u, decode, positions, active, paged_kv, page_table, train,
                state_rows, lengths).astype(x.dtype)

        def ffn(u, logits=None):
            rows, on = b, row_active
            if train:            # every token a row of its own, as in decode
                u = u.reshape(b * t, 1, c)
                if logits is not None:
                    logits = logits.reshape(b * t, 1, -1)
            elif wide and a.parallel and t % _FFN_ROWS == 0:
                rows = b * (t // _FFN_ROWS)
                u = u.reshape(rows, _FFN_ROWS, c)
                on = None if on is None else jnp.repeat(on, rows // b)
            if self.dense:
                init = nn.initializers.normal(stddev=0.02)
                f = a.intermediate_size
                mlp = [self.param(f"mlp_{n}", init, shape, self.param_dtype)
                       for n, shape in (("gate", (c, f)), ("up", (c, f)),
                                        ("down", (f, c)))]
                one = lambda u_: gated_silu(u_, *mlp, self.dtype)  # noqa: E731
                with jax.named_scope("tpunet_dense_mlp"):
                    y = by_row(one, on, u) if wide else one(u)
            else:
                pick = (lambda v: v) if wide else (lambda v: v[:, 0])  # noqa: E731
                y = RoutedShareMlp(
                    a.n_routed_experts, a.moe_intermediate_size,
                    a.num_experts_per_tok, held=a.held_experts,
                    scaling=a.routed_scaling_factor,
                    scoring="softmax" if a.hybrid or a.early_router
                    else "sigmoid",
                    shared_gate=a.hybrid, n_shared=a.num_shared_experts,
                    router_bias=not a.parallel,
                    act=a.expert_act,
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name="moe")(
                        pick(u), on, None if logits is None else pick(logits))
            return y.reshape(b, t, c).astype(x.dtype)

        if a.parallel:
            u = norm("ln1", x)
            return x + mix(u) + ffn(u)
        logits = None
        if a.early_router and not self.dense:
            with jax.named_scope("tpunet_moe_router"):
                logits = router_logits(x, self.param(
                    "router", nn.initializers.normal(stddev=0.02),
                    (c, a.n_routed_experts), self.param_dtype))
        x = x + mix(norm("ln1", x))
        return x + ffn(norm("ln2", x), logits)


def _block_class(remat: bool):
    """``LatentBlock``, recomputed in the backward where ``remat`` (its
    two flags are static: argnums count self as 0)."""
    return (nn.remat(LatentBlock, static_argnums=(2, 7)) if remat
            else LatentBlock)


class MtpModule(nn.Module):
    """Multi-token prediction, depth 1 (arXiv:2412.19437 section 2.2):
    position i joins the trunk's last block output there with the
    embedding of token i+1 — ``W_eh [RMSNorm_e(Emb(t_{i+1}));
    RMSNorm_h(h_i)]`` — and passes one more whole expert block and a
    final RMSNorm of its own; the trunk's head then predicts token
    i+2. ``x`` [B, T, C] float32, ``nxt`` the embeddings of the tokens
    one to the right; returns the normed state [B, T, C]."""

    arch: LatentArch
    remat: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, nxt, positions, train: bool = False):
        a = self.arch
        c = a.hidden_size

        def norm(name, v):
            scale = self.param(name, nn.initializers.ones, (c,),
                               self.param_dtype)
            return rms_norm(v, scale, a.rms_norm_eps, dtype=self.dtype)

        eh = self.param("eh_proj", nn.initializers.normal(stddev=0.02),
                        (2 * c, c), self.param_dtype)
        y = jnp.dot(jnp.concatenate([norm("enorm", nxt), norm("hnorm", x)],
                                    -1), eh.astype(self.dtype))
        y = _block_class(self.remat and train)(
            a, a.layer_types[-1], dense=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name="block")(
            y.astype(jnp.float32), False, positions, None, None, None, train)
        return norm("ln", y)


class LatentLM(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32, with
    ``TransformerLM``'s call signature (the serve engine's masked step
    and the page operations take either)."""

    arch: LatentArch
    vocab_size: int = 256
    max_len: int = 1024
    remat: bool = False                # recompute each block in the backward
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    input_kind = "tokens"
    # no parameter's shape depends on the sequence: init runs this short
    init_seq_len = 8

    @property
    def hidden(self) -> int:
        return self.arch.hidden_size

    @property
    def heads(self) -> int:
        return self.arch.num_attention_heads

    def expert_gauges(self, prefix: str) -> dict:
        """``<prefix>_experts_held`` of ``<prefix>_experts_total``."""
        a = self.arch
        held = (a.n_routed_experts if a.held_experts is None
                else len(a.held_experts))
        return {f"{prefix}_experts_total": a.n_routed_experts,
                f"{prefix}_experts_held": held}

    def train_gauges(self, seq_len: int) -> dict:
        """What the trainer sets once, at construction: the experts
        held and, where layers have a window and train through
        ``flash_prefill``, the share of a row's causal (query block,
        key block) pairs that kernel's grids visit in such a layer."""
        a, out = self.arch, self.expert_gauges("train")
        if (a.early_router or a.parallel) \
                and "sliding_attention" in a.layer_types:
            from tpunet.ops.flash import causal_blocks_visited
            visited, causal = causal_blocks_visited(
                seq_len, a.gqa("sliding_attention")["window"])
            out["train_attn_window_blocks_visited_pct"] = \
                100.0 * visited / causal
        return out

    def cache_specs(self) -> list:
        """Each layer's ``cache_spec``, as its mixer states it."""
        a = self.arch
        return [mixer_class(a, kind).cache_spec(a, kind, self.dtype)
                for kind in a.layer_types]

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes a slot keeps whatever its length (all layers): the
        engine's state pool holds ``slots`` such rows. 0 = every cache
        of this model is paged."""
        return sum(math.prod(shape) * jnp.dtype(dtype).itemsize
                   for spec in self.cache_specs()
                   for shape, dtype in spec["state"].values())

    def serve_gauges(self) -> dict:
        """What the serve engine sets once, at construction: bytes a
        token keeps per cache kind (all layers of the kind, lane-rounded
        rows as stored), bytes a slot keeps beside its pages, and the
        experts held of the router's width. Where no layer's one-token
        call goes through ``tpunet_paged_decode``, the gauge that the
        engine set from that kernel's dispatch reads 0."""
        specs = self.cache_specs()
        per: dict = {}
        for spec in specs:
            for cache, (width, dtype) in spec["paged"].items():
                per[cache] = per.get(cache, 0) \
                    + width * jnp.dtype(dtype).itemsize
        out = {f"serve_cache_bytes_per_token_{k}": v for k, v in per.items()}
        if self.state_bytes_per_slot:
            out["serve_state_bytes_per_slot"] = self.state_bytes_per_slot
        if not any(spec["decode_kernel"] for spec in specs):
            out["serve_decode_attend_kernel"] = 0
        return {**out, **self.expert_gauges("serve")}

    @nn.compact
    def __call__(self, tokens, train: bool = False, decode: bool = False,
                 pos_offset=0, segment_ids=None,
                 return_hidden: bool = False, decode_active=None,
                 paged_kv=None, page_table=None, state_rows=None,
                 lengths=None):
        """As ``TransformerLM.__call__``; ``pos_offset`` is a scalar or
        an int32 [B] of each row's first position. ``state_rows`` [B]
        names each row's row of the per-slot state pool and ``lengths``
        [B] how many of the call's positions are real (mixers that keep
        a state; see ``mixer_class``). ``train`` takes the
        batch-wide causal path and, where the architecture has a
        multi-token-prediction module, returns ``(logits, logits of the
        token after next)``. Packed sequences (``segment_ids``) are not
        built."""
        if segment_ids is not None:
            raise ValueError("latent_lm has no packed sequences")
        if train and decode:
            raise ValueError("latent_lm trains without a cache")
        a = self.arch
        b, t = tokens.shape
        if t > self.max_len:
            raise ValueError(f"sequence {t} exceeds max_len {self.max_len}")
        positions = jnp.broadcast_to(jnp.asarray(pos_offset, jnp.int32), (b,))
        embed = nn.Embed(self.vocab_size, a.hidden_size,
                         embedding_init=nn.initializers.normal(stddev=0.02),
                         param_dtype=self.param_dtype, name="embed")
        x = embed(tokens).astype(jnp.float32)
        Block = _block_class(self.remat and train)
        for i, kind in enumerate(a.layer_types):
            x = Block(a, kind, dense=i < a.first_k_dense_replace,
                      dtype=self.dtype, param_dtype=self.param_dtype,
                      name=f"block{i:02d}")(
                x, decode, positions, decode_active, paged_kv, page_table,
                train, state_rows, lengths)
        h = block_norm(a, x, norm_param(self, "ln", a.hidden_size, a,
                                        self.param_dtype), self.dtype)
        if return_hidden:
            return h.astype(jnp.float32)
        if a.parallel:           # the head is the embedding, scaled
            head, vocab_axis = embed.embedding, 0
        else:
            head, vocab_axis = self.param(
                "head", nn.initializers.normal(stddev=0.02),
                (a.hidden_size, self.vocab_size), self.param_dtype), 1

        def logits_of(h_):
            with jax.named_scope("tpunet_head"):
                out = lax.dot_general(
                    h_, head.astype(self.dtype),
                    (((h_.ndim - 1,), (1 - vocab_axis,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return out * a.logit_scale if a.logit_scale != 1.0 else out

        if not (a.num_nextn_predict_layers
                and (train or self.is_initializing())):
            return logits_of(h)
        # the trunk's embedding and head are the module's too; the last
        # position has no next token (it takes the row's first, and the
        # loss leaves it out)
        with jax.named_scope("tpunet_mtp"):
            h_next = MtpModule(a, self.remat, dtype=self.dtype,
                               param_dtype=self.param_dtype, name="mtp")(
                x, embed(jnp.roll(tokens, -1, axis=1)), positions, train)
        return logits_of(h), logits_of(h_next)


def create_model(cfg: ModelConfig, mesh=None) -> LatentLM:
    if mesh is not None and mesh.size > 1:
        raise ValueError("latent_lm runs on one device (a chip's share of "
                         "an expert-parallel deployment); no mesh lowering")
    if not cfg.latent:
        raise ValueError("model latent_lm needs ModelConfig.latent (the "
                         "published config keys)")
    return LatentLM(arch=LatentArch.from_mapping(cfg.latent),
                    vocab_size=cfg.vocab_size, max_len=cfg.max_seq_len,
                    remat=cfg.remat, dtype=jnp.dtype(cfg.dtype),
                    param_dtype=jnp.dtype(cfg.param_dtype))
