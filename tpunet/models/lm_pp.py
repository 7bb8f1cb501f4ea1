"""Pipeline-parallel causal LM ("lm_pp").

The LM family is where pipeline parallelism earns its keep (depth grows
with model scale while the vision models stay shallow), so the decoder
gets the same treatment as tpunet/models/vit_pp.py: encoder blocks as
*stacked functional parameters* (leading ``depth`` dim, sharded over the
mesh 'pipe' axis by the path rule in tpunet/parallel/tp.py) streamed
through the GPipe executor (tpunet/parallel/pp.py) — one jitted SPMD
program, activations hopping stage-to-stage via ``lax.ppermute``.

Architecture matches tpunet/models/lm.py's TransformerLM: token
embedding + learned positions -> pre-LN causal blocks -> final LN ->
logits tied to the embedding transpose. Causality comes from the dense
attention mask inside block_apply (causal=True). With
``--attention ulysses`` or ``--attention ring`` the sequence is ALSO
sharded (SP x PP, dp x sp x pp meshes): the pipeline executor passes
the 'seq' axis through its shard_map and each stage runs its SP
collectives over that already-manual axis — Ulysses' all-to-all pair
around a locally-dense core (exact global causality: the core sees the
full sequence per head group), or the ring's per-step K/V ppermute
rotation (exact global causality via global positions,
tpunet/ops/attention.py ring_attention). Both ops are axis-name
shard_map-body functions, so no shard_map nesting is involved; pick
ulysses when the 'seq' axis size divides the head count (2
collectives/call), ring when it doesn't or when per-hop ICI traffic
must stay neighbor-only.

Dropout is fully supported: the train step's dropout rng threads
through gpipe, folded per (tick, stage, layer). Grad accumulation
composes too — the accumulation scan in steps.py wraps the whole
pipelined program (microbatching in TIME over microbatching in STAGES).

Packed sequences compose: ``segment_ids`` travel as the executors'
per-microbatch ``extra`` input (each stage indexes its current
microbatch's ids — batch metadata never hops), masking attention to
same-segment tokens inside every block; ``--pack-docs --model lm_pp``
works under both schedules, including packed x SP with Ulysses
(the seq-sharded id slice rides ``extra`` and the full-sequence local
core masks exactly after one [mb, T/sp] -> [mb, T] id all_gather —
tpunet/ops/attention.py ulysses_attention). Ring stays excluded: its
state-merging core has no segment operands (the __call__ error).

MoE composes as well (EP x PP): with ``--moe-experts`` the stacks are
organized as SUPER-layers — ``moe_every - 1`` dense blocks plus one
routed block per scan step — so the per-stage program stays one
uniform ``lax.scan`` despite heterogeneous layers (depth must divide
into whole super-layers, and super-layers across stages). The routed
block runs the same functional core as MoeMlp
(tpunet/models/moe.py moe_apply); the load-balance aux loss threads
through the executors' ``with_aux`` contract (sum over stages, mean
over microbatch-shards — the equal-weight semantics grad-accum uses,
tpunet/train/steps.py) and is sown into the standard 'losses'
collection. With pipe > 1 each microbatch-shard routes its tokens
independently with per-shard capacity (the standard shard_map MoE
scope; the unpipelined model under GSPMD routes globally — documented
deviation, exact parity at n_micro=1). With a mesh 'model' axis > 1
the expert stacks (and their Adam moments) shard over it INSIDE the
stages — true EP x PP: routing/dispatch replicated per shard (cheap,
O(n x E)), expert FFNs on the local expert slice, one psum per MoE
layer assembles the output (no token all-to-all: tokens are
replicated over 'model'). Grad parity vs the replicated run is exact
under both schedules; the 1F1B manual backward handles the
unreduced-cotangent convention the in-stage psum transposes imply
(tpunet/parallel/pp.py onef1b ep_axis).

With pipe == 1 the stacked params run as a plain lax.scan over layers —
the same math, which the parity tests assert. No KV-cache decode path
in this module: generation/serving unstacks lm_pp checkpoints into the
(architecturally identical) TransformerLM via to_transformer_lm_params
(tpunet/infer/generate.py --model lm_pp); the reference has no LM
serving at all (SURVEY.md section 0 — this whole family is beyond
parity).

Measured on the v5e chip (scripts/bench_lm.py --model lm_pp, T=2048
B=8 depth=4 hidden=512): 276-290k tok/s at pipe=1 with the flash core
(--attention flash/auto; inside the pipeline's shard_map the local
kernel variant runs, outside it the mesh-split one —
resolve_block_cores) — 1.85x the
unrolled DENSE TransformerLM (157k) and within 19% of the unrolled
flash one (357k); that residual scan-over-layers overhead is the price
of being shardable over 'pipe', which pays only at real multi-stage
meshes (unmeasurable on this 1-chip environment; the dp x pp dryrun
leg validates the program, not its scaling). With the dense core this
was 132k tok/s.

Schedule note: three executors (``--pp-schedule``). "gpipe" (default)
lets reverse-mode AD through the scan+ppermute emit the standard
backward pipeline (all forwards, then all backwards — its residuals
stack every per-tick intermediate). "1f1b" is the hand-written VJP
(tpunet/parallel/pp.py onef1b): the backward replays forwards and runs
backwards interleaved per microbatch in 1F1B order, holding at most
min(S, M) stage inputs live — the 1F1B activation bound — at the cost
of one rematerialized stage forward per microbatch. Same grads
(parity-tested), same bubble fraction; pick 1f1b when activation
memory, not compute, is the binding constraint. "interleaved" adds
virtual pipeline stages (``--pp-virtual`` chunks per device on a full
activation ring, chunk-permuted 'pipe' storage): ~v-fold smaller
bubble at a 1F1B-style bounded memory cost (pp.py interleaved).
Composes with packed sequences and MoE/EP (chunks hold whole
super-layers); SP stays with gpipe/1f1b. Interleaved checkpoints
persist their layout (resume guard + the best_meta.json serving
sidecar) because the stacks are chunk-permuted.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpunet.config import ModelConfig
from tpunet.models.moe import moe_apply
from tpunet.models.vit_pp import (_dropout, _stacked_lecun_normal,
                                  attn_half_apply, block_apply,
                                  resolve_block_cores)
from tpunet.ops.attention import (ring_attention, ring_self_attention,
                                  ulysses_attention,
                                  ulysses_self_attention)
from tpunet.parallel.pp import (gpipe, interleaved,
                                interleaved_layer_order, onef1b)


def _stacked_expert_normal(key, shape, dtype=jnp.float32):
    """flax variance_scaling(2.0, fan_in, truncated_normal) for stacked
    [G, e, d_in, d_out] expert kernels, matching MoeMlp's UNSTACKED
    [e, d_in, d_out] fan exactly (flax treats leading dims as the
    receptive field: fan_in = e * d_in) — the stacked G dim must not
    fold into the fan."""
    fan_in = shape[-3] * shape[-2]
    std = (2.0 / fan_in) ** 0.5 / 0.87962566103423978
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


_ATTN_KEYS = ("ln1s", "ln1b", "qkv_k", "qkv_b", "out_k", "out_b",
              "ln2s", "ln2b")
_FC_KEYS = ("fc1_k", "fc1_b", "fc2_k", "fc2_b")
_MOE_KEYS = ("rk", "rb", "wi", "bi", "wo", "bo")


def _moe_block_apply(pa, pm, x, *, heads, top_k, capacity_factor,
                     dropout_rate=0.0, key=None, attn,
                     segment_ids=None, ep_axis=None,
                     ep_impl="replicated"):
    """One pre-LN block whose MLP is the routed MoE core: the shared
    attention half (vit_pp.attn_half_apply — same dropout placements
    and key split as dense blocks), then moe_apply
    (tpunet/models/moe.py) instead of the dense fc pair. Router math
    in float32 on the float32 router params (the stacked analogue of
    MoeMlp's float32 Dense). ``ep_axis`` (EP x PP): the expert params
    hold only this device's shard over that mesh axis; ``ep_impl``
    picks the lowering — "alltoall" (GShard capacity-buffer token
    exchange; each device routes its 1/ep token slice) or
    "replicated" (every device routes all tokens, one psum assembles
    the output). Returns (x, aux)."""
    mb, t, c = x.shape
    x, y, km = attn_half_apply(pa, x, heads=heads, causal=True,
                               dropout_rate=dropout_rate, key=key,
                               attn=attn, segment_ids=segment_ids)
    tokens = y.reshape(mb * t, c)
    logits = (tokens.astype(jnp.float32) @ pm["rk"].astype(jnp.float32)
              + pm["rb"].astype(jnp.float32))
    out, aux = moe_apply(tokens, logits, pm["wi"], pm["bi"], pm["wo"],
                         pm["bo"], top_k=top_k,
                         capacity_factor=capacity_factor, dtype=x.dtype,
                         ep_axis=ep_axis, ep_impl=ep_impl)
    out = out.reshape(mb, t, c)
    if dropout_rate > 0.0 and km is not None:
        out = _dropout(out, dropout_rate, km)
    return x + out, aux


class PipelinedLM(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32, pipelined."""

    vocab_size: int = 256
    hidden: int = 192
    depth: int = 6
    heads: int = 3
    mlp_ratio: float = 4.0
    max_len: int = 1024
    n_micro: int = 4
    dropout_rate: float = 0.0
    moe_experts: int = 0               # 0 = dense MLP everywhere
    moe_every: int = 2                 # MoE in every moe_every-th block
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_dispatch: str = "auto"         # EP lowering (moe.py docstring)
    attention: str = "dense"   # dense | flash | auto | ulysses | ring
    attention_core: Any = None         # SP local core (None = auto)
    attention_block: int = 512         # blockwise/flash block inside SP
    schedule: str = "gpipe"    # gpipe | 1f1b | interleaved (pp.py)
    virtual: int = 2                   # chunks/device for interleaved
    mesh: Any = None                   # jax.sharding.Mesh or None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    input_kind = "tokens"              # init_variables dispatch

    @nn.compact
    def __call__(self, tokens, train: bool = False, segment_ids=None,
                 return_hidden: bool = False):
        """``segment_ids`` [B, T] enables packed-sequence training:
        attention masks to same-segment tokens (composed with
        causality in the core). The ids travel through the pipeline as
        the executors' non-differentiable ``extra`` input — indexed
        per microbatch by each stage, never hopped.
        ``return_hidden=True``: final-LN hidden states [B, T, C]
        float32 instead of logits (the vocab-sharded CE hook,
        tpunet/ops/vocab_ce.py — at real vocabs the replicated
        [B, T, V] float32 logits this skips dwarf the activation
        memory the 1F1B executor saves)."""
        if self.hidden % self.heads:
            raise ValueError(f"hidden {self.hidden} not divisible by "
                             f"{self.heads} heads")
        packed = segment_ids is not None
        if packed and self.attention == "ring":
            raise ValueError(
                "packed sequences don't compose with ring attention: "
                "the ring merges per-block (out, lse) attention STATES "
                "and the flash state kernel has no segment operands "
                "(tpunet/ops/flash.py local_flash_attention_state) — "
                "use --attention ulysses (segment-capable SP: the "
                "local core sees the full sequence and masks exactly) "
                "or dense/flash/auto")
        b, t = tokens.shape
        if t > self.max_len:
            raise ValueError(f"sequence {t} exceeds max_len {self.max_len}")
        embed = nn.Embed(self.vocab_size, self.hidden,
                         embedding_init=nn.initializers.normal(stddev=0.02),
                         param_dtype=self.param_dtype, name="embed")
        x = embed(tokens).astype(self.dtype)
        pos = self.param("pos_embed", nn.initializers.normal(stddev=0.02),
                         (1, self.max_len, self.hidden), self.param_dtype)
        x = x + pos[:, :t].astype(self.dtype)

        rate = self.dropout_rate if train else 0.0
        key = self.make_rng("dropout") if rate > 0.0 else None
        if key is not None:
            x = _dropout(x, rate, self.make_rng("dropout"))

        ln_ones = nn.initializers.ones
        zeros = nn.initializers.zeros
        winit = _stacked_lecun_normal
        L, C, H = self.depth, self.hidden, int(self.hidden * self.mlp_ratio)
        moe = self.moe_experts > 0
        m_every = self.moe_every if moe else 1
        if moe and L % m_every:
            raise ValueError(f"depth {L} not divisible by moe_every "
                             f"{m_every} (whole super-layers required)")
        G = L // m_every
        # Dense-MLP stacks cover only the dense slots: with MoE every
        # m_every-th block routes instead, so the fc stacks hold
        # G * (m_every - 1) layers ordered by (super-layer, slot) —
        # matching TransformerLM's layout where MoE blocks have no
        # dense mlp params at all.
        n_fc = G * (m_every - 1) if moe else L
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
        }
        if n_fc > 0:
            blocks.update({
                "fc1_k": self.param("blocks_fc1_k", winit, (n_fc, C, H),
                                    self.param_dtype),
                "fc1_b": self.param("blocks_fc1_b", zeros, (n_fc, H),
                                    self.param_dtype),
                "fc2_k": self.param("blocks_fc2_k", winit, (n_fc, H, C),
                                    self.param_dtype),
                "fc2_b": self.param("blocks_fc2_b", zeros, (n_fc, C),
                                    self.param_dtype),
            })
        blocks = jax.tree_util.tree_map(
            lambda a: a.astype(self.dtype), blocks)
        if moe:
            E = self.moe_experts
            # Router params stay float32 (MoeMlp's float32 Dense);
            # expert kernels keep param_dtype and moe_apply casts them
            # to the compute dtype itself — so none of these take the
            # blanket dtype cast above.
            blocks.update({
                "moe_rk": self.param(
                    "blocks_moe_rk", nn.initializers.normal(stddev=0.02),
                    (G, C, E), jnp.float32),
                "moe_rb": self.param("blocks_moe_rb", zeros, (G, E),
                                     jnp.float32),
                "moe_wi": self.param("blocks_moe_wi",
                                     _stacked_expert_normal, (G, E, C, H),
                                     self.param_dtype),
                "moe_bi": self.param("blocks_moe_bi", zeros, (G, E, H),
                                     self.param_dtype),
                "moe_wo": self.param("blocks_moe_wo",
                                     _stacked_expert_normal, (G, E, H, C),
                                     self.param_dtype),
                "moe_bo": self.param("blocks_moe_bo", zeros, (G, E, C),
                                     self.param_dtype),
            })
        heads = self.heads

        pipelined = (self.mesh is not None
                     and self.mesh.shape.get("pipe", 1) > 1)
        sp = self.attention in ("ulysses", "ring")
        if sp:
            if pipelined:
                # SP x PP: runs INSIDE the pipeline's shard_map, so the
                # stage body is already device-local — both SP ops are
                # axis-name collectives over the mesh 'seq' axis:
                # Ulysses' all-to-all pair around a locally-dense core,
                # or the ring's K/V rotation (global positions keep
                # causality exact either way).
                if self.attention == "ulysses":
                    # segment_ids (packed x SP): the seq-SHARDED id
                    # slice rides the executors' 'extra' input;
                    # ulysses_attention gathers it to global ids for
                    # its full-sequence local core.
                    def attn(q, k, v, causal=True, segment_ids=None):
                        return ulysses_attention(
                            q, k, v, axis_name="seq", causal=causal,
                            core=self.attention_core,
                            block=self.attention_block,
                            segment_ids=segment_ids)
                else:
                    def attn(q, k, v, causal=True):
                        return ring_attention(q, k, v, "seq",
                                              causal=causal,
                                              core=self.attention_core)
            elif self.attention == "ulysses":
                # pipe == 1: the partitioned wrapper shard_maps over
                # 'seq' per block, same as the unpipelined LM family.
                def attn(q, k, v, causal=True, segment_ids=None):
                    return ulysses_self_attention(
                        q, k, v, self.mesh, causal=causal,
                        core=self.attention_core,
                        block=self.attention_block,
                        segment_ids=segment_ids)
            else:
                def attn(q, k, v, causal=True):
                    return ring_self_attention(q, k, v, self.mesh,
                                               causal=causal,
                                               core=self.attention_core)
        else:
            seq_core, pipe_core = resolve_block_cores(self.attention)
            attn = pipe_core if pipelined else seq_core
        sp_in_pipe = sp and pipelined

        top_k, cap_f = self.moe_top_k, self.moe_capacity_factor
        # EP x PP: shard the expert stacks over the mesh 'model' axis
        # inside the pipeline. The lowering (--moe-dispatch) resolves
        # here against the static per-stage token count: "alltoall" is
        # the GShard capacity-buffer dispatch (each device routes its
        # 1/ep slice of the stage's tokens and two all_to_alls carry
        # the exchange), "replicated" the routing-everywhere psum
        # fallback (moe.py module docstring for the accounting).
        ep_axis = ("model" if (moe and pipelined
                               and self.mesh.shape.get("model", 1) > 1)
                   else None)
        ep_impl = "replicated"
        if ep_axis is not None:
            from tpunet.models.moe import resolve_moe_dispatch
            ep = self.mesh.shape["model"]
            dp = self.mesh.shape.get("data", 1)
            sp_n = self.mesh.shape.get("seq", 1) if sp else 1
            if (b % (dp * self.n_micro) == 0 and t % sp_n == 0):
                n_stage = (b // dp // self.n_micro) * (t // sp_n)
            elif self.moe_dispatch == "alltoall":
                raise ValueError(
                    f"moe_dispatch='alltoall' needs batch {b} divisible "
                    f"by data axis x microbatches ({dp} x "
                    f"{self.n_micro}) and seq {t} by the seq axis "
                    f"({sp_n}) to slice stage tokens over the expert "
                    "axis")
            else:
                n_stage = 1   # indivisible; the executor will raise
                #               its own divisibility error (auto path)
            ep_impl = resolve_moe_dispatch(self.moe_dispatch, ep=ep,
                                           n_tokens=n_stage,
                                           n_experts=self.moe_experts)
        elif self.moe_dispatch == "alltoall" and moe:
            raise ValueError(
                "moe_dispatch='alltoall' needs the pipelined EP x PP "
                "path (mesh 'pipe' > 1 and 'model' > 1); the "
                "unpipelined lm/vit models lower it via MoeMlp")

        def stage_apply(params, xs, *rest):
            # rest per the executor protocol: (extra?, key?) — extra is
            # this microbatch's [mb, T] segment-id slice when packed.
            if packed:
                seg_pair = (rest[0], rest[0])
                rest = rest[1:]
            else:
                seg_pair = None
            k = rest[0] if rest else None
            if k is not None and sp_in_pipe:
                # x is seq-sharded inside the pipeline under SP
                # (ulysses or ring): without this fold every
                # sequence shard would draw
                # IDENTICAL dropout masks (correlated positions T/sp
                # apart). Dense/flash stages must NOT fold — their x is
                # replicated over 'seq' and diverging masks would break
                # the replication invariant.
                k = jax.random.fold_in(k, jax.lax.axis_index("seq"))

            if not moe:
                def body(carry, inp):
                    pl, i = inp
                    lk = (jax.random.fold_in(k, i) if k is not None
                          else None)
                    return block_apply(pl, carry, heads=heads,
                                       causal=True, dropout_rate=rate,
                                       key=lk, attn=attn,
                                       segment_ids=seg_pair), None
                idx = jnp.arange(
                    jax.tree_util.tree_leaves(params)[0].shape[0])
                out, _ = jax.lax.scan(body, xs, (params, idx))
                return out

            # MoE: scan over SUPER-layers (m_every - 1 dense blocks +
            # one MoE block each) so the per-stage program stays a
            # uniform lax.scan despite heterogeneous layers. The local
            # [L_local, ...] stacks reshape to [G_local, slot, ...]
            # (contiguous, since stages hold whole super-layers).
            gl = params["moe_wi"].shape[0]
            pa = {kk: params[kk].reshape((gl, m_every)
                                         + params[kk].shape[1:])
                  for kk in _ATTN_KEYS}
            pf = ({kk: params[kk].reshape((gl, m_every - 1)
                                          + params[kk].shape[1:])
                   for kk in _FC_KEYS} if m_every > 1 else {})
            pm = {kk: params["moe_" + kk] for kk in _MOE_KEYS}

            def body(carry, inp):
                xc, auxc = carry
                pa_g, pf_g, pm_g, g = inp
                for j in range(m_every - 1):
                    pl = {kk: pa_g[kk][j] for kk in _ATTN_KEYS}
                    pl.update({kk: pf_g[kk][j] for kk in _FC_KEYS})
                    lk = (jax.random.fold_in(k, g * m_every + j)
                          if k is not None else None)
                    xc = block_apply(pl, xc, heads=heads, causal=True,
                                     dropout_rate=rate, key=lk,
                                     attn=attn, segment_ids=seg_pair)
                pl = {kk: pa_g[kk][m_every - 1] for kk in _ATTN_KEYS}
                lk = (jax.random.fold_in(k, g * m_every + m_every - 1)
                      if k is not None else None)
                xc, a = _moe_block_apply(pl, pm_g, xc, heads=heads,
                                         top_k=top_k,
                                         capacity_factor=cap_f,
                                         dropout_rate=rate, key=lk,
                                         attn=attn,
                                         segment_ids=seg_pair,
                                         ep_axis=ep_axis,
                                         ep_impl=ep_impl)
                return (xc, auxc + a), None

            (out, aux), _ = jax.lax.scan(
                body, (xs, jnp.zeros((), jnp.float32)),
                (pa, pf, pm, jnp.arange(gl)))
            return out, aux

        if pipelined and self.schedule == "interleaved":
            # Virtual stages: the executor reinterprets each device's
            # contiguous P('pipe') slice as `virtual` chunks (global
            # stage j*S + d — chunk-PERMUTED storage,
            # interleaved_layer_order; to_transformer_lm_params takes
            # (pipe, virtual) to unstack such checkpoints). Packed
            # segment ids ride the executor's `extra` input and MoE
            # composes too (chunks hold whole super-layers; aux via
            # with_aux, EP via ep_axis + the uniform backward); SP
            # stays with gpipe/1f1b — interleaved's contribution is
            # the ~v-fold smaller bubble (create_model rejects it).
            if sp:
                raise ValueError(
                    "pp_schedule='interleaved' does not compose with "
                    "SP attention — use gpipe/1f1b for dp x sp x pp")
            pspecs = None
            kw = {}
            if ep_axis is not None:
                from tpunet.parallel.tp import pp_stack_spec
                pspecs = {kk: pp_stack_spec("blocks_" + kk)
                          for kk in blocks}
                kw["ep_axis"] = ep_axis
            x = interleaved(stage_apply, blocks, x, mesh=self.mesh,
                            n_micro=self.n_micro,
                            n_virtual=self.virtual, key=key,
                            extra=segment_ids, with_aux=moe,
                            param_specs=pspecs, **kw)
        elif pipelined:
            executor = onef1b if self.schedule == "1f1b" else gpipe
            pspecs = None
            kw = {}
            if ep_axis is not None:
                # One source of truth for the stack shardings: the
                # same path rules the Trainer stores params under
                # (tpunet/parallel/tp.py VIT_PP_RULES).
                from tpunet.parallel.tp import pp_stack_spec
                pspecs = {kk: pp_stack_spec("blocks_" + kk)
                          for kk in blocks}
            if self.schedule == "1f1b":
                # the manual backward completes per-tick cotangents
                # over the EP axis itself and resolves its own
                # uniform_bwd from seq/ep (onef1b's ep_axis note)
                kw["ep_axis"] = ep_axis
            x = executor(stage_apply, blocks, x, mesh=self.mesh,
                         n_micro=self.n_micro, key=key,
                         seq_axis="seq" if sp else None,
                         with_aux=moe, extra=segment_ids,
                         param_specs=pspecs, **kw)
        else:
            args = (x,) if segment_ids is None else (x, segment_ids)
            x = (stage_apply(blocks, *args) if key is None
                 else stage_apply(blocks, *args, key))
        if moe:
            # One scalar for the whole program: sum over layers, and
            # with pipe > 1 the executor's mean over microbatch-shards
            # (tpunet/parallel/pp.py gpipe docstring). Sown into the
            # standard 'losses' collection, so the train step's
            # _aux_term picks it up exactly like MoeMlp's sow.
            x, aux = x
            self.sow("losses", "moe_aux", aux)

        x = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype,
                         name="ln")(x)
        if return_hidden:
            return x.astype(jnp.float32)
        logits = embed.attend(x.astype(self.param_dtype))
        return logits.astype(jnp.float32)


def to_transformer_lm_params(params: dict, *, pipe: int = None,
                             virtual: int = None) -> dict:
    """Unstack a PipelinedLM param tree into TransformerLM's layout
    (block{i:02d}/attn/..., tpunet/models/lm.py) — the two are the same
    architecture, so lm_pp training checkpoints serve through the
    TransformerLM KV-cache generation path. MoE stacks (present when
    the model was trained with --moe-experts) unstack into the
    block{i}/moe/{router, wi, bi, wo, bo} layout of MoeMlp; the MoE
    period is recovered from the stack shapes (L / G).

    ``pipe`` + ``virtual`` (interleaved checkpoints): stacks trained
    with pp_schedule='interleaved' are stored chunk-PERMUTED
    (interleaved_layer_order — device d's contiguous 'pipe' slice
    holds chunks d, S+d, ...), so unstacking them needs the training
    run's pipe-axis size and --pp-virtual to recover semantic layer
    order. Leave both None for gpipe/1f1b checkpoints."""
    if (pipe is None) != (virtual is None):
        raise ValueError("pass pipe and virtual together (both from "
                         "the interleaved training run) or neither")
    out = {"embed": params["embed"], "pos_embed": params["pos_embed"],
           "ln": params["ln"]}
    L = params["blocks_qkv_k"].shape[0]
    if pipe is not None:
        # Invert the chunk permutation per stack granularity: block
        # stacks at layer granularity [L], MoE stacks at super-layer
        # granularity [G] (chunks hold whole super-layers), dense-fc
        # stacks at [G * (m_every - 1)] expanded from the G ordering.
        order = interleaved_layer_order(L, pipe, virtual)
        invs = {L: sorted(range(L), key=order.__getitem__)}
        if "blocks_moe_wi" in params:
            G = params["blocks_moe_wi"].shape[0]
            order_g = interleaved_layer_order(G, pipe, virtual)
            inv_g = sorted(range(G), key=order_g.__getitem__)
            invs[G] = inv_g
            me = L // G
            if me > 1:
                invs[G * (me - 1)] = [g * (me - 1) + o for g in inv_g
                                      for o in range(me - 1)]
        params = {k: (v[jnp.asarray(invs[v.shape[0]])]
                      if k.startswith("blocks_") and v.shape[0] in invs
                      else v)
                  for k, v in params.items()}
    moe = "blocks_moe_wi" in params
    m_every = L // params["blocks_moe_wi"].shape[0] if moe else 0
    for i in range(L):
        block = {
            "ln1": {"scale": params["blocks_ln1s"][i],
                    "bias": params["blocks_ln1b"][i]},
            "attn": {"qkv": {"kernel": params["blocks_qkv_k"][i],
                             "bias": params["blocks_qkv_b"][i]},
                     "out": {"kernel": params["blocks_out_k"][i],
                             "bias": params["blocks_out_b"][i]}},
            "ln2": {"scale": params["blocks_ln2s"][i],
                    "bias": params["blocks_ln2b"][i]},
        }
        if moe and i % m_every == m_every - 1:
            g = i // m_every
            block["moe"] = {
                "router": {"kernel": params["blocks_moe_rk"][g],
                           "bias": params["blocks_moe_rb"][g]},
                "wi": params["blocks_moe_wi"][g],
                "bi": params["blocks_moe_bi"][g],
                "wo": params["blocks_moe_wo"][g],
                "bo": params["blocks_moe_bo"][g],
            }
        else:
            fi = ((i // m_every) * (m_every - 1) + i % m_every
                  if moe else i)
            block["mlp"] = {"fc1": {"kernel": params["blocks_fc1_k"][fi],
                                    "bias": params["blocks_fc1_b"][fi]},
                            "fc2": {"kernel": params["blocks_fc2_k"][fi],
                                    "bias": params["blocks_fc2_b"][fi]}}
        out[f"block{i:02d}"] = block
    return out


def create_model(cfg: ModelConfig, mesh=None) -> PipelinedLM:
    """Build a PipelinedLM; unsupported 'lm' features fail loudly."""
    if cfg.attention not in ("dense", "flash", "auto", "ulysses", "ring"):
        raise ValueError(
            f"lm_pp supports dense/flash/auto and ulysses/ring (SP x "
            f"PP) causal attention (got {cfg.attention!r})")
    if cfg.attention in ("ulysses", "ring"):
        if mesh is None:
            raise ValueError(
                f"attention={cfg.attention!r} requires a mesh")
        sp_size = mesh.shape.get("seq", 1)
        if (cfg.attention == "ulysses" and sp_size > 1
                and cfg.vit_heads % sp_size):
            raise ValueError(
                f"--vit-heads {cfg.vit_heads} not divisible by the "
                f"mesh 'seq' axis ({sp_size}) — Ulysses re-shards "
                "heads over it (ring SP has no head constraint)")
    if cfg.moe_experts > 0:
        if cfg.moe_every < 1:
            raise ValueError(f"moe_every must be >= 1, got "
                             f"{cfg.moe_every}")
        if cfg.vit_depth % cfg.moe_every:
            raise ValueError(
                f"--vit-depth {cfg.vit_depth} not divisible by "
                f"--moe-every {cfg.moe_every}: lm_pp stacks whole "
                "super-layers (moe_every-1 dense blocks + 1 MoE block)")
        stages = mesh.shape.get("pipe", 1) if mesh is not None else 1
        if stages > 1 and (cfg.vit_depth // cfg.moe_every) % stages:
            raise ValueError(
                f"{cfg.vit_depth // cfg.moe_every} MoE super-layers "
                f"(depth {cfg.vit_depth} / moe_every {cfg.moe_every}) "
                f"not divisible by {stages} pipeline stages")
        ep = mesh.shape.get("model", 1) if mesh is not None else 1
        if stages > 1 and ep > 1 and cfg.moe_experts % ep:
            raise ValueError(
                f"--moe-experts {cfg.moe_experts} not divisible by "
                f"the mesh 'model' axis ({ep}) — EP x PP shards the "
                "expert dim over it")
    if cfg.remat:
        raise ValueError("lm_pp does not support --remat (the pipeline "
                         "scan already bounds activation memory per "
                         "stage)")
    if cfg.pp_schedule not in ("gpipe", "1f1b", "interleaved"):
        raise ValueError(f"unknown pp_schedule {cfg.pp_schedule!r}; "
                         "expected gpipe|1f1b|interleaved")
    if cfg.pp_schedule == "interleaved":
        stages = mesh.shape.get("pipe", 1) if mesh is not None else 1
        if stages < 2:
            raise ValueError(
                "pp_schedule='interleaved' needs a mesh 'pipe' axis "
                "> 1 (at pipe=1 use gpipe/1f1b — the sequential "
                "fallback would have to un-permute chunk storage)")
        if cfg.pp_virtual < 2:
            raise ValueError(f"--pp-virtual must be >= 2 (got "
                             f"{cfg.pp_virtual}); v=1 IS gpipe/1f1b")
        if cfg.vit_depth % (stages * cfg.pp_virtual):
            raise ValueError(
                f"--vit-depth {cfg.vit_depth} not divisible by "
                f"{stages} stages x {cfg.pp_virtual} virtual chunks")
        if cfg.pp_microbatches % stages:
            raise ValueError(
                f"--pp-microbatches {cfg.pp_microbatches} not "
                f"divisible by the pipe axis ({stages}) — the "
                "interleaved F-stream cycles chunks per "
                "stage-count-sized microbatch group")
        if cfg.attention in ("ulysses", "ring"):
            raise ValueError(
                "pp_schedule='interleaved' does not compose with SP "
                "attention (ulysses/ring) — use gpipe/1f1b for "
                "dp x sp x pp")
        if cfg.moe_experts > 0:
            lc = cfg.vit_depth // (stages * cfg.pp_virtual)
            if lc % cfg.moe_every:
                raise ValueError(
                    f"interleaved chunks hold {lc} layers "
                    f"(depth {cfg.vit_depth} / {stages} stages / "
                    f"{cfg.pp_virtual} virtual) — not whole "
                    f"super-layers of moe_every={cfg.moe_every}")
    if mesh is not None:
        stages = mesh.shape.get("pipe", 1)
        if stages > 1 and cfg.vit_depth % stages:
            raise ValueError(
                f"--vit-depth {cfg.vit_depth} (the transformer depth "
                f"flag — for lm_pp it is the LM's layer count) is not "
                f"divisible by {stages} pipeline stages")
    return PipelinedLM(
        vocab_size=cfg.vocab_size,
        hidden=cfg.vit_hidden,
        depth=cfg.vit_depth,
        heads=cfg.vit_heads,
        mlp_ratio=cfg.vit_mlp_ratio,
        max_len=cfg.max_seq_len,
        n_micro=cfg.pp_microbatches,
        dropout_rate=cfg.dropout_rate,
        moe_experts=cfg.moe_experts,
        moe_every=cfg.moe_every,
        moe_top_k=cfg.moe_top_k,
        moe_capacity_factor=cfg.moe_capacity_factor,
        moe_dispatch=cfg.moe_dispatch,
        attention=cfg.attention,
        attention_core=(None if cfg.attention_core == "auto"
                        else cfg.attention_core),
        attention_block=cfg.attention_block,
        schedule=cfg.pp_schedule,
        virtual=cfg.pp_virtual,
        mesh=mesh,
        dtype=jnp.dtype(cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype),
    )
