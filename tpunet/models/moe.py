"""Mixture-of-Experts MLPs: two expert layers, for two jobs.

1. ``MoeMlp`` — the capacity-buffer layer that *trains* (models ``lm``,
   ``lm_pp``, the ViT family; ``--moe-experts``): softmax gates, top-k
   with a per-expert capacity buffer that DROPS what overflows, GELU
   experts, a load-balance loss, and the two shard_map expert-parallel
   lowerings. Everything from the next paragraph to the end of this
   text describes it.
2. ``RoutedShareMlp`` / ``routed_share`` — the no-drop layer that
   *serves* (model ``latent_lm``, the benchmark's ``dots3-note-prev``):
   sigmoid gates (with a selection bias or none) or softmax, top-k
   renormalised over the selected, gated-SiLU experts beside one shared
   expert or several averaged ones, and no
   capacity: the (token, expert) pairs are sorted by expert and go
   through one grouped product (``jax.lax.ragged_dot``). The layer is
   told which experts it ``held`` (one chip's share under expert
   parallelism): the router keeps all its outputs, the top-k and the
   normalisation run over all of them, and the layer returns the part
   of the sum its own experts give plus the shared part. Nothing
   stands in for the absent chips.

The reference is a dense CNN (SURVEY.md 2b lists EP/MoE as absent);
tpunet adds a ViT-MoE-style sparse MLP so expert parallelism is a real,
tested strategy rather than an open mesh axis. Design follows the
einsum dense-dispatch formulation (Mesh-TensorFlow / ViT-MoE / Switch):

- Router: Dense(E) over tokens -> softmax probs -> top-k experts per
  token (k=2 default), gate values renormalized over the selected k.
- Capacity: each expert processes at most C = ceil(k*N/E * factor)
  tokens; overflow tokens are dropped for that expert (their gate mass
  simply doesn't contribute — standard Switch behavior). Position in
  expert is assigned by token order via cumsum, all inside jit with
  static shapes (no sorting, no dynamic shapes — XLA/MXU friendly).
- Dispatch/combine are one-hot einsums; expert FFNs are a single
  batched einsum over the expert dim ([E, d, h] / [E, h, d] params).
- Expert parallelism = sharding the expert dim of those params over
  the mesh 'model' axis (path rules in tpunet/parallel/tp.py).
- Load-balance aux loss (Shazeer et al.): E * sum_e(frac_dispatched_e
  * mean_router_prob_e), sown into the 'losses' collection; the train
  step adds cfg.moe_aux_weight * sum(losses) to the CE loss.

Two manual (shard_map) expert-parallel lowerings, selected by
``ep_impl`` / ``--moe-dispatch``:

- ``"alltoall"`` (preferred; ``auto`` picks it when shapes divide):
  the GShard/Switch capacity-buffer dispatch. Each device takes its
  1/ep SLICE of the (ep-replicated) token block, routes only that
  slice, builds per-global-expert capacity buffers [E, c, d], and one
  ``all_to_all`` over the expert axis ships each buffer row to the
  device that owns that expert; local FFNs run on [E/ep, ep*c, d];
  a second ``all_to_all`` returns expert outputs to the token owners
  and one ``all_gather`` restores the replicated [n, d] output.
- ``"replicated"`` (fallback, exact-global-routing semantics): every
  device routes ALL n tokens, slices dispatch/combine to its local
  experts, and one ``psum`` assembles the output.

Comm/compute accounting, per MoE layer per device (d = model dim,
n = tokens in the block, ep = expert-axis size, k*f = top_k *
capacity_factor, ring collectives, bytes = dtype width):

- replicated: psum of [n, d]  ->  2*(ep-1)/ep * n * d     bytes/layer
  (grows with n); dispatch/combine einsums cost O(n * E * c) FLOPs on
  EVERY device (replicated work).
- alltoall:   2 a2a of [E, c_l, d] + 1 all_gather of [n/ep, d]
              -> (ep-1)/ep * (2*k*f*n/ep + n) * d          bytes/layer
  — the a2a pair scales with tokens/ep (k*f*n/ep each way); only the
  boundary all_gather (restoring ep-replication for the surrounding
  dense/attention compute, at HALF a psum's cost) still scales with n.
  Dispatch/combine einsums drop to O(n/ep * E * c_l) — ep-fold less
  replicated work. Crossover vs replicated at ep ≈ 2*k*f - 2 (≈ 3 at
  the k=2, f=1.25 defaults): at ep=8 the a2a path ships 1.625x n*d vs
  psum's 1.75x ... 2x, and its routing compute is 8x cheaper. A fully
  token-sharded caller (tokens NOT replicated over the ep axis) would
  drop the all_gather term entirely; at this interface the surrounding
  per-stage compute is ep-replicated, so the boundary gather is the
  price of composing with it.

Routing-scope note: the alltoall path routes each 1/ep token slice
independently with per-slice capacity c_l = ceil(k*(n/ep)/E * f) —
the standard GShard scope — while the replicated path routes all n
tokens against one global capacity. With ample capacity (no drops) the
two produce identical outputs and identical aux (the a2a path psums
its [E]-sized count/prob statistics over the expert axis, so the aux
scope stays the full n-token block); under overflow the drop sets can
differ. Same class of documented deviation as per-microbatch-shard
routing under pipe > 1 (tpunet/models/lm_pp.py).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from jax import shard_map


def _route(probs, k: int, e: int, cap: int):
    """Top-k capacity-bounded routing: ``probs`` [n, e] float32 ->
    (dispatch [n, e, cap], combine [n, e, cap]) in float32.

    Shared by both expert-parallel lowerings: position in each
    expert's buffer is assigned by token order via a slot-major
    cumsum (slot-0 assignments win buffer space first), overflow
    positions are dropped, and combine carries the renormalized
    top-k gate values."""
    n = probs.shape[0]
    gate_vals, expert_idx = jax.lax.top_k(probs, k)    # [n, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # [n,k,e]
    flat = onehot.transpose(1, 0, 2).reshape(k * n, e)  # slot-major
    pos_flat = jnp.cumsum(flat, axis=0) * flat - 1.0    # [k*n, e]
    pos = pos_flat.reshape(k, n, e).transpose(1, 0, 2)  # [n, k, e]
    fits = (pos >= 0) & (pos < cap)

    pos_cap = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)
    pos_onehot = jax.nn.one_hot(pos_cap, cap, dtype=jnp.float32)
    kept = onehot * fits.astype(jnp.float32)            # [n, k, e]
    dispatch = jnp.einsum("nke,nkec->nec", kept, pos_onehot)
    combine = jnp.einsum("nke,nkec->nec",
                         kept * gate_vals[:, :, None], pos_onehot)
    return dispatch, combine


def _expert_ffn(xin, wi, bi, wo, bo, dtype):
    """Batched per-expert FFN on capacity buffers ``xin`` [e, c, d]."""
    h = jnp.einsum("ecd,edf->ecf", xin, wi.astype(dtype))
    h = nn.gelu(h + bi[:, None, :].astype(dtype))
    out = jnp.einsum("ecf,efd->ecd", h, wo.astype(dtype))
    return out + bo[:, None, :].astype(dtype)


def moe_apply(tokens, router_logits, wi, bi, wo, bo, *,
              top_k: int, capacity_factor: float, dtype,
              ep_axis=None, ep_impl: str = "replicated",
              aux_axes=None) -> tuple:
    """Functional MoE MLP core: ``tokens`` [n, d] + float32 router
    logits [n, e] -> ([n, d], aux).

    The routing/dispatch/FFN math of :class:`MoeMlp` as a pure function
    of its parameters, shared by the flax module (which adds the
    router Dense, dropout and sow around it) and the stacked pipelined
    LM (tpunet/models/lm_pp.py), whose params carry a leading layer
    dim and cannot be flax submodules. Callers compute the router
    logits in float32 — gate probabilities are numerically
    load-bearing and tiny relative to the FFN cost; ``aux`` is the
    Shazeer load-balance term computed over exactly the ``n`` tokens
    given (callers decide the batch scope: global under GSPMD,
    per-shard inside shard_map).

    ``ep_axis`` (manual expert parallelism, shard_map callers): when
    given, ``wi/bi/wo/bo`` hold only this device's expert SHARD
    (global expert dim / axis size) and ``tokens`` are replicated over
    the axis. ``ep_impl`` picks the lowering (module docstring):
    ``"alltoall"`` is the GShard capacity-buffer dispatch (token work
    and a2a traffic scale with tokens/ep); ``"replicated"`` routes all
    n tokens on every device and psums the output (exact global
    routing, no token exchange — the small-scale fallback).
    ``aux_axes`` (alltoall only) widens the aux statistics' psum scope
    beyond (ep_axis,) — e.g. the unpipelined shard_map lowering passes
    its data/seq axes so aux stays the global-batch scalar GSPMD
    computes.

    Gradient correctness under manual sharding: with the output
    psummed (or a2a'd + gathered), each device's backward sees only
    its LOCAL experts' cotangent paths. JAX's shard_map AD tracks
    varying-manual-axes and completes those partial cotangents with
    the right collectives itself — measured exact against the
    unsharded reference for every leaf (expert grads bitwise) — so no
    manual cotangent hooks are needed (an explicit identity-fwd/
    psum-bwd hook DOUBLE-counts: the vma machinery has already
    inserted the psum). The 1F1B executor's hand-written backward
    handles both lowerings with one convention (tpunet/parallel/pp.py
    onef1b ep_axis): all_gather/dynamic_slice transposes
    (psum-of-shares / zero-padded partials) and the self-transposing
    all_to_alls all preserve its sums-to-truth-over-ep invariant.
    """
    if ep_impl == "alltoall":
        if ep_axis is None:
            raise ValueError("ep_impl='alltoall' requires ep_axis")
        return _moe_apply_a2a(tokens, router_logits, wi, bi, wo, bo,
                              top_k=top_k,
                              capacity_factor=capacity_factor,
                              dtype=dtype, ep_axis=ep_axis,
                              aux_axes=aux_axes)
    if ep_impl != "replicated":
        raise ValueError(f"unknown ep_impl {ep_impl!r}; "
                         "expected replicated|alltoall")
    n, d = tokens.shape
    e_local = wi.shape[0]
    ep = jax.lax.psum(1, ep_axis) if ep_axis is not None else 1
    e = e_local * ep
    k = min(top_k, e)
    cap = max(k, math.ceil(k * n / e * capacity_factor))

    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    dispatch, combine = _route(probs, k, e, cap)

    # Load-balance aux loss (fraction dispatched x mean router prob).
    frac = jnp.sum(dispatch, axis=(0, 2)) / jnp.maximum(
        jnp.sum(dispatch), 1.0)                         # [e]
    mean_prob = jnp.mean(probs, axis=0)                 # [e]
    aux = e * jnp.sum(frac * mean_prob)

    # Expert FFN: one batched einsum pair over the expert dim; the
    # expert axis of wi/wo is what expert parallelism shards. Under
    # ``ep_axis`` each device runs only its expert shard's slice of
    # the dispatch/combine tensors and one psum assembles the output
    # (tokens are replicated over the axis, so no token all-to-all is
    # needed — GShard's replicated-data degenerate case; prefer the
    # alltoall lowering past toy scales, module docstring).
    if ep_axis is not None:
        lo = jax.lax.axis_index(ep_axis) * e_local
        dispatch = jax.lax.dynamic_slice_in_dim(dispatch, lo, e_local, 1)
        combine = jax.lax.dynamic_slice_in_dim(combine, lo, e_local, 1)
    xin = jnp.einsum("nec,nd->ecd", dispatch.astype(dtype),
                     tokens.astype(dtype))
    out = _expert_ffn(xin, wi, bi, wo, bo, dtype)
    y = jnp.einsum("nec,ecd->nd", combine.astype(dtype), out)
    if ep_axis is not None:
        y = jax.lax.psum(y, ep_axis)
    return y, aux


def _moe_apply_a2a(tokens, router_logits, wi, bi, wo, bo, *,
                   top_k: int, capacity_factor: float, dtype,
                   ep_axis, aux_axes=None) -> tuple:
    """GShard/Switch capacity-buffer ``all_to_all`` dispatch over the
    expert axis (module docstring). ``tokens`` [n, d] replicated over
    ``ep_axis``; returns ([n, d] replicated, aux)."""
    n, d = tokens.shape
    e_local = wi.shape[0]
    ep = jax.lax.psum(1, ep_axis)           # static: the axis size
    e = e_local * ep
    if n % ep:
        raise ValueError(f"alltoall dispatch needs tokens ({n}) "
                         f"divisible by the expert axis ({ep})")
    n_l = n // ep
    idx = jax.lax.axis_index(ep_axis)
    tokens_l = jax.lax.dynamic_slice_in_dim(tokens, idx * n_l, n_l, 0)
    logits_l = jax.lax.dynamic_slice_in_dim(router_logits,
                                            idx * n_l, n_l, 0)
    k = min(top_k, e)
    cap = max(k, math.ceil(k * n_l / e * capacity_factor))

    probs = jax.nn.softmax(logits_l.astype(jnp.float32), axis=-1)
    dispatch, combine = _route(probs, k, e, cap)     # [n_l, e, cap]

    # Aux statistics psum over the expert axis (plus any caller axes),
    # so the scalar keeps the full n-token scope of the replicated
    # path despite per-slice routing — two [e]-sized collectives.
    # ``aux_axes`` WIDENS the scope: the expert axis is always
    # included (omitting it would leave per-slice counts unsummed —
    # aux diverging across ep devices).
    axes = (ep_axis,) + tuple(ax for ax in (aux_axes or ())
                              if ax != ep_axis)
    group = 1
    for ax in axes:
        group *= jax.lax.psum(1, ax)
    tot_counts = jax.lax.psum(jnp.sum(dispatch, axis=(0, 2)), axes)
    tot_probs = jax.lax.psum(jnp.sum(probs, axis=0), axes)
    frac = tot_counts / jnp.maximum(jnp.sum(tot_counts), 1.0)
    mean_prob = tot_probs / (n_l * group)
    aux = e * jnp.sum(frac * mean_prob)

    # Dispatch: per-global-expert capacity buffers from the LOCAL
    # token slice; the tiled all_to_all ships buffer rows
    # [o*e_local:(o+1)*e_local] to expert-owner o. Received dim 0
    # indexes (source shard, local expert).
    xin = jnp.einsum("nec,nd->ecd", dispatch.astype(dtype),
                     tokens_l.astype(dtype))         # [e, cap, d]
    xin = jax.lax.all_to_all(xin, ep_axis, 0, 0, tiled=True)
    xin = (xin.reshape(ep, e_local, cap, d)
           .transpose(1, 0, 2, 3).reshape(e_local, ep * cap, d))
    out = _expert_ffn(xin, wi, bi, wo, bo, dtype)
    # Return trip: regroup by destination shard and invert the a2a;
    # dim 0 is the global expert id again, aligned with combine's.
    out = (out.reshape(e_local, ep, cap, d)
           .transpose(1, 0, 2, 3).reshape(e, cap, d))
    out = jax.lax.all_to_all(out, ep_axis, 0, 0, tiled=True)
    y = jnp.einsum("nec,ecd->nd", combine.astype(dtype), out)
    # Boundary: restore ep-replication for the surrounding compute
    # (all_gather = half a psum's bytes; a token-sharded caller could
    # skip this — module docstring accounting).
    return jax.lax.all_gather(y, ep_axis, axis=0, tiled=True), aux


def resolve_moe_dispatch(dispatch: str, *, ep: int, n_tokens: int,
                         n_experts: int) -> str:
    """Resolve a ``--moe-dispatch`` setting against static shapes.

    ``auto`` prefers ``alltoall`` whenever the shapes divide (tokens
    by the expert-axis size, experts likewise) and falls back to
    ``replicated`` otherwise; an explicit ``alltoall`` raises instead
    of silently degrading. ``ep <= 1`` always means replicated (there
    is no axis to exchange over)."""
    if dispatch not in ("auto", "alltoall", "replicated"):
        raise ValueError(f"unknown moe_dispatch {dispatch!r}; "
                         "expected auto|alltoall|replicated")
    if ep <= 1 or dispatch == "replicated":
        if dispatch == "alltoall":
            raise ValueError("moe_dispatch='alltoall' needs an expert "
                             "axis > 1 (mesh 'model')")
        return "replicated"
    ok = n_tokens % ep == 0 and n_experts % ep == 0
    if dispatch == "alltoall" and not ok:
        raise ValueError(
            f"moe_dispatch='alltoall' needs tokens ({n_tokens}) and "
            f"experts ({n_experts}) divisible by the expert axis ({ep})")
    return "alltoall" if ok else "replicated"


class MoeMlp(nn.Module):
    """Sparse MLP: top-k routed experts, capacity-bounded dense dispatch.

    Input/output [B, T, d] — drop-in replacement for the dense MlpBlock.

    ``mesh`` + ``dispatch`` (the unpipelined models' expert-parallel
    lowering, --moe-dispatch): with a mesh whose 'model' axis > 1 and
    ``dispatch`` resolving to "alltoall", the core runs inside a
    shard_map over (data, seq, model) — tokens sharded over data/seq,
    experts over 'model', the GShard a2a dispatch between them —
    instead of leaving GSPMD to partition the global-routing einsums
    (which psum token buffers over 'data'). Routing scope becomes
    per-(data x seq)-shard with per-slice capacity (the documented
    GShard deviation; aux stays the global-batch scalar via psums over
    all three axes). Falls back to the GSPMD path when the mesh or
    divisibility doesn't allow it (or dispatch="replicated")."""

    num_experts: int
    mlp_dim: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dropout_rate: float = 0.0
    dispatch: str = "auto"             # auto | alltoall | replicated
    mesh: Any = None                   # jax.sharding.Mesh or None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def _resolved_dispatch(self, b: int, t: int) -> str:
        """Resolve dispatch for a [b, t, d] input against the mesh:
        auto needs every involved axis to divide (batch by 'data', seq
        by 'seq', the per-shard token count and the expert count by
        'model'); explicit alltoall raises where auto falls back."""
        mesh = self.mesh
        if mesh is None or not {"data", "seq", "model"} <= set(mesh.shape):
            if self.dispatch == "alltoall":
                raise ValueError("moe_dispatch='alltoall' requires a "
                                 "mesh with data/seq/model axes")
            return "replicated"
        ep = mesh.shape["model"]
        dp = mesh.shape.get("data", 1)
        sp = mesh.shape.get("seq", 1)
        if b % dp or t % sp:
            if self.dispatch == "alltoall":
                raise ValueError(
                    f"moe_dispatch='alltoall' needs batch {b} divisible "
                    f"by the data axis ({dp}) and seq {t} by the seq "
                    f"axis ({sp})")
            return "replicated"
        return resolve_moe_dispatch(
            self.dispatch, ep=ep, n_tokens=(b // dp) * (t // sp),
            n_experts=self.num_experts)

    @nn.compact
    def __call__(self, x, train: bool = False):
        b, t, d = x.shape
        e = self.num_experts
        tokens = x.reshape(b * t, d)

        logits = nn.Dense(e, dtype=jnp.float32,
                          param_dtype=jnp.float32,
                          kernel_init=nn.initializers.normal(stddev=0.02),
                          name="router")(tokens.astype(jnp.float32))
        wi = self.param("wi", nn.initializers.variance_scaling(
            2.0, "fan_in", "truncated_normal"), (e, d, self.mlp_dim),
            self.param_dtype)
        bi = self.param("bi", nn.initializers.zeros, (e, self.mlp_dim),
                        self.param_dtype)
        wo = self.param("wo", nn.initializers.variance_scaling(
            2.0, "fan_in", "truncated_normal"), (e, self.mlp_dim, d),
            self.param_dtype)
        bo = self.param("bo", nn.initializers.zeros, (e, d),
                        self.param_dtype)
        if self._resolved_dispatch(b, t) == "alltoall":
            y, aux = self._a2a_sharded(x, logits.reshape(b, t, e),
                                       wi, bi, wo, bo)
            y = y.reshape(b * t, d)
        else:
            y, aux = moe_apply(
                tokens, logits, wi, bi, wo, bo,
                top_k=self.top_k, capacity_factor=self.capacity_factor,
                dtype=self.dtype)
        self.sow("losses", "moe_aux", aux)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        return y.reshape(b, t, d)

    def _a2a_sharded(self, x, logits, wi, bi, wo, bo):
        """shard_map the a2a core over (data, seq, model): tokens and
        router logits arrive (data x seq)-sharded and ep-replicated,
        experts 'model'-sharded; outputs shard like the input and aux
        replicates (its statistics psum over all three axes)."""
        top_k, cap_f, dtype = self.top_k, self.capacity_factor, self.dtype

        def body(x_l, lg_l, wi_l, bi_l, wo_l, bo_l):
            bl, tl, dd = x_l.shape
            y, aux = moe_apply(
                x_l.reshape(bl * tl, dd), lg_l.reshape(bl * tl, -1),
                wi_l, bi_l, wo_l, bo_l, top_k=top_k,
                capacity_factor=cap_f, dtype=dtype, ep_axis="model",
                ep_impl="alltoall", aux_axes=("data", "seq", "model"))
            return y.reshape(bl, tl, dd), aux

        tok_spec = P("data", "seq", None)
        fn = shard_map(
            body, mesh=self.mesh,
            in_specs=(tok_spec, tok_spec, P("model", None, None),
                      P("model", None), P("model", None, None),
                      P("model", None)),
            out_specs=(tok_spec, P()), check_vma=False)
        return fn(x, logits, wi, bi, wo, bo)


# -- the no-drop share (serving, and training under the Trainer) -------------

def gated_silu(x, gate, up, down, dtype):
    """``down(silu(gate x) * up x)`` on ``x`` [n, d]; products in
    ``dtype`` with float32 accumulation."""
    x = x.astype(dtype)
    h = nn.silu(jnp.dot(x, gate.astype(dtype))) * jnp.dot(x, up.astype(dtype))
    return jnp.dot(h, down.astype(dtype))


def router_logits(u, router):
    """``W_r u`` in float32 (``highest``): ``u`` [..., d] -> [..., E]."""
    return jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def route_sigmoid(u, router, bias, top_k: int, scaling: float = 1.0):
    """Sigmoid routing without auxiliary loss (``noaux_tc``): ``u``
    [n, d] -> (expert ids [n, k] int32, weights [n, k] float32). The
    scores ``p = sigmoid(W_r u)`` and everything after them are
    float32; ``bias`` only chooses (top-k of ``p + bias``; None: of
    ``p``), the weights are ``p`` renormalised over the chosen k."""
    p = jax.nn.sigmoid(router_logits(u, router))
    _, idx = jax.lax.top_k(
        p if bias is None else p + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(p, idx, axis=-1)
    return idx, scaling * chosen / jnp.sum(chosen, -1, keepdims=True)


def route_softmax(u, router, top_k: int, logits=None):
    """Softmax routing: ``u`` [n, d] -> (expert ids [n, k] int32,
    weights [n, k] float32). ``p = softmax(W_r u)`` over ALL experts in
    float32, the ``top_k`` largest chosen, their ``p`` renormalised
    over the chosen k; no bias, no scaling. ``logits`` [n, E]: ``W_r u``
    where the caller has it already (a router that reads another input
    than the experts do); ``u`` and ``router`` are then not read."""
    p = jax.nn.softmax(router_logits(u, router) if logits is None
                       else logits.astype(jnp.float32), -1)
    chosen, idx = jax.lax.top_k(p, top_k)
    return idx, chosen / jnp.sum(chosen, -1, keepdims=True)


def by_row(fn, active, *xs):
    """``fn(*rows)`` on each leading-axis row of ``xs`` in turn
    (``lax.map``: one row's temporaries at a time), skipping the rows
    whose ``active`` is False — those return zeros, and on the TPU the
    skipped branch costs nothing. ``active`` None runs every row. How a
    bucket-wide ``[slots, T]`` prefill keeps one row's (token, expert)
    pairs and attention scores alive at a time, and pays only for the
    slots being prefilled."""
    if active is None:
        return jax.lax.map(lambda row: fn(*row), xs)

    def body(args):
        on, row = args[0], args[1:]
        zeros = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(fn, *row))
        return jax.lax.cond(on, lambda: fn(*row), lambda: zeros)
    return jax.lax.map(body, (active, *xs))


@jax.custom_vjp
def _held_rows(x, valid):
    """``x`` [m, n] as it is (no operation going forward); coming back,
    the rows that ``valid`` [m, 1] marks False get a zero cotangent by
    a select. ``jax.lax.ragged_dot`` and its transposes leave the rows
    outside every group unwritten on the TPU: going forward those rows
    are dropped by the caller's own select, and coming back whatever
    stands there (it can be NaN, and 0 x NaN is NaN) must not reach the
    gradients through the SiLU's product or the gather's scatter-add."""
    return x


def _held_rows_fwd(x, valid):
    return x, valid


def _held_rows_bwd(valid, g):
    return jnp.where(valid, g, 0), None


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


# Rows of sorted (token, expert) pairs one pass of the grouped products
# takes. A layer with more pairs than this walks them in chunks of this
# many and stops after the last pair on a held expert; a layer with no
# more (a decode step) makes one call over all of them.
PAIR_CHUNK = 8192


ACTIVATIONS = {"silu": nn.silu, "relu": nn.relu}


def _gated_experts(rows, valid, sizes, gate, up, down, act: str = "silu"):
    """``down_g(act(gate_g x) * up_g x)`` on ``rows`` [m, d], the first
    ``sizes[0]`` of them through expert 0 of ``gate``/``up`` [h, d, f]
    and ``down`` [h, f, d], the next ``sizes[1]`` through expert 1, and
    so on: three grouped products. ``valid`` [m, 1] marks the rows the
    groups cover; the others come out zero, and give no gradient."""
    rows = _held_rows(rows, valid)
    a = _held_rows(jax.lax.ragged_dot(rows, gate, sizes), valid)
    b = _held_rows(jax.lax.ragged_dot(rows, up, sizes), valid)
    y = jax.lax.ragged_dot(ACTIVATIONS[act](a) * b, down, sizes)
    return jnp.where(valid, y, 0)


def _chunk(j, plan):
    """Chunk ``j`` of the sorted pairs: its first row, its rows' tokens,
    which of its rows lie in a group, and each group's rows inside it."""
    lo = j * PAIR_CHUNK
    sizes = plan["sizes"]
    ends = jnp.cumsum(sizes)
    valid = (lo + jnp.arange(PAIR_CHUNK) < ends[-1])[:, None]
    inside = jnp.clip(jnp.minimum(ends, lo + PAIR_CHUNK)
                      - jnp.maximum(ends - sizes, lo), 0)
    return (lo, jax.lax.dynamic_slice_in_dim(plan["tok"], lo, PAIR_CHUNK),
            valid, inside)


def _live_chunks(sizes):
    """Chunks that start before the last pair of the groups ``sizes``."""
    return (jnp.sum(sizes) + PAIR_CHUNK - 1) // PAIR_CHUNK


def _sum_pairs(y, back, weight):
    """[n, d]: each token's weighted sum over its ``k`` pairs, in
    float32. ``y`` holds a row a SORTED pair, ``back`` [n * k] a pair's
    sorted row, ``weight`` [n, k] is in pair order."""
    n, k = weight.shape
    # gathered pair-major: [k, n, d] splits the rows into whole tiles,
    # [n, k, d] would pad every token's k rows to a tile and copy
    y = jnp.take(y, back.reshape(n, k).T.reshape(-1), axis=0)
    return jnp.sum(y.reshape(k, n, -1).astype(jnp.float32)
                   * weight.T[:, :, None], axis=0).astype(y.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _experts_of_held(u, weight, gate, up, down, plan, act):
    """``_sum_pairs`` of ``_gated_experts`` on the sorted pairs' rows
    ``u[tok]``, for a layer with more pairs than ``PAIR_CHUNK``.
    ``plan`` holds the pairs' places: ``tok`` / ``order`` (token and
    pair of each sorted row, padded to whole chunks), ``back`` and
    ``sizes`` [h].

    The grouped products end at the last group by themselves; what a
    single call pays for every pair, held or not, is all around them:
    the row gather, the selects and element-wise passes and, coming
    back, a scatter over all pairs for the un-sort, a scatter-add for
    the row gather and a float32 ``[n * k, d]`` cotangent of the sum.
    So the sorted rows go through a chunk of ``PAIR_CHUNK`` at a time
    and only the chunks that start before the groups end: a loop as
    long as the held pairs, whatever their number; the chunks past it
    stay zero. Coming back the same chunks are walked again: each
    gathers its tokens' rows of the cotangent and weighs them (which
    is the transpose of sum and un-sort together, for its rows),
    recomputes its own forward, and adds to the cotangents of the
    weights, summed in the parameters' own dtype; the cotangent of
    ``u`` is each token's sum over its pairs' rows, in float32."""
    return _experts_of_held_fwd(u, weight, gate, up, down, plan, act)[0]


def _experts_of_held_fwd(u, weight, gate, up, down, plan, act):
    experts = tuple(w.astype(u.dtype) for w in (gate, up, down))

    def body(j, y):
        lo, tok, valid, inside = _chunk(j, plan)
        out = _gated_experts(jnp.take(u, tok, axis=0), valid, inside,
                             *experts, act)
        return jax.lax.dynamic_update_slice_in_dim(y, out, lo, 0)

    y = jax.lax.fori_loop(
        0, _live_chunks(plan["sizes"]), body,
        jnp.zeros(plan["tok"].shape + u.shape[1:], u.dtype))
    return (_sum_pairs(y, plan["back"], weight),
            (u, weight, gate, up, down, plan))


def _experts_of_held_bwd(act, res, g):
    u, weight, gate, up, down, plan = res
    experts = tuple(w.astype(u.dtype) for w in (gate, up, down))
    g = g.astype(jnp.float32)

    def body(j, acc):
        lo, tok, valid, inside = _chunk(j, plan)
        pair = jax.lax.dynamic_slice_in_dim(plan["order"], lo, PAIR_CHUNK)
        out, pull = jax.vjp(
            lambda rows, *w: _gated_experts(rows, valid, inside, *w, act),
            jnp.take(u, tok, axis=0), *experts)
        g_tok = jnp.take(g, tok, axis=0)
        d_rows, *d_w = pull(
            (g_tok * jnp.take(weight.reshape(-1), pair)[:, None]
             ).astype(out.dtype))
        return (jax.lax.dynamic_update_slice_in_dim(acc[0], d_rows, lo, 0),
                jax.lax.dynamic_update_slice_in_dim(
                    acc[1], jnp.sum(g_tok * out.astype(jnp.float32), axis=-1),
                    lo, 0),
                *(a + d.astype(a.dtype) for a, d in zip(acc[2:], d_w)))

    d_rows, d_weight, *d_w = jax.lax.fori_loop(
        0, _live_chunks(plan["sizes"]), body,
        (jnp.zeros(plan["tok"].shape + u.shape[1:], u.dtype),
         jnp.zeros(plan["tok"].shape, jnp.float32),
         *(jnp.zeros_like(w) for w in (gate, up, down))))
    # a token's rows summed as going forward: a scatter-add here is
    # expanded into a loop over rows whose operations carry no scope
    return (_sum_pairs(d_rows, plan["back"], jnp.ones_like(weight)),
            jnp.take(d_weight, plan["back"]).reshape(weight.shape), *d_w,
            None)


_experts_of_held.defvjp(_experts_of_held_fwd, _experts_of_held_bwd)


def routed_share(u, router, bias, gate, up, down, held, *, top_k: int,
                 scaling: float = 1.0, dtype=jnp.bfloat16,
                 scoring: Optional[str] = None, logits=None,
                 act: str = "silu"):
    """One chip's share of a routed expert layer, without capacity.

    ``u`` [n, d]; ``router`` [d, E] and ``bias`` [E] over ALL ``E``
    experts; ``scoring`` "softmax" (``route_softmax``, which has neither
    bias nor scaling; what a ``bias`` of None means where ``scoring`` is
    not given) or "sigmoid" (``route_sigmoid``, with or without a
    bias); ``logits`` [n, E] takes the router's place where the caller
    computed ``W_r`` times another input (softmax scoring; ``router`` is
    then None); ``gate``/``up`` [len(held), d, f] and ``down``
    [len(held), f, d] for the experts held here, gated by ``act``
    ("silu" or "relu"); ``held`` their ids (static).
    Returns ``(y [n, d] in ``dtype``, stats)`` with ``y = sum over the
    chosen experts that are held of weight * expert(u)``: the pairs are
    sorted by held expert (pairs on absent experts last, computed by
    nobody), the experts run as grouped products over contiguous rows
    (``_gated_experts``), and each token sums its pairs back in pair
    order. A layer with more pairs than ``PAIR_CHUNK`` does all of that
    over the held pairs alone (``_experts_of_held``); one with fewer (a
    decode step) in one call over all of them. Differentiable in
    ``u``, ``router`` and the experts' weights; ``bias`` only chooses,
    so its gradient is zero. ``stats`` (float32
    scalars) counts the routing load: ``held_pair_share`` = pairs on
    held experts / pairs, ``held_load_max_over_mean`` = largest / mean
    load over the held experts, ``held_chunks_run_share`` = chunks of
    sorted pairs that went through the products / chunks (1 where one
    call takes all pairs)."""
    n, d = u.shape
    h = len(held)
    m = n * top_k
    if scoring is None:
        scoring = "softmax" if bias is None else "sigmoid"
    with jax.named_scope("tpunet_moe_router"):
        if scoring == "softmax":
            idx, weight = route_softmax(u, router, top_k, logits)
        elif logits is not None:
            raise ValueError("logits from the caller route by softmax")
        else:
            idx, weight = route_sigmoid(u, router, bias, top_k, scaling)
    with jax.named_scope("tpunet_moe_experts"):
        # a pair's place among the held experts, h where nobody here
        # holds its expert: compares, a table lookup is a gather a pair
        slot = h + jnp.sum(
            jnp.where(idx.reshape(-1, 1) == jnp.asarray(held, jnp.int32),
                      jnp.arange(h, dtype=jnp.int32) - h, 0), axis=1)  # [n*k]
        order = jnp.argsort(slot, stable=True)
        sizes = jnp.sum(slot[:, None] == jnp.arange(h)[None, :], axis=0,
                        dtype=jnp.int32)                         # [h]
        tok = order // top_k
        back = jnp.argsort(order)                                # pair order
        chunks = -(-m // PAIR_CHUNK)
        if chunks == 1:
            on_held = (jnp.take(slot, order) < h)[:, None]
            y = _gated_experts(jnp.take(u.astype(dtype), tok, axis=0),
                               on_held, sizes, gate.astype(dtype),
                               up.astype(dtype), down.astype(dtype), act)
            y = _sum_pairs(y, back, weight)
            run = jnp.float32(1.0)
        else:
            pad = (0, chunks * PAIR_CHUNK - m)
            y = _experts_of_held(
                u.astype(dtype), weight, gate, up, down,
                {"tok": jnp.pad(tok, pad), "order": jnp.pad(order, pad),
                 "back": back, "sizes": sizes}, act)
            run = _live_chunks(sizes).astype(jnp.float32) / chunks
        load = sizes.astype(jnp.float32)
        stats = {"held_pair_share": jnp.sum(load) / m,
                 "held_load_max_over_mean":
                     jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
                 "held_chunks_run_share": run}
    return y, stats


class RoutedShareMlp(nn.Module):
    """``routed_share`` plus the shared experts: the FFN of an expert
    layer as one chip of an expert-parallel deployment computes it, on
    ``x`` [n, d] or, one batch row at a time (``by_row``, skipping rows
    whose ``row_active`` is False), on ``x`` [B, T, d]. ``held`` lists
    the routed experts whose weights live here (all of them by
    default). ``scoring`` "softmax" routes by ``route_softmax`` (no
    ``router_bias`` parameter then, ``scaling`` unused); "sigmoid" has
    that parameter unless ``router_bias`` is False (a configuration
    that chooses by the scores alone). ``n_shared`` shared experts of
    ``width`` are AVERAGED, as one gated product ``n_shared * width``
    wide times ``1 / n_shared`` (the hidden columns of the experts side
    by side: the down projection sums over all of them, which is the
    sum of the experts' outputs), and with ``n_shared`` 0 there is no
    shared part and none of its parameters; ``shared_gate``
    multiplies the shared part by ``sigmoid(x . w)``, one
    number a token (``shared_expert_gate`` [d, 1]). ``act`` gates the
    routed experts ("silu", or "relu": ReGLU). A call that is handed
    ``logits`` (shaped as ``x`` but ``n_experts`` wide: a family whose
    router reads the block's input, ``latent_lm.LatentBlock``) routes
    by them, and the module then has no ``router`` of its own. The
    routing load is ``sow``n into the ``stats`` collection (per batch
    row for a 3-D ``x``): free unless a caller makes it mutable (the
    serve engine's step does not; the LM train step does, and sums it
    into the trainer's gauges)."""

    n_experts: int
    width: int
    top_k: int
    held: Any = None                   # tuple of expert ids; None = all
    scaling: float = 1.0
    scoring: str = "sigmoid"           # sigmoid | softmax
    shared_gate: bool = False
    n_shared: int = 1                  # shared experts, averaged
    router_bias: bool = True           # sigmoid scoring: a bias that chooses
    act: str = "silu"                  # silu | relu, of the routed experts
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, row_active=None, logits=None):
        d, f = x.shape[-1], self.width
        held = (tuple(range(self.n_experts)) if self.held is None
                else tuple(self.held))
        init = nn.initializers.normal(stddev=0.02)

        def w(name, *shape):
            return self.param(name, init, shape, self.param_dtype)

        router = w("router", d, self.n_experts) if logits is None else None
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown scoring {self.scoring!r}")
        bias = (self.param("router_bias", nn.initializers.zeros,
                           (self.n_experts,), self.param_dtype)
                if self.scoring == "sigmoid" and self.router_bias else None)
        experts = (w("experts_gate", len(held), d, f),
                   w("experts_up", len(held), d, f),
                   w("experts_down", len(held), f, d))
        fs = self.n_shared * f
        shared = (w("shared_gate", d, fs), w("shared_up", d, fs),
                  w("shared_down", fs, d)) if fs else None
        open_w = w("shared_expert_gate", d, 1) if self.shared_gate else None

        def ffn(u, logits=None):
            y, stats = routed_share(u, router, bias, *experts, held,
                                    top_k=self.top_k, scaling=self.scaling,
                                    dtype=self.dtype, scoring=self.scoring,
                                    logits=logits, act=self.act)
            if shared is None:
                return y, stats
            with jax.named_scope("tpunet_moe_shared"):
                y_shared = gated_silu(u, *shared, self.dtype)
                if self.n_shared > 1:
                    y_shared = y_shared * (1.0 / self.n_shared)
                if open_w is not None:
                    y_shared = (y_shared * jax.nn.sigmoid(jnp.dot(
                        u.astype(jnp.float32), open_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST))
                    ).astype(self.dtype)
                y = y + y_shared
            return y, stats

        given = () if logits is None else (logits,)
        y, stats = (ffn(x, *given) if x.ndim == 2
                    else by_row(ffn, row_active, x, *given))
        self.sow("stats", "routing", stats)
        return y
