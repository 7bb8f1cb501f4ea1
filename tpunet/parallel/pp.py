"""Pipeline parallelism: GPipe-style SPMD executor over the 'pipe' axis.

The reference has no pipeline parallelism (single-file model, SURVEY.md
2b); tpunet implements it the TPU way: no per-stage processes, no
send/recv threads — ONE jitted SPMD program in which every device runs
the same code, holds one pipeline stage's worth of stacked layer
parameters (leading dim sharded over 'pipe'), and activations hop
stage-to-stage with ``lax.ppermute`` (one ICI neighbor hop per tick).

Schedule: plain GPipe with M microbatches over S stages; the static
scan runs M + S - 1 ticks. At tick t, stage s computes microbatch
m = t - s (masked out when m is out of range — idle bubble ticks
compute on zeros and are discarded). Stage 0 reads microbatches from
the (replicated) input; stage S-1 accumulates results into the output
buffer, which a final psum over 'pipe' replicates (all other stages
contribute zeros).

Differentiable end-to-end: reverse-mode AD through scan + ppermute
yields the standard backward pipeline (the transpose of a shifted
ppermute is the reverse shift).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def gpipe(stage_apply: Callable, stacked_params, x, *,
          mesh: Mesh, n_micro: int, axis_name: str = "pipe",
          data_axis: str = "data", seq_axis: str = None, key=None,
          with_aux: bool = False, extra=None, param_specs=None):
    """Run ``x`` through all pipeline stages.

    stage_apply(local_params, x_micro) applies one stage's layer stack
    to one microbatch; it is called inside shard_map, where every leaf
    of ``local_params`` is the device-local slice (leading dim
    total_layers/S) of ``stacked_params``.

    ``key`` (optional PRNG key) enables stochastic stages (dropout):
    stage_apply is then called as stage_apply(local_params, x_micro,
    key) with a key folded per (tick, stage) — unique randomness per
    microbatch per stage, identical math under AD.

    x: [B, T, C] (batch sharded over ``data_axis``); returns [B, T, C].
    ``seq_axis`` (SP x PP composition): when given, T is sharded over
    that mesh axis too and each stage body sees [mb, T/sp, C] — the
    stage must then handle the sequence sharding itself via axis-name
    collectives over ``seq_axis`` (Ulysses all-to-alls or ring
    ppermute rotations, tpunet/models/lm_pp.py). Executor logic is
    untouched: microbatching, ppermute hops and buffers all act on
    the batch dim only.

    ``with_aux`` (MoE x PP): stage_apply then returns ``(y, aux)``
    with ``aux`` a float32 scalar per (stage, microbatch) — e.g. the
    MoE load-balance term of the stage's layers — and the executor
    returns ``(out, aux_total)`` where ``aux_total`` is the SUM over
    stages and the MEAN over microbatches and data/seq shards
    (matching the equal-weight semantics gradient accumulation uses
    for count-independent loss terms, tpunet/train/steps.py). With
    pipe > 1 each microbatch-shard routes its tokens independently —
    per-shard stats, the standard shard_map MoE scope — whereas
    pipe == 1 routes the full global batch like the unpipelined model.

    ``extra`` (packed x PP): an optional per-example array [B, ...]
    (e.g. packed-sequence segment ids) microbatched alongside ``x``.
    It does NOT hop between stages: it is batch-constant metadata,
    replicated over 'pipe', so every stage just indexes its current
    microbatch's slice. Stage protocol becomes
    ``stage_apply(params, x_micro, extra_micro[, key])``.

    ``param_specs`` (EP x PP): an optional pytree of PartitionSpecs
    overriding the default ``P('pipe')`` per leaf — e.g. MoE expert
    stacks sharded ``P('pipe', 'model')`` so each device holds only
    its expert shard; the stage body then runs its own collectives
    over the extra axis (one psum per MoE layer in lm_pp).
    """
    n_stages = mesh.shape[axis_name]
    if n_stages == 1:
        args = ((x,) if extra is None else (x, extra))
        return (stage_apply(stacked_params, *args) if key is None
                else stage_apply(stacked_params, *args, key))

    _check_stacked(stacked_params, n_stages)

    p_specs = (param_specs if param_specs is not None else
               jax.tree_util.tree_map(lambda _: P(axis_name),
                                      stacked_params))
    x_spec = P(data_axis, seq_axis, None)
    out_specs = (x_spec, P()) if with_aux else x_spec
    has_extra = extra is not None
    e_spec = P(data_axis, seq_axis) if has_extra else None

    kw = dict(n_micro=n_micro, axis_name=axis_name, data_axis=data_axis,
              seq_axis=seq_axis, with_aux=with_aux, has_extra=has_extra)
    if key is None:
        body = functools.partial(_gpipe_body, stage_apply, **kw)
        in_specs = (p_specs, x_spec) + ((e_spec,) if has_extra else ())
        args = (stacked_params, x) + ((extra,) if has_extra else ())
    else:
        body = functools.partial(_gpipe_body_keyed, stage_apply, **kw)
        in_specs = ((p_specs, x_spec)
                    + ((e_spec,) if has_extra else ()) + (P(),))
        args = ((stacked_params, x)
                + ((extra,) if has_extra else ()) + (key,))

    fn = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)
    return fn(*args)


def _gpipe_body_keyed(stage_apply, local_params, xl, *rest, n_micro,
                      axis_name, data_axis="data", seq_axis=None,
                      with_aux=False, has_extra=False):
    """_gpipe_body with a per-(tick, stage) folded PRNG key (always
    the LAST positional arg; an ``extra`` slice precedes it when
    present — see :func:`gpipe`'s stage protocol)."""
    key = rest[-1]
    s = jax.lax.axis_index(axis_name)

    def keyed_apply(params, x, *inner):
        # inner = (extra_micro?, step): fold the tick into the key and
        # forward everything but the step to the user's stage_apply.
        step = inner[-1]
        k = jax.random.fold_in(jax.random.fold_in(key, step), s)
        return stage_apply(params, x, *inner[:-1], k)

    return _gpipe_body(keyed_apply, local_params, xl, *rest[:-1],
                       n_micro=n_micro, axis_name=axis_name,
                       data_axis=data_axis, seq_axis=seq_axis,
                       with_aux=with_aux, has_extra=has_extra,
                       pass_step=True)


def _shard_norm(data_axis, seq_axis):
    """(grad/aux normalization axes, shard count over them)."""
    axes = (data_axis,) if seq_axis is None else (data_axis, seq_axis)
    n = 1
    for ax in axes:
        n = n * jax.lax.psum(1, ax)
    return axes, n


def _gpipe_body(stage_apply, local_params, xl, *rest, n_micro, axis_name,
                data_axis="data", seq_axis=None, with_aux=False,
                has_extra=False, pass_step=False):
    extra = rest[0] if has_extra else None
    s = jax.lax.axis_index(axis_name)
    n_stages = jax.lax.psum(1, axis_name)
    bl, t, c = xl.shape
    if bl % n_micro:
        raise ValueError(f"local batch {bl} not divisible by "
                         f"{n_micro} microbatches")
    mb = bl // n_micro
    xm = xl.reshape(n_micro, mb, t, c)
    em = (extra.reshape((n_micro, mb) + extra.shape[1:])
          if has_extra else None)
    perm = [(i, i + 1) for i in range(n_stages - 1)]  # no wraparound

    def tick(carry, step):
        act_in, outbuf, auxsum = carry
        m = step - s
        valid = (m >= 0) & (m < n_micro)
        mc = jnp.clip(m, 0, n_micro - 1)
        inp = jnp.where(s == 0,
                        jax.lax.dynamic_index_in_dim(xm, mc, 0,
                                                     keepdims=False),
                        act_in)
        args = (local_params, inp)
        if has_extra:
            args += (jax.lax.dynamic_index_in_dim(em, mc, 0,
                                                  keepdims=False),)
        y = (stage_apply(*args, step) if pass_step
             else stage_apply(*args))
        if with_aux:
            y, a = y
            auxsum = auxsum + jnp.where(valid,
                                        a.astype(jnp.float32), 0.0)
        y = jnp.where(valid, y, jnp.zeros_like(y))
        is_last = s == n_stages - 1
        outbuf = jax.lax.dynamic_update_index_in_dim(
            outbuf,
            jnp.where(valid & is_last, y,
                      jax.lax.dynamic_index_in_dim(outbuf, mc, 0,
                                                   keepdims=False)),
            mc, 0)
        act_next = jax.lax.ppermute(y, axis_name, perm)
        return (act_next, outbuf, auxsum), None

    act0 = jnp.zeros((mb, t, c), xl.dtype)
    outbuf = jnp.zeros_like(xm)
    (_, outbuf, auxsum), _ = jax.lax.scan(
        tick, (act0, outbuf, jnp.zeros((), jnp.float32)),
        jnp.arange(n_micro + n_stages - 1))
    # Only the last stage wrote real activations; psum replicates them.
    outbuf = jax.lax.psum(
        jnp.where(s == n_stages - 1, outbuf, jnp.zeros_like(outbuf)),
        axis_name)
    out = outbuf.reshape(bl, t, c)
    if not with_aux:
        return out
    # Sum over stages ('pipe' psum), mean over microbatches and
    # data/seq shards (each routed its tokens independently).
    norm_axes, n_shards = _shard_norm(data_axis, seq_axis)
    aux = jax.lax.psum(jax.lax.psum(auxsum, axis_name), norm_axes)
    return out, aux / (n_micro * n_shards)


# ---------------------------------------------------------------------------
# 1F1B: manual-VJP executor with an interleaved fwd/bwd backward schedule.
# ---------------------------------------------------------------------------

def _check_stacked(stacked_params, n_stages: int) -> None:
    for path, leaf in jax.tree_util.tree_flatten_with_path(stacked_params)[0]:
        if leaf.shape[0] % n_stages:
            raise ValueError(
                f"stacked param {jax.tree_util.keystr(path)} has leading "
                f"(layer) dim {leaf.shape[0]} not divisible by "
                f"{n_stages} pipeline stages")


def onef1b_schedule(n_stages: int, n_micro: int) -> list:
    """The 1F1B tick table, host-side, for tests and inspection:
    ``table[t][s]`` is ``("F", m)``, ``("B", m)``, or ``None`` (idle).

    Closed form (the device-side scan uses the same integer math):
    forward of microbatch m runs at stage s on tick ``s + 2m``;
    backward on tick ``2S - 1 - s + 2m``. F-ticks at stage s all share
    parity ``s % 2`` and B-ticks parity ``(s+1) % 2``, so the two
    streams interleave without collision; the last stage runs
    ``F(m), B(m), F(m+1), B(m+1), ...`` — one-forward-one-backward.
    Total ticks ``2(M + S - 1)``, the same bubble fraction as GPipe
    (non-interleaved 1F1B improves memory, not bubble).
    """
    S, M = n_stages, n_micro
    total = 2 * (M + S - 1)
    table = [[None] * S for _ in range(total)]
    for s in range(S):
        for m in range(M):
            table[s + 2 * m][s] = ("F", m)
            table[2 * S - 1 - s + 2 * m][s] = ("B", m)
    return table


def onef1b(stage_apply: Callable, stacked_params, x, *,
           mesh: Mesh, n_micro: int, axis_name: str = "pipe",
           data_axis: str = "data", seq_axis: str = None, key=None,
           with_aux: bool = False, extra=None, param_specs=None,
           uniform_bwd: bool = None, ep_axis: str = None):
    """GPipe-compatible pipeline executor with a manual VJP whose
    backward runs the 1F1B schedule.

    Same contract as :func:`gpipe` (identical primal math, identical
    dropout key folding, so the two are grad-for-grad interchangeable —
    the parity tests assert it). The difference is memory: reverse-mode
    AD through the GPipe scan stacks EVERY per-tick intermediate (each
    stage's per-layer internals x ``M + S - 1`` ticks) as scan
    residuals, O(M) microbatches live at once. Here the forward is
    wrapped in ``jax.custom_vjp`` and saves only ``(params, x, key)``;
    the hand-written backward replays forwards and runs backwards in
    ONE combined scan in 1F1B order — forward of microbatch m at stage
    s on tick ``s + 2m``, backward on tick ``2S - 1 - s + 2m``
    (:func:`onef1b_schedule`) — holding a ring buffer of at most
    ``min(S, M)`` stage-input activations per device, the 1F1B
    in-flight bound. Per-tick vjp internals are transient (freed every
    tick), never stacked.

    Cost: one extra stage forward per microbatch (the replay), the
    standard price of rematerialized pipeline backward — the loss and
    its cotangent live OUTSIDE the executor (final LN/logits/CE run on
    the full output), so true no-remat 1F1B (loss inside the last
    stage) is not expressible at this interface. Collectives are
    hoisted out of the fwd/bwd branch (``lax.cond`` branches must not
    diverge on collectives): every tick runs exactly one forward-shift
    and one reverse-shift ``ppermute``, with zeros masked in for
    whichever stream a stage isn't driving. Under SP x PP
    (``seq_axis`` given) the stage BODY itself contains seq
    collectives, so the F/B ``lax.cond`` disappears entirely: each
    tick runs one ``jax.vjp`` on a role-selected input, keeping the
    collective sequence identical on every device every tick
    (branch-divergent in-stage collectives measurably corrupt
    gradients — see the body comment). Double differentiation is not
    supported (custom_vjp). ``with_aux`` matches :func:`gpipe`'s
    contract: stage_apply returns (y, aux); the executor returns
    (out, aux_total) and the manual backward pulls the aux cotangent
    through the same per-tick vjp as the activation cotangent.
    ``extra`` matches gpipe's contract too (per-microbatch metadata,
    e.g. packed segment ids) and is treated as NON-differentiable —
    its cotangent is zero. ``param_specs`` matches gpipe's (per-leaf
    spec override, e.g. expert stacks over ('pipe', 'model')).
    ``uniform_bwd`` forces the collective-uniform one-vjp-per-tick
    backward; it defaults to on exactly when ``seq_axis`` is given,
    and callers whose stage bodies contain OTHER in-stage collectives
    (EP's 'model' psums) must pass True themselves — in-stage
    collectives inside the diverging F/B lax.cond corrupt gradients
    (see the body comment). ``ep_axis`` (EP x PP): the mesh axis the
    stage bodies' expert psums run over; the manual backward then
    psums each tick's input-cotangent over it before shipping
    upstream — the per-tick vjp hands back only the LOCAL expert
    shard's cotangent paths (partial over the axis), and unlike
    gpipe-AD (whose shard_map transpose completes them via
    varying-manual-axes tracking) this hand-written boundary logic
    must restore replication itself, per tick, so the NEXT stage's
    expert-weight grads see a complete cotangent.
    """
    n_stages = mesh.shape[axis_name]
    has_extra = extra is not None
    # In-stage collectives categorically require the uniform backward;
    # resolve here so no caller can pass ep_axis without it.
    uniform_bwd = (bool(uniform_bwd) or seq_axis is not None
                   or ep_axis is not None)
    if n_stages == 1:
        args = ((x,) if extra is None else (x, extra))
        return (stage_apply(stacked_params, *args) if key is None
                else stage_apply(stacked_params, *args, key))
    _check_stacked(stacked_params, n_stages)

    p_specs = (param_specs if param_specs is not None else
               jax.tree_util.tree_map(lambda _: P(axis_name),
                                      stacked_params))
    x_spec = P(data_axis, seq_axis, None)
    keyed = key is not None
    kk = key if keyed else jnp.zeros((2,), jnp.uint32)
    # Fixed custom_vjp arity: a zero-size placeholder when no extra.
    ex = extra if has_extra else jnp.zeros((0,), jnp.int32)
    e_spec = P(data_axis, seq_axis) if has_extra else P()

    fwd_out_specs = (x_spec, P()) if with_aux else x_spec
    kw = dict(n_micro=n_micro, axis_name=axis_name, data_axis=data_axis,
              seq_axis=seq_axis, with_aux=with_aux, has_extra=has_extra)

    def fwd_program(params, xx, exx, k):
        e_args = (exx,) if has_extra else ()
        e_in = (e_spec,) if has_extra else ()
        if keyed:
            body = functools.partial(_gpipe_body_keyed, stage_apply,
                                     **kw)
            return shard_map(
                body, mesh=mesh,
                in_specs=(p_specs, x_spec) + e_in + (P(),),
                out_specs=fwd_out_specs, check_vma=False)(
                    params, xx, *e_args, k)
        body = functools.partial(_gpipe_body, stage_apply, **kw)
        return shard_map(
            body, mesh=mesh, in_specs=(p_specs, x_spec) + e_in,
            out_specs=fwd_out_specs, check_vma=False)(
                params, xx, *e_args)

    def bwd_program(params, xx, exx, k, dy, daux):
        body = functools.partial(_onef1b_bwd_body, stage_apply,
                                 n_stages=n_stages, keyed=keyed,
                                 uniform_bwd=uniform_bwd,
                                 ep_axis=ep_axis,
                                 param_specs=p_specs, **kw)
        return shard_map(
            body, mesh=mesh,
            in_specs=(p_specs, x_spec, e_spec, P(), x_spec, P()),
            out_specs=(p_specs, x_spec), check_vma=False)(
                params, xx, exx, k, dy, daux)

    @jax.custom_vjp
    def run(params, xx, exx, k):
        return fwd_program(params, xx, exx, k)

    def run_fwd(params, xx, exx, k):
        return fwd_program(params, xx, exx, k), (params, xx, exx, k)

    def run_bwd(res, ct):
        params, xx, exx, k = res
        if with_aux:
            dy, daux = ct
        else:
            dy, daux = ct, jnp.zeros((), jnp.float32)
        dparams, dx = bwd_program(params, xx, exx, k, dy,
                                  daux.astype(jnp.float32))
        # PRNG keys and (integer) extras have float0 cotangents.
        dk = np.zeros(np.shape(k), dtype=jax.dtypes.float0)
        dex = (np.zeros(np.shape(exx), dtype=jax.dtypes.float0)
               if jnp.issubdtype(exx.dtype, jnp.integer)
               else jnp.zeros_like(exx))
        return dparams, dx, dex, dk

    run.defvjp(run_fwd, run_bwd)
    return run(stacked_params, x, ex, kk)


def _onef1b_bwd_body(stage_apply, local_params, xl, exl, key, dyl,
                     dauxl=None, *, n_micro, axis_name, data_axis,
                     seq_axis, n_stages, keyed, with_aux=False,
                     has_extra=False, uniform_bwd=False, ep_axis=None,
                     param_specs=None):
    """Device-local 1F1B backward: one scan over 2(M+S-1) ticks.

    Carry: (act_in, cot_in, resid ring, dparam accumulator fp32,
    dx buffer). Each tick a stage is an F-tick (replay one stage
    forward, save its input to the ring, ship the activation down),
    a B-tick (vjp the saved input against the incoming cotangent,
    accumulate dparams, ship the input-cotangent up), or idle
    (masked). F/B tick parities differ per stage (onef1b_schedule), so
    one ``lax.cond`` picks the work; both ppermutes run unconditionally
    with masked zeros. With ``with_aux`` each B-tick's vjp also pulls
    the executor-level aux cotangent ``daux / (M * n_shards)`` — the
    transpose of the forward's sum-over-stages / mean-over-
    microbatch-shards aux reduction (:func:`_gpipe_body`).
    """
    s = jax.lax.axis_index(axis_name)
    S, M = n_stages, n_micro
    bl, t, c = xl.shape
    if bl % M:
        raise ValueError(f"local batch {bl} not divisible by "
                         f"{M} microbatches")
    mb = bl // M
    xm = xl.reshape(M, mb, t, c)
    dym = dyl.reshape(M, mb, t, c)
    exm = (exl.reshape((M, mb) + exl.shape[1:]) if has_extra else None)
    epn = jax.lax.psum(1, ep_axis) if ep_axis is not None else 1
    if ep_axis is not None:
        # In-stage EP psums put this backward in JAX's UNREDUCED
        # cotangent convention (psum's transpose inside jax.vjp is
        # psum — it COMPLETES a per-device partial cotangent; feeding
        # it a complete/replicated one doubles everything downstream).
        # Speak the convention: divide the entering cotangent by the
        # axis size so every cotangent in the scan is an unreduced
        # 1/ep share, then complete each result at the end — psum over
        # ep for every leaf NOT sharded over it, and for dx (both
        # replicated over ep); model-sharded leaves complete without
        # the ep psum. Permutation collectives (SP's ppermute /
        # all_to_all) are convention-agnostic, so SP x EP composes.
        dym = dym / epn
    if with_aux:
        _, n_shards = _shard_norm(data_axis, seq_axis)
        aux_ct = dauxl.astype(jnp.float32) / (M * n_shards * epn)
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    rev_perm = [(i + 1, i) for i in range(S - 1)]
    n_buf = min(S, M)   # 1F1B in-flight bound (residency at stage s
    #                     is S - s microbatches; see overwrite proof
    #                     in tests/test_pp_1f1b.py)

    def apply_f(params, inp, m):
        args = (params, inp)
        if has_extra:
            args += (jax.lax.dynamic_index_in_dim(exm, m, 0,
                                                  keepdims=False),)
        if keyed:
            # EXACTLY _gpipe_body_keyed's folding — fwd tick = m + s —
            # so replayed dropout masks match the primal bit-for-bit.
            k = jax.random.fold_in(jax.random.fold_in(key, m + s), s)
            return stage_apply(*args, k)
        return stage_apply(*args)

    def tick(carry, t_):
        act_in, cot_in, resid, dpsum, dxbuf = carry
        df = t_ - s
        m_f = df // 2
        f_valid = (df >= 0) & (df % 2 == 0) & (m_f < M)
        db = t_ - (2 * S - 1 - s)
        m_b = db // 2
        b_valid = (db >= 0) & (db % 2 == 0) & (m_b < M)
        m_fc = jnp.clip(m_f, 0, M - 1)
        m_bc = jnp.clip(m_b, 0, M - 1)

        f_inp = jnp.where(
            s == 0,
            jax.lax.dynamic_index_in_dim(xm, m_fc, 0, keepdims=False),
            act_in)
        g_in = jnp.where(
            s == S - 1,
            jax.lax.dynamic_index_in_dim(dym, m_bc, 0, keepdims=False),
            cot_in)
        b_slot = m_bc % n_buf
        b_inp = jax.lax.dynamic_index_in_dim(resid, b_slot, 0,
                                             keepdims=False)

        if uniform_bwd:
            # SP x PP / EP x PP: the stage body contains collectives
            # (seq-axis ring ppermutes / Ulysses all-to-alls, or EP's
            # 'model' psums).
            # Those must NOT sit inside diverging lax.cond branches:
            # the F/B predicate varies over 'pipe', so stages would
            # execute DIFFERENT collective ops whose participant sets
            # span all stages — undefined pairing (measured: wrong
            # gradients with a ring stage; a deadlock risk on real
            # ICI). Instead run ONE vjp per tick on a role-selected
            # input — every device then executes an identical
            # collective sequence every tick; the unused half of each
            # (primal, pulled-grad) pair is masked below. Costs a
            # wasted pull on F-ticks, the price of collective
            # uniformity.
            m_sel = jnp.where(f_valid, m_fc, m_bc)
            inp = jnp.where(f_valid, f_inp, b_inp)
            y, pull = jax.vjp(lambda p, xi: apply_f(p, xi, m_sel),
                              local_params, inp)
            if with_aux:
                y, _ = y
                dp, dx = pull((g_in, aux_ct))
            else:
                dp, dx = pull(g_in)
        else:
            # No seq sharding -> stage bodies are collective-free and
            # the cheap schedule runs only the branch each tick needs.
            zero_dp = jax.tree_util.tree_map(jnp.zeros_like,
                                             local_params)

            def do_f(_):
                yf = apply_f(local_params, f_inp, m_fc)
                if with_aux:
                    yf = yf[0]
                return yf, jnp.zeros_like(f_inp), zero_dp

            def do_b(_):
                # Recompute this stage's forward and pull the cotangent
                # back through it — idle ticks also land here on zeros,
                # masked out below (dp/dx are b_valid-masked, so the
                # unmasked aux cotangent never leaks from idle ticks).
                _, pull = jax.vjp(lambda p, xi: apply_f(p, xi, m_bc),
                                  local_params, b_inp)
                dpb, dxb = pull((g_in, aux_ct) if with_aux else g_in)
                return jnp.zeros_like(f_inp), dxb, dpb

            y, dx, dp = jax.lax.cond(f_valid, do_f, do_b, None)
        y = jnp.where(f_valid, y, jnp.zeros_like(y))
        dx = jnp.where(b_valid, dx, jnp.zeros_like(dx))
        dpsum = jax.tree_util.tree_map(
            lambda acc, g: acc + jnp.where(b_valid, g,
                                           jnp.zeros_like(g)
                                           ).astype(acc.dtype),
            dpsum, dp)

        f_slot = m_fc % n_buf
        old = jax.lax.dynamic_index_in_dim(resid, f_slot, 0,
                                           keepdims=False)
        resid = jax.lax.dynamic_update_index_in_dim(
            resid, jnp.where(f_valid, f_inp, old), f_slot, 0)
        oldx = jax.lax.dynamic_index_in_dim(dxbuf, m_bc, 0,
                                            keepdims=False)
        dxbuf = jax.lax.dynamic_update_index_in_dim(
            dxbuf, jnp.where(b_valid & (s == 0), dx, oldx), m_bc, 0)

        act_next = jax.lax.ppermute(y, axis_name, fwd_perm)
        cot_next = jax.lax.ppermute(dx, axis_name, rev_perm)
        return (act_next, cot_next, resid, dpsum, dxbuf), None

    carry0 = (
        jnp.zeros((mb, t, c), xl.dtype),
        jnp.zeros((mb, t, c), dyl.dtype),
        jnp.zeros((n_buf, mb, t, c), xl.dtype),
        jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), local_params),
        jnp.zeros_like(dym),
    )
    (_, _, _, dpsum, dxbuf), _ = jax.lax.scan(
        tick, carry0, jnp.arange(2 * (M + S - 1)))
    # Stage 0 holds the real input-cotangents; replicate like the
    # forward's output buffer. dparams stay per-stage (out spec 'pipe')
    # but each data shard only saw ITS microbatches — and under SP x PP
    # each seq shard only its token slice — so sum the partial param
    # grads over 'data' AND (when sharded) the seq axis: exactly the
    # psums GPipe-AD's transpose inserts for every mesh axis the
    # params' in_spec replicates over but the cotangent varies over.
    # (dx needs no seq psum: its out_spec CARRIES the seq sharding.)
    # Under EP the unreduced-convention shares (see the dym / epn note)
    # complete here too: psum over ep for dx and for every leaf NOT
    # sharded over the ep axis; ep-sharded leaves hold per-shard grads
    # and must not mix.
    dx_axes = ((axis_name,) if ep_axis is None
               else (axis_name, ep_axis))
    dx = jax.lax.psum(
        jnp.where(s == 0, dxbuf, jnp.zeros_like(dxbuf)), dx_axes)
    grad_axes = ((data_axis,) if seq_axis is None
                 else (data_axis, seq_axis))

    def leaf_axes(spec):
        if ep_axis is None or (spec is not None
                               and ep_axis in tuple(spec)):
            return grad_axes
        return grad_axes + (ep_axis,)

    # PartitionSpec is a tuple subclass (a pytree NODE), so flatten the
    # spec tree with is_leaf instead of a joint tree_map.
    flat_p, treedef = jax.tree_util.tree_flatten(local_params)
    flat_acc = jax.tree_util.tree_leaves(dpsum)
    if param_specs is None:
        flat_specs = [None] * len(flat_p)
    else:
        flat_specs = jax.tree_util.tree_leaves(
            param_specs, is_leaf=lambda v: isinstance(v, P))
    dparams = treedef.unflatten([
        jax.lax.psum(acc, leaf_axes(sp_)).astype(p.dtype)
        for acc, p, sp_ in zip(flat_acc, flat_p, flat_specs)])
    return dparams, dx.reshape(bl, t, c)


# ---------------------------------------------------------------------------
# Interleaved 1F1B: virtual pipeline stages (Megatron-style), manual VJP.
# ---------------------------------------------------------------------------
#
# Each device holds v model CHUNKS instead of one contiguous stage:
# global stage g = j * S + d lives on device d as its local chunk j
# (stacked params stay P('pipe')-sharded; the contiguous local slice is
# REINTERPRETED as [v, layers/chunk] — the chunk-permuted storage order,
# interleaved_layer_order). Activations hop a FULL ring (wraparound
# (S-1) -> 0 carries chunk j's output into chunk j+1).
#
# Why: the non-interleaved schedules' bubble fraction is
# (S-1)/(M+S-1) regardless of schedule (gpipe == 1f1b there). Counting
# in CHUNK-ticks (1 chunk = 1/v of a device's layers — the honest unit
# when comparing against v chunks/device), a non-interleaved step costs
# 2v(M + S - 1) chunk-ticks; the interleaved forward is a dense
# closed-form circular pipeline finishing in vM + S - 1, and the
# combined replay/backward table measures ~2vM + O(vS) — bubble
# fraction ~(S-1)/(vM), the ~v-fold reduction (Narayanan et al. 2021).
# Measured tables (tests/test_pp_interleaved.py): the schedule-table
# bubble and the XLA memory analysis quantify bubble x memory against
# gpipe/1f1b.
#
# The backward is a hand-written custom_vjp like onef1b: one combined
# scan replays chunk forwards and runs chunk backwards in Megatron's
# warmup / one-F-one-B / cooldown order. Unlike onef1b's closed-form
# tick table, hop slack here is NOT uniformly 1 (steady-state F and B
# streams cross devices with phase offsets), so the schedule is built
# HOST-SIDE by a greedy list scheduler (interleaved_bwd_schedule) that
# also performs interval allocation for three bounded ring buffers —
# saved chunk inputs (residuals) and in-flight F/B arrivals — and the
# device-side scan just indexes the resulting [T, S] tables. Residency
# stays O(S*v + slack) chunk inputs per device (measured in the memory
# test), not gpipe-AD's O(M) stacked per-tick internals.
#
# Scope (fail-loud): no with_aux/MoE, no seq sharding, no extra/packed
# metadata — compose those with gpipe/1f1b; interleaved's contribution
# is the bubble. Requires n_micro % S == 0 (Megatron's constraint: the
# F-stream cycles chunks per S-microbatch group) and layers % (S*v) == 0.


def interleaved_layer_order(L: int, S: int, v: int) -> list:
    """``order[storage_idx] = semantic layer`` for the chunk-permuted
    stacking: device d's contiguous P('pipe') slice holds chunks
    d, S+d, 2S+d, ... (global stage g = j*S + d), so storage position
    d*(v*lc) + j*lc + o carries semantic layer (j*S + d)*lc + o."""
    if L % (S * v):
        raise ValueError(f"{L} layers not divisible by {S} stages x "
                         f"{v} virtual chunks")
    lc = L // (S * v)
    order = []
    for d in range(S):
        for j in range(v):
            g = j * S + d
            order.extend(range(g * lc, (g + 1) * lc))
    return order


def interleaved_fwd_schedule(S: int, M: int, v: int) -> list:
    """The closed-form dense forward table: ``table[t][d]`` is
    ``("F", m, j)`` or None. Device d runs its k-th chunk-op at tick
    d + k with k enumerating (microbatch-group, chunk, in-group
    microbatch): k = (m // S)*S*v + j*S + (m % S). Every hop
    (d -> d+1, and the (S-1) -> 0 wrap into the next chunk) lands with
    slack exactly 1, so the forward needs no arrival buffering and
    finishes in vM + S - 1 ticks."""
    if M % S:
        raise ValueError(f"interleaved needs microbatches ({M}) "
                         f"divisible by stages ({S})")
    n = v * M
    table = [[None] * S for _ in range(n + S - 1)]
    for d in range(S):
        for k in range(n):
            r, kk = divmod(k, S * v)
            j, i = divmod(kk, S)
            table[d + k][d] = ("F", r * S + i, j)
    return table


def _interleaved_oplist(S: int, M: int, v: int, d: int) -> list:
    """Device d's backward-scan op order (Megatron interleaved 1F1B):
    W(d) warmup chunk-forwards, then one-F-one-B, then B cooldown.
    F-stream order matches the forward schedule; the B stream is the
    same enumeration with chunks reversed (deepest chunk first)."""
    def fop(k):
        r, kk = divmod(k, S * v)
        j, i = divmod(kk, S)
        return ("F", r * S + i, j)

    def bop(b):
        r, bb = divmod(b, S * v)
        j, i = divmod(bb, S)
        return ("B", r * S + i, v - 1 - j)

    n = v * M
    W = min(n, 2 * (S - 1 - d) + (v - 1) * S)
    ops = [fop(k) for k in range(W)]
    f, b = W, 0
    while f < n:
        ops.append(fop(f)); f += 1
        ops.append(bop(b)); b += 1
    while b < n:
        ops.append(bop(b)); b += 1
    return ops


def _alloc_intervals(intervals):
    """Greedy interval-graph slot allocation: ``intervals`` is a list of
    (start, end, key) with inclusive occupancy [start, end]; returns
    ({key: slot}, n_slots)."""
    slots = {}
    free = []
    busy = []   # (end, slot) active
    n = 0
    for start, end, key in sorted(intervals):
        # release slots whose interval ended before this start
        still = []
        for e, sl in busy:
            if e < start:
                free.append(sl)
            else:
                still.append((e, sl))
        busy = still
        if free:
            sl = free.pop()
        else:
            sl = n
            n += 1
        busy.append((end, sl))
        slots[key] = sl
    return slots, max(n, 1)


def interleaved_bwd_schedule(S: int, M: int, v: int) -> dict:
    """Host-side greedy list scheduling of the combined replay/backward
    scan, plus buffer allocation. Returns numpy tables [T, S]:

    - kind (0 idle / 1 F / 2 B), m, j;
    - rs_save / rs_read: residual-ring slot an F-tick saves its chunk
      input into / a B-tick reads from (-1 none);
    - af_save / ab_save: arrival-ring slot to store THIS tick's
      ppermute delivery into (-1 discard) — hop slack can exceed 1, so
      deliveries wait in per-device rings until their consumer tick;
    - af_read / ab_read: arrival slot an F/B-tick reads its input
      cotangent/activation from (-1 = boundary: xm / dy);

    and scalars n_resid / n_arr_f / n_arr_b / n_ticks. Dependencies
    (producer tick + 1 <= consumer tick, F-before-its-B) are enforced
    during construction; the property tests re-verify independently."""
    import numpy as np
    if M % S:
        raise ValueError(f"interleaved needs microbatches ({M}) "
                         f"divisible by stages ({S})")
    n = v * M
    ops = [_interleaved_oplist(S, M, v, d) for d in range(S)]
    for d in range(S):   # F(m, j) precedes B(m, j) on every device
        pos = {op: i for i, op in enumerate(ops[d])}
        for (kind, m, j), i in pos.items():
            if kind == "B":
                assert pos[("F", m, j)] < i, (d, m, j)
    ptr = [0] * S
    done_f, done_b = {}, {}
    rows = []
    t = 0
    while any(p < len(o) for p, o in zip(ptr, ops)):
        row = [None] * S
        for d in range(S):
            if ptr[d] >= len(ops[d]):
                continue
            kind, m, j = ops[d][ptr[d]]
            if kind == "F":
                if d > 0:
                    ready = done_f.get((d - 1, m, j))
                elif j > 0:
                    ready = done_f.get((S - 1, m, j - 1))
                else:
                    ready = -1                      # xm always there
            else:
                own = done_f.get((d, m, j))
                if d < S - 1:
                    up = done_b.get((d + 1, m, j))
                elif j < v - 1:
                    up = done_b.get((0, m, j + 1))
                else:
                    up = -1                         # dy always there
                ready = (None if own is None or up is None
                         else max(own, up))
            if ready is not None and t >= ready + 1:
                row[d] = (kind, m, j)
        if all(r is None for r in row):
            raise RuntimeError(
                f"interleaved schedule deadlock at tick {t} "
                f"(S={S}, M={M}, v={v})")
        for d in range(S):
            if row[d] is not None:
                kind, m, j = row[d]
                (done_f if kind == "F" else done_b)[(d, m, j)] = t
                ptr[d] += 1
        rows.append(row)
        t += 1
    T = len(rows)

    kind = np.zeros((T, S), np.int32)
    mi = np.zeros((T, S), np.int32)
    ji = np.zeros((T, S), np.int32)
    rs_save = -np.ones((T, S), np.int32)
    rs_read = -np.ones((T, S), np.int32)
    af_save = -np.ones((T, S), np.int32)
    af_read = -np.ones((T, S), np.int32)
    ab_save = -np.ones((T, S), np.int32)
    ab_read = -np.ones((T, S), np.int32)
    for t, row in enumerate(rows):
        for d, op in enumerate(row):
            if op is None:
                continue
            kind[t, d] = 1 if op[0] == "F" else 2
            mi[t, d] = op[1]
            ji[t, d] = op[2]

    n_res = n_af = n_ab = 1
    for d in range(S):
        # residuals: input saved at F(m, j), read at B(m, j)
        iv = [(done_f[(d, m, j)], done_b[(d, m, j)], (m, j))
              for m in range(M) for j in range(v)]
        sl, nr = _alloc_intervals(iv)
        n_res = max(n_res, nr)
        for (m, j), s_ in sl.items():
            rs_save[done_f[(d, m, j)], d] = s_
            rs_read[done_b[(d, m, j)], d] = s_
        # F arrivals: produced upstream at tp, stored here at tp+1,
        # read at this device's F tick
        iv = []
        for m in range(M):
            for j in range(v):
                if d > 0:
                    tp = done_f[(d - 1, m, j)]
                elif j > 0:
                    tp = done_f[(S - 1, m, j - 1)]
                else:
                    continue                        # from xm
                iv.append((tp + 1, done_f[(d, m, j)], (m, j)))
        if iv:
            sl, na = _alloc_intervals(iv)
            n_af = max(n_af, na)
            for (m, j), s_ in sl.items():
                iv_start = [x for x in iv if x[2] == (m, j)][0][0]
                af_save[iv_start, d] = s_
                af_read[done_f[(d, m, j)], d] = s_
        # B arrivals: cotangent produced downstream at tp
        iv = []
        for m in range(M):
            for j in range(v):
                if d < S - 1:
                    tp = done_b[(d + 1, m, j)]
                elif j < v - 1:
                    tp = done_b[(0, m, j + 1)]
                else:
                    continue                        # from dy
                iv.append((tp + 1, done_b[(d, m, j)], (m, j)))
        if iv:
            sl, nb = _alloc_intervals(iv)
            n_ab = max(n_ab, nb)
            for (m, j), s_ in sl.items():
                iv_start = [x for x in iv if x[2] == (m, j)][0][0]
                ab_save[iv_start, d] = s_
                ab_read[done_b[(d, m, j)], d] = s_
    return dict(kind=kind, m=mi, j=ji, rs_save=rs_save, rs_read=rs_read,
                af_save=af_save, af_read=af_read, ab_save=ab_save,
                ab_read=ab_read, n_resid=n_res, n_arr_f=n_af,
                n_arr_b=n_ab, n_ticks=T)


def interleaved(stage_apply: Callable, stacked_params, x, *,
                mesh: Mesh, n_micro: int, n_virtual: int = 2,
                axis_name: str = "pipe", data_axis: str = "data",
                key=None, extra=None, with_aux: bool = False,
                param_specs=None, ep_axis: str = None):
    """Interleaved-1F1B pipeline executor (module section comment).

    Contract differs from gpipe/onef1b in ONE way: ``stage_apply``
    receives a CHUNK's params — leading dim layers/(S*v) — instead of
    a stage's, with ``key`` (when given) already folded per
    (microbatch, global stage); the chunk body folds per local layer.
    ``stacked_params`` leaves are the usual [L, ...] stacks sharded
    P('pipe'), REINTERPRETED chunk-permuted (interleaved_layer_order):
    callers that assign semantic meaning to stack positions (unstack
    converters, sequential fallbacks) must apply the permutation.
    ``extra`` matches gpipe's contract (per-microbatch metadata, e.g.
    packed segment ids — every chunk-op indexes its microbatch's
    slice, treated as non-differentiable; stage protocol becomes
    ``stage_apply(chunk_params, x, extra_micro[, key])``).
    ``with_aux`` matches gpipe's too (chunk returns (y, aux); the
    executor returns (out, aux_total) = sum over chunk-ops, mean over
    microbatches and data shards). ``param_specs`` / ``ep_axis``
    (MoE/EP x interleaved): per-leaf spec overrides (expert stacks
    P('pipe','model')) and the mesh axis the chunk bodies' expert
    collectives run over — with ``ep_axis`` the backward switches to
    the collective-uniform one-vjp-per-tick form (in-stage
    collectives inside the diverging F/B cond corrupt gradients,
    onef1b's documented trap) and speaks onef1b's
    unreduced-cotangent convention (entering cotangent divided by
    the axis size, every leaf completed at the end per its spec).
    No seq_axis support (compose SP with gpipe/1f1b)."""
    S = mesh.shape[axis_name]
    v = n_virtual
    if v < 2:
        raise ValueError(f"interleaved needs n_virtual >= 2 chunks "
                         f"per device (got {v}); use gpipe/1f1b at "
                         "v=1")
    if S == 1:
        raise ValueError("interleaved needs a 'pipe' mesh axis > 1 "
                         "(the sequential fallback would have to "
                         "un-permute the chunk storage; use "
                         "gpipe/1f1b at pipe=1)")
    if n_micro % S:
        raise ValueError(f"interleaved needs n_micro ({n_micro}) "
                         f"divisible by the pipe axis ({S}) — the "
                         "F-stream cycles chunks per S-microbatch "
                         "group")
    for path, leaf in jax.tree_util.tree_flatten_with_path(stacked_params)[0]:
        if leaf.shape[0] % (S * v):
            raise ValueError(
                f"stacked param {jax.tree_util.keystr(path)} leading "
                f"dim {leaf.shape[0]} not divisible by {S} stages x "
                f"{v} chunks")

    sched = interleaved_bwd_schedule(S, n_micro, v)
    p_specs = (param_specs if param_specs is not None else
               jax.tree_util.tree_map(lambda _: P(axis_name),
                                      stacked_params))
    x_spec = P(data_axis, None, None)
    keyed = key is not None
    kk = key if keyed else jnp.zeros((2,), jnp.uint32)
    has_extra = extra is not None
    ex = extra if has_extra else jnp.zeros((0,), jnp.int32)
    e_spec = P(data_axis) if has_extra else P()
    kw = dict(n_micro=n_micro, n_virtual=v, n_stages=S,
              axis_name=axis_name, data_axis=data_axis, keyed=keyed,
              has_extra=has_extra, with_aux=with_aux, ep_axis=ep_axis)
    fwd_out_specs = (x_spec, P()) if with_aux else x_spec

    def fwd_program(params, xx, exx, k):
        body = functools.partial(_ileave_fwd_body, stage_apply, **kw)
        return shard_map(
            body, mesh=mesh, in_specs=(p_specs, x_spec, e_spec, P()),
            out_specs=fwd_out_specs, check_vma=False)(params, xx, exx, k)

    def bwd_program(params, xx, exx, k, dy, daux):
        body = functools.partial(_ileave_bwd_body, stage_apply,
                                 sched=sched, param_specs=p_specs,
                                 **kw)
        return shard_map(
            body, mesh=mesh,
            in_specs=(p_specs, x_spec, e_spec, P(), x_spec, P()),
            out_specs=(p_specs, x_spec), check_vma=False)(
                params, xx, exx, k, dy, daux)

    @jax.custom_vjp
    def run(params, xx, exx, k):
        return fwd_program(params, xx, exx, k)

    def run_fwd(params, xx, exx, k):
        return fwd_program(params, xx, exx, k), (params, xx, exx, k)

    def run_bwd(res, ct):
        params, xx, exx, k = res
        if with_aux:
            dy, daux = ct
        else:
            dy, daux = ct, jnp.zeros((), jnp.float32)
        dparams, dx = bwd_program(params, xx, exx, k, dy,
                                  daux.astype(jnp.float32))
        dk = np.zeros(np.shape(k), dtype=jax.dtypes.float0)
        dex = (np.zeros(np.shape(exx), dtype=jax.dtypes.float0)
               if jnp.issubdtype(exx.dtype, jnp.integer)
               else jnp.zeros_like(exx))
        return dparams, dx, dex, dk

    run.defvjp(run_fwd, run_bwd)
    return run(stacked_params, x, ex, kk)


def _ileave_chunks(local_params, v):
    """Reinterpret the local [L/S, ...] stack as [v, lc, ...] chunks."""
    return jax.tree_util.tree_map(
        lambda p: p.reshape((v, p.shape[0] // v) + p.shape[1:]),
        local_params)


def _ileave_chunk_params(chunks, j):
    """Chunk j's param slice out of the [v, lc, ...] local stacks."""
    return jax.tree_util.tree_map(
        lambda p: jax.lax.dynamic_index_in_dim(p, j, 0, keepdims=False),
        chunks)


def _ileave_run(stage_apply, cp, x, m, g, key, keyed, em=None):
    """Apply one chunk with the key folded per (microbatch, global
    stage). The ONE fold location: forward body, backward replay and
    the backward's vjp'd function all route through here, so replayed
    dropout masks match the primal bit-for-bit by construction.
    ``em`` ([M, mb, ...] microbatched extra metadata): this
    microbatch's slice is indexed here, keeping the extra protocol
    in one place too."""
    args = (cp, x)
    if em is not None:
        args += (jax.lax.dynamic_index_in_dim(em, m, 0,
                                              keepdims=False),)
    if keyed:
        k = jax.random.fold_in(jax.random.fold_in(key, m), g)
        return stage_apply(*args, k)
    return stage_apply(*args)


def _ileave_apply(stage_apply, chunks, j, x, m, s, S, key, keyed,
                  em=None):
    """Index chunk j and run it (see _ileave_run)."""
    cp = _ileave_chunk_params(chunks, j)
    return cp, _ileave_run(stage_apply, cp, x, m, j * S + s, key,
                           keyed, em)


def _ileave_fwd_body(stage_apply, local_params, xl, exl, key, *,
                     n_micro, n_virtual, n_stages, axis_name,
                     data_axis, keyed, has_extra=False, with_aux=False,
                     ep_axis=None):
    """Dense circular forward: vM + S - 1 ticks, closed-form indices
    (interleaved_fwd_schedule), full-ring ppermute each tick. With
    ``with_aux`` each chunk-op's scalar accumulates; the total is the
    sum over all (device, chunk) ops and the mean over microbatches
    and data shards — gpipe's aux semantics."""
    s = jax.lax.axis_index(axis_name)
    S, M, v = n_stages, n_micro, n_virtual
    bl, t, c = xl.shape
    if bl % M:
        raise ValueError(f"local batch {bl} not divisible by "
                         f"{M} microbatches")
    mb = bl // M
    xm = xl.reshape(M, mb, t, c)
    em = (exl.reshape((M, mb) + exl.shape[1:]) if has_extra else None)
    chunks = _ileave_chunks(local_params, v)
    ring = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t_):
        act_in, outbuf, auxsum = carry
        k = t_ - s
        valid = (k >= 0) & (k < v * M)
        kc = jnp.clip(k, 0, v * M - 1)
        kk = kc % (S * v)
        m = (kc // (S * v)) * S + (kk % S)
        j = kk // S
        inp = jnp.where((s == 0) & (j == 0),
                        jax.lax.dynamic_index_in_dim(xm, m, 0,
                                                     keepdims=False),
                        act_in)
        _, y = _ileave_apply(stage_apply, chunks, j, inp, m, s, S,
                             key, keyed, em)
        if with_aux:
            y, a = y
            auxsum = auxsum + jnp.where(valid,
                                        a.astype(jnp.float32), 0.0)
        y = jnp.where(valid, y, jnp.zeros_like(y))
        is_out = valid & (s == S - 1) & (j == v - 1)
        outbuf = jax.lax.dynamic_update_index_in_dim(
            outbuf,
            jnp.where(is_out, y,
                      jax.lax.dynamic_index_in_dim(outbuf, m, 0,
                                                   keepdims=False)),
            m, 0)
        return (jax.lax.ppermute(y, axis_name, ring), outbuf,
                auxsum), None

    act0 = jnp.zeros((mb, t, c), xl.dtype)
    (_, outbuf, auxsum), _ = jax.lax.scan(
        tick, (act0, jnp.zeros_like(xm), jnp.zeros((), jnp.float32)),
        jnp.arange(v * M + S - 1))
    outbuf = jax.lax.psum(
        jnp.where(s == S - 1, outbuf, jnp.zeros_like(outbuf)),
        axis_name)
    out = outbuf.reshape(bl, t, c)
    if not with_aux:
        return out
    n_data = jax.lax.psum(1, data_axis)
    aux = jax.lax.psum(jax.lax.psum(auxsum, axis_name), data_axis)
    return out, aux / (M * n_data)


def _ileave_bwd_body(stage_apply, local_params, xl, exl, key, dyl,
                     dauxl=None, *, sched, n_micro, n_virtual,
                     n_stages, axis_name, data_axis, keyed,
                     has_extra=False, with_aux=False, ep_axis=None,
                     param_specs=None):
    """Combined replay/backward scan over the host-built table: per
    tick, store ring-delivered arrivals into their allocated slots,
    run this device's op (F replay saving its input to the residual
    ring, or B vjp-ing the saved input against the arrived cotangent),
    and ppermute both streams around the full ring. With ``ep_axis``
    the chunk bodies contain expert collectives, so every tick runs
    ONE vjp on a role-selected input (collective-uniform; the F/B
    cond's diverging collectives corrupt gradients — onef1b's
    documented trap) and the scan speaks the unreduced-cotangent
    convention: entering cotangents divided by the axis size, every
    leaf completed at the end per its spec (onef1b's ep notes)."""
    s = jax.lax.axis_index(axis_name)
    S, M, v = n_stages, n_micro, n_virtual
    bl, t, c = xl.shape
    mb = bl // M
    xm = xl.reshape(M, mb, t, c)
    em = (exl.reshape((M, mb) + exl.shape[1:]) if has_extra else None)
    dym = dyl.reshape(M, mb, t, c)
    epn = jax.lax.psum(1, ep_axis) if ep_axis is not None else 1
    if ep_axis is not None:
        dym = dym / epn          # sums-to-truth shares (onef1b note)
    if with_aux:
        n_data = jax.lax.psum(1, data_axis)
        aux_ct = dauxl.astype(jnp.float32) / (M * n_data * epn)
    uniform = ep_axis is not None
    chunks = _ileave_chunks(local_params, v)
    fwd_ring = [(i, (i + 1) % S) for i in range(S)]
    bwd_ring = [((i + 1) % S, i) for i in range(S)]
    tbl = jax.tree_util.tree_map(
        jnp.asarray, {k_: sched[k_] for k_ in
                      ("kind", "m", "j", "rs_save", "rs_read",
                       "af_save", "af_read", "ab_save", "ab_read")})

    def store(buf, slot, val):
        cur = jax.lax.dynamic_index_in_dim(
            buf, jnp.maximum(slot, 0), 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            buf, jnp.where(slot >= 0, val, cur), jnp.maximum(slot, 0), 0)

    def load(buf, slot):
        return jax.lax.dynamic_index_in_dim(
            buf, jnp.maximum(slot, 0), 0, keepdims=False)

    def tick(carry, row):
        act_in, cot_in, arr_f, arr_b, resid, dpsum, dxbuf = carry
        col = {k_: row[k_][s] for k_ in row}
        kind, m, j = col["kind"], col["m"], col["j"]
        is_f, is_b = kind == 1, kind == 2
        # 1. bank this tick's ring deliveries
        arr_f = store(arr_f, col["af_save"], act_in)
        arr_b = store(arr_b, col["ab_save"], cot_in)
        # 2. inputs
        x_f = jnp.where((s == 0) & (j == 0) & (col["af_read"] < 0),
                        jax.lax.dynamic_index_in_dim(xm, m, 0,
                                                     keepdims=False),
                        load(arr_f, col["af_read"]))
        x_b = load(resid, col["rs_read"])
        g_in = jnp.where((s == S - 1) & (j == v - 1)
                         & (col["ab_read"] < 0),
                         jax.lax.dynamic_index_in_dim(dym, m, 0,
                                                      keepdims=False),
                         load(arr_b, col["ab_read"]))
        # 3. the op. Without ep collectives the cheap cond schedule
        # runs only the branch each tick needs (idle ticks land in
        # do_b on zeros, masked below — onef1b's trick); with them,
        # ONE vjp per tick on a role-selected input keeps the
        # collective sequence identical on every device every tick.
        zero_dp = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape[1:], p.dtype), chunks)

        def chunk_fn(c, xi):
            return _ileave_run(stage_apply, c, xi, m, j * S + s,
                               key, keyed, em)

        def pull_ct(pull):
            return pull((g_in, aux_ct) if with_aux else g_in)

        if uniform:
            inp = jnp.where(is_f, x_f, x_b)
            cp = _ileave_chunk_params(chunks, j)
            y, pull = jax.vjp(chunk_fn, cp, inp)
            if with_aux:
                y = y[0]
            dp, dx = pull_ct(pull)
        else:
            def do_f(_):
                _, y = _ileave_apply(stage_apply, chunks, j, x_f, m,
                                     s, S, key, keyed, em)
                if with_aux:
                    y = y[0]
                return y, jnp.zeros_like(x_f), zero_dp

            def do_b(_):
                cp = _ileave_chunk_params(chunks, j)
                _, pull = jax.vjp(chunk_fn, cp, x_b)
                dp, dx = pull_ct(pull)
                return jnp.zeros_like(x_b), dx, dp

            y, dx, dp = jax.lax.cond(is_f, do_f, do_b, None)
        y = jnp.where(is_f, y, jnp.zeros_like(y))
        dx = jnp.where(is_b, dx, jnp.zeros_like(dx))
        # 4. bookkeeping
        resid = store(resid, jnp.where(is_f, col["rs_save"], -1), x_f)
        dpsum = jax.tree_util.tree_map(
            lambda acc, g_: jax.lax.dynamic_update_index_in_dim(
                acc,
                jax.lax.dynamic_index_in_dim(acc, j, 0, keepdims=False)
                + jnp.where(is_b, g_, jnp.zeros_like(g_)
                            ).astype(acc.dtype),
                j, 0),
            dpsum, dp)
        oldx = jax.lax.dynamic_index_in_dim(dxbuf, m, 0, keepdims=False)
        dxbuf = jax.lax.dynamic_update_index_in_dim(
            dxbuf, jnp.where(is_b & (s == 0) & (j == 0), dx, oldx),
            m, 0)
        return (jax.lax.ppermute(y, axis_name, fwd_ring),
                jax.lax.ppermute(dx, axis_name, bwd_ring),
                arr_f, arr_b, resid, dpsum, dxbuf), None

    shp = (mb, t, c)
    carry0 = (
        jnp.zeros(shp, xl.dtype),
        jnp.zeros(shp, dyl.dtype),
        jnp.zeros((sched["n_arr_f"],) + shp, xl.dtype),
        jnp.zeros((sched["n_arr_b"],) + shp, dyl.dtype),
        jnp.zeros((sched["n_resid"],) + shp, xl.dtype),
        jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), chunks),
        jnp.zeros_like(dym),
    )
    (_, _, _, _, _, dpsum, dxbuf), _ = jax.lax.scan(
        tick, carry0, tbl)
    # Stage-0 holds the real input cotangents; with ep the unreduced
    # shares complete over the ep axis too (dx is ep-replicated).
    dx_axes = ((axis_name,) if ep_axis is None
               else (axis_name, ep_axis))
    dx = jax.lax.psum(
        jnp.where(s == 0, dxbuf, jnp.zeros_like(dxbuf)), dx_axes)
    # Chunk grads back to the [L/S, ...] stack; each data shard saw
    # only its microbatches -> complete over 'data', and under ep over
    # the ep axis for every leaf NOT sharded over it (ep-sharded
    # expert stacks hold per-shard grads and must not mix) — exactly
    # onef1b's leaf rule.
    flat_p, treedef = jax.tree_util.tree_flatten(local_params)
    flat_acc = jax.tree_util.tree_leaves(dpsum)
    if param_specs is None or ep_axis is None:
        flat_specs = [None] * len(flat_p)
    else:
        flat_specs = jax.tree_util.tree_leaves(
            param_specs, is_leaf=lambda vv: isinstance(vv, P))

    def leaf_axes(spec):
        if ep_axis is None or (spec is not None
                               and ep_axis in tuple(spec)):
            return (data_axis,)
        return (data_axis, ep_axis)

    dparams = treedef.unflatten([
        jax.lax.psum(
            acc.reshape((acc.shape[0] * acc.shape[1],)
                        + acc.shape[2:]),
            leaf_axes(sp_)).astype(p.dtype)
        for acc, p, sp_ in zip(flat_acc, flat_p, flat_specs)])
    return dparams, dx.reshape(bl, t, c)
