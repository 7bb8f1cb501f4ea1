"""Jitted train / eval steps.

The reference's hot loop (cifar10_mpi_mobilenet_224.py:173-185) is:
h2d copy -> zero_grad -> DDP forward -> CE loss -> backward (bucketed
NCCL allreduce hooks) -> Adam step -> metric accumulation. Here the
entire iteration — on-device augmentation, forward, loss, backward,
cross-device gradient reduction, optimizer update, metric sums — is ONE
jitted XLA program per step; the gradient all-reduce is inserted by XLA
from the sharding layout (batch on the 'data' mesh axis, params
replicated) rather than by framework hooks.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from tpunet.config import DataConfig, ModelConfig, OptimConfig
from tpunet.data.augment import (make_eval_preprocess, make_train_augment,
                                 mixup_cutmix)
from tpunet.train import metrics as M
from tpunet.train.state import TrainState


def _ce_loss(logits, targets, smoothing: float):
    """Per-example/token CE, optionally label-smoothed (StepLR stack's
    CrossEntropyLoss analogue; shared by the image and LM steps)."""
    if smoothing > 0:
        return optax.softmax_cross_entropy(
            logits, optax.smooth_labels(
                jax.nn.one_hot(targets, logits.shape[-1]), smoothing))
    return _int_label_ce(logits, targets)


@jax.custom_vjp
def _int_label_ce(logits, targets):
    """``logsumexp(logits) - logits[target]`` per position, in float32:
    optax's integer-label cross-entropy to the bit, but the target is
    picked by a compare against the vocabulary's iota, not a gather.
    The gather comes back as a scatter into the flattened logits, and
    the TPU compiler wraps that in two serial reshape loops and four
    more passes over the ``[positions, vocabulary]`` cotangent (31 ms
    of a 378 ms step at 8,191 positions: PERF.md section 6, PR 43).
    This backward is one elementwise pass, and what it keeps is the
    logits and one number a row."""
    return _int_label_ce_fwd(logits, targets)[0]


def _int_label_ce_fwd(logits, targets):
    wide = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(wide, axis=-1)
    hot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.bool_)
    picked = jnp.sum(jnp.where(hot, wide, 0.0), axis=-1)
    return lse - picked, (logits, lse, targets)


def _int_label_ce_bwd(res, g):
    logits, lse, targets = res
    softmax = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    hot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
    return ((softmax - hot) * g[..., None]).astype(logits.dtype), None


_int_label_ce.defvjp(_int_label_ce_fwd, _int_label_ce_bwd)


def _aux_term(mutated, aux_weight: float):
    """Weighted sum of the MoE load-balance terms sown into 'losses'
    (0.0 when absent/unweighted) — the ONE place the aux rule lives."""
    aux_terms = jax.tree_util.tree_leaves(mutated.get("losses", {}))
    if aux_terms and aux_weight > 0:
        return aux_weight * sum(aux_terms)
    return 0.0


def _with_aux(loss, mutated, aux_weight: float):
    """Add weighted MoE load-balance terms sown into 'losses'."""
    return loss + _aux_term(mutated, aux_weight)


def _routing_load(mutated) -> dict:
    """Mean over the expert layers of the routing load that
    ``moe.RoutedShareMlp`` sows into ``stats`` ({} where none did).
    Every layer routes the same pairs, so the mean of their shares is
    pairs on held experts over pairs (and chunks of sorted pairs that
    went through the grouped products over chunks)."""
    def is_load(s):
        return hasattr(s, "keys") and "held_pair_share" in s

    found = [s for s in jax.tree_util.tree_leaves(
        mutated.get("stats", {}), is_leaf=is_load) if is_load(s)]
    if not found:
        return {}
    return {name: sum(jnp.mean(s[k]) for s in found) / len(found)
            for k, name in (
                ("held_pair_share", "moe_held_pair_share"),
                ("held_load_max_over_mean", "moe_held_load_max_over_mean"),
                ("held_chunks_run_share", "moe_chunks_run_share"))}


def _steps_from_micro(micro: Callable, accum: int, mesh,
                      gather_params=None, ema_decay: float = 0.0,
                      count_fn: Optional[Callable] = None) -> Callable:
    """Lift micro(params, batch_stats, apply_fn, x, y, rng) ->
    (grads, new_stats, metrics) into train_step(state, x, y, rng).

    accum == 1: one microbatch IS the batch (no scan overhead).
    accum > 1: the global batch is split into `accum` equal microbatches
    scanned *in time* — gradients averaged (mean of equal-sized means ==
    the full-batch mean), BatchNorm stats threaded through microbatches
    (torch semantics: stats update every forward), ONE optimizer update.
    ``count_fn`` (packed sequences): microbatch example counts are
    UNEQUAL (valid-target counts vary with packing), so the GLOBAL
    valid-target count ``count_fn(y)`` is computed up front and passed
    to the micro as ``grad_norm=(total, accum)`` — the micro normalizes
    its CE gradient by the global count (sum of microbatch grads then
    IS the full-batch mean) and any count-independent terms (MoE aux
    loss) by 1/accum (equal weighting).  Scaling whole microbatch
    gradients by their counts instead would bias count-independent
    terms toward fuller microbatches.
    Activation memory drops by ~1/accum; the XLA program stays static.
    The split is STRIDED (microbatch i = rows i, i+accum, ...): under
    the P('data') batch layout a contiguous split would move most rows
    off their home device every step, while the strided split maps each
    device's contiguous rows exactly onto its shard of every microbatch
    — zero resharding traffic. The partition is irrelevant to the math
    (the epoch shuffle already randomized row order).

    gather_params (the FSDP path): params are all-gathered ONCE at step
    start to ``gather_params`` — a params-tree of NamedShardings giving
    each leaf its COMPUTE layout: the TP/PP spec for model/pipe-sharded
    leaves (tensor/pipeline compute sharding is preserved, only the
    FSDP 'data' shard is gathered), replicated for the rest. Left to
    sharding propagation instead, GSPMD pushes the weight shards into
    attention activations and falls back to 'involuntary full
    rematerialization' reshards. The constraint's transpose reshards
    each weight's gradient straight back to its 'data' shard, and the
    Adam update then runs on 1/N-sized moment shards — sharded state,
    DP/TP/PP-layout compute.
    """

    # jax.named_scope labels below cost nothing at runtime (they apply
    # at trace time) but carry through to HLO op names, so xprof traces
    # attribute device time to fwd/bwd vs optimizer vs EMA phases.
    def finish(state, grads, stats):
        with jax.named_scope("tpunet_optimizer"):
            state = state.apply_gradients(grads=grads, batch_stats=stats)
        if ema_decay > 0:
            # EMA tracks the POST-update params AND the BN running
            # stats (evaluating EMA weights against live stats would
            # mismatch normalization); eval/best-ckpt read the pair.
            ema = lambda old, new: jax.tree_util.tree_map(
                lambda e, p: ema_decay * e + (1.0 - ema_decay) * p,
                old, new)
            with jax.named_scope("tpunet_ema"):
                state = state.replace(
                    ema_params=ema(state.ema_params, state.params),
                    ema_batch_stats=ema(state.ema_batch_stats,
                                        state.batch_stats))
        return state

    def train_step(state: TrainState, x, y, rng):
        params = state.params
        if gather_params is not None:
            params = jax.lax.with_sharding_constraint(params, gather_params)

        if accum == 1:
            with jax.named_scope("tpunet_fwd_bwd"):
                grads, stats, m = micro(params, state.batch_stats,
                                        state.apply_fn, x, y, rng)
            return finish(state, grads, stats), m

        mb = x.shape[0] // accum
        xs = x.reshape(mb, accum, *x.shape[1:]).swapaxes(0, 1)
        ys = y.reshape(mb, accum, *y.shape[1:]).swapaxes(0, 1)
        if mesh is not None:
            sh = lambda a: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P(None, "data")))
            xs, ys = sh(xs), sh(ys)
        rngs = jax.random.split(rng, accum)
        total = count_fn(y) if count_fn is not None else None

        def body(carry, inp):
            stats, gsum, msum = carry
            mx, my, mr = inp
            if count_fn is not None:
                grads, stats, m = micro(params, stats, state.apply_fn,
                                        mx, my, mr,
                                        grad_norm=(total, accum))
            else:
                grads, stats, m = micro(params, stats, state.apply_fn,
                                        mx, my, mr)
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            # (a step's extra means, metrics.STEP_MEANS, ride only the
            # unscanned step: the carry is the triple)
            return (stats, gsum, M.accumulate(
                msum, {k: m[k] for k in msum})), None

        gzero = jax.tree_util.tree_map(jnp.zeros_like, state.params)
        with jax.named_scope("tpunet_fwd_bwd"):
            (stats, gsum, msum), _ = jax.lax.scan(
                body, (state.batch_stats, gzero, M.zeros_metrics()),
                (xs, ys, rngs))
        if count_fn is not None:
            grads = gsum        # micro already normalized globally
        else:
            grads = jax.tree_util.tree_map(lambda g: g / accum, gsum)
        return finish(state, grads, stats), msum

    return train_step


def make_train_step(data_cfg: DataConfig,
                    optim_cfg: OptimConfig,
                    model_cfg: Optional[ModelConfig] = None,
                    mesh=None, gather_params=None) -> Callable:
    """Build train_step(state, images_u8, labels, rng) -> (state, metrics).

    ``images_u8`` is the raw (global_batch, 32, 32, 3) uint8 batch;
    augmentation runs inside the step (fused by XLA with the forward).
    With optim_cfg.grad_accum > 1 the batch is scanned as microbatches;
    ``gather_params`` is the FSDP compute-layout sharding tree (see
    _steps_from_micro).
    """
    augment = make_train_augment(data_cfg)
    smoothing = optim_cfg.label_smoothing
    aux_weight = model_cfg.moe_aux_weight if model_cfg is not None else 0.0
    mixing = data_cfg.mixup_alpha > 0 or data_cfg.cutmix_alpha > 0

    def micro(params, batch_stats, apply_fn, images_u8, labels, rng):
        if mixing:
            aug_rng, dropout_rng, mix_rng = jax.random.split(rng, 3)
        else:
            # 2-way split when not mixing: keeps the augment/dropout
            # streams (and thus seed-for-seed runs) identical to
            # configs that predate the mixup option.
            aug_rng, dropout_rng = jax.random.split(rng)
        # Named scope: the on-device augmentation gets its own bucket
        # in the byte/time attributions (tpunet/obs/hlo_bytes.py) —
        # round 5 found ~20% of the step hiding here, so it must not
        # blur into the generic fwd/elementwise categories.
        with jax.named_scope("tpunet_augment"):
            images = augment(aug_rng, images_u8)
            if mixing:
                images, labels_b, lam = mixup_cutmix(
                    mix_rng, images, labels,
                    data_cfg.mixup_alpha, data_cfg.cutmix_alpha)

        def loss_fn(params):
            # mutable=["batch_stats"] is harmless for models without
            # BatchNorm (ViT): the mutated collection comes back empty.
            # "losses" carries MoE load-balance terms sown by MoeMlp.
            logits, mutated = apply_fn(
                {"params": params, "batch_stats": batch_stats},
                images, train=True,
                rngs={"dropout": dropout_rng},
                mutable=["batch_stats", "losses"])
            ce = _ce_loss(logits, labels, smoothing)
            if mixing:
                # Convex label combination; accuracy below stays vs the
                # PRIMARY label (standard mixup reporting).
                ce = lam * ce + (1.0 - lam) * _ce_loss(logits, labels_b,
                                                       smoothing)
            loss = _with_aux(ce.mean(), mutated, aux_weight)
            return loss, (logits, mutated.get("batch_stats", {}))

        (loss, (logits, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        n = labels.shape[0]
        correct = jnp.sum(jnp.argmax(logits, -1) == labels)
        return grads, new_stats, M.from_batch(loss * n, correct, n)

    return _steps_from_micro(micro, max(1, optim_cfg.grad_accum), mesh,
                             gather_params=gather_params,
                             ema_decay=optim_cfg.ema_decay)


def _packed_target_weights(segs):
    """[B, T-1] float weights for next-token prediction under packing:
    a target is valid iff it continues the SAME document (segment id
    unchanged) and is not padding (id 0) — no prediction crosses a
    document boundary or lands on pad."""
    return ((segs[:, 1:] == segs[:, :-1])
            & (segs[:, 1:] != 0)).astype(jnp.float32)


def make_lm_train_step(optim_cfg: OptimConfig,
                       model_cfg: ModelConfig,
                       mesh=None, gather_params=None,
                       packed: bool = False) -> Callable:
    """train_step(state, tokens, labels, rng) -> (state, metrics) for
    the LM family: targets are the input shifted by one; metrics count
    next-token predictions (accuracy ~0.8 is ceiling on the synthetic
    bigram data, tpunet/data/lm.py). ``packed=True``: ``labels``
    carries [B, T] segment ids (tpunet/data/lm.py text_lm_packed) —
    attention is segment-masked inside the model and the loss/metrics
    drop cross-document and padding targets.

    With ``--vocab-ce`` resolving to "sharded" (auto: a mesh 'model'
    axis > 1 dividing the vocab) the model returns final-LN hidden
    states and the CE runs vocab-sharded against the tied embedding —
    the replicated [B, T, V] float32 logits never materialize
    (tpunet/ops/vocab_ce.py).

    A model whose training forward returns ``(logits, logits of the
    token after next)`` (a multi-token-prediction module) has the
    second cross-entropy added at ``model_cfg.mtp_loss_weight``, each
    a mean over the positions that have its target; both losses and
    the routing load a no-drop expert layer sows into ``stats`` ride
    the step's metrics (train/metrics.py ``STEP_MEANS``)."""
    aux_weight = model_cfg.moe_aux_weight
    mtp_weight = model_cfg.mtp_loss_weight
    smoothing = optim_cfg.label_smoothing
    from tpunet.ops.vocab_ce import resolve_vocab_ce, vocab_parallel_ce
    sharded_ce = (resolve_vocab_ce(model_cfg.vocab_ce, mesh,
                                   model_cfg.vocab_size) == "sharded")

    def micro(params, batch_stats, apply_fn, tokens, labels, rng,
              grad_norm=None):
        segs = labels if packed else None

        def loss_fn(params):
            kwargs = {"segment_ids": segs} if packed else {}
            tgt = tokens[:, 1:]
            means = {}
            if sharded_ce:
                h, mutated = apply_fn(
                    {"params": params, "batch_stats": batch_stats},
                    tokens, train=True, return_hidden=True,
                    rngs={"dropout": rng},
                    mutable=["batch_stats", "losses"], **kwargs)
                ce, hit = vocab_parallel_ce(
                    h[:, :-1], params["embed"]["embedding"], tgt,
                    mesh, smoothing=smoothing)
            else:
                logits, mutated = apply_fn(
                    {"params": params, "batch_stats": batch_stats},
                    tokens, train=True,
                    rngs={"dropout": rng},
                    mutable=["batch_stats", "losses", "stats"], **kwargs)
                if isinstance(logits, tuple):
                    if packed:
                        raise ValueError("no multi-token-prediction loss "
                                         "over packed sequences")
                    logits, ahead = logits
                    means["mtp_loss"] = _ce_loss(
                        ahead[:, :-2], tokens[:, 2:], smoothing).mean()
                lg = logits[:, :-1]
                ce = _ce_loss(lg, tgt, smoothing)
                hit = (jnp.argmax(lg, -1) == tgt).astype(jnp.float32)
            aux = _aux_term(mutated, aux_weight)
            means.update(_routing_load(mutated))
            if packed:
                wt = _packed_target_weights(segs)
                ce_sum = jnp.sum(ce * wt)
                n_valid = jnp.maximum(jnp.sum(wt), 1.0)
                if grad_norm is None:
                    loss = ce_sum / n_valid + aux
                    loss_sum = ce_sum + aux * n_valid
                else:
                    # Grad-accum: CE over the GLOBAL valid-target count
                    # and the count-independent aux term over 1/accum,
                    # so plain summation of microbatch grads restores
                    # the full-batch CE mean + equal-weighted aux mean
                    # (see _steps_from_micro's count_fn contract). The
                    # METRIC weights aux the same way: summed loss_sums
                    # divided by the total count give exactly
                    # CE_global_mean + mean_i(aux_i) — the objective
                    # being optimized, not a count-weighted variant.
                    total, accum = grad_norm
                    loss = ce_sum / total + aux / accum
                    loss_sum = ce_sum + aux * total / accum
            else:
                loss = ce.mean() + aux
                if "mtp_loss" in means:
                    means["main_loss"] = ce.mean()
                    loss = loss + mtp_weight * means["mtp_loss"]
                loss_sum = loss * tgt.size
            return loss, (hit, mutated.get("batch_stats", {}),
                          loss_sum, means)

        (_, (hit, new_stats, loss_sum, means)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if packed:
            wt = _packed_target_weights(segs)
            n = jnp.sum(wt)
            correct = jnp.sum(hit * wt)
        else:
            n = hit.size
            correct = jnp.sum(hit)
        return grads, new_stats, M.with_step_means(
            M.from_batch(loss_sum, correct, n), means)

    def packed_count(y):
        return jnp.maximum(jnp.sum(_packed_target_weights(y)), 1.0)

    return _steps_from_micro(micro, max(1, optim_cfg.grad_accum), mesh,
                             gather_params=gather_params,
                             ema_decay=optim_cfg.ema_decay,
                             count_fn=packed_count if packed else None)


def make_lm_eval_step(model_cfg: Optional[ModelConfig] = None,
                      mesh=None, gather_params=None,
                      packed: bool = False) -> Callable:
    """eval_step(state, tokens, labels, mask) -> metrics; ``mask`` [B]
    zeroes padded sequences so the test set is counted exactly.
    ``packed=True``: ``labels`` carries [B, T] segment ids, composing
    the per-sequence mask with the per-token packing weights.
    ``gather_params``: FSDP compute-layout tree, same as the train step
    (without it the eval forward re-runs under the pathological GSPMD
    propagation the train step avoids). ``model_cfg`` + ``mesh``:
    --vocab-ce resolution, mirroring the train step (the eval forward
    is where full logits would otherwise peak at the same size)."""
    from tpunet.ops.vocab_ce import resolve_vocab_ce, vocab_parallel_ce
    sharded_ce = (model_cfg is not None
                  and resolve_vocab_ce(model_cfg.vocab_ce, mesh,
                                       model_cfg.vocab_size) == "sharded")

    @jax.named_scope("tpunet_eval_forward")
    def eval_step(state: TrainState, tokens, labels, mask):
        params = state.params
        if gather_params is not None:
            params = jax.lax.with_sharding_constraint(params, gather_params)
        kwargs = {"segment_ids": labels} if packed else {}
        tgt = tokens[:, 1:]
        if sharded_ce:
            h = state.apply_fn(
                {"params": params, "batch_stats": state.batch_stats},
                tokens, train=False, return_hidden=True, **kwargs)
            losses, correct = vocab_parallel_ce(
                h[:, :-1], params["embed"]["embedding"], tgt, mesh)
        else:
            logits = state.apply_fn(
                {"params": params, "batch_stats": state.batch_stats},
                tokens, train=False, **kwargs)
            lg = logits[:, :-1]
            losses = optax.softmax_cross_entropy_with_integer_labels(
                lg, tgt)
            correct = (jnp.argmax(lg, -1) == tgt).astype(jnp.float32)
        wt = mask[:, None]
        if packed:
            wt = wt * _packed_target_weights(labels)
        return M.from_batch(jnp.sum(losses * wt), jnp.sum(correct * wt),
                            jnp.sum(wt) if packed
                            else jnp.sum(wt) * tgt.shape[1])

    return eval_step


def make_eval_step(data_cfg: DataConfig, gather_params=None) -> Callable:
    """Build eval_step(state, images_u8, labels, mask) -> metrics.

    ``mask`` zeroes padded examples so the test set is counted exactly
    (fixes the reference's local-approximate accuracy, :196,224).
    ``gather_params``: FSDP compute-layout tree, as in the train step.
    """
    preprocess = make_eval_preprocess(data_cfg)

    @jax.named_scope("tpunet_eval_forward")
    def eval_step(state: TrainState, images_u8, labels, mask):
        params = state.params
        if gather_params is not None:
            params = jax.lax.with_sharding_constraint(params, gather_params)
        images = preprocess(images_u8)
        logits = state.apply_fn(
            {"params": params, "batch_stats": state.batch_stats},
            images, train=False)
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels)
        correct = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
        return M.from_batch(jnp.sum(losses * mask),
                            jnp.sum(correct * mask),
                            jnp.sum(mask))

    return eval_step
