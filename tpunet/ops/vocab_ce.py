"""Vocab-sharded (Megatron-style) cross-entropy for the LM families.

The reference computes its CE on full logits (a 10-class CNN —
cifar10_mpi_mobilenet_224.py:157 nn.CrossEntropyLoss — where that is
free); tpunet's LMs tie the output projection to the embedding and at
real vocabularies the [B, T, V] float32 logits tensor is the single
largest array in the train step — at V=32k, B=8, T=2048 it is 2.1 GB,
dwarfing the activation memory the 1F1B pipeline executor saves. This
op never materializes it: the final hidden states enter a shard_map
over ('data', 'model'), each device computes logits against only its
VOCAB SLICE of the (tied) embedding — [B/dp, T, V/vp] — and the
softmax statistics are assembled with three tiny collectives over
'model' (pmax of the row max, psum of the exp-sum, psum of the
target's logit), the standard max-subtract log-sum-exp factorization:

    ce = lse - tgt_logit,
    lse = m + log(psum_v sum exp(logits_v - m)),  m = pmax_v max logits_v

Peak logits memory drops vp-fold (measured via XLA memory analysis in
tests/test_vocab_ce.py); comm cost is O(B*T) scalars per collective —
independent of V — plus nothing else: the embedding table stays
REPLICATED in storage (at [V, C] it is ~1000x smaller than the logits
it replaces; each shard_map body slices its vocab rows locally for
free), so checkpoints, serving and the input lookup are untouched.

Gradients flow through the same factorization (the row max is
stop-gradient'd — analytically it cancels from lse, so this changes
nothing but removes the pmax from the backward): shard_map AD psums
the hidden-state cotangent over 'model' and concatenates the per-slice
embedding cotangents, giving 1e-6-level parity with the full-logits
path (asserted in tests/test_vocab_ce.py).

Accuracy under sharding: ``hit`` is ``tgt_logit >= global_max`` —
identical to ``argmax == target`` except when the max is achieved by
several classes at once (then argmax's first-index tie-break may miss
the target while hit counts it). Ties on float32 LM logits are
measure-zero; documented deviation.

The model-side hook is ``return_hidden=True`` on TransformerLM /
PipelinedLM (the final-LN hidden states instead of logits); the train
and eval steps wire it when ``--vocab-ce`` resolves to "sharded"
(tpunet/train/steps.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map


def resolve_vocab_ce(vocab_ce: str, mesh, vocab_size: int) -> str:
    """Resolve a ``--vocab-ce`` setting: "auto" prefers "sharded"
    whenever the mesh has a 'model' axis > 1 that divides the vocab,
    else "full"; explicit "sharded" raises where auto falls back."""
    if vocab_ce not in ("auto", "sharded", "full"):
        raise ValueError(f"unknown vocab_ce {vocab_ce!r}; "
                         "expected auto|sharded|full")
    vp = mesh.shape.get("model", 1) if mesh is not None else 1
    ok = vp > 1 and vocab_size % vp == 0
    if vocab_ce == "sharded" and not ok:
        raise ValueError(
            f"vocab_ce='sharded' needs a mesh 'model' axis > 1 that "
            f"divides the vocab ({vocab_size}); have "
            f"{'no mesh' if mesh is None else f'model={vp}'}")
    if vocab_ce == "full":
        return "full"
    return "sharded" if ok else "full"


@jax.custom_vjp
def _pmax_model_const(x):
    """pmax over 'model' with a zero vjp: the row max is a numerical
    shift that cancels analytically from the log-sum-exp, so its true
    cotangent contribution is zero — and jax.lax.pmax has no
    differentiation rule to say so itself."""
    return jax.lax.pmax(x, "model")


def _pmax_fwd(x):
    return _pmax_model_const(x), None


def _pmax_bwd(_, ct):
    return (jnp.zeros_like(ct),)


_pmax_model_const.defvjp(_pmax_fwd, _pmax_bwd)


def vocab_parallel_ce(h, emb, targets, mesh, *, smoothing: float = 0.0):
    """Per-token CE and argmax-hit from hidden states, vocab-sharded.

    h [B, T, C] (any float dtype; cast to float32), emb [V, C] (the
    tied embedding, replicated), targets [B, T] int32. Returns
    (ce [B, T] float32, hit [B, T] float32) — exactly
    ``optax.softmax_cross_entropy*(h @ emb.T, targets)`` and
    ``argmax(h @ emb.T) == targets`` (up to ties), with per-device
    logits bounded at [B/dp, T, V/vp]. ``smoothing`` matches
    optax.smooth_labels semantics: the smoothed CE is
    ``lse - ((1-s)*tgt_logit + (s/V)*sum_logits)``."""
    v, _ = emb.shape
    vp = mesh.shape["model"]
    if v % vp:
        raise ValueError(f"vocab {v} not divisible by the mesh "
                         f"'model' axis ({vp})")
    b = h.shape[0]
    dp = mesh.shape.get("data", 1)
    if b % dp:
        raise ValueError(f"batch {b} not divisible by the mesh "
                         f"'data' axis ({dp})")

    def body(h_l, emb_l, tgt_l):
        v_l = emb_l.shape[0]
        logits = jnp.einsum("btc,vc->btv", h_l.astype(jnp.float32),
                            emb_l.astype(jnp.float32))   # [b_l, T, v_l]
        # Row max over the FULL vocab (zero-vjp pmax: it cancels
        # analytically from lse, see _pmax_model_const).
        m = _pmax_model_const(jnp.max(logits, -1))       # [b_l, T]
        z = jax.lax.psum(
            jnp.sum(jnp.exp(logits - m[..., None]), -1), "model")
        lse = m + jnp.log(z)
        off = jax.lax.axis_index("model") * v_l
        loc = jnp.clip(tgt_l - off, 0, v_l - 1)
        tl = jnp.take_along_axis(logits, loc[..., None], -1)[..., 0]
        mine = ((tgt_l >= off) & (tgt_l < off + v_l)).astype(jnp.float32)
        tgt_logit = jax.lax.psum(tl * mine, "model")
        if smoothing > 0.0:
            mean_logit = jax.lax.psum(jnp.sum(logits, -1), "model") / v
            ce = lse - ((1.0 - smoothing) * tgt_logit
                        + smoothing * mean_logit)
        else:
            ce = lse - tgt_logit
        hit = (tgt_logit >= m).astype(jnp.float32)
        return ce, hit

    tok = P("data", None)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None, None), P("model", None), tok),
        out_specs=(tok, tok), check_vma=False)
    return fn(h, emb, targets)
