"""Device-side batched sampling for the continuous-batching engine.

``batched_sample`` makes every row's choice on the device, as ONE
batch-wide computation fused onto the engine's masked step: no
full-vocab logits row is ferried to the host, and only the sampled
int32 tokens cross the boundary.

Semantics mirror ``models.lm.filter_logits`` exactly (sequential
HF-warper order: top-k truncation first, then the nucleus over the
RENORMALIZED post-top-k distribution), generalized to PER-ROW
parameters: every slot carries its own temperature/top_k/top_p/seed,
because co-resident requests disagree about all four. Greedy rows
(temperature <= 0) are exact ``argmax`` over the raw float32 logits,
which is what keeps greedy serve output token-identical to solo
``models.lm.generate``.

Randomness is counter-based: row b's key is
``fold_in(fold_in(PRNGKey(seed_b), SALT), step_b)`` where ``step_b``
is how many tokens the request has generated so far. Keys never live
between steps (nothing to checkpoint, nothing to desync), the stream
is deterministic per (seed, step) — a preempted-and-resumed request
continues its exact sample sequence — and rows are independent across
slots by construction.
"""

from __future__ import annotations

# Salt folded into every per-request key so the serve sample stream
# can never collide with a training PRNG stream built from the same
# user seed.
_SAMPLE_SALT = 0x5E12


def batched_sample(logits, temperature, top_k, top_p, seeds, steps):
    """One sampled token per row from ``logits`` [B, V] float32.

    ``temperature``/``top_p`` are float32 [B], ``top_k``/``seeds``/
    ``steps`` int32 [B]. Rows with ``temperature <= 0`` are greedy
    argmax of the RAW logits; other rows follow filter_logits
    semantics with a per-(seed, step) categorical draw. Returns int32
    [B].
    """
    import jax
    import jax.numpy as jnp

    b, v = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def stochastic():
        # Scaled logits for the sampling branch (safe divisor for
        # greedy rows — their result is discarded by the where()).
        safe_t = jnp.where(temperature > 0, temperature, 1.0)
        lg = logits / safe_t[:, None]

        srt = jnp.sort(lg, axis=-1)[:, ::-1]                  # [B, V] desc
        # -- per-row top-k (filter_logits: keep lg >= k-th largest) ---
        apply_k = (top_k > 0) & (top_k < v)
        kth = jnp.take_along_axis(
            srt, jnp.clip(top_k - 1, 0, v - 1)[:, None], axis=1)
        lg = jnp.where(apply_k[:, None] & (lg < kth), -jnp.inf, lg)
        srt = jnp.where(apply_k[:, None]
                        & (jnp.arange(v)[None, :] >= top_k[:, None]),
                        -jnp.inf, srt)
        # -- per-row nucleus over the renormalized post-top-k dist ----
        apply_p = (top_p > 0.0) & (top_p < 1.0)
        probs = jax.nn.softmax(srt, axis=-1)
        keep = jnp.cumsum(probs, axis=-1) - probs < top_p[:, None]
        cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                         keepdims=True)                       # [B, 1]
        lg = jnp.where(apply_p[:, None] & (lg < cutoff), -jnp.inf, lg)

        def draw(key_seed, key_step, row):
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(key_seed),
                                   _SAMPLE_SALT), key_step)
            return jax.random.categorical(key, row)

        sampled = jax.vmap(draw)(seeds, steps, lg).astype(jnp.int32)
        return jnp.where(temperature > 0, sampled, greedy)

    # Greedy batches are the common serving case: skip the whole
    # sort/softmax/cumsum/per-row-PRNG pipeline at runtime unless at
    # least one resident row actually samples.
    return jax.lax.cond(jnp.any(temperature > 0), stochastic,
                        lambda: greedy)


def batched_sample_positions(logits, temperature, top_k, top_p, seeds,
                             steps0):
    """Per-position sampling for the speculative verify step: one
    token per (row, position) from ``logits`` [B, T, V] float32.

    Position ``j`` of row ``b`` draws with step ``steps0[b] + j`` —
    exactly the key the sequential decode loop would have used when
    it reached that position, which is what makes spec-on sampled
    output bitwise-identical to spec-off per (seed, step) and keeps
    failover resume deterministic. ``T`` is static (K+1), so the
    per-position loop unrolls at trace time into T reuses of the
    [B]-wide ``batched_sample``. Returns int32 [B, T].
    """
    import jax.numpy as jnp

    t = logits.shape[1]
    cols = [batched_sample(logits[:, j], temperature, top_k, top_p,
                           seeds, steps0 + j) for j in range(t)]
    return jnp.stack(cols, axis=1)
