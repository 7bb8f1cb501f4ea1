"""Plain reference for GPT-2 (Radford et al. 2019), as the configuration
file sizes it: token + learned position embeddings, pre-LayerNorm
blocks (LN -> fused QKV -> causal softmax attention -> projection;
LN -> 4x GELU(tanh) MLP), final LN, logits against the tied embedding.

Float32, every product at ``highest`` precision, no kernel, no cache,
no batching: one sequence at a time, the blocks scanned over a stack of
their parameters. It imports nothing of the program and takes none of
its arrays: the weights come from ``benchmark/weights.py`` and the seed.

Departures from the published model, both stated in the configuration
file: LayerNorm's epsilon is the configuration's (the program builds
its blocks with 1e-6), and every weight is drawn from N(0, 0.02)
(positions 0.01) with zero biases — no residual-depth scaling.
"""

from __future__ import annotations

import math

from benchmark import weights
from benchmark.reference import _numerics as N


def sizes(cfg: dict, section: str) -> dict:
    """Published sizes, with the section's overrides (``train`` cuts
    ``n_layer``)."""
    out = {k: cfg[k] for k in ("n_embd", "n_layer", "n_head", "n_positions",
                               "vocab_size", "layer_norm_epsilon")}
    out.update(cfg.get(section, {}).get("overrides", {}))
    return out


_BLOCK_LEAVES = (
    # rest-of-path, shape as a function of C, kind, std
    ("ln1/scale", lambda c: (c,), "ones", 0.0),
    ("ln1/bias", lambda c: (c,), "zeros", 0.0),
    ("attn/qkv/kernel", lambda c: (c, 3 * c), "normal", 0.02),
    ("attn/qkv/bias", lambda c: (3 * c,), "zeros", 0.0),
    ("attn/out/kernel", lambda c: (c, c), "normal", 0.02),
    ("attn/out/bias", lambda c: (c,), "zeros", 0.0),
    ("ln2/scale", lambda c: (c,), "ones", 0.0),
    ("ln2/bias", lambda c: (c,), "zeros", 0.0),
    ("mlp/fc1/kernel", lambda c: (c, 4 * c), "normal", 0.02),
    ("mlp/fc1/bias", lambda c: (4 * c,), "zeros", 0.0),
    ("mlp/fc2/kernel", lambda c: (4 * c, c), "normal", 0.02),
    ("mlp/fc2/bias", lambda c: (c,), "zeros", 0.0),
)


def _top_leaves(s: dict) -> dict:
    c = s["n_embd"]
    return {
        "embed/embedding": ((s["vocab_size"], c), "normal", 0.02),
        "pos_embed": ((1, s["n_positions"], c), "normal", 0.01),
        "ln/scale": ((c,), "ones", 0.0),
        "ln/bias": ((c,), "zeros", 0.0),
    }


def param_spec(cfg: dict, section: str) -> dict:
    """``{path: (shape, kind, std)}`` of the tree the program holds."""
    s = sizes(cfg, section)
    spec = _top_leaves(s)
    for i in range(s["n_layer"]):
        for rest, shape, kind, std in _BLOCK_LEAVES:
            spec[f"block{i:02d}/{rest}"] = (shape(s["n_embd"]), kind, std)
    return spec


def make_params(cfg: dict, section: str, seed: int) -> dict:
    """Flat float32 params with the blocks stacked: ``blocks/<rest>`` is
    ``[n_layer, ...]``. Bit-equal to what the program is handed."""
    import jax

    s = sizes(cfg, section)

    @jax.jit
    def build(key):
        out = {p: weights.make_leaf(key, p, shape, kind, std)
               for p, (shape, kind, std) in _top_leaves(s).items()}
        for rest, shape, kind, std in _BLOCK_LEAVES:
            out[f"blocks/{rest}"] = weights.make_stacked(
                key, rest, s["n_layer"], shape(s["n_embd"]), kind, std)
        return out

    return build(weights.seed_key(seed))


def _ln(x, scale, bias, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * (1.0 / jnp.sqrt(var + eps)) * scale + bias


def _gelu_tanh(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, s, precision):
    """One block on ``x`` [T, C]; ``p`` maps rest-of-path to array."""
    import jax
    import jax.numpy as jnp

    t, c = x.shape
    h, eps = s["n_head"], s["layer_norm_epsilon"]
    d = c // h
    y = _ln(x, p["ln1/scale"], p["ln1/bias"], eps)
    qkv = N.mm(y, p["attn/qkv/kernel"], precision) + p["attn/qkv/bias"]
    qkv = qkv.reshape(t, 3, h, d)
    q, k, v = (jnp.swapaxes(qkv[:, i], 0, 1) for i in range(3))  # [H,T,D]
    scores = N.mm(q, jnp.swapaxes(k, 1, 2), precision) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    y = jnp.swapaxes(N.mm(probs, v, precision), 0, 1).reshape(t, c)
    x = x + N.mm(y, p["attn/out/kernel"], precision) + p["attn/out/bias"]
    y = _ln(x, p["ln2/scale"], p["ln2/bias"], eps)
    y = _gelu_tanh(N.mm(y, p["mlp/fc1/kernel"], precision)
                   + p["mlp/fc1/bias"])
    return x + N.mm(y, p["mlp/fc2/kernel"], precision) + p["mlp/fc2/bias"]


def logits_fn(params: dict, tokens, s: dict, precision: str):
    """tokens [T] int32 -> logits [T, V] float32."""
    import jax

    t = tokens.shape[0]
    x = params["embed/embedding"][tokens] + params["pos_embed"][0, :t]
    stacked = {k[len("blocks/"):]: v for k, v in params.items()
               if k.startswith("blocks/")}
    block = jax.checkpoint(lambda x, p: _block(x, p, s, precision))
    x, _ = jax.lax.scan(lambda x, p: (block(x, p), None), x, stacked)
    x = _ln(x, params["ln/scale"], params["ln/bias"],
            s["layer_norm_epsilon"])
    return N.mm(x, params["embed/embedding"].T, precision)


def loss_and_grads_fn(cfg: dict, section: str, precision: str):
    """``f(params, x [B,T], y, key) -> (loss, grads)``: mean next-token
    cross-entropy over every row, the rows taken one at a time."""
    import jax
    import jax.numpy as jnp

    s = sizes(cfg, section)

    def row_loss(params, row):
        lg = logits_fn(params, row, s, precision)[:-1]
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], axis=-1))

    @jax.jit
    def f(params, x, y, key):
        del y, key                       # targets are the shifted inputs
        vg = jax.value_and_grad(row_loss)

        def body(acc, row):
            loss, g = vg(params, row)
            return (acc[0] + loss,
                    jax.tree_util.tree_map(jnp.add, acc[1], g)), None

        zero = (jnp.float32(0), jax.tree_util.tree_map(jnp.zeros_like,
                                                       params))
        (loss, grads), _ = jax.lax.scan(body, zero, x)
        n = x.shape[0]
        return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)

    return f


def token_gaps_fn(cfg: dict, section: str):
    """``f(params, tokens [T], precision) -> (gap [T-1], low_gap [T-1])``
    under the float32 reference's logits: ``gap[j]`` is how far the
    logit of ``tokens[j+1]`` lies below the best at position ``j``;
    ``low_gap[j]`` the same for the token the lower precision puts
    first there (zeros when the precision is float32)."""
    import functools

    import jax
    import jax.numpy as jnp

    s = sizes(cfg, section)

    @functools.partial(jax.jit, static_argnums=2)
    def f(params, tokens, precision):
        lg = logits_fn(params, tokens, s, "float32")[:-1]
        best = jnp.max(lg, axis=-1)
        served = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
        if precision == "float32":
            return best - served, jnp.zeros_like(best)
        low = jnp.argmax(logits_fn(params, tokens, s, precision)[:-1], -1)
        low_lg = jnp.take_along_axis(lg, low[:, None], axis=-1)[:, 0]
        return best - served, best - low_lg

    return f
