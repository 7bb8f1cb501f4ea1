"""Plain reference for command-a-plus-05-2026's language model
(``model_type`` ``cohere2_moe``), as the configuration file sizes and
cuts it.

PARALLEL blocks: one LayerNorm a layer (mean subtracted, a weight, no
bias) feeds attention and the expert layer side by side, ``x <- x +
attn(n) + moe(n)``; a final LayerNorm; the head is the embedding
(tied), times ``logit_scale``. No bias anywhere.

Attention is grouped-query (query head ``h`` reads KV head ``h //
group``), no per-head norm, no gate, by layer kind (``layer_types``):

- ``sliding_attention``: rotary positions on the leading ``rotary_pct``
  of each head of q and k in the INTERLEAVED layout — pairs ``(x_2j,
  x_2j+1)`` rotated by ``pos * theta ** (-2j / d)`` — and query ``t``
  sees keys ``t - sliding_window < s <= t`` (the token itself counts).
- ``full_attention``: NO positions on q or k; query ``t`` sees every
  ``s <= t``.

Every layer routes each token to ``num_experts_per_tok`` of
``num_experts_published`` experts by SIGMOID scores over all of them
(no selection bias, no scaling factor; the chosen scores renormalised)
beside ``num_shared_experts`` shared experts whose outputs are AVERAGED
and added; one expert, routed or shared, is ``down(silu(gate u) * up
u)`` of width ``intermediate_size``. THE SHARE: this chip holds the
experts ``held_experts`` of each layer and a slice of the vocabulary; a
layer's routed part is the sum over the chosen experts that are held
here, the others' part is left out, and that partial result goes on to
the next layer — in the program and here alike.

Float32, every matrix product through ``_numerics.mm`` at ``highest``
precision, no cache, no kernel, one sequence at a time; attention in
query blocks over ALL keys (a mask, no window arithmetic on the keys),
the held experts one at a time, the shared experts one at a time (the
program multiplies them as one product; their weights lie side by side
in ``shared_*``: expert j is columns ``j f .. (j + 1) f``). The router
is float32 in the configuration's stated precision, so the control
leaves it as it is and rounds the products the program makes in
bfloat16. The weights are the bfloat16 values the program is handed
(``weights.py``, the seed), widened where a product takes them. It
imports nothing of the program. The readings that are inferences are
listed under ``assumed`` in the configuration file.
"""

from __future__ import annotations

from benchmark import weights
from benchmark.reference import _numerics as N

_Q_BLOCK = 64            # queries per block of attention


def sizes(cfg: dict, section: str) -> dict:
    out = dict(cfg)
    out.update(cfg.get(section, {}).get("overrides", {}))
    out["layer_types"] = list(out["layer_types"][:out["num_hidden_layers"]])
    return out


def param_spec(cfg: dict, section: str) -> dict:
    """``{path: (shape, kind, std)}`` of the tree the program holds.
    ``moe/shared_gate`` / ``shared_up`` are ``[hidden, n_shared * f]``
    and ``shared_down`` ``[n_shared * f, hidden]``: the shared experts'
    hidden columns side by side. No ``head`` (tied), no ``ln2`` (one
    norm a block), no router bias."""
    s = sizes(cfg, section)
    c, v = s["hidden_size"], s["vocab_size"]
    w = lambda *shape: (shape, "normal", 0.02)  # noqa: E731
    one = lambda n: ((n,), "ones", 0.0)         # noqa: E731
    spec = {"embed/embedding": w(v, c), "ln": one(c)}
    held, e = len(s["held_experts"]), s["num_experts_published"]
    f, fs = s["intermediate_size"], s["num_shared_experts"] \
        * s["intermediate_size"]
    h, hkv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    for i in range(len(s["layer_types"])):
        b = f"block{i:02d}"
        spec.update({
            f"{b}/ln1": one(c),
            f"{b}/attn/q_proj": w(c, h * d), f"{b}/attn/k_proj": w(c, hkv * d),
            f"{b}/attn/v_proj": w(c, hkv * d), f"{b}/attn/o_proj": w(h * d, c),
            f"{b}/moe/router": w(c, e),
            f"{b}/moe/experts_gate": w(held, c, f),
            f"{b}/moe/experts_up": w(held, c, f),
            f"{b}/moe/experts_down": w(held, f, c),
            f"{b}/moe/shared_gate": w(c, fs), f"{b}/moe/shared_up": w(c, fs),
            f"{b}/moe/shared_down": w(fs, c)})
    return spec


def make_params(cfg: dict, section: str, seed: int) -> dict:
    """Flat ``{path: array}`` in the program's parameter dtype, bit-equal
    to what the program is handed."""
    import jax

    spec = param_spec(cfg, section)
    dtype = cfg["param_dtype"]

    @jax.jit
    def build(key):
        return {p: weights.make_leaf(key, p, shape, kind, std, dtype)
                for p, (shape, kind, std) in spec.items()}

    return build(weights.seed_key(seed))


# -- arithmetic ---------------------------------------------------------------

def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def layer_norm(x, w, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(w)


def rope_pairs(x, pos, theta, rot):
    """Interleaved rotary embedding of the leading ``rot`` dims of ``x``
    [T, heads, d] at ``pos`` [T]: pair j = ``(x_2j, x_2j+1)``, turned by
    ``pos * theta ** (-2j / rot)``. The turned pairs come back
    DE-INTERLEAVED — all first members, then all second members, then
    the dims past ``rot`` — which is one fixed permutation of a head's
    dims: applied to q and k alike, no score sees it (and nothing else
    reads a rotated q or k)."""
    import jax.numpy as jnp

    freq = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = (pos.astype(jnp.float32)[:, None] * freq)[:, None, :]
    a, b = x[..., 0:rot:2], x[..., 1:rot:2]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang),
                            x[..., rot:]], -1)


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def _gated(u, gate, up, down, precision):
    """``down(silu(gate u) * up u)``; the barriers keep the three
    products apart (dots3-note-prev.py says why)."""
    import jax

    a, b = jax.lax.optimization_barrier(
        (N.mm(u, _f32(gate), precision), N.mm(u, _f32(up), precision)))
    h = jax.lax.optimization_barrier(_silu(a) * b)
    return N.mm(h, _f32(down), precision)


def _query_blocks(t: int) -> int:
    return _Q_BLOCK if t % _Q_BLOCK == 0 else t


def attention(u, p, s, kind, precision):
    """One attention layer of ``kind`` on the normed input ``u``
    [T, C]."""
    import jax
    import jax.numpy as jnp

    h, hkv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    grp, t, pos = h // hkv, u.shape[0], jnp.arange(u.shape[0])
    sliding = kind == "sliding_attention"
    q = N.mm(u, _f32(p["q_proj"]), precision).reshape(t, h, d)
    k = N.mm(u, _f32(p["k_proj"]), precision).reshape(t, hkv, d)
    v = N.mm(u, _f32(p["v_proj"]), precision).reshape(t, hkv, d)
    if sliding:
        rot, theta = int(d * s["rotary_pct"]), float(s["rope_theta"])
        q, k = rope_pairs(q, pos, theta, rot), rope_pairs(k, pos, theta, rot)
    k_t = jnp.transpose(k, (1, 2, 0))                  # [hkv, d, T]
    v_h = jnp.swapaxes(v, 0, 1)                        # [hkv, T, d]
    bq = _query_blocks(t)

    def block(i):
        q_b = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)       # [bq, h, d]
        q_b = jnp.transpose(q_b.reshape(bq, hkv, grp, d), (1, 2, 0, 3))
        sc = N.mm(q_b, k_t[:, None], precision) / d ** 0.5  # [hkv,grp,bq,T]
        at = (i * bq + jnp.arange(bq))[:, None]
        keep = pos[None, :] <= at
        if sliding:
            keep = keep & (pos[None, :] > at - s["sliding_window"])
        prob = jax.nn.softmax(jnp.where(keep[None, None], sc, -1e30), -1)
        o = N.mm(prob, v_h[:, None], precision)            # [hkv,grp,bq,d]
        return jnp.transpose(o, (2, 0, 1, 3)).reshape(bq, h, d)

    o = jax.lax.map(block, jnp.arange(t // bq)).reshape(t, h * d)
    return N.mm(o, _f32(p["o_proj"]), precision)


def routed_part(u, p, s, precision, held=None):
    """The chosen experts that are ``held`` (ids into the router's
    outputs; the parameters' own by default), one at a time."""
    import jax
    import jax.numpy as jnp

    held = s["held_experts"] if held is None else held
    score = jax.nn.sigmoid(jnp.matmul(
        u, _f32(p["router"]), precision=jax.lax.Precision.HIGHEST))
    chosen, idx = jax.lax.top_k(score, s["num_experts_per_tok"])
    weight = chosen / jnp.sum(chosen, -1, keepdims=True)          # [T, k]

    def one(acc, xs):
        e, gate, up, down = xs
        w_e = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=-1)  # [T]
        return acc + w_e[:, None] * _gated(u, gate, up, down, precision), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.asarray(held, jnp.int32), p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    return acc


def shared_part(u, p, s, precision):
    """The mean of the shared experts' outputs, one expert at a time."""
    n, f = s["num_shared_experts"], s["intermediate_size"]
    total = 0.0
    for j in range(n):
        cols = slice(j * f, (j + 1) * f)
        total = total + _gated(u, p["shared_gate"][:, cols],
                               p["shared_up"][:, cols],
                               p["shared_down"][cols], precision)
    return total / n


def expert_layer(u, p, s, precision, held=None):
    """The expert layer's share on ``u`` [T, C]."""
    return routed_part(u, p, s, precision, held) \
        + shared_part(u, p, s, precision)


def _sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def logits_fn(params: dict, tokens, s: dict, precision: str):
    """tokens [T] int32 -> logits [T, V held] float32."""
    eps = s["layer_norm_eps"]
    x = _f32(params["embed/embedding"][tokens])
    for i, kind in enumerate(s["layer_types"]):
        p = _sub(params, f"block{i:02d}/")
        n = layer_norm(x, p["ln1"], eps)
        x = x + attention(n, _sub(p, "attn/"), s, kind, precision) \
            + expert_layer(n, _sub(p, "moe/"), s, precision)
    h = layer_norm(x, params["ln"], eps)
    return s["logit_scale"] * N.mm(h, _f32(params["embed/embedding"]).T,
                                   precision)


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
