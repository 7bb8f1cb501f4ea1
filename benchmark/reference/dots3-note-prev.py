"""Plain reference for dots3-note-prev's language model, as the
configuration file sizes and cuts it.

Pre-RMSNorm blocks, rotate-half rotary positions, an untied head. Per
layer one of two attentions (``layer_types``), each gated per head by a
sigmoid of the block's normed input before the output projection:

- ``full_attention``: multi-head latent attention, UNABSORBED — the
  latents ``c_q`` and ``c_kv`` are up-projected to per-head queries,
  keys and values — with the learned sparse indexer: every query scores
  every earlier key through 64 small heads (ReLU, a learned weight per
  head), keeps the ``index_topk`` best and attends to those alone;
- ``sliding_attention``: the same latent attention with its own ranks
  and head sizes over the last ``sliding_window_size`` positions (the
  token itself counts), no indexer.

Layer 0 has a dense gated-SiLU MLP; later layers route each token to
``num_experts_per_tok`` of ``n_routed_experts_published`` experts by
sigmoid scores (a bias chooses, the unbiased scores weigh, renormalised
over the chosen) beside one shared expert. THE SHARE: this chip holds
the experts ``held_experts`` of each layer and a slice of the
vocabulary; a layer's routed part is the sum over the chosen experts
that are held here, the others' part is left out, and that partial
result goes on to the next layer — in the program and here alike.

Float32, every product through ``_numerics.mm`` at ``highest``
precision, no cache, no kernel, one sequence at a time; queries in
blocks so that no ``[heads, T, T]`` tensor exists, the held experts one
at a time. The weights are the bfloat16 values the program is handed
(``weights.py``, the seed), kept in bfloat16 and widened where a
product takes them: exact, and 4.09 B float32 parameters would not fit
the chip. It imports nothing of the program. The readings that are
inferences are listed under ``assumed`` in the configuration file.
"""

from __future__ import annotations

import math

from benchmark import weights
from benchmark.reference import _numerics as N

_Q_BLOCK = 128           # queries per block of attention
# The router's selection bias is drawn small: it has to be there (it
# chooses, the unbiased scores weigh), but a deployed ``noaux_tc`` bias
# is trained to BALANCE the experts' load, and a random one of 0.1
# unbalances it — the share of routed pairs that lands on this chip's 32
# experts then ranges 0.103-0.155 over seeds instead of 0.125, and both
# end-to-end metrics follow it (PERF.md section 6, PR 27).
_BIAS_STD = 0.005


def sizes(cfg: dict, section: str) -> dict:
    out = dict(cfg)
    out.update(cfg.get(section, {}).get("overrides", {}))
    out["layer_types"] = list(out["layer_types"][:out["num_hidden_layers"]])
    return out


def attn_sizes(s: dict, kind: str) -> dict:
    p = "" if kind == "full_attention" else "swa_"
    rq, rkv = s[p + "q_lora_rank"], s[p + "kv_lora_rank"]
    rescale = s["apply_mla_qkv_lora_rescale"]
    return {"h": s[p + "num_attention_heads"], "rq": rq, "rkv": rkv,
            "dn": s[p + "qk_nope_head_dim"], "dr": s[p + "qk_rope_head_dim"],
            "dv": s[p + "v_head_dim"], "theta": float(s[p + "rope_theta"]),
            "s_q": math.sqrt(s["hidden_size"] / rq) if rescale else 1.0,
            "s_kv": math.sqrt(s["hidden_size"] / rkv) if rescale else 1.0}


def param_spec(cfg: dict, section: str) -> dict:
    """``{path: (shape, kind, std)}`` of the tree the program holds."""
    s = sizes(cfg, section)
    c, v = s["hidden_size"], s["vocab_size"]
    w = lambda *shape: (shape, "normal", 0.02)  # noqa: E731
    ones = lambda n: ((n,), "ones", 0.0)  # noqa: E731
    spec = {"embed/embedding": w(v, c), "ln": ones(c), "head": w(c, v)}
    held = len(s["held_experts"])
    f = s["moe_intermediate_size"]
    for i, kind in enumerate(s["layer_types"]):
        z, b = attn_sizes(s, kind), f"block{i:02d}"
        spec.update({
            f"{b}/ln1": ones(c), f"{b}/ln2": ones(c),
            f"{b}/attn/dq": w(c, z["rq"]), f"{b}/attn/q_norm": ones(z["rq"]),
            f"{b}/attn/uq": w(z["rq"], z["h"] * (z["dn"] + z["dr"])),
            f"{b}/attn/dkv": w(c, z["rkv"] + z["dr"]),
            f"{b}/attn/kv_norm": ones(z["rkv"]),
            f"{b}/attn/ukv": w(z["rkv"], z["h"] * (z["dn"] + z["dv"])),
            f"{b}/attn/gate": w(c, z["h"]),
            f"{b}/attn/out": w(z["h"] * z["dv"], c)})
        if kind == "full_attention":
            hi, di = s["index_n_heads"], s["index_head_dim"]
            spec.update({
                f"{b}/attn/iq": w(z["rq"], hi * di),
                f"{b}/attn/ik": w(c, di), f"{b}/attn/iw": w(c, hi),
                f"{b}/attn/ik_norm_scale": ones(di),
                f"{b}/attn/ik_norm_bias": ((di,), "zeros", 0.0)})
        if i < s["first_k_dense_replace"]:
            d = s["intermediate_size"]
            spec.update({f"{b}/mlp_gate": w(c, d), f"{b}/mlp_up": w(c, d),
                         f"{b}/mlp_down": w(d, c)})
        else:
            e = s["n_routed_experts_published"]
            spec.update({
                f"{b}/moe/router": w(c, e),
                f"{b}/moe/router_bias": ((e,), "normal", _BIAS_STD),
                f"{b}/moe/experts_gate": w(held, c, f),
                f"{b}/moe/experts_up": w(held, c, f),
                f"{b}/moe/experts_down": w(held, f, c),
                f"{b}/moe/shared_gate": w(c, f), f"{b}/moe/shared_up": w(c, f),
                f"{b}/moe/shared_down": w(f, c)})
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


def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


def _layer_norm(x, scale, bias, eps=1e-5):
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(scale) + _f32(bias)


def _rope(x, pos, theta):
    """Rotate-half rotary embedding of ``x`` [T, ..., d] at positions
    ``pos`` [T] (any axes between are heads)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freq
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), d // 2)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def _gated(u, gate, up, down, precision):
    """``down(silu(gate u) * up u)``. The barriers change no value: they
    keep the three products apart, because the TPU compiler's fusion of
    quantise -> product -> SiLU -> quantise made NaN of finite float8
    operands in a jitted call where the same operations one by one, or
    on the CPU, did not (PERF.md section 6, PR 27)."""
    import jax

    a, b = jax.lax.optimization_barrier(
        (N.mm(u, _f32(gate), precision), N.mm(u, _f32(up), precision)))
    h = jax.lax.optimization_barrier(_silu(a) * b)
    return N.mm(h, _f32(down), precision)


def _query_blocks(t: int) -> int:
    return _Q_BLOCK if t % _Q_BLOCK == 0 else t


def _attention(u, p, s, kind, precision):
    """One attention layer on the normed input ``u`` [T, C]."""
    import jax
    import jax.numpy as jnp

    z = attn_sizes(s, kind)
    t = u.shape[0]
    h, dn, dr, dv, rkv = z["h"], z["dn"], z["dr"], z["dv"], z["rkv"]
    eps, pos = s["rms_norm_eps"], jnp.arange(t)
    c_q = z["s_q"] * _rms(N.mm(u, _f32(p["dq"]), precision), p["q_norm"], eps)
    q = N.mm(c_q, _f32(p["uq"]), precision).reshape(t, h, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], pos, z["theta"])
    kv = N.mm(u, _f32(p["dkv"]), precision)
    c_kv = z["s_kv"] * _rms(kv[:, :rkv], p["kv_norm"], eps)
    k_r = _rope(kv[:, rkv:], pos, z["theta"])                   # [T, dr]
    kv_up = N.mm(c_kv, _f32(p["ukv"]), precision).reshape(t, h, dn + dv)
    k_n, v = kv_up[..., :dn], kv_up[..., dn:]
    full = kind == "full_attention"
    if full:
        hi, di = s["index_n_heads"], s["index_head_dim"]
        q_i = N.mm(c_q, _f32(p["iq"]), precision).reshape(t, hi, di)
        q_i = jnp.concatenate([_rope(q_i[..., :dr], pos, z["theta"]),
                               q_i[..., dr:]], -1)
        k_i = _layer_norm(N.mm(u, _f32(p["ik"]), precision),
                          p["ik_norm_scale"], p["ik_norm_bias"])
        k_i = jnp.concatenate([_rope(k_i[:, :dr], pos, z["theta"]),
                               k_i[:, dr:]], -1)
        w_i = N.mm(u, _f32(p["iw"]), precision) / math.sqrt(hi)  # [T, hi]
    bq = _query_blocks(t)
    window, topk = s["sliding_window_size"], s["index_topk"]

    def block(i):
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * bq, bq)  # noqa: E731
        qpos = i * bq + jnp.arange(bq)
        keep = pos[None, :] <= qpos[:, None]                     # [bq, T]
        if full:
            sc = N.mm(jnp.swapaxes(sl(q_i), 0, 1), k_i.T, precision)
            sc = jnp.sum(jnp.maximum(sc, 0.0)
                         * jnp.swapaxes(sl(w_i), 0, 1)[:, :, None],
                         axis=0) / math.sqrt(di)                 # [bq, T]
            if topk < t:
                _, best = jax.lax.top_k(jnp.where(keep, sc, -jnp.inf), topk)
                chosen = jnp.zeros((bq, t), bool).at[
                    jnp.arange(bq)[:, None], best].set(True)
                keep = keep & chosen
        else:
            keep = keep & (pos[None, :] > qpos[:, None] - window)
        sc = (N.mm(jnp.swapaxes(sl(q_n), 0, 1),
                   jnp.transpose(k_n, (1, 2, 0)), precision)
              + N.mm(jnp.swapaxes(sl(q_r), 0, 1), k_r.T, precision))
        sc = sc / math.sqrt(dn + dr)                              # [h, bq, T]
        prob = jax.nn.softmax(jnp.where(keep[None], sc, -1e30), axis=-1)
        return jnp.swapaxes(N.mm(prob, jnp.swapaxes(v, 0, 1), precision),
                            0, 1)                                 # [bq, h, dv]

    o = jax.lax.map(block, jnp.arange(t // bq)).reshape(t, h, dv)
    g = jax.nn.sigmoid(N.mm(u, _f32(p["gate"]), precision))       # [T, h]
    return N.mm((o * g[:, :, None]).reshape(t, h * dv), _f32(p["out"]),
                precision)


def expert_layer(u, p, s, precision, held=None):
    """The expert layer's share on ``u`` [T, C]: the chosen experts
    that are ``held`` (ids into the router's outputs; the parameters'
    own by default), one at a time, plus the shared expert."""
    import jax
    import jax.numpy as jnp

    held = s["held_experts"] if held is None else held
    k = s["num_experts_per_tok"]
    score = jax.nn.sigmoid(N.mm(u, _f32(p["router"]), precision))  # [T, E]
    _, idx = jax.lax.top_k(score + _f32(p["router_bias"]), k)
    chosen = jnp.take_along_axis(score, idx, axis=-1)
    weight = s["routed_scaling_factor"] * chosen / jnp.sum(
        chosen, -1, keepdims=True)                                # [T, k]

    def one(acc, xs):
        e, gate, up, down = xs
        w_e = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=-1)  # [T]
        return acc + w_e[:, None] * _gated(u, gate, up, down, precision), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.asarray(held, jnp.int32), p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    return acc + _gated(u, p["shared_gate"], p["shared_up"],
                        p["shared_down"], precision)


def _sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def logits_fn(params: dict, tokens, s: dict, precision: str):
    """tokens [T] int32 -> logits [T, V held] float32."""
    eps = s["rms_norm_eps"]
    x = _f32(params["embed/embedding"][tokens])
    for i, kind in enumerate(s["layer_types"]):
        p = _sub(params, f"block{i:02d}/")
        x = x + _attention(_rms(x, p["ln1"], eps), _sub(p, "attn/"), s, kind,
                           precision)
        u = _rms(x, p["ln2"], eps)
        if i < s["first_k_dense_replace"]:
            x = x + _gated(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                           precision)
        else:
            x = x + expert_layer(u, _sub(p, "moe/"), s, precision)
    return N.mm(_rms(x, params["ln"], eps), _f32(params["head"]), precision)


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
