"""Plain reference for Qwen3-Next-80B-A3B's language model, as the
configuration file sizes and cuts it.

Pre-norm blocks with ZERO-CENTRED RMSNorm weights (``1 + w``), an untied
head, no bias anywhere. Per layer one of two mixers (``layer_types``):

- ``linear_attention``: the gated delta rule. One packed projection
  gives, per KEY head, ``q``, ``k`` and its ``r`` value heads' ``v`` and
  ``z``; a second gives ``b`` and ``a``. ``q, k, v`` pass a depthwise
  causal convolution (kernel 4, zeros before the sequence) and SiLU;
  ``q`` and ``k`` are repeated to the value heads and l2-normalised,
  ``q`` scaled by ``d_k ** -0.5``; ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``. Per value head a state ``S``
  [d_k, d_v], zero before the sequence, and TOKEN BY TOKEN
  (``lax.scan`` over positions, no chunks)::

      S <- exp(g_t) S;  delta = beta_t (v_t - S^T k_t);
      S <- S + k_t delta^T;  o_t = S^T q_t

  then ``o_t`` RMS-normed per head (a plain weight), times ``silu(z_t)``,
  and the output projection.
- ``full_attention``: grouped-query causal softmax attention (query head
  ``h`` reads KV head ``h // group``), the query projection twice as
  wide: per head a query and a gate. ``norm0`` per head on q and k,
  rotate-half rotary positions on the first ``partial_rotary_factor`` of
  each head, ``out = (attention * sigmoid(gate)) W_o``.

Every layer routes each token to ``num_experts_per_tok`` of
``num_experts_published`` experts by softmax scores over all of them
(the chosen scores renormalised; no bias, no scaling) beside one shared
expert whose output is multiplied by ``sigmoid(u . w)``. THE SHARE: this
chip holds the experts ``held_experts`` of each layer and a slice of the
vocabulary; a layer's routed part is the sum over the chosen experts
that are held here, the others' part is left out, and that partial
result goes on to the next layer — in the program and here alike.

Float32, every matrix product through ``_numerics.mm`` at ``highest``
precision, no cache, no kernel, one sequence at a time; attention in
query blocks, the held experts one at a time. The recurrence itself
(``S^T k``, the rank-one update, ``S^T q``), ``b``/``a``, the
convolution and the router are float32 in the configuration's stated
precision, so the control leaves them as they are and rounds the
products the program makes in bfloat16. The weights are the bfloat16
values the program is handed (``weights.py``, the seed), widened where a
product takes them. It imports nothing of the program. The readings that
are inferences are listed under ``assumed`` in the configuration file.
"""

from __future__ import annotations

from benchmark import weights
from benchmark.reference import _numerics as N

_Q_BLOCK = 128           # queries per block of attention
# The harness draws a leaf as N(0, std), ones or zeros (weights.py). The
# family's own initialisation of these two (A ~ U(0, 16), dt log-uniform
# in [1e-3, 0.1]) is neither, so they are drawn wide around zero
# instead: per value head log(-g) = A_log + log softplus(a + dt_bias)
# then spreads over ~+-3, a fifth of the heads keep their state over
# hundreds of tokens (exp(g) > 0.94), two fifths over a few, the rest
# forget at once — the spread the published initialisation gives, about
# another centre (configuration file, ``assumed``).
_A_LOG_STD = 2.0
_DT_BIAS_STD = 3.0


def sizes(cfg: dict, section: str) -> dict:
    out = dict(cfg)
    out.update(cfg.get(section, {}).get("overrides", {}))
    out["layer_types"] = list(out["layer_types"][:out["num_hidden_layers"]])
    return out


def linear_sizes(s: dict) -> dict:
    hk, hv = s["linear_num_key_heads"], s["linear_num_value_heads"]
    dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
    return {"hk": hk, "hv": hv, "dk": dk, "dv": dv, "r": hv // hk,
            "taps": s["linear_conv_kernel_dim"],
            "conv": 2 * hk * dk + hv * dv}


def param_spec(cfg: dict, section: str) -> dict:
    """``{path: (shape, kind, std)}`` of the tree the program holds.
    ``linear_attn/in_proj_qkvz`` columns are the published checkpoint's
    packing: per key head ``[q (dk) | k (dk) | v (r dv) | z (r dv)]``;
    ``in_proj_ba``: per key head ``[b (r) | a (r)]``; ``conv`` is
    ``[tap, channel]`` over ``[q | k | v]`` flat; ``attn/q_proj``: per
    head ``[query (D) | gate (D)]``."""
    s = sizes(cfg, section)
    c, v = s["hidden_size"], s["vocab_size"]
    w = lambda *shape: (shape, "normal", 0.02)  # noqa: E731
    zc = lambda n: ((n,), "normal", 0.02)       # noqa: E731 — 1 + w norms
    spec = {"embed/embedding": w(v, c), "ln": zc(c), "head": w(c, v)}
    held, e = len(s["held_experts"]), s["num_experts_published"]
    f, fs = s["moe_intermediate_size"], s["shared_expert_intermediate_size"]
    z = linear_sizes(s)
    h, hkv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    for i, kind in enumerate(s["layer_types"]):
        b = f"block{i:02d}"
        spec.update({f"{b}/ln1": zc(c), f"{b}/ln2": zc(c)})
        if kind == "linear_attention":
            m = f"{b}/linear_attn"
            spec.update({
                f"{m}/in_proj_qkvz": w(c, 2 * z["hk"] * z["dk"]
                                       + 2 * z["hv"] * z["dv"]),
                f"{m}/in_proj_ba": w(c, 2 * z["hv"]),
                f"{m}/conv": w(z["taps"], z["conv"]),
                f"{m}/A_log": ((z["hv"],), "normal", _A_LOG_STD),
                f"{m}/dt_bias": ((z["hv"],), "normal", _DT_BIAS_STD),
                f"{m}/norm": ((z["dv"],), "ones", 0.0),
                f"{m}/out_proj": w(z["hv"] * z["dv"], c)})
        else:
            m = f"{b}/attn"
            spec.update({
                f"{m}/q_proj": w(c, h * 2 * d), f"{m}/k_proj": w(c, hkv * d),
                f"{m}/v_proj": w(c, hkv * d), f"{m}/o_proj": w(h * d, c),
                f"{m}/q_norm": zc(d), f"{m}/k_norm": zc(d)})
        spec.update({
            f"{b}/moe/router": w(c, e),
            f"{b}/moe/experts_gate": w(held, c, f),
            f"{b}/moe/experts_up": w(held, c, f),
            f"{b}/moe/experts_down": w(held, f, c),
            f"{b}/moe/shared_gate": w(c, fs), f"{b}/moe/shared_up": w(c, fs),
            f"{b}/moe/shared_down": w(fs, c),
            f"{b}/moe/shared_expert_gate": w(c, 1)})
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


def _norm0(x, w, eps):
    """RMSNorm with a zero-centred weight."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + _f32(w))


def _rope(x, pos, theta):
    """Rotate-half rotary embedding of ``x`` [T, heads, d] at ``pos``
    [T]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (pos.astype(jnp.float32)[:, None] * freq)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


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


def delta_rule(q, k, v, g, beta, state=None):
    """Token by token: ``q, k`` [T, H, dk], ``v`` [T, H, dv], ``g,
    beta`` [T, H] -> ``(o [T, H, dv], final state [H, dk, dv])``."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[:, None, None] * s
        m = jnp.einsum("hkv,hk->hv", s, k_t, precision=hi)
        delta = b_t[:, None] * (v_t - m)
        s = s + k_t[:, :, None] * delta[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=hi)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def linear_attention(u, p, s, precision):
    """One ``linear_attention`` layer on the normed input ``u`` [T, C]."""
    import jax
    import jax.numpy as jnp

    z = linear_sizes(s)
    hk, hv, dk, dv, r, taps = (z["hk"], z["hv"], z["dk"], z["dv"], z["r"],
                               z["taps"])
    t = u.shape[0]
    mixed = N.mm(u, _f32(p["in_proj_qkvz"]), precision).reshape(
        t, hk, 2 * dk + 2 * r * dv)
    q, k, v, gate = jnp.split(mixed, (dk, 2 * dk, 2 * dk + r * dv), -1)
    ba = jnp.matmul(u, _f32(p["in_proj_ba"]),
                    precision=jax.lax.Precision.HIGHEST).reshape(t, hk, 2 * r)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(t, hv))
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(
        ba[..., r:].reshape(t, hv) + _f32(p["dt_bias"]))
    flat = jnp.concatenate([x.reshape(t, -1) for x in (q, k, v)], -1)
    padded = jnp.pad(flat, ((taps - 1, 0), (0, 0)))
    conv = _silu(sum(_f32(p["conv"])[j] * padded[j:j + t]
                     for j in range(taps)))
    q, k, v = jnp.split(conv, (hk * dk, 2 * hk * dk), -1)

    def unit(x):
        x = jnp.repeat(x.reshape(t, hk, dk), r, axis=1)
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    o, _ = delta_rule(unit(q) / dk ** 0.5, unit(k), v.reshape(t, hv, dv),
                      g, beta)
    y = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                     + s["rms_norm_eps"]) * _f32(p["norm"])
    y = y * _silu(gate.reshape(t, hv, dv))
    return N.mm(y.reshape(t, hv * dv), _f32(p["out_proj"]), precision)


def _query_blocks(t: int) -> int:
    return _Q_BLOCK if t % _Q_BLOCK == 0 else t


def full_attention(u, p, s, precision):
    """One gated ``full_attention`` layer on the normed input ``u``
    [T, C]."""
    import jax
    import jax.numpy as jnp

    h, hkv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    grp, rot = h // hkv, int(d * s["partial_rotary_factor"])
    t, eps, pos = u.shape[0], s["rms_norm_eps"], jnp.arange(u.shape[0])
    theta = float(s["rope_theta"])
    qg = N.mm(u, _f32(p["q_proj"]), precision).reshape(t, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = N.mm(u, _f32(p["k_proj"]), precision).reshape(t, hkv, d)
    v = N.mm(u, _f32(p["v_proj"]), precision).reshape(t, hkv, d)

    def rotary(x):
        return jnp.concatenate([_rope(x[..., :rot], pos, theta),
                                x[..., rot:]], -1)

    q, k = rotary(_norm0(q, p["q_norm"], eps)), rotary(_norm0(k, p["k_norm"],
                                                              eps))
    k_t = jnp.transpose(k, (1, 2, 0))                  # [hkv, d, T]
    v_h = jnp.swapaxes(v, 0, 1)                        # [hkv, T, d]
    bq = _query_blocks(t)

    def block(i):
        q_b = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)       # [bq, h, d]
        q_b = jnp.transpose(q_b.reshape(bq, hkv, grp, d), (1, 2, 0, 3))
        sc = N.mm(q_b, k_t[:, None], precision) / d ** 0.5  # [hkv,grp,bq,T]
        keep = pos[None, :] <= (i * bq + jnp.arange(bq))[:, None]
        prob = jax.nn.softmax(jnp.where(keep[None, None], sc, -1e30), -1)
        o = N.mm(prob, v_h[:, None], precision)            # [hkv,grp,bq,d]
        return jnp.transpose(o, (2, 0, 1, 3)).reshape(bq, h, d)

    o = jax.lax.map(block, jnp.arange(t // bq)).reshape(t, h, d)
    o = o * jax.nn.sigmoid(gate)
    return N.mm(o.reshape(t, h * d), _f32(p["o_proj"]), precision)


def expert_layer(u, p, s, precision, held=None):
    """The expert layer's share on ``u`` [T, C]: the chosen experts
    that are ``held`` (ids into the router's outputs; the parameters'
    own by default), one at a time, plus the gated shared expert."""
    import jax
    import jax.numpy as jnp

    held = s["held_experts"] if held is None else held
    score = jax.nn.softmax(jnp.matmul(
        u, _f32(p["router"]), precision=jax.lax.Precision.HIGHEST), -1)
    chosen, idx = jax.lax.top_k(score, s["num_experts_per_tok"])
    weight = chosen / jnp.sum(chosen, -1, keepdims=True)          # [T, k]

    def one(acc, xs):
        e, gate, up, down = xs
        w_e = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=-1)  # [T]
        return acc + w_e[:, None] * _gated(u, gate, up, down, precision), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.asarray(held, jnp.int32), p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    return acc + shared_expert(u, p, precision)


def shared_expert(u, p, precision):
    import jax
    import jax.numpy as jnp

    opened = jax.nn.sigmoid(jnp.matmul(
        u, _f32(p["shared_expert_gate"]),
        precision=jax.lax.Precision.HIGHEST))
    return opened * _gated(u, p["shared_gate"], p["shared_up"],
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
        u = _norm0(x, p["ln1"], eps)
        if kind == "linear_attention":
            x = x + linear_attention(u, _sub(p, "linear_attn/"), s, precision)
        else:
            x = x + full_attention(u, _sub(p, "attn/"), s, precision)
        x = x + expert_layer(_norm0(x, p["ln2"], eps), _sub(p, "moe/"), s,
                             precision)
    return N.mm(_norm0(x, params["ln"], eps), _f32(params["head"]), precision)


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
