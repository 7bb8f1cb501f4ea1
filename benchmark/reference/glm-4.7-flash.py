"""Plain reference for GLM-4.7-Flash's language model with its
multi-token-prediction module, as the configuration file sizes and cuts
it, for TRAINING: forward, both losses and every gradient.

Every layer equation is DeepSeek-V3's (arXiv:2412.19437) at this
model's sizes. Pre-RMSNorm blocks, an untied head. Attention (section
2.1), the same on every layer, UNABSORBED: ``c_q = RMSNorm(x W_dq)``,
``[q_n; q_r] = c_q W_uq``, ``[c_kv; k_r] = x W_dkv``, ``c_kv =
RMSNorm(c_kv)``, rotate-half rotary positions on ``q_r`` and on the one
``k_r`` all heads share, ``[k_n; v] = c_kv W_ukv``, causal softmax of
``(q_n . k_n + q_r . k_r) / sqrt(d_n + d_r)``, ``W_o``. No indexer, no
gate, no latent rescale. Layer 0 has a dense gated-SiLU MLP; later
layers route each token to ``num_experts_per_tok`` of
``n_routed_experts_published`` experts by sigmoid scores (section
2.1.2: a bias chooses, the unbiased scores weigh, renormalised over the
chosen, times ``routed_scaling_factor``) beside one shared expert.
Multi-token prediction, depth 1 (section 2.2): ``h' = W_eh
[RMSNorm_e(Emb(t_{i+1})); RMSNorm_h(h_i)]`` with ``h_i`` the trunk's
last block output before its final norm, one more whole expert block,
a final RMSNorm of its own, the trunk's embedding and head; loss =
CE(trunk, t_{i+1}) + ``mtp_loss_weight`` x CE(module, t_{i+2}), each a
mean over the positions that have its target.

THE SHARE: this chip holds the experts ``held_experts`` of each layer
and a slice of the vocabulary; a layer's routed part is the sum over
the chosen experts that are held here, the others' part is left out,
and that partial result goes on — in the program and here alike.

Float32, every product through ``_numerics.mm`` at ``highest``
precision, no kernel, no sort, no grouped product: one sequence at a
time, queries in blocks (each block recomputed in the backward, as is
each layer, so that no ``[heads, T, T]`` tensor stands whole: 5.4 GB a
layer at 8192), the held experts one at a time over every token with a
one-hot weight. It imports nothing of the program.

WHERE THE ARRAYS LIVE: ``_numerics.three_steps`` keeps the parameters,
their first values, Adam's two moments and the gradients, and its
update donates nothing: 2 x 12 B a parameter at its peak, 17 GB for
this configuration's 706.5 M, more than the chip has. So
``make_params`` hands the tree over on the HOST's device (made on the
default device first: the program's weights are made there, and the
two must be bit-equal), ``three_steps``' Adam then runs where its
arguments are, and ``loss_and_grads_fn`` carries the parameters to the
default device, computes there and brings the gradients back. On the
CPU both devices are one and nothing moves.
"""

from __future__ import annotations

import math

from benchmark import weights
from benchmark.reference import _numerics as N

_Q_BLOCK = 128           # queries per block of attention
# As dots3-note-prev's file argues: the selection bias has to be there
# (it chooses, the unbiased scores weigh), and small, because a deployed
# noaux_tc bias balances the load where a random one of 0.1 unbalances it.
_BIAS_STD = 0.005


def sizes(cfg: dict, section: str) -> dict:
    out = dict(cfg)
    out.update(cfg.get(section, {}).get("overrides", {}))
    return out


def _block_spec(s: dict, prefix: str, dense: bool) -> dict:
    c, h = s["hidden_size"], s["num_attention_heads"]
    rq, rkv = s["q_lora_rank"], s["kv_lora_rank"]
    dn, dr, dv = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                  s["v_head_dim"])
    w = lambda *shape: (shape, "normal", 0.02)  # noqa: E731
    ones = lambda n: ((n,), "ones", 0.0)  # noqa: E731
    spec = {"ln1": ones(c), "ln2": ones(c),
            "attn/dq": w(c, rq), "attn/q_norm": ones(rq),
            "attn/uq": w(rq, h * (dn + dr)), "attn/dkv": w(c, rkv + dr),
            "attn/kv_norm": ones(rkv), "attn/ukv": w(rkv, h * (dn + dv)),
            "attn/out": w(h * dv, c)}
    if dense:
        d = s["intermediate_size"]
        spec.update({"mlp_gate": w(c, d), "mlp_up": w(c, d),
                     "mlp_down": w(d, c)})
    else:
        e, f = s["n_routed_experts_published"], s["moe_intermediate_size"]
        held = len(s["held_experts"])
        spec.update({
            "moe/router": w(c, e),
            "moe/router_bias": ((e,), "normal", _BIAS_STD),
            "moe/experts_gate": w(held, c, f), "moe/experts_up": w(held, c, f),
            "moe/experts_down": w(held, f, c),
            "moe/shared_gate": w(c, f), "moe/shared_up": w(c, f),
            "moe/shared_down": w(f, c)})
    return {f"{prefix}/{k}": v for k, v in spec.items()}


def param_spec(cfg: dict, section: str) -> dict:
    """``{path: (shape, kind, std)}`` of the tree the program holds."""
    s = sizes(cfg, section)
    c, v = s["hidden_size"], s["vocab_size"]
    spec = {"embed/embedding": ((v, c), "normal", 0.02),
            "ln": ((c,), "ones", 0.0), "head": ((c, v), "normal", 0.02)}
    for i in range(s["num_hidden_layers"]):
        spec.update(_block_spec(s, f"block{i:02d}",
                                i < s["first_k_dense_replace"]))
    if s["num_nextn_predict_layers"]:
        spec.update({"mtp/enorm": ((c,), "ones", 0.0),
                     "mtp/hnorm": ((c,), "ones", 0.0),
                     "mtp/eh_proj": ((2 * c, c), "normal", 0.02),
                     "mtp/ln": ((c,), "ones", 0.0)})
        spec.update(_block_spec(s, "mtp/block", False))
    return spec


def _host():
    import jax

    return jax.devices("cpu")[0]


def _moved(tree: dict, device) -> dict:
    """``tree``'s leaves on ``device``, carried over one at a time, each
    waited for and its source freed where that is another device's: the
    transfers' staging stays one leaf large instead of one tree large
    (the host holds Adam's 23 GB and the TPU runtime's 14 already, of
    the machine's 45 GiB)."""
    import jax

    out = {}
    for path in list(tree):
        leaf = tree.pop(path)
        out[path] = jax.block_until_ready(jax.device_put(leaf, device))
        if device == _host() and leaf.devices() != {device}:
            leaf.delete()                # a device's temporary, now here
    return out


def make_params(cfg: dict, section: str, seed: int) -> dict:
    """Flat ``{path: float32 array}``, bit-equal to what the program is
    handed, resting on the host's device (see the module's text)."""
    import jax

    spec = param_spec(cfg, section)

    @jax.jit
    def build(key):
        return {p: weights.make_leaf(key, p, shape, kind, std)
                for p, (shape, kind, std) in spec.items()}

    return _moved(build(weights.seed_key(seed)), _host())


# -- arithmetic ---------------------------------------------------------------

def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


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


def _gated(u, gate, up, down, precision):
    """``down(silu(gate u) * up u)``. The barriers change no value: they
    keep the three products apart (the TPU compiler's fusion of quantise
    -> product -> SiLU -> quantise made NaN of finite float8 operands;
    PERF.md section 6, PR 27)."""
    import jax

    a, b = jax.lax.optimization_barrier(
        (N.mm(u, gate, precision), N.mm(u, up, precision)))
    h = jax.lax.optimization_barrier(a * jax.nn.sigmoid(a) * b)
    return N.mm(h, down, precision)


def attention(u, p, s, precision):
    """One attention layer on the normed input ``u`` [T, C]."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    h, rkv = s["num_attention_heads"], s["kv_lora_rank"]
    dn, dr, dv = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                  s["v_head_dim"])
    eps, theta, pos = s["rms_norm_eps"], float(s["rope_theta"]), jnp.arange(t)
    c_q = _rms(N.mm(u, p["dq"], precision), p["q_norm"], eps)
    q = N.mm(c_q, p["uq"], precision).reshape(t, h, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], pos, theta)
    kv = N.mm(u, p["dkv"], precision)
    c_kv = _rms(kv[:, :rkv], p["kv_norm"], eps)
    k_r = _rope(kv[:, rkv:], pos, theta)                         # [T, dr]
    kv_up = N.mm(c_kv, p["ukv"], precision).reshape(t, h, dn + dv)
    k_n, v = kv_up[..., :dn], kv_up[..., dn:]
    bq = _Q_BLOCK if t % _Q_BLOCK == 0 else t

    @jax.checkpoint
    def block(i):
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * bq, bq)  # noqa: E731
        qpos = i * bq + jnp.arange(bq)
        sc = (N.mm(jnp.swapaxes(sl(q_n), 0, 1),
                   jnp.transpose(k_n, (1, 2, 0)), precision)
              + N.mm(jnp.swapaxes(sl(q_r), 0, 1), k_r.T, precision))
        sc = sc / math.sqrt(dn + dr)                              # [h, bq, T]
        keep = pos[None, :] <= qpos[:, None]
        prob = jax.nn.softmax(jnp.where(keep[None], sc, -1e30), axis=-1)
        return jnp.swapaxes(N.mm(prob, jnp.swapaxes(v, 0, 1), precision),
                            0, 1)                                 # [bq, h, dv]

    o = jax.lax.map(block, jnp.arange(t // bq)).reshape(t, h * dv)
    return N.mm(o, p["out"], precision)


def expert_layer(u, p, s, precision, held=None):
    """The expert layer's share on ``u`` [T, C]: the chosen experts
    that are ``held`` (ids into the router's outputs; the
    configuration's by default), one at a time over every token under a
    one-hot weight, plus the shared expert."""
    import jax
    import jax.numpy as jnp

    held = s["held_experts"] if held is None else held
    k = s["num_experts_per_tok"]
    score = jax.nn.sigmoid(N.mm(u, p["router"], precision))       # [T, E]
    _, idx = jax.lax.top_k(score + p["router_bias"], k)
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


def _layer(x, p, s, dense, precision):
    eps = s["rms_norm_eps"]
    x = x + attention(_rms(x, p["ln1"], eps), _sub(p, "attn/"), s, precision)
    u = _rms(x, p["ln2"], eps)
    if dense:
        return x + _gated(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                          precision)
    return x + expert_layer(u, _sub(p, "moe/"), s, precision)


def logits_fn(params: dict, tokens, s: dict, precision: str):
    """tokens [T] int32 -> (logits [T, V held], logits of the token
    after next [T, V held] or None without the module). The module's
    last position has no next token: it takes the first, and the loss
    leaves it out."""
    import jax
    import jax.numpy as jnp

    eps = s["rms_norm_eps"]

    def layer(x, p, dense):              # recomputed in the backward
        return jax.checkpoint(
            lambda x_, p_: _layer(x_, p_, s, dense, precision))(x, p)

    embed = params["embed/embedding"]
    x = embed[tokens]
    for i in range(s["num_hidden_layers"]):
        x = layer(x, _sub(params, f"block{i:02d}/"),
                  i < s["first_k_dense_replace"])
    head = lambda h: N.mm(h, params["head"], precision)  # noqa: E731
    logits = head(_rms(x, params["ln"], eps))
    if not s["num_nextn_predict_layers"]:
        return logits, None
    m = _sub(params, "mtp/")
    joined = jnp.concatenate([_rms(embed[jnp.roll(tokens, -1)], m["enorm"],
                                   eps), _rms(x, m["hnorm"], eps)], -1)
    y = layer(N.mm(joined, m["eh_proj"], precision), _sub(m, "block/"),
              False)
    return logits, head(_rms(y, m["ln"], eps))


def row_losses(params: dict, row, s: dict, precision: str):
    """``(next-token loss, loss of the token after next)`` of one
    sequence, each a mean over the positions that have its target."""
    import jax
    import jax.numpy as jnp

    def ce(lg, targets):
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))

    lg, ahead = logits_fn(params, row, s, precision)
    main = ce(lg[:-1], row[1:])
    return main, (ce(ahead[:-2], row[2:]) if ahead is not None
                  else jnp.float32(0))


def loss_and_grads_fn(cfg: dict, section: str, precision: str):
    """``f(params, x [B,T], y, key) -> (loss, grads)``: the mean over
    the rows, taken one at a time, of next-token loss +
    ``mtp_loss_weight`` x the module's; parameters and gradients rest
    on the host's device, the computation runs on the default one."""
    import jax
    import jax.numpy as jnp

    s = sizes(cfg, section)
    lam = s["mtp_loss_weight"]

    def row_loss(params, row):
        main, ahead = row_losses(params, row, s, precision)
        return main + lam * ahead

    @jax.jit
    def on_device(params, x):
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

    def f(params, x, y, key):
        del y, key                       # targets are the shifted inputs
        device, host = jax.devices()[0], _host()
        if device == host:
            return on_device(params, x)
        loss, grads = on_device(_moved(dict(params), device),
                                jax.device_put(x, device))
        grads = _moved(grads, host)
        print(f"# reference step: loss {float(loss)!r}, host peak RSS "
              f"{_peak_rss_gb():.1f} GB", flush=True)
        return loss, grads

    return f


def _peak_rss_gb() -> float:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
