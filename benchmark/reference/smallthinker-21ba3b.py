"""Plain reference for SmallThinker-21BA3B-Instruct's language model, as
the configuration file sizes and cuts it, for TRAINING: forward, the
next-token loss and every gradient.

One layer, on the residual stream ``x`` [T, C] float32 that enters it
(written from the published ``config.json`` keys and the family's
public description; ``kind`` from ``rope_layout`` /
``sliding_window_layout``, which agree layer for layer)::

    r      = x W_r                     # router logits, from the block's
                                       # INPUT, before the attention norm
    u      = RMSNorm(x; w_1)
    q,k,v  = u W_q [T,H,D], u W_k [T,Hkv,D], u W_v [T,Hkv,D]
    sliding layer: q,k <- RoPE(q,k), rotate-half over the whole head;
                   key s visible to query t iff t - window < s <= t
    global  layer: no positions;       key s visible iff s <= t
    o      = softmax(q k^T / sqrt(D) + mask) v,  query head h on KV
             head h // (H / Hkv)
    h      = x + o W_o
    n      = RMSNorm(h; w_2)
    idx    = top-k of r;  g = softmax(r[idx])
    y      = h + sum_{e in idx} g_e W_down,e (relu(n W_gate,e) * (n W_up,e))

then a final RMSNorm and an untied head; the loss is next-token
cross-entropy, a mean over the positions that have a target. No bias,
no per-head norm, no gate, no shared expert, no dense layer.

THE SHARE: this chip holds the experts ``held_experts`` of each layer
and a slice of the vocabulary; a layer's expert part is the sum over
the chosen experts that are held here, the others' part is left out,
and that partial result goes on — in the program and here alike.

Departures from the published description, each also an ``assumed``
line of the configuration file: the router reads the raw block input
(the family's public implementations take the logits from the layer's
input before the attention norm); rotate-half rotary layout over all
``head_dim`` dims; the window counts the token itself; no auxiliary
balance loss (the config has no coefficient); the description's
"secondary experts" have no key in the config and are not built.

Float32, every product through ``_numerics.mm`` at ``highest``
precision, no kernel, no sort, no grouped product: one sequence at a
time, attention dense and masked with the queries in blocks (each block
recomputed in the backward, as is each layer, so that no ``[heads, T,
T]`` tensor stands whole: 7.5 GB a layer at 8192), the held experts one
at a time over every token under a one-hot weight. The router is
float32 in the configuration's stated precision, so the control leaves
it as it is and rounds the products the program makes in bfloat16. It
imports nothing of the program.

WHERE THE ARRAYS LIVE: as ``glm-4.7-flash.py`` — ``_numerics.three_steps``
keeps parameters, their first values, Adam's two moments and the
gradients and donates nothing, more than the chip has for 656.5 M
parameters; so ``make_params`` hands the tree over on the HOST's device
(made on the default device first: the program's weights are made
there, and the two must be bit-equal), Adam runs where its arguments
are, and ``loss_and_grads_fn`` carries the parameters to the default
device, computes there and brings the gradients back. On the CPU both
devices are one and nothing moves.
"""

from __future__ import annotations

import math

from benchmark import weights
from benchmark.reference import _numerics as N

_Q_BLOCK = 128           # queries per block of attention


def sizes(cfg: dict, section: str) -> dict:
    out = dict(cfg)
    out.update(cfg.get(section, {}).get("overrides", {}))
    n = out["num_hidden_layers"]
    if out["rope_layout"][:n] != out["sliding_window_layout"][:n]:
        raise ValueError("a layer with rotary positions is a windowed layer")
    out["sliding"] = [bool(on) for on in out["rope_layout"][:n]]
    return out


def param_spec(cfg: dict, section: str) -> dict:
    """``{path: (shape, kind, std)}`` of the tree the program holds: the
    router is the BLOCK's (it reads the block's input), the expert
    layer holds the ``held_experts`` alone. Embedding rows are N(0, 1)
    and the two projections that write to the residual stream
    (``o_proj``, ``experts_down``) N(0, 0.02 / sqrt(2 x published
    depth)): the router reads the RAW stream, and with N(0, 0.02)
    everywhere the stream's common component outgrows its token-specific
    one by the third layer — every token then chooses the same six
    experts, a layer's held share is 17-49 % by the seed and the step's
    time with it (the configuration's ``assumed`` has the readings)."""
    s = sizes(cfg, section)
    c, v = s["hidden_size"], s["vocab_size"]
    h, hkv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    e, f = s["moe_num_primary_experts_published"], s["moe_ffn_hidden_size"]
    held = len(s["held_experts"])
    w = lambda *shape: (shape, "normal", 0.02)  # noqa: E731
    out = lambda *shape: (shape, "normal", 0.02 / math.sqrt(  # noqa: E731
        2 * s["num_hidden_layers_published"]))
    ones = lambda n: ((n,), "ones", 0.0)        # noqa: E731
    spec = {"embed/embedding": ((v, c), "normal", 1.0), "ln": ones(c),
            "head": w(c, v)}
    for i in range(s["num_hidden_layers"]):
        b = f"block{i:02d}"
        spec.update({
            f"{b}/ln1": ones(c), f"{b}/ln2": ones(c), f"{b}/router": w(c, e),
            f"{b}/attn/q_proj": w(c, h * d), f"{b}/attn/k_proj": w(c, hkv * d),
            f"{b}/attn/v_proj": w(c, hkv * d), f"{b}/attn/o_proj": out(h * d, c),
            f"{b}/moe/experts_gate": w(held, c, f),
            f"{b}/moe/experts_up": w(held, c, f),
            f"{b}/moe/experts_down": out(held, f, c)})
    return spec


def _host():
    import jax

    return jax.devices("cpu")[0]


def _moved(tree: dict, device) -> dict:
    """``tree``'s leaves on ``device``, carried over one at a time, each
    waited for and its source freed where that is another device's (the
    transfers' staging stays one leaf large)."""
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
    """Rotate-half rotary embedding of ``x`` [T, heads, d] at positions
    ``pos`` [T]: pair j is ``(x_j, x_{j + d/2})``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (pos.astype(jnp.float32)[:, None] * freq)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention(u, p, s, sliding: bool, precision):
    """One attention layer on the normed input ``u`` [T, C]."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    h, hkv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    grp, window, pos = h // hkv, s["sliding_window_size"], jnp.arange(t)
    q = N.mm(u, p["q_proj"], precision).reshape(t, h, d)
    k = N.mm(u, p["k_proj"], precision).reshape(t, hkv, d)
    v = N.mm(u, p["v_proj"], precision).reshape(t, hkv, d)
    if sliding:
        q, k = (_rope(x, pos, float(s["rope_theta"])) for x in (q, k))
    k_t = jnp.transpose(k, (1, 2, 0))                            # [Hkv, d, T]
    v_t = jnp.swapaxes(v, 0, 1)                                  # [Hkv, T, d]
    bq = _Q_BLOCK if t % _Q_BLOCK == 0 else t

    @jax.checkpoint
    def block(i):
        qpos = i * bq + jnp.arange(bq)
        q_b = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)        # [bq, H, d]
        # query head n * grp + g reads KV head n
        q_b = jnp.transpose(q_b.reshape(bq, hkv, grp, d),
                            (1, 2, 0, 3)).reshape(hkv, grp * bq, d)
        sc = N.mm(q_b, k_t, precision).reshape(hkv, grp, bq, t)
        keep = pos[None, :] <= qpos[:, None]
        if sliding:                      # the token itself counts
            keep = keep & (pos[None, :] > qpos[:, None] - window)
        prob = jax.nn.softmax(jnp.where(keep, sc / math.sqrt(d), -1e30), -1)
        o = N.mm(prob.reshape(hkv, grp * bq, t), v_t, precision)
        return jnp.transpose(o.reshape(hkv, grp, bq, d),
                             (2, 0, 1, 3)).reshape(bq, h * d)

    o = jax.lax.map(block, jnp.arange(t // bq)).reshape(t, h * d)
    return N.mm(o, p["o_proj"], precision)


def _reglu(n, gate, up, down, precision):
    """``down(relu(gate n) * up n)``; the barriers change no value (they
    keep the three products apart, as ``glm-4.7-flash.py`` says why)."""
    import jax
    import jax.numpy as jnp

    a, b = jax.lax.optimization_barrier(
        (N.mm(n, gate, precision), N.mm(n, up, precision)))
    hid = jax.lax.optimization_barrier(jnp.maximum(a, 0.0) * b)
    return N.mm(hid, down, precision)


def expert_layer(n, r, p, s, precision, held=None):
    """The expert layer's share on ``n`` [T, C] under the router's
    logits ``r`` [T, E]: the chosen experts that are ``held`` (ids into
    the router's outputs; the configuration's by default), one at a
    time over every token under a one-hot weight. No shared expert."""
    import jax
    import jax.numpy as jnp

    held = s["held_experts"] if held is None else held
    top, idx = jax.lax.top_k(r, s["moe_num_active_primary_experts"])
    weight = jax.nn.softmax(top, axis=-1)                         # [T, k]

    def one(acc, xs):
        e, gate, up, down = xs
        w_e = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=-1)  # [T]
        return acc + w_e[:, None] * _reglu(n, gate, up, down, precision), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        jnp.asarray(held, jnp.int32), p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    return acc


def _sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _layer(x, p, s, sliding, precision):
    eps = s["rms_norm_eps"]
    # the router's own product stays float32 whatever the control rounds
    r = N.mm(x, p["router"], "float32")
    x = x + attention(_rms(x, p["ln1"], eps), _sub(p, "attn/"), s, sliding,
                      precision)
    return x + expert_layer(_rms(x, p["ln2"], eps), r, _sub(p, "moe/"), s,
                            precision)


def logits_fn(params: dict, tokens, s: dict, precision: str):
    """tokens [T] int32 -> logits [T, V held]."""
    import jax

    x = params["embed/embedding"][tokens]
    for i, sliding in enumerate(s["sliding"]):
        x = jax.checkpoint(                  # recomputed in the backward
            lambda x_, p_, sl=sliding: _layer(x_, p_, s, sl, precision))(
            x, _sub(params, f"block{i:02d}/"))
    return N.mm(_rms(x, params["ln"], s["rms_norm_eps"]), params["head"],
                precision)


def row_loss(params: dict, row, s: dict, precision: str):
    """Next-token cross-entropy of one sequence, a mean over the
    positions that have a target."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits_fn(params, row, s, precision)[:-1], -1)
    return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], axis=-1))


def loss_and_grads_fn(cfg: dict, section: str, precision: str):
    """``f(params, x [B,T], y, key) -> (loss, grads)``: the mean over
    the rows, taken one at a time; parameters and gradients rest on the
    host's device, the computation runs on the default one."""
    import jax

    s = sizes(cfg, section)

    @jax.jit
    def on_device(params, x):
        vg = jax.value_and_grad(lambda p, row: row_loss(p, row, s, precision))
        loss, grads = vg(params, x[0])
        for row in x[1:]:                # (a batch of one adds nothing)
            more, g = vg(params, row)
            loss = loss + more
            grads = jax.tree_util.tree_map(lambda a, b: a + b, grads, g)
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
