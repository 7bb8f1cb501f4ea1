"""The mixers beside latent attention under ``latent_lm``'s block and LM
shell: the delta rule of a hybrid decoder (``LatentArch.model_type``
``qwen3_next``; the benchmark's ``qwen3-next-80b-a3b`` is the worked
configuration) and ONE grouped-query attention whose properties are the
family's — the hybrid decoder's is gated, with per-head norms and
partial rotate-half rotary; the parallel family's (``cohere2_moe``; the
benchmark's ``command-a-plus-05-2026``) has neither gate nor norm, and
per layer kind interleaved rotary positions under a sliding window or no
positions at all.

- ``GatedDeltaNet`` (``linear_attention`` layers): linear attention by
  the gated delta rule. Per value head a state ``S`` [d_k, d_v] float32
  that every token decays, corrects and reads::

      S <- exp(g_t) S;  delta = beta_t (v_t - S^T k_t);
      S <- S + k_t delta^T;  o_t = S^T q_t

  with ``q, k, v`` from one packed projection through a depthwise causal
  convolution (kernel ``linear_conv_kernel_dim``) and SiLU, ``q`` and
  ``k`` l2-normalised, ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)``, and the output RMS-normed per head and gated
  by ``silu(z)``. What a sequence leaves behind is FIXED in size: ``S``
  and the last ``kernel - 1`` rows of the convolution's input — a row of
  the engine's state pool, one per slot, beside the page pools.
- ``GroupedQueryAttention`` (``full_attention`` and, in the parallel
  family, ``sliding_attention`` layers): grouped-query softmax
  attention (``num_attention_heads`` queries over
  ``num_key_value_heads`` keys/values of ``head_dim``); gate, per-head
  norms, rotary share and layout, positions or none, window or none
  are ``LatentArch.gqa(kind)``'s. Its cache is paged: ``k`` (after
  norm and rotary) and ``v``, ``num_key_value_heads * head_dim``
  numbers each a token; a sliding layer keeps every position in pages
  too (one page table) and says how far back it reads.

Each mixer states what it keeps (``cache_spec``): numbers a token keeps
in page pools, and arrays a SLOT keeps in the state pool. The engine and
the model's gauges read that statement; nothing else knows a cache by
name.

Two forms of the delta rule, one mathematics. A call of one token per
row is the recurrence once, against the row's state. A wider call (a
``[1, bucket]`` prefill, a plain forward) takes ``_CHUNK`` positions at
a time in the WY form: inside a chunk the corrections ``delta`` solve a
unit-lower-triangular system (``(I + A) Delta = beta (V - Gamma K
S_0)``, ``A_ij = beta_i exp(G_i - G_j) k_i.k_j`` for ``j < i``), whose
two right-hand sides do not depend on the carried state and are solved
for every chunk at once; a ``lax.scan`` over the chunks then carries
``S`` with three small products a chunk. A row whose first position is
0 starts from a zero state whatever its pool row holds (so a slot's
next tenant needs no reset pass); positions at or past ``lengths`` and
rows that are not ``active`` get ``beta = 0, g = 0`` and change no
state, and the convolution's tail is taken at the last real token.

Precision: the projections take bfloat16 operands and accumulate in
float32; ``b, a`` (from the block's float32 normed input), ``g``,
``beta``, the convolution, the normalisations, the state and every
product of the recurrence are float32 (``highest``): the state is a sum
over thousands of tokens, and a decay ``exp(g)`` rounded to 8 bits is a
different recurrence.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from tpunet.models.latent_lm import (LatentArch, _block, lane_rounded,
                                     rms_norm, rope)
from tpunet.models.moe import by_row
from tpunet.ops.attention import _NEG_INF

_CHUNK = 64              # positions per chunk of the wide delta rule
_Q_BLOCK = 512           # queries per block over a row's pooled keys
_HI = lax.Precision.HIGHEST


def _ein(spec, *xs):
    return jnp.einsum(spec, *xs, precision=_HI,
                      preferred_element_type=jnp.float32)


# -- the delta rule -----------------------------------------------------------

def gdn_step(q, k, v, g, beta, s):
    """The recurrence once: ``q, k`` [B, H, dk], ``v`` [B, H, dv],
    ``g, beta`` [B, H], ``s`` [B, H, dk, dv], all float32 ->
    ``(o [B, H, dv], new s)``. One pass over ``s`` gives ``S^T k`` and
    ``S^T q`` together (``o = exp(g) S^T q + (k.q) delta``), a second
    writes the new state."""
    decay = jnp.exp(g)[..., None]
    both = _ein("bhkv,bhjk->bhjv", s, jnp.stack([k, q], axis=2))
    delta = beta[..., None] * (v - decay * both[:, :, 0])
    o = decay * both[:, :, 1] + jnp.sum(k * q, -1, keepdims=True) * delta
    return o, decay[..., None] * s + k[..., :, None] * delta[..., None, :]


def gdn_sequential(q, k, v, g, beta, s):
    """``gdn_step`` over the positions of ``q, k`` [B, T, H, dk], ``v``
    [B, T, H, dv], ``g, beta`` [B, T, H] in turn (what the chunked form
    is held to in the tests)."""
    def body(s, xs):
        o, s = gdn_step(*xs, s)
        return s, o

    s, o = lax.scan(body, s, tuple(jnp.swapaxes(x, 0, 1)
                                   for x in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), s


def gdn_chunked(q, k, v, g, beta, s, chunk: int = 0):
    """The same over ``chunk`` positions at a time (the module's text);
    T is padded up to a whole chunk with positions that change nothing.
    Shapes as ``gdn_sequential``."""
    chunk = chunk or _CHUNK
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad))
                                    + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def chunks(x):           # [B, T, H, ...] -> [B, H, n, C, ...]
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    big_g = jnp.cumsum(g, axis=-1)                     # [B, H, n, C]
    at = jnp.arange(chunk)
    upto = at[:, None] >= at[None, :]                  # j <= i
    # exp(G_i - G_j) for j <= i: the exponent is never positive
    decay = jnp.exp(jnp.where(upto, big_g[..., :, None]
                              - big_g[..., None, :], -jnp.inf))
    a = jnp.where(at[:, None] > at[None, :],
                  beta[..., None] * decay * _ein("...id,...jd->...ij", k, k),
                  0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v,
         (beta * jnp.exp(big_g))[..., None] * k], -1)  # [.., C, dv + dk]
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(chunk, dtype=a.dtype), rhs, lower=True,
        unit_diagonal=True)
    u, w = solved[..., :dv], solved[..., dv:]
    qk = jnp.where(upto, _ein("...id,...jd->...ij", q, k) * decay, 0.0)
    to_end = jnp.exp(big_g[..., -1:] - big_g)          # [B, H, n, C]

    def body(s, xs):
        u_c, w_c, q_c, k_c, qk_c, g_c, end_c = xs
        delta = u_c - _ein("bhck,bhkv->bhcv", w_c, s)
        o = (jnp.exp(g_c)[..., None] * _ein("bhck,bhkv->bhcv", q_c, s)
             + _ein("bhcj,bhjv->bhcv", qk_c, delta))
        s = (jnp.exp(g_c[..., -1])[..., None, None] * s
             + _ein("bhck,bhcv->bhkv", k_c * end_c[..., None], delta))
        return s, o

    s, o = lax.scan(body, s, tuple(jnp.moveaxis(x, 2, 0) for x in
                                   (u, w, q, k, qk, big_g, to_end)))
    o = jnp.moveaxis(o, 0, 2)                          # [B, H, n, C, dv]
    return jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, dv)[:, :t], s


def _pool_rows(pool, rows, b: int):
    """The state of the call's ``b`` batch rows: ``pool[rows]``, or the
    pool as it lies when batch row i IS pool row i (``rows`` None and as
    many batch rows as the pool has: the engine's width-1 step, which
    then neither gathers nor scatters the pool)."""
    if rows is None and pool.shape[0] == b:
        return pool
    return pool[jnp.arange(b) if rows is None else rows]


def _put_rows(var, rows, new):
    if rows is None and var.value.shape[0] == new.shape[0]:
        var.value = new
    else:
        at = jnp.arange(new.shape[0]) if rows is None else rows
        var.value = var.value.at[at].set(new)


class GatedDeltaNet(nn.Module):
    """One ``linear_attention`` layer on the block's float32 normed
    input ``u`` [B, T, C]; see the module's text. ``state_rows`` [B]
    int32 names each batch row's row of the state pool (None: row i),
    ``lengths`` [B] int32 how many of the call's T positions are real
    (None: all)."""

    arch: LatentArch
    kind: str = "linear_attention"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def sizes(a: LatentArch) -> dict:
        hk, hv = a.linear_num_key_heads, a.linear_num_value_heads
        dk, dv = a.linear_key_head_dim, a.linear_value_head_dim
        return {"hk": hk, "hv": hv, "dk": dk, "dv": dv, "r": hv // hk,
                "taps": a.linear_conv_kernel_dim,
                "conv": 2 * hk * dk + hv * dv}

    @classmethod
    def cache_spec(cls, a: LatentArch, kind: str, dtype) -> dict:
        z = cls.sizes(a)
        return {"paged": {}, "decode_kernel": False,
                "state": {"state": ((z["hv"], z["dk"], z["dv"]),
                                    jnp.float32),
                          "conv": ((z["taps"] - 1, z["conv"]),
                                   jnp.float32)}}

    @nn.compact
    def __call__(self, u, decode: bool = False, positions=None,
                 active=None, paged_kv=None, page_table=None,
                 train: bool = False, state_rows=None, lengths=None):
        if train:
            raise ValueError("the delta rule's scan has no backward here: "
                             "latent_lm trains full layers only")
        a, z = self.arch, self.sizes(self.arch)
        hk, hv, dk, dv, r = z["hk"], z["hv"], z["dk"], z["dv"], z["r"]
        taps, conv_dim = z["taps"], z["conv"]
        b, t, c = u.shape
        dt, f32 = self.dtype, jnp.float32
        init = nn.initializers.normal(stddev=0.02)

        def w(name, *shape, init=init):
            return self.param(name, init, shape, self.param_dtype)

        # the published checkpoint's packing: per KEY head its q, k and
        # its r value heads' v and z; per key head its r b's, then a's
        w_qkvz = w("in_proj_qkvz", c, 2 * hk * dk + 2 * hv * dv)
        w_ba = w("in_proj_ba", c, 2 * hv)
        w_conv = w("conv", taps, conv_dim)
        a_log, dt_bias = w("A_log", hv), w("dt_bias", hv)
        w_norm = w("norm", dv, init=nn.initializers.ones)
        w_out = w("out_proj", hv * dv, c)

        pool = tail = None
        if decode:
            spec = self.cache_spec(a, self.kind, dt)["state"]
            is_init = not self.has_variable("cache", "state")
            pool, tail = (self.variable("cache", name, jnp.zeros,
                                        (b, *shape), dtype)
                          for name, (shape, dtype) in spec.items())
            if is_init:              # the pool has the init batch's rows
                return jnp.zeros_like(u)
        if positions is None:
            positions = jnp.zeros((b,), jnp.int32)

        with jax.named_scope("tpunet_gdn"):
            p = jnp.dot(u.astype(dt), w_qkvz.astype(dt),
                        preferred_element_type=f32)
            p = p.reshape(b, t, hk, 2 * dk + 2 * r * dv)
            q, k, v, gate = jnp.split(p, (dk, 2 * dk, 2 * dk + r * dv), -1)
            ba = jnp.dot(u.astype(f32), w_ba.astype(f32),
                         precision=_HI).reshape(b, t, hk, 2 * r)
            beta = jax.nn.sigmoid(ba[..., :r].reshape(b, t, hv))
            g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                ba[..., r:].reshape(b, t, hv) + dt_bias.astype(f32))
            real = jnp.ones((b, t), bool)
            if lengths is not None:
                real = jnp.arange(t)[None, :] < lengths[:, None]
            if active is not None:
                real = real & active[:, None]
            beta = jnp.where(real[..., None], beta, 0.0)
            g = jnp.where(real[..., None], g, 0.0)

            # a row that starts at position 0 starts from nothing; a row
            # with no real position (inactive) keeps what it holds
            n_real = jnp.sum(real, axis=1)
            fresh = (positions == 0) & (n_real > 0)
            if pool is not None:
                held_s = _pool_rows(pool.value, state_rows, b)
                held_tail = _pool_rows(tail.value, state_rows, b)
                s0 = jnp.where(fresh[:, None, None, None], 0.0, held_s)
                tail0 = jnp.where(fresh[:, None, None], 0.0, held_tail)
            else:
                s0 = jnp.zeros((b, hv, dk, dv), f32)
                tail0 = jnp.zeros((b, taps - 1, conv_dim), f32)

            mixed = jnp.concatenate(
                [tail0, jnp.concatenate([x.reshape(b, t, -1)
                                         for x in (q, k, v)], -1)], 1)
            conv = sum(w_conv[j].astype(f32) * mixed[:, j:j + t]
                       for j in range(taps))
            conv = conv * jax.nn.sigmoid(conv)                    # SiLU
            q, k, v = jnp.split(conv, (hk * dk, 2 * hk * dk), -1)

            def unit(x):     # [B, T, hk*dk] -> hv l2-normalised heads
                x = jnp.repeat(x.reshape(b, t, hk, dk), r, axis=2)
                return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                     + 1e-6)

            q, k = unit(q) * dk ** -0.5, unit(k)
            v = v.reshape(b, t, hv, dv)
            with jax.named_scope("tpunet_gdn_scan"):
                if t == 1:
                    o, s = gdn_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0], s0)
                    o = o[:, None]
                else:
                    o, s = gdn_chunked(q, k, v, g, beta, s0)
            if pool is not None:
                # the tail at the last real token: rows len .. len+taps-2
                # of ``mixed`` (which leads with the taps-1 earlier rows)
                new_tail = jax.vmap(lambda m, n: lax.dynamic_slice_in_dim(
                    m, n, taps - 1))(mixed, n_real)
                keep = (n_real > 0)
                _put_rows(pool, state_rows,
                          jnp.where(keep[:, None, None, None], s, held_s))
                _put_rows(tail, state_rows,
                          jnp.where(keep[:, None, None], new_tail,
                                    held_tail))
            y = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + a.rms_norm_eps) * w_norm.astype(f32)
            gate = gate.reshape(b, t, hv, dv)
            y = (y * gate * jax.nn.sigmoid(gate)).astype(dt)
            return jnp.dot(y.reshape(b, t, hv * dv), w_out.astype(dt))


# -- grouped-query attention ----------------------------------------------------

class GroupedQueryAttention(nn.Module):
    """One grouped-query softmax-attention layer on the block's normed
    input ``u`` [B, T, C]: ``num_attention_heads`` queries over
    ``num_key_value_heads`` keys/values of ``head_dim``, query head h on
    KV head ``h // group``. What else it has is the family's, read from
    ``LatentArch.gqa(kind)``: a sigmoid gate per output number from the
    query projection's second half, zero-centred RMSNorm per head on q
    and k, rotary positions on the leading ``rotary`` dims of a head in
    the rotate-half or the interleaved layout — or no positions at all —
    and a ``window`` of keys a query looks back over (itself counted).
    The cache is paged: ``k`` (after norm and rotary) and ``v``.

    Three calls: one token per row against the paged pool (the kernel
    ``tpunet_paged_decode`` where ``paged_decode.kernel_applies``, else
    the row's pages gathered and dense; a window starts the kernel's
    walk at the chunk that holds the oldest key in sight); a wider call
    whose rows all start at position 0 (a prefill with no adopted
    prefix, a plain forward) through the flash kernel over the call's
    own tokens (``flash_prefill``: K and V read by head group, a band
    of blocks under a window); a wider call that continues a row (an
    adopted prefix) over the row's pooled keys in position order,
    queries in blocks, a windowed block over its own stretch of keys."""

    arch: LatentArch
    kind: str = "full_attention"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @classmethod
    def cache_spec(cls, a: LatentArch, kind: str, dtype) -> dict:
        z = a.gqa(kind)
        width = lane_rounded(a.num_key_value_heads * a.head_dim)
        return {"paged": {z["cache"]: (2 * width, dtype)}, "state": {},
                "decode_kernel": True, "window": z["window"]}

    @nn.compact
    def __call__(self, u, decode: bool = False, positions=None,
                 active=None, paged_kv=None, page_table=None,
                 train: bool = False, state_rows=None, lengths=None):
        a, z = self.arch, self.arch.gqa(self.kind)
        h, hkv, d = a.num_attention_heads, a.num_key_value_heads, a.head_dim
        grp, rot, window = h // hkv, z["rotary"], z["window"]
        b, t, c = u.shape
        dt, eps, scale = self.dtype, a.rms_norm_eps, d ** -0.5
        u = u.astype(dt)
        init = nn.initializers.normal(stddev=0.02)

        def w(name, *shape, init=init):
            return self.param(name, init, shape, self.param_dtype)

        w_q = w("q_proj", c, h * (2 * d if z["gated"] else d))
        w_k, w_v = w("k_proj", c, hkv * d), w("v_proj", c, hkv * d)
        w_o = w("o_proj", h * d, c)
        qn, kn = ((w(n, d, init=nn.initializers.zeros)
                   for n in ("q_norm", "k_norm")) if z["qk_norm"]
                  else (None, None))
        if positions is None:
            positions = jnp.zeros((b,), jnp.int32)
        pos_t = positions[:, None] + jnp.arange(t)[None, :]

        def placed(x, norm):
            """A head's numbers as they are scored (and cached)."""
            if norm is not None:
                x = rms_norm(x, norm, eps, offset=1.0)
            if not rot:
                return x
            turned = rope(x[..., :rot], pos_t, float(a.rope_theta),
                          interleaved=z["layout"] == "interleaved")
            return turned if rot == d else jnp.concatenate(
                [turned, x[..., rot:]], -1)

        with jax.named_scope(z["scope"]):
            q = jnp.dot(u, w_q.astype(dt)).reshape(b, t, h, -1)
            q, gate = q[..., :d], (q[..., d:] if z["gated"] else None)
            k = jnp.dot(u, w_k.astype(dt)).reshape(b, t, hkv, d)
            v = jnp.dot(u, w_v.astype(dt)).reshape(b, t, hkv, d)
            q, k = placed(q, qn), placed(k, kn)

        def project_out(o):
            with jax.named_scope(z["scope"]):
                if gate is not None:
                    o = o.astype(jnp.float32) * jax.nn.sigmoid(
                        gate.astype(jnp.float32))
                return jnp.dot(o.astype(dt).reshape(b, t, h * d),
                               w_o.astype(dt))

        def own_tokens():
            from tpunet.ops.flash import flash_prefill
            with jax.named_scope(z["scope"]):
                return flash_prefill(q, k, v, scale=scale,
                                     window=window).astype(dt)

        if not decode:
            return project_out(own_tokens())

        if paged_kv is None:
            raise ValueError("latent_lm keeps its cache in pages: decode "
                             "needs paged_kv and a page table (the serve "
                             "engine's default)")
        if paged_kv.quantized:
            raise ValueError("latent_lm has no int8 page payload")
        pt = paged_kv.page_tokens
        store = paged_kv.store_dtype(dt)
        hd, wide = hkv * d, lane_rounded(hkv * d)
        is_init = not self.has_variable("cache", "cached_k")
        ck, cv = (self.variable("cache", name, jnp.zeros,
                                (paged_kv.pages * pt, wide), store)
                  for name in ("cached_k", "cached_v"))
        if is_init:
            return jnp.zeros_like(u)
        if page_table is None:
            raise ValueError("paged decode requires engine-owned per-row "
                             "positions and a page table")

        with jax.named_scope(z["scope"]):
            page = jnp.take_along_axis(
                page_table, jnp.clip(pos_t // pt, 0, page_table.shape[1] - 1),
                axis=1)
            new = page * pt + pos_t % pt
            if active is not None:
                new = jnp.where(active[:, None], new, 0)   # the garbage page
            for var, rows in ((ck, k), (cv, v)):
                rows = jnp.pad(rows.reshape(b * t, hd),
                               ((0, 0), (0, wide - hd)))
                var.value = var.value.at[new.reshape(-1)].set(
                    rows.astype(store))
        k_max = page_table.shape[1] * pt

        def pooled(table):
            """One row's keys and values in position order."""
            rows = (table[:, None] * pt + jnp.arange(pt)).reshape(k_max)
            return tuple(jnp.take(var.value, rows, axis=0)[:, :hd]
                         .astype(dt).reshape(k_max, hkv, d)
                         for var in (ck, cv))

        def attend(q_, qpos, kf, vf, first=0):
            """``q_`` [Q, H, D] at positions ``qpos`` over a row's
            pooled keys from position ``first`` on -> [Q, H, D]
            float32."""
            n = q_.shape[0]
            s = jnp.einsum("qngd,knd->ngqk", q_.reshape(n, hkv, grp, d), kf,
                           preferred_element_type=jnp.float32) * scale
            kpos = first + jnp.arange(kf.shape[0])
            keep = kpos[None, :] <= qpos[:, None]
            if window:
                keep = keep & (kpos[None, :] > qpos[:, None] - window)
            p = jax.nn.softmax(jnp.where(keep[None, None], s, _NEG_INF), -1)
            o = jnp.einsum("ngqk,knd->qngd", p.astype(dt), vf,
                           preferred_element_type=jnp.float32)
            return o.reshape(n, h, d)

        if t == 1:
            from tpunet.ops import paged_decode
            with jax.named_scope(z["scope"]):
                if paged_decode.kernel_applies(paged_kv, t, store):
                    live = positions + 1
                    if active is not None:
                        live = jnp.where(active, live, 0)
                    o = paged_decode.paged_decode_attention(
                        q[:, 0], ck.value, cv.value, page_table, live,
                        page_tokens=pt, scale=scale, kv_heads=hkv,
                        window=window)[:, None]
                else:
                    o = jax.vmap(lambda q_, pos, table: attend(
                        q_, pos[None], *pooled(table)))(
                            q[:, 0][:, None], positions, page_table)
            return project_out(o)

        def continued():
            # (blocks sized to the score tensor: 512 queries of 16 heads)
            bq = _block(t, max(8, _Q_BLOCK * 16 // h))
            kb = min(k_max, bq + window - 1) if window else k_max

            def row(q_, start, table):
                kf, vf = pooled(table)

                def block(xs):
                    q_b, qpos_b = xs
                    if kb == k_max:
                        return attend(q_b, qpos_b, kf, vf)
                    first = jnp.clip(qpos_b[0] - (window - 1), 0,
                                     k_max - kb)
                    return attend(
                        q_b, qpos_b,
                        lax.dynamic_slice_in_dim(kf, first, kb),
                        lax.dynamic_slice_in_dim(vf, first, kb), first)

                o = lax.map(block, (
                    q_.reshape(t // bq, bq, h, d),
                    (start + jnp.arange(t)).reshape(t // bq, bq)))
                return o.reshape(t, h, d).astype(dt)
            with jax.named_scope(z["scope"]):
                return by_row(row, active, q, positions, page_table)

        return project_out(lax.cond(jnp.all(positions == 0), own_tokens,
                                    continued))


GatedAttention = GroupedQueryAttention   # the hybrid family's layers are gated
