"""The training augmentation of the CIFAR-10 -> 224px reference workload,
written out plainly: per image, from one key, horizontal flip ->
rotation by +-``rotation_degrees`` at the 32px source (three shears,
edge fill) -> colour jitter (brightness, contrast, saturation, hue, in
that order) -> random-resized crop and resize to ``image_size`` as two
bilinear matrices -> the rotated frame's coverage mask -> normalise.

A copy, in ``jax.numpy`` only, of the specification the program's
``tpunet/data/augment.py`` implements (same draws from the same key, in
the same order), kept with the benchmark so that a later change to the
program cannot move the yardstick. It imports nothing of the program.
All parameters come from the configuration file's ``augment`` section.
"""

from __future__ import annotations

import math

SRC = 32
_GRAY = (0.299, 0.587, 0.114)


def _hat(s, src):
    import jax.numpy as jnp

    s = jnp.clip(s, 0.0, src - 1.0)
    j = jnp.arange(src, dtype=jnp.float32)
    return jnp.maximum(0.0, 1.0 - jnp.abs(s[..., None] - j))


def _bilinear(start, size, out, src):
    import jax.numpy as jnp

    i = jnp.arange(out, dtype=jnp.float32)
    return _hat(start + (i + 0.5) * size / out - 0.5, src)


def _rot_coords(h, w, angle):
    import jax.numpy as jnp

    cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
    yy, xx = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return (cos * (yy - cy) + sin * (xx - cx) + cy,
            -sin * (yy - cy) + cos * (xx - cx) + cx)


def _rotate_shear(img, angle):
    import jax.numpy as jnp

    h, w = img.shape[0], img.shape[1]
    cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
    i = jnp.arange(w, dtype=jnp.float32)
    mx = _hat(i[None, :] + (-jnp.tan(angle / 2.0)
                            * (jnp.arange(h, dtype=jnp.float32) - cy))[:, None],
              w)
    my = _hat(jnp.arange(h, dtype=jnp.float32)[None, :]
              + (jnp.sin(angle) * (i - cx))[:, None], h)
    img = jnp.einsum("hij,hjc->hic", mx, img)
    img = jnp.einsum("wij,jwc->iwc", my, img)
    return jnp.einsum("hij,hjc->hic", mx, img)


def _border_mask(size, angle):
    import jax.numpy as jnp

    sy, sx = _rot_coords(size, size, angle)

    def cov(s):
        i0 = jnp.floor(s)
        f = s - i0
        v0 = ((i0 >= 0) & (i0 <= size - 1)).astype(jnp.float32)
        v1 = ((i0 + 1 >= 0) & (i0 + 1 <= size - 1)).astype(jnp.float32)
        return (1.0 - f) * v0 + f * v1

    return cov(sy) * cov(sx)


def _rgb_to_hsv(x):
    import jax.numpy as jnp

    r, g, b = x[..., 0], x[..., 1], x[..., 2]
    maxc, minc = jnp.max(x, -1), jnp.min(x, -1)
    d = maxc - minc
    safe = jnp.where(d == 0, 1.0, d)
    s = jnp.where(maxc == 0, 0.0, d / jnp.where(maxc == 0, 1.0, maxc))
    rc, gc, bc = (maxc - r) / safe, (maxc - g) / safe, (maxc - b) / safe
    h = jnp.where(maxc == r, bc - gc,
                  jnp.where(maxc == g, 2.0 + rc - bc, 4.0 + gc - rc))
    return jnp.where(d == 0, 0.0, (h / 6.0) % 1.0), s, maxc


def _hsv_to_rgb(h, s, v):
    import jax
    import jax.numpy as jnp

    i = jnp.floor(h * 6.0)
    f = h * 6.0 - i
    i = i.astype(jnp.int32) % 6
    p, q, t = v * (1 - s), v * (1 - f * s), v * (1 - (1 - f) * s)
    hot = jax.nn.one_hot(i, 6, dtype=v.dtype)
    pick = lambda *c: (jnp.stack(c, -1) * hot).sum(-1)  # noqa: E731
    return jnp.stack([pick(v, q, p, p, t, v), pick(t, v, v, q, p, p),
                      pick(p, p, t, v, v, q)], -1)


def _jitter(key, x, a):
    import jax
    import jax.numpy as jnp

    gray_w = jnp.asarray(_GRAY, jnp.float32)
    kb, kc, ks, kh = jax.random.split(key, 4)
    u = lambda k, lo, hi: jax.random.uniform(k, (), minval=lo, maxval=hi)  # noqa: E731
    if a["jitter_brightness"] > 0:
        x = jnp.clip(x * u(kb, 1 - a["jitter_brightness"],
                           1 + a["jitter_brightness"]), 0.0, 1.0)
    if a["jitter_contrast"] > 0:
        c = u(kc, 1 - a["jitter_contrast"], 1 + a["jitter_contrast"])
        x = jnp.clip(c * x + (1 - c) * jnp.mean(x @ gray_w), 0.0, 1.0)
    if a["jitter_saturation"] > 0:
        s = u(ks, 1 - a["jitter_saturation"], 1 + a["jitter_saturation"])
        x = jnp.clip(s * x + (1 - s) * (x @ gray_w)[..., None], 0.0, 1.0)
    if a["jitter_hue"] > 0:
        dh = u(kh, -a["jitter_hue"], a["jitter_hue"])
        h, s_, v = _rgb_to_hsv(x)
        x = _hsv_to_rgb((h + dh) % 1.0, s_, v)
    return x


def _crop_box(key, a):
    import jax
    import jax.numpy as jnp

    ka, kr, ky, kx = jax.random.split(key, 4)
    target = jax.random.uniform(ka, (), minval=a["rrc_scale"][0],
                                maxval=a["rrc_scale"][1]) * float(SRC * SRC)
    ratio = jnp.exp(jax.random.uniform(
        kr, (), minval=math.log(a["rrc_ratio"][0]),
        maxval=math.log(a["rrc_ratio"][1])))
    w = jnp.clip(jnp.sqrt(target * ratio), 1.0, SRC)
    h = jnp.clip(jnp.sqrt(target / ratio), 1.0, SRC)
    top = jax.random.uniform(ky, (), minval=0.0, maxval=SRC - h)
    left = jax.random.uniform(kx, (), minval=0.0, maxval=SRC - w)
    return top, left, h, w


def augment_one(key, img_u8, a: dict):
    import jax
    import jax.numpy as jnp

    if not 0 < a["rotation_degrees"] <= 30.0:
        raise ValueError("the reference covers the three-shear rotation "
                         "(0 < rotation_degrees <= 30)")
    kf, kr, kc, kj = jax.random.split(key, 4)
    x = img_u8.astype(jnp.float32) / 255.0
    x = jnp.where(jax.random.bernoulli(kf), x[:, ::-1, :], x)
    angle = jax.random.uniform(
        kr, (), minval=-a["rotation_degrees"],
        maxval=a["rotation_degrees"]) * (math.pi / 180.0)
    x = _rotate_shear(x, angle)
    x = _jitter(kj, x, a)
    top, left, h, w = _crop_box(kc, a)
    size = a["image_size"]
    x = jnp.einsum("oh,hwc->owc", _bilinear(top, h, size, SRC), x)
    x = jnp.einsum("pw,owc->opc", _bilinear(left, w, size, SRC), x)
    x = x * _border_mask(size, angle)[..., None]
    return ((x - jnp.asarray(a["mean"], jnp.float32))
            / jnp.asarray(a["std"], jnp.float32))


def augment(key, images_u8, a: dict):
    """[B,32,32,3] uint8 -> [B,S,S,3] float32; one key per image."""
    import jax

    keys = jax.random.split(key, images_u8.shape[0])
    return jax.vmap(lambda k, im: augment_one(k, im, a))(keys, images_u8)
