"""Weights made from ``--seed`` by the benchmark, on the device.

The program's own initialisers are not used: the program under test and
the plain reference are both handed the tree made here, so neither takes
anything the other has made. A leaf's values depend only on the seed and
on the leaf's path, so any leaf (or a stack of the same leaf over the
blocks) can be made alone, in any process.

A spec is ``{path: (shape, kind, std)}`` with ``kind`` one of
``normal`` (mean 0, the given std), ``ones`` and ``zeros``; a path is
``a/b/c``. Leaves that repeat once per block are written
``block{i:02d}/...``: their key folds in the block index last, so the
stack over blocks is one vmapped draw.
"""

from __future__ import annotations

import re
import zlib

_BLOCK = re.compile(r"^block(\d+)/(.*)$")


def seed_key(seed):
    """A PRNG key for any whole number up to 2**62 (``--seed`` is a
    little over 2**31, more than an int32 holds). A key passes through
    unchanged: jitted builders take the key as an argument, so that
    one compiled program serves every seed."""
    import jax

    if not isinstance(seed, int):
        return seed
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf_key(key, path: str):
    """Key of one leaf: the path's CRC folded in and, for a per-block
    leaf, the block index folded in after it."""
    import jax

    m = _BLOCK.match(path)
    rest = m.group(2) if m else path
    k = jax.random.fold_in(key, zlib.crc32(rest.encode()) & 0x7FFFFFFF)
    if m:
        k = jax.random.fold_in(k, int(m.group(1)))
    return k


def _draw(key, shape, kind: str, std: float, dtype):
    import jax
    import jax.numpy as jnp

    if kind == "normal":
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    raise ValueError(f"unknown weight kind {kind!r}")


def make_leaf(seed, path: str, shape, kind: str, std: float,
              dtype="float32"):
    """One leaf (traceable: call it inside a jitted function, with the
    seed's key as an argument of that function)."""
    return _draw(_leaf_key(seed_key(seed), path), tuple(shape), kind, std,
                 dtype)


def make_stacked(seed, rest: str, depth: int, shape, kind: str,
                 std: float, dtype="float32"):
    """``[depth, *shape]``: leaf ``block{i:02d}/<rest>`` for every i,
    bit-equal to ``depth`` calls of ``make_leaf``."""
    import jax
    import jax.numpy as jnp

    base = jax.random.fold_in(seed_key(seed),
                              zlib.crc32(rest.encode()) & 0x7FFFFFFF)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(depth))
    return jax.vmap(lambda k: _draw(k, tuple(shape), kind, std, dtype))(keys)


def nest(flat: dict) -> dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``."""
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    """Inverse of ``nest`` for nested dicts (flax ``params`` trees)."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict) or hasattr(v, "items"):
            out.update(flatten(dict(v), path))
        else:
            out[path] = v
    return out


def make_tree(spec: dict, seed: int, dtype="float32") -> dict:
    """The whole nested tree in ONE jitted call, on the default device."""
    import jax

    @jax.jit
    def build(key):
        return {p: make_leaf(key, p, s, kind, std, dtype)
                for p, (s, kind, std) in spec.items()}

    return nest(build(seed_key(seed)))
