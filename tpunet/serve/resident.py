"""The parameter tree a serve engine keeps on the device.

A model that computes in a narrower type than its parameters are
handed in converts each product weight on every call: the same
rounding of the same array, once a decode step, read at full width.
``resident_params`` does that rounding once. It reads the model's own
``apply`` (abstractly — nothing runs) and takes a leaf only when every
equation that consumes it is a ``convert_element_type`` to one
narrower floating type; the step then finds the leaf already in that
type and converts nothing, so the equations it runs are the same
equations less the conversions. Any other use — a gather, a nested
call, a float32 product, a reshape, two different conversions, a
widening — leaves the leaf as it was given. Nothing here knows a
model or a leaf by name.
"""

from __future__ import annotations


def _narrowing_casts(jaxpr, n_leaves: int) -> dict:
    """``{leaf index: dtype}`` for the first ``n_leaves`` inputs of
    ``jaxpr`` whose ONLY uses are conversions to one narrower floating
    type."""
    import numpy as np
    from jax import numpy as jnp
    from jax.extend.core import Var

    leaf_of = {var: i for i, var in enumerate(jaxpr.invars[:n_leaves])}
    casts: dict = {}
    other = {leaf_of[v] for v in jaxpr.outvars
             if isinstance(v, Var) and v in leaf_of}
    for eqn in jaxpr.eqns:
        for var in eqn.invars:
            i = leaf_of.get(var) if isinstance(var, Var) else None
            if i is None:
                continue
            if eqn.primitive.name == "convert_element_type" \
                    and not eqn.params.get("weak_type") \
                    and eqn.params.get("sharding") is None:
                casts.setdefault(i, set()).add(
                    np.dtype(eqn.params["new_dtype"]))
            else:
                other.add(i)
    out = {}
    for i, to in casts.items():
        if i in other or len(to) != 1:
            continue
        (to,) = to
        given = np.dtype(jaxpr.invars[i].aval.dtype)
        if jnp.issubdtype(given, jnp.floating) \
                and jnp.issubdtype(to, jnp.floating) \
                and to.itemsize < given.itemsize:
            out[i] = to
    return out


def held_types(apply, params, arg_sets) -> list:
    """Per leaf of ``params`` (arrays or shapes, flattened order) the
    type ``apply(params, *args)`` converts it to where that conversion
    is the leaf's only use and narrows it, else None — judged the same
    way at every entry of ``arg_sets`` (one tuple of shapes per kind
    of call; a leaf they disagree on gets None). Abstract: nothing
    runs."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = jax.tree_util.tree_unflatten(
        treedef, [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in leaves])
    first, *rest = [
        _narrowing_casts(jax.make_jaxpr(apply)(shapes, *args).jaxpr,
                         len(leaves))
        for args in arg_sets]
    return [first.get(i) if all(p.get(i) == first.get(i) for p in rest)
            else None for i in range(len(leaves))]


def resident_params(apply, params, arg_sets):
    """``(tree, leaves_precast)``: ``params`` with each leaf held in
    the type ``held_types`` names for it. The tree has ``params``'
    structure; a leaf not taken is the caller's own array, a leaf
    taken is a new one (sharded as the given one was). The caller's
    tree is not touched."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(params)
    types = held_types(apply, params, arg_sets)
    held = [a if to is None else jax.lax.convert_element_type(a, to)
            for a, to in zip(leaves, types)]
    return (jax.tree_util.tree_unflatten(treedef, held),
            sum(to is not None for to in types))


def tree_bytes(tree) -> int:
    """Bytes of every array in ``tree``."""
    import jax
    return int(sum(a.nbytes for a in jax.tree_util.tree_leaves(tree)))
