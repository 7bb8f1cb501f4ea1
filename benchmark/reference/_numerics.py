"""Arithmetic shared by the plain references: precision control, the
three reference training steps, and the worst-leaf comparison.

A reference computes in float32 with every matrix product at
``highest`` precision. ``precision="fp8"`` is the control of "How
correct is decided": the same reference with the operands and the result of every
matrix product and convolution rounded to float8 going forward (e4m3)
and their cotangents rounded to float8 coming back (e5m2), one scale
per tensor, accumulation in float32 — as the program holds operands
and results in bfloat16 — the float8 training recipe, the step that would tempt a
later PR on a bfloat16 configuration. Nothing here imports the program.
"""

from __future__ import annotations

import statistics

PRECISIONS = ("float32", "fp8")


def _round_fp8(x, dtype, top: float):
    """``x`` rounded to an 8-bit float with one scale per tensor."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _fp8():
    """The float8 recipe as a function with its own gradient: operands
    rounded to e4m3 going forward, cotangents rounded to e5m2 coming
    back (what float8 training does)."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def q(x):
        return _round_fp8(x, jnp.float8_e4m3fn, 448.0)

    def fwd(x):
        return q(x), None

    def bwd(_, g):
        return (_round_fp8(g, jnp.float8_e5m2, 57344.0),)

    q.defvjp(fwd, bwd)
    return q


def quant(x, precision: str):
    """Round ``x`` to the control's precision (identity for float32)."""
    if precision == "float32":
        return x
    if precision == "fp8":
        return _fp8()(x)
    raise ValueError(f"unknown precision {precision!r}")


def mm(a, b, precision: str):
    import jax
    import jax.numpy as jnp

    return quant(jnp.matmul(quant(a, precision), quant(b, precision),
                            precision=jax.lax.Precision.HIGHEST), precision)


def leaf_norms(tree: dict) -> dict:
    """``{path: l2 norm}`` of a flat ``{path: array}`` dict. A stacked
    per-block leaf ``blocks/<rest>`` of shape [depth, ...] becomes
    ``block{i:02d}/<rest>`` entries."""
    import jax.numpy as jnp

    out = {}
    for path, x in tree.items():
        x = x.astype(jnp.float32)
        if path.startswith("blocks/"):
            n = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            for i in range(x.shape[0]):
                out[f"block{i:02d}/{path[len('blocks/'):]}"] = n[i]
        else:
            out[path] = jnp.sqrt(jnp.sum(x * x))
    return out


def three_steps(loss_and_grads, params: dict, xs, ys, keys, optim: dict):
    """Follow the program's first steps: per step the loss, after the
    first the per-leaf gradient norms, after the last the per-leaf
    norms of the parameters' change. ``params`` is a flat dict of
    float32 arrays; ``loss_and_grads(params, x, y, key)`` is jitted by
    the caller. Adam as optax writes it, at the configuration's rate."""
    import jax
    import jax.numpy as jnp
    import optax

    if optim["name"] != "adam" or optim.get("weight_decay", 0.0):
        raise ValueError(f"reference optimizer covers plain adam, got {optim}")
    tx = optax.adam(optim["learning_rate"], b1=optim["b1"], b2=optim["b2"],
                    eps=optim["eps"])
    opt = tx.init(params)

    @jax.jit
    def update(params, grads, opt):
        upd, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, upd), opt

    norms = jax.jit(leaf_norms)
    start = params
    losses, grad_norms = [], None
    for i in range(len(xs)):
        loss, grads = loss_and_grads(params, xs[i], ys[i], keys[i])
        losses.append(float(loss))
        if i == 0:
            grad_norms = {k: float(v) for k, v in norms(grads).items()}
        params, opt = update(params, grads, opt)
        del grads
    delta = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k] for k in a}))(params, start)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": {k: float(v) for k, v in delta.items()}}


def worst_leaf_gap(got: dict, ref: dict):
    """Largest, over the leaves, of ``|got - ref| / max(ref, median
    ref)``: the gap between the two norms of a leaf, not the norm of a
    difference, held against the reference's norm of that leaf or of
    the median leaf (some gradients are all but zero). Returns
    ``(gap, path)``; a leaf one side lacks is an infinite gap."""
    if set(got) != set(ref):
        missing = sorted(set(got) ^ set(ref))
        return float("inf"), f"leaves differ: {missing[:3]}"
    med = statistics.median(ref.values())
    worst, where = 0.0, ""
    for path, r in ref.items():
        g = got[path]
        if not (g == g):                            # NaN
            return float("inf"), path
        gap = abs(g - r) / max(r, med, 1e-30)
        if gap > worst:
            worst, where = gap, path
    return worst, where


def global_gap(got: dict, ref: dict) -> float:
    """``|norm(got) - norm(ref)| / norm(ref)`` over all leaves together
    (the root of the summed squares): steady where the worst leaf is
    one whose gradient is all but zero in exact arithmetic."""
    g = sum(v * v for v in got.values()) ** 0.5
    r = sum(v * v for v in ref.values()) ** 0.5
    return abs(g - r) / max(r, 1e-30) if g == g else float("inf")


def train_numbers(got: dict, ref: dict) -> dict:
    """The numbers a training cell compares: each step's loss gap
    (relative), the gradient-norm gap and the parameter-change gap,
    each by the worst leaf and over all leaves together. ``got``/``ref``
    as ``three_steps`` returns."""
    out = {}
    for i, (g, r) in enumerate(zip(got["losses"], ref["losses"])):
        out[f"loss_gap_step{i + 1}"] = (abs(g - r) / max(abs(r), 1e-30)
                                        if g == g else float("inf"))
    out["grad_norm_gap"], out["grad_norm_gap_leaf"] = worst_leaf_gap(
        got["grad_norms"], ref["grad_norms"])
    out["delta_norm_gap"], out["delta_norm_gap_leaf"] = worst_leaf_gap(
        got["delta_norms"], ref["delta_norms"])
    out["grad_norm_gap_global"] = global_gap(got["grad_norms"],
                                             ref["grad_norms"])
    out["delta_norm_gap_global"] = global_gap(got["delta_norms"],
                                              ref["delta_norms"])
    return out


def leaf_gap_table(got: dict, ref: dict) -> dict:
    """Per leaf, for gradients and for the parameters' change:
    ``[reference norm, gap]`` (for reading where a worst-leaf number
    comes from when limits are set)."""
    out = {}
    for kind in ("grad_norms", "delta_norms"):
        med = statistics.median(ref[kind].values())
        out[kind] = {p: [r, abs(got[kind][p] - r) / max(r, med, 1e-30)]
                     for p, r in ref[kind].items()}
        out[kind + "_median"] = med
    return out


def batch_var_after_one_step(running: dict, bn: dict) -> dict:
    """``{layer: batch variance}`` from the running statistics a model
    holds after ONE train step from their initial value 1:
    ``running = momentum * 1 + (1 - momentum) * batch``. ``running``
    maps ``<layer>/bn/var`` (and ``.../mean``) to arrays."""
    import numpy as np

    m = bn["momentum"]
    return {path[:-len("/bn/var")]: (np.asarray(v, np.float64) - m) / (1 - m)
            for path, v in running.items() if path.endswith("/bn/var")}


def batch_var_gaps(got: dict, ref: dict) -> dict:
    """Per BatchNorm layer ``|var_got - var_ref| / |var_ref|`` (vector
    norms over the channels): a difference, first order in rounding,
    of a quantity that is far from zero in every channel."""
    import numpy as np

    out = {}
    for layer, (_, var) in ref.items():
        var = np.asarray(var, np.float64)
        g = np.asarray(got[layer], np.float64)
        out[layer] = float(np.linalg.norm(g - var) / np.linalg.norm(var))
    return out
