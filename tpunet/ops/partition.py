"""Run a per-shard Pallas kernel across the devices of the step's mesh.

A ``pallas_call`` lowers to a custom call XLA's SPMD partitioner knows
nothing about: in a multi-device jit it would all-gather the operands
and run the whole kernel on every device. ``jax``'s answer for that,
``custom_partitioning``, does not exist on libtpu — the partitioner
callback lives in jaxlib's XLA while libtpu compiles with its own, and
a multi-chip jit dies with "Custom emitter for CustomSPMDPartitioning
not found" (measured on a v5e 2x2, jax 0.9.0 / libtpu 0.0.34; it works
on the CPU backend, which is why only a chip run shows it). So the
kernels are split with ``jax.shard_map`` instead, which IS supported
everywhere: each device runs the kernel on its own shard.

``shard_map`` needs the mesh at trace time, and the kernels sit deep
inside model code that has no mesh argument. The Trainer jits its
train and eval steps through ``traced_under(mesh, fn)``, which traces
the body under ``kernel_mesh(mesh)``; ``sharded`` reads it. With no
mesh, or a mesh of one device, or a dimension its axis does not divide,
the kernel is called as it is (one device: nothing to split; the rest:
XLA replicates it, which is correct).

The kernels are parallel over batch (mesh axis ``data``) and — flash
attention only — heads (mesh axis ``model``). Everything a kernel
reduces across the batch (BN partials, dw partials) comes out as a
per-image partial, still batch-sharded, and is summed in XLA outside,
so the cross-device reduction stays an all-reduce XLA inserts.
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
from jax.sharding import PartitionSpec as P

_local = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Trace the body with ``mesh`` as the mesh kernels split over."""
    prev = getattr(_local, "mesh", None)
    _local.mesh = mesh
    try:
        yield
    finally:
        _local.mesh = prev


def traced_under(mesh, fn):
    """``fn``, its body traced under ``kernel_mesh(mesh)`` — what a
    step builder hands to ``jax.jit``."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with kernel_mesh(mesh):
            return fn(*args, **kwargs)
    return wrapped


def _fits(mesh, spec, shape) -> bool:
    return all(axis is None or dim % mesh.shape[axis] == 0
               for dim, axis in zip(shape, spec))


def sharded(fn, in_specs, out_specs):
    """``fn(*operands, *statics)`` with its ``len(in_specs)`` leading
    array operands split over the current kernel mesh by ``in_specs``
    (axes the mesh lacks are dropped from the specs)."""

    def call(*args):
        operands, statics = args[:len(in_specs)], args[len(in_specs):]
        mesh = getattr(_local, "mesh", None)
        if mesh is None or mesh.size == 1:
            return fn(*args)

        def known(spec):
            return P(*(a if a in mesh.axis_names else None for a in spec))
        ins = tuple(known(s) for s in in_specs)
        if not all(_fits(mesh, s, o.shape)
                   for s, o in zip(ins, operands)):
            return fn(*args)
        outs = jax.tree_util.tree_map(
            known, out_specs, is_leaf=lambda s: isinstance(s, P))
        return jax.shard_map(
            lambda *ops: fn(*ops, *statics), mesh=mesh, in_specs=ins,
            out_specs=outs, check_vma=False)(*operands)

    return call
