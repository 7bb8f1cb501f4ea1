"""Globally-exact metrics as psum-able sums.

The reference accumulates loss*batch and correct counts per rank, then
all-reduces only the losses — accuracy stays a rank-local approximation
(cifar10_mpi_mobilenet_224.py:181-196,216-224). Here every metric is a
(loss_sum, correct, count) triple of *global* sums: reductions happen
inside the jitted step over the globally-sharded batch, so XLA inserts
the cross-device psum and all three numbers are exact on any mesh.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

Metrics = Dict[str, jax.Array]


def from_batch(loss_sum, correct, count) -> Metrics:
    return {
        "loss_sum": jnp.asarray(loss_sum, jnp.float32),
        "correct": jnp.asarray(correct, jnp.float32),
        "count": jnp.asarray(count, jnp.float32),
    }


def zeros_metrics() -> Metrics:
    return from_batch(0.0, 0.0, 0.0)


def accumulate(acc: Metrics, new: Metrics) -> Metrics:
    return jax.tree_util.tree_map(jnp.add, acc, new)


# What a step may add to the triple, each a per-step mean; ``steps``
# counts the steps summed (train/steps.py ``_step_means``).
STEP_MEANS = ("main_loss", "mtp_loss", "moe_held_pair_share",
              "moe_held_load_max_over_mean", "moe_chunks_run_share")

# Process-wide sums of the step means over every chunk a trainer of
# this process has summarized, for a reader that runs after the
# trainer is closed (as utils/cache._COMPILES counts compiles).
STEP_MEAN_TOTALS: Dict[str, float] = {}


def with_step_means(metrics: Metrics, means: Dict[str, jax.Array]) -> Metrics:
    """``metrics`` plus one step's ``means`` as summable entries."""
    if not means:
        return metrics
    extra = {k: jnp.asarray(v, jnp.float32) for k, v in means.items()}
    return {**metrics, **extra, "steps": jnp.float32(1.0)}


def summarize_step_means(acc: Metrics) -> Dict[str, float]:
    """Means over the accumulated steps of whatever of ``STEP_MEANS``
    the steps reported ({} where none did), also added to
    ``STEP_MEAN_TOTALS``."""
    if "steps" not in acc:
        return {}
    steps = float(acc["steps"])
    sums = {k: float(acc[k]) for k in STEP_MEANS if k in acc}
    for k, v in {**sums, "steps": steps}.items():
        STEP_MEAN_TOTALS[k] = STEP_MEAN_TOTALS.get(k, 0.0) + v
    return {k: v / max(steps, 1.0) for k, v in sums.items()}


def summarize(acc: Metrics) -> Dict[str, float]:
    """Device scalars -> python floats {loss, accuracy, count}."""
    count = max(float(acc["count"]), 1.0)
    return {
        "loss": float(acc["loss_sum"]) / count,
        "accuracy": float(acc["correct"]) / count,
        "count": float(acc["count"]),
    }
