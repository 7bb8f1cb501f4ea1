#!/usr/bin/env python
"""Benchmark: MobileNetV2/CIFAR-10 training throughput per chip.

Measures steady-state images/sec of the full jitted training step (raw
uint8 32x32 batch in -> on-device augmentation -> forward -> backward ->
Adam update -> metrics) on the reference workload shape (224x224, the
reference's single-V100 config trains ~94.7 img/s, BASELINE.md). Prints
ONE JSON line; vs_baseline is the ratio to that single-GPU baseline.

Input batches are pre-staged on device and cycled with fresh RNG keys so
the number measures the accelerator compute path; the real input path
ships the same uint8 batches (3 KB/image), far below HBM/PCIe limits.

Synchronization: the timed region ends by waiting on the whole updated
train state AND fetching one parameter element to the host. A parameter
element is data-dependent on the last step's gradient/Adam work, so its
fetched value cannot exist before the work is done.

A measurement needs the chip: on any other platform, or a TPU whose
``device_kind`` is not in the peak tables below, the script exits
non-zero. ``--smoke`` is the one exception — a CPU plumbing check of
the JSON/bytes path on tiny shapes, whose record carries byte counts
(computed from the compiled program) and no rate, time or utilization.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

# Persistent compiled-program cache (the 224px step compiles for the
# better part of a minute); one home for the path, tpunet.utils.cache.
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "scripts"))
from _chip import device_record, require_tpu  # noqa: E402
from tpunet.utils.cache import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()

BASELINE_IMG_PER_SEC = 94.7  # 1x V100, BASELINE.md ("north star" x4 target)

# Dense bf16 peak FLOP/s per chip by device kind (for the MFU estimate;
# public spec-sheet numbers). An unknown kind is an error, not a null.
_PEAK_FLOPS = (
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("v6", 918e12), ("trillium", 918e12), ("v4", 275e12), ("v3", 123e12),
)

# HBM bandwidth per chip (public spec-sheet numbers, bytes/s) — the
# roofline's second axis. MobileNetV2 is depthwise/elementwise-heavy:
# its arithmetic intensity sits far below the MXU ridge point, so the
# MXU-peak MFU is the wrong denominator ("wrong units, not 4% of
# attainable" — VERDICT r3). roofline_attainable below is the classic
# two-resource bound: attainable img/s = 1 / max(flops_img/peak_flops,
# bytes_img/hbm_bw); pct_of_roofline = measured / attainable.
#
# Method note — the bytes term. XLA's cost_analysis "bytes accessed"
# counts every op's operands+outputs as HBM traffic, re-counting
# values that fusion keeps on-chip; measured on the v5e it OVERcounts
# ~2x (a "roofline" built from it put measured throughput at 198% of
# attainable — not a bound at all). Instead the traffic model walks
# the step's jaxpr and counts the MATERIALIZED tensors: operands +
# results of convolutions and dot_generals only (elementwise/BN/
# cast/reduce chains are assumed fused into their producers — how the
# TPU compiler actually schedules them), scan bodies multiplied by
# trip count. That is a fusion-OPTIMISTIC lower bound on true
# traffic, so roofline_attainable is a true upper bound on attainable
# throughput and pct_of_roofline a meaningful "fraction of what a
# perfectly-fused program could reach". The raw cost-analysis count
# ships alongside as xla_bytes_accessed for reference.
_HBM_BW = (
    ("v5 lite", 819e9), ("v5e", 819e9), ("v5p", 2765e9),
    ("v6", 1640e9), ("trillium", 1640e9), ("v4", 1228e9), ("v3", 900e9),
)


def _conv_dot_traffic(jaxpr, mult: float = 1.0) -> float:
    """Materialized-tensor HBM traffic estimate (method note above):
    sum of operand+result bytes over conv/dot equations, recursing
    into pjit/scan/cond/custom-vjp sub-jaxprs (scan bodies scaled by
    trip count)."""
    total = 0.0

    def nbytes(v):
        aval = v.aval
        try:
            return aval.size * aval.dtype.itemsize
        except Exception:
            return 0.0

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("conv_general_dilated", "dot_general"):
            total += mult * (sum(nbytes(v) for v in eqn.invars)
                             + sum(nbytes(v) for v in eqn.outvars))
            continue
        sub_mult = mult
        if name == "scan":
            sub_mult = mult * eqn.params.get("length", 1)
        for pname, p in eqn.params.items():
            vals = p if isinstance(p, (list, tuple)) else (p,)
            for item in vals:
                inner = getattr(item, "jaxpr", None)   # ClosedJaxpr
                if inner is None and hasattr(item, "eqns"):
                    inner = item                       # bare Jaxpr
                if inner is not None:
                    total += _conv_dot_traffic(inner, sub_mult)
    return total


def _chip_spec(table) -> float | None:
    kind = jax.devices()[0].device_kind.lower()
    return next((v for k, v in table if k in kind), None)




def _note(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def _measure(per_chip_batch: int, timed: int = 24, image_size: int = 224):
    """Steady-state throughput of the full train step at the given
    per-chip batch. Returns (img/s/chip, flops-per-execution or 0)."""
    from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                               ModelConfig, OptimConfig, TrainConfig)
    from tpunet.data.cifar10 import synthetic_cifar10
    from tpunet.parallel import shard_host_batch
    from tpunet.train.loop import Trainer
    from tpunet.utils.prng import step_key

    n_chips = jax.device_count()
    batch = per_chip_batch * n_chips
    cfg = TrainConfig(
        data=DataConfig(dataset="synthetic", batch_size=batch,
                        image_size=image_size),
        model=ModelConfig(),  # bf16 compute
        optim=OptimConfig(),
        mesh=MeshConfig(),
        checkpoint=CheckpointConfig(save_best=False, save_last=False),
    )
    ds = synthetic_cifar10(n_train=4 * batch, n_test=batch)
    trainer = Trainer(cfg, dataset=ds)
    # Identity stamp for the BENCH record: run_id + config fingerprint
    # let the run-history store (tpunet/obs/history/) join this bench
    # round to training runs of the same workload — previously they
    # correlated only by BENCH_r* filename convention.
    identity = {k: v for k, v in trainer.obs.registry.identity().items()
                if k in ("run_id", "config_fingerprint")}

    # Pre-staged device batches (cycled), fresh rng per step.
    batches = []
    rng = np.random.default_rng(0)
    for _ in range(4):
        x = rng.integers(0, 256, size=(batch, 32, 32, 3), dtype=np.uint8)
        y = rng.integers(0, 10, size=batch).astype(np.int32)
        batches.append(shard_host_batch(trainer.mesh, x, y))

    state = trainer.state
    step = trainer.train_step

    def sync(state):
        # Belt and braces: wait on every leaf, then fetch one parameter
        # element — a value data-dependent on the final Adam update (the
        # step counter alone would only force its increment chain; a
        # param element cannot exist before the gradient work ran).
        jax.block_until_ready(state)
        leaf = jax.tree_util.tree_leaves(state.params)[0]
        return float(np.asarray(leaf.ravel()[0]))

    warmup, reps = 3, 2
    _note(f"compiling + warming up ({jax.devices()[0].platform}, "
          f"batch {batch})...")
    t0 = time.perf_counter()
    for i in range(warmup):
        gx, gy = batches[i % len(batches)]
        state, _ = step(state, gx, gy, step_key(0, i))
    sync(state)
    _note(f"warmup done in {time.perf_counter()-t0:.1f}s")

    # XLA's own FLOP count for one execution of the whole step program
    # (augment + fwd + bwd + Adam) feeds the MFU estimate; the roofline
    # bytes come from the materialized-tensor jaxpr walk (method note
    # at _HBM_BW), with the raw cost-analysis count kept for reference
    # and DECOMPOSED by op category from the optimized module text
    # (tpunet/obs/hlo_bytes.py) so a bytes regression names the
    # category that moved.
    # A failure in any of the three is an error: a record with holes
    # in it reads as a measurement.
    from tpunet.obs import hlo_bytes
    gx, gy = batches[0]
    compiled = step.lower(state, gx, gy, step_key(0, 0)).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    xla_bytes = float(ca.get("bytes accessed", 0.0))
    # compiled.as_text() is the per-device SPMD module, like
    # cost_analysis — scale by the per-chip image count.
    bytes_breakdown = hlo_bytes.per_image_breakdown(
        compiled.as_text(), batch // n_chips)
    jx = jax.make_jaxpr(step)(state, gx, gy, step_key(0, 0))
    # global-program tensors; per-chip share for the roofline
    traffic = _conv_dot_traffic(jx.jaxpr) / n_chips

    best_dt, k = float("inf"), warmup
    for _ in range(reps):
        t0 = time.perf_counter()
        for i in range(timed):
            gx, gy = batches[k % len(batches)]
            state, _ = step(state, gx, gy, step_key(0, k))
            k += 1
        sync(state)
        best_dt = min(best_dt, time.perf_counter() - t0)

    trainer.close()
    return (timed * batch / best_dt / n_chips, flops, best_dt / timed,
            traffic, xla_bytes, batch // n_chips, bytes_breakdown,
            identity)


def main() -> None:
    n_chips = jax.device_count()
    smoke = "--smoke" in sys.argv[1:]
    if not smoke:
        # A measurement path that finds no chip fails; it does not
        # fall back to the CPU (that check is --smoke), and an unknown
        # chip has no peak to divide by.
        require_tpu(_PEAK_FLOPS, _HBM_BW)
    if smoke:
        # Harness sanity check on small shapes (CPU-friendly): the
        # JSON/bytes plumbing is what's exercised. Its record (below)
        # carries counts only — no rate, time or utilization.
        (peak_ips, flops, dt_step, traffic, xla_bytes, pcb,
         breakdown, identity) = _measure(8, timed=3, image_size=32)
        ref_ips = None
    elif "--peak-only" in sys.argv[1:]:
        # XLA-flag sweeps (scripts/flag_sweep.py): just the peak-shape
        # number (the batch-128 companion costs a second warmup and
        # doesn't move with flags).
        # The batch128_* fields become null — aliasing them to the
        # batch-512 figure would fabricate a measurement under a name
        # that promises the reference shape.
        (peak_ips, flops, dt_step, traffic, xla_bytes, pcb,
         breakdown, identity) = _measure(512)
        ref_ips = None
    else:
        # Peak-throughput shape (per-chip batch sweep optimum) and the
        # reference's exact shape (cifar10_128batch.py:59: batch 128).
        (peak_ips, flops, dt_step, traffic, xla_bytes, pcb,
         breakdown, identity) = _measure(512)
        ref_ips = _measure(128)[0]

    peak = _chip_spec(_PEAK_FLOPS)
    bw = _chip_spec(_HBM_BW)
    mfu = None
    if peak and flops:
        # Compiled.cost_analysis() reports the PER-DEVICE FLOPs of the
        # SPMD-partitioned module (verified empirically on a sharded
        # matmul), so it divides by step time and chip peak directly.
        mfu = round(flops / dt_step / peak, 4)

    # Two-resource roofline (method note at _HBM_BW): attainable
    # img/s/chip = 1 / max(compute time, memory time) per image; the
    # binding resource says which wall the step leans on. On this
    # depthwise model the bytes term binds — the MXU MFU is reported
    # for continuity but pct_of_roofline is the meaningful "how close"
    # number.
    roofline = pct = bound = None
    if peak and bw and flops and traffic:
        t_img = max(flops / peak, traffic / bw) / pcb
        roofline = round(1.0 / t_img, 2)
        pct = round(peak_ips / roofline, 4)
        bound = ("hbm" if traffic / bw > flops / peak else "compute")

    record = {
        "metric": "train_images_per_sec_per_chip",
        "value": round(peak_ips, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(peak_ips / BASELINE_IMG_PER_SEC, 3),
        # reference-shape figure (per-chip batch 128, the V100 config) so
        # the vs_baseline ratio has a shape-matched companion
        "batch128_img_per_sec_per_chip": (
            round(ref_ips, 2) if ref_ips is not None else None),
        "batch128_vs_baseline": (
            round(ref_ips / BASELINE_IMG_PER_SEC, 3)
            if ref_ips is not None else None),
        "mfu": mfu,
        "roofline_attainable": roofline,
        "pct_of_roofline": pct,
        "roofline_bound": bound,
        "roofline_bytes_per_image": (round(traffic / pcb)
                                     if traffic else None),
        "xla_bytes_accessed_per_image": (round(xla_bytes / pcb)
                                         if xla_bytes else None),
        # Per-HLO-op-category decomposition of the cost-analysis bytes
        # (tpunet/obs/hlo_bytes.py; 'total' is the parsed sum, which
        # tracks xla_bytes_accessed_per_image to <1%).
        "bytes_per_image_breakdown": breakdown,
        **device_record(),
        # History-store join keys (tpunet/obs/history/): the peak-shape
        # trainer's run identity + config fingerprint.
        **identity,
    }
    if smoke:
        # Counts computed from the compiled program survive; nothing
        # derived from a clock does, and the record does not carry a
        # device metric's name.
        record = {"metric": "bench_smoke_plumbing",
                  **{k: record[k] for k in (
                      "roofline_bytes_per_image",
                      "xla_bytes_accessed_per_image",
                      "bytes_per_image_breakdown", "platform",
                      "device_kind", "device_count", *identity)}}
    print(json.dumps(record))

    if "--enforce-budget" in sys.argv[1:]:
        # Regression gate against the checked-in budget
        # (docs/bytes_budget.json): nonzero exit when bytes/image
        # regresses past the budget's tolerance on this device kind.
        from check_bytes_budget import check_record, load_budget
        ok, msgs = check_record(record, load_budget())
        for m in msgs:
            _note(m)
        if not ok:
            sys.exit(3)


if __name__ == "__main__":
    main()
