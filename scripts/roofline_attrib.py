#!/usr/bin/env python
"""Per-op time attribution for the flagship bench step (VERDICT r4 #3).

Traces the exact bench.py workload (MobileNetV2 @224, bf16, full train
step: augment + fwd + bwd + Adam + metrics) with the JAX profiler on the
real chip, names each device operation by the step's own HLO text
(tpunet/obs/device_time.py: JAX alone, no xprof), and writes a
measured per-op/per-category breakdown of where the step time goes —
turning the round-4 "residual is unfused BN/elementwise traffic,
sub-peak bandwidth, depthwise VPU time" *guess* into numbers.

Usage: python scripts/roofline_attrib.py [--batch 512] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

sys.path.insert(0, os.path.join(REPO, "scripts"))

from _chip import require_tpu  # noqa: E402
from tpunet.utils.cache import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()


def build_step(per_chip_batch: int, image_size: int = 224):
    from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                               ModelConfig, OptimConfig, TrainConfig)
    from tpunet.data.cifar10 import synthetic_cifar10
    from tpunet.parallel import shard_host_batch
    from tpunet.train.loop import Trainer

    # GLOBAL batch = per-chip x n_chips, matching bench.py's per-chip
    # convention so the attribution and bench records compare 1:1 on
    # any chip count.
    batch = per_chip_batch * jax.device_count()
    cfg = TrainConfig(
        data=DataConfig(dataset="synthetic", batch_size=batch,
                        image_size=image_size),
        model=ModelConfig(),
        optim=OptimConfig(),
        mesh=MeshConfig(),
        checkpoint=CheckpointConfig(save_best=False, save_last=False),
    )
    ds = synthetic_cifar10(n_train=2 * batch, n_test=batch)
    trainer = Trainer(cfg, dataset=ds)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(batch, 32, 32, 3), dtype=np.uint8)
    y = rng.integers(0, 10, size=batch).astype(np.int32)
    gx, gy = shard_host_batch(trainer.mesh, x, y)
    return trainer, gx, gy


def sync(state):
    jax.block_until_ready(state)
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    return float(np.asarray(leaf.ravel()[0]))


def trace_step(trainer, gx, gy, steps: int, trace_dir: str) -> float:
    from tpunet.utils.prng import step_key

    state = trainer.state
    for i in range(3):
        state, _ = trainer.train_step(state, gx, gy, step_key(0, i))
    sync(state)
    t0 = time.perf_counter()
    with jax.profiler.trace(trace_dir):
        for i in range(steps):
            state, _ = trainer.train_step(state, gx, gy, step_key(0, 3 + i))
        sync(state)
    return time.perf_counter() - t0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--out", default=os.path.join(
        REPO, "runs", "bench-roofline", "ATTRIB_r05.json"))
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--from-trace", default=None,
                    help="parse an existing trace dir instead of "
                         "re-tracing (batch/steps must match how it "
                         "was captured for the throughput numbers)")
    args = ap.parse_args()

    bytes_breakdown = texts = None
    if args.from_trace:
        # Parsing a kept trace needs no chip, and the process that
        # parses it cannot know which device recorded it; the step's
        # text lies beside the trace (``*.hlo.txt``).
        trace_dir, wall, trainer = args.from_trace, None, None
        device = {"platform": None, "device_kind": None,
                  "device_count": None}
    else:
        device = require_tpu()
        trainer, gx, gy = build_step(args.batch, args.image_size)
        # Byte attribution from the optimized module text (same
        # decomposition bench.py ships as bytes_per_image_breakdown);
        # lowering it here warms the executable the trace reuses.
        from tpunet.obs import hlo_bytes
        texts = trainer.program_texts()
        (text,) = texts.values()
        bytes_breakdown = hlo_bytes.per_image_breakdown(text, args.batch)
        trace_dir = tempfile.mkdtemp(prefix="tpunet-roofline-trace-")
        wall = trace_step(trainer, gx, gy, args.steps, trace_dir)
        if args.keep_trace:
            from tpunet.obs import device_time
            device_time.write_program_texts(trace_dir)
        print(f"# traced {args.steps} steps in {wall:.2f}s "
              f"({args.steps * args.batch / wall:.0f} img/s/chip, incl. "
              "profiler overhead)", file=sys.stderr)

    # Everything past the trace runs under try/finally: reading the
    # trace and the output write can fail — neither may leak the
    # mkdtemp trace dir this run created, or skip closing the
    # trainer's checkpointer/threads.
    try:
        _attrib_and_write(args, trace_dir, wall, bytes_breakdown,
                          device, texts)
    finally:
        if args.from_trace or args.keep_trace:
            # Never delete a trace the CALLER owns (--from-trace) or
            # asked to keep; only the tempdir this run created is
            # cleaned up.
            print(f"# trace kept at {trace_dir}", file=sys.stderr)
        else:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
        if trainer is not None:
            trainer.close()


def _attrib_and_write(args, trace_dir: str, wall, bytes_breakdown,
                      device: dict, texts=None) -> None:
    from tpunet.obs.device_time import op_rows
    from tpunet.obs.hlo_bytes import categorize, phase_of

    by_cat = {}
    by_src = {}
    by_phase = {}
    ops = []
    for r in op_rows(trace_dir, texts):
        t = r["Total self time (us)"]
        name = r["Framework op name"]
        # the byte table's categories (conv_fwd / bn / optimizer ...):
        # the instruction's name stands in for its opcode
        cat = categorize(r["HLO op name"].split(".")[0], name)
        r["HLO op category"] = cat
        by_cat[cat] = by_cat.get(cat, 0.0) + t
        # attribute to framework source (module/op) for actionability
        src = (name or "?").split("/")
        src = "/".join(src[1:3]) if len(src) > 2 else "/".join(src)
        by_src[src] = by_src.get(src, 0.0) + t
        # and to the training phase (fwd / bwd / optimizer / ema) —
        # the same classifier scripts/obs_report.py --trace uses, so
        # the time and bytes tables split the step identically.
        ph = phase_of(name)
        by_phase[ph] = by_phase.get(ph, 0.0) + t
        ops.append((t, r))
    total = sum(by_cat.values()) or 1.0
    ops.sort(key=lambda x: -x[0])

    def top(n):
        return [
            {"pct": round(100.0 * t / total, 2),
             "us_per_step": round(t / args.steps, 1),
             "category": r["HLO op category"],
             "op": r["HLO op name"],
             "source": (r["Framework op name"] or "")[:140]}
            for t, r in ops[:n]]

    out = {
        "batch_per_chip": args.batch,
        **device,
        "steps_traced": args.steps,
        "wall_seconds": wall and round(wall, 3),
        "img_per_sec_per_chip_traced": wall and round(
            args.steps * args.batch / wall, 1),
        "total_profiled_us_per_step": round(total / args.steps, 1),
        "by_phase_pct": {
            k: round(100.0 * v / total, 2)
            for k, v in sorted(by_phase.items(), key=lambda kv: -kv[1])},
        "bytes_per_image_breakdown": bytes_breakdown,
        "by_category_pct": {
            k: round(100.0 * v / total, 2)
            for k, v in sorted(by_cat.items(), key=lambda kv: -kv[1])},
        "by_source_pct_top": {
            k: round(100.0 * v / total, 2)
            for k, v in sorted(by_src.items(), key=lambda kv: -kv[1])[:25]},
        "top_ops": top(40),
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fp:
        json.dump(out, fp, indent=1)
    print(json.dumps({k: out[k] for k in
                      ("platform", "device_kind", "device_count",
                       "img_per_sec_per_chip_traced",
                       "total_profiled_us_per_step",
                       "by_phase_pct", "by_category_pct")}, indent=1))
    print(f"# wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
