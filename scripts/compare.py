#!/usr/bin/env python
"""The reference's benchmark-comparison methodology as one command (C16).

The reference's published result is a three-config comparison — serial
CPU vs single GPU vs MPI+DDP — recorded as SLURM run logs
(logs_cifar10_cpu_27299.out, cifar10_128_gpu_27326.out,
cifar_mpi_gpu128_26188.out) and summarized in its README performance
table. This script produces the tpunet equivalent as a committed
artifact: it runs the three presets back-to-back, parses each run's
metrics.jsonl, and emits a markdown table (COMPARE.md) + machine-
readable COMPARE.json with wall-clock, img/s, and accuracy per config.

Real CIFAR-10 is used when present under --data-dir (or downloadable);
otherwise the deterministic synthetic stand-in keeps the artifact
reproducible in no-egress environments (the mode is recorded in the
output). Device placement per mode:

  serial       1 CPU device   (reference: CPU-pinned, :19)
  single       1 device of the default platform (TPU chip when present)
  distributed  all devices of the default platform (8-way virtual CPU
               mesh when no accelerator), per-device batch 128 like the
               reference's per-rank 128 (:117)

    python scripts/compare.py                   # auto: real if present
    python scripts/compare.py --epochs 3 --image-size 96 --synthetic
    python scripts/compare.py --platform cpu    # hermetic CPU run
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Persistent compile cache path convention has ONE home
# (tpunet.utils.cache), shared with tests/dryruns.
from tpunet.utils.cache import cache_dir  # noqa: E402


def cpu_env(n_devices: int = 1) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir())
    return env


def probe_devices(env: dict) -> tuple[str, int]:
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices(); print(d[0].platform, len(d))"],
        env=env, cwd=REPO, capture_output=True, text=True, check=True)
    platform, n = out.stdout.strip().split()[-2:]
    return platform, int(n)


def run_mode(mode: str, env: dict, out_dir: str, common: list[str],
             batch: int, log_name: str, label: str | None = None) -> dict:
    """Run one preset; ``label`` names the output row/dirs when the same
    preset appears twice (e.g. the matched-batch control)."""
    label = label or mode
    ckpt = os.path.join(out_dir, label, "ckpt")
    cmd = [sys.executable, "-u", "train.py", "--preset", mode,
           "--batch-size", str(batch), "--checkpoint-dir", ckpt] + common
    print(f"[{label}] {' '.join(cmd[1:])}", flush=True)
    t0 = time.time()
    with open(os.path.join(out_dir, log_name), "w") as log:
        subprocess.run(cmd, env=env, cwd=REPO, stdout=log,
                       stderr=subprocess.STDOUT, check=True)
    wall = time.time() - t0
    rows = [json.loads(l) for l in
            open(os.path.join(ckpt, "metrics.jsonl"))]
    partial = [r for r in rows if r.get("partial")]
    rows = [r for r in rows if not r.get("partial")]
    if partial:
        raise RuntimeError(
            f"[{label}] run was preempted mid-epoch (partial row at epoch "
            f"{partial[-1]['epoch']}); rerun to get a complete comparison")
    total = sum(r["seconds"] for r in rows)
    # Steady state = the fastest epoch: short runs put the (possibly
    # minutes-long on a cold cache) XLA compile inside epoch 1, which
    # the reference's 20-epoch totals amortize away but a 2-epoch
    # artifact does not.
    return {
        "mode": label,
        "preset": mode,
        "global_batch": batch,
        "epochs": len(rows),
        "total_seconds": round(total, 2),
        "wall_seconds": round(wall, 2),  # includes compile/startup
        "images_per_sec": round(sum(r["examples_per_sec"] * r["seconds"]
                                    for r in rows) / total, 2),
        "steady_epoch_seconds": round(min(r["seconds"] for r in rows), 2),
        "steady_images_per_sec": max(r["examples_per_sec"] for r in rows),
        "best_test_accuracy": max(r["test_accuracy"] for r in rows),
        "final_train_loss": rows[-1]["train_loss"],
        # Per-epoch times make run-to-run variance visible in the
        # artifact: at 1 process the single and distributed presets
        # build IDENTICAL configs (tpunet/config.py preset()) and thus
        # identical XLA programs, so any single/distributed gap at
        # n_dist=1 is environment noise, measurable from this column.
        "epoch_seconds": [round(r["seconds"], 2) for r in rows],
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", default="runs/compare")
    p.add_argument("--data-dir", default="data")
    p.add_argument("--epochs", type=int, default=None,
                   help="default: 20 on real data (reference EPOCHS), "
                        "3 on synthetic")
    p.add_argument("--image-size", type=int, default=None,
                   help="default: 224 on real data (reference), 96 on "
                        "synthetic (keeps the CPU run short)")
    p.add_argument("--synthetic", action="store_true",
                   help="force the synthetic dataset even if CIFAR-10 "
                        "is present")
    p.add_argument("--synthetic-size", type=int, default=2048)
    p.add_argument("--platform", choices=["auto", "cpu"], default="auto",
                   help="cpu: run every mode on CPU devices (hermetic); "
                        "auto: single/distributed use the default "
                        "platform (TPU when attached)")
    p.add_argument("--pretrained", default=None,
                   help="forwarded to train.py on real data (e.g. auto)")
    args = p.parse_args(argv)

    have_real = not args.synthetic and (
        os.path.isdir(os.path.join(args.data_dir, "cifar-10-batches-py"))
        or os.path.exists(os.path.join(args.data_dir,
                                       "cifar-10-python.tar.gz")))
    epochs = args.epochs or (20 if have_real else 3)
    image_size = args.image_size or (224 if have_real else 96)
    out_dir = os.path.join(REPO, args.out)
    os.makedirs(out_dir, exist_ok=True)

    common = ["--epochs", str(epochs), "--image-size", str(image_size),
              "--data-dir", args.data_dir]
    if have_real:
        common += ["--dataset", "cifar10"]
        if args.pretrained:
            common += ["--pretrained", args.pretrained]
    else:
        common += ["--dataset", "synthetic", "--dtype", "float32",
                   "--synthetic-size", str(args.synthetic_size)]

    if args.platform == "cpu":
        accel_env = cpu_env(1)
        dist_env = cpu_env(8)
    else:
        accel_env = dict(os.environ)
        dist_env = dict(os.environ)
    accel_platform, _ = probe_devices(accel_env)
    if accel_platform == "cpu" and args.platform == "auto":
        # No accelerator attached: fall back to the hermetic CPU layout
        # so "distributed" still demonstrates an 8-way mesh.
        accel_env, dist_env = cpu_env(1), cpu_env(8)
        accel_platform = "cpu"
    dist_platform, n_dist = probe_devices(dist_env)

    results = []
    hw = {"serial": "1x cpu", "single": f"1x {accel_platform}",
          "single-b64": f"1x {accel_platform}",
          "distributed": f"{n_dist}x {dist_platform}"}
    results.append(run_mode("serial", cpu_env(1), out_dir, common,
                            64, "serial.log"))
    results.append(run_mode("single", accel_env, out_dir, common,
                            128, "single.log"))
    # Matched-optimization CONTROL (VERDICT r4 #4): the single preset at
    # the SERIAL run's global batch 64 — same step count, same LR, same
    # schedule; the only variable left is the execution mode. The
    # reference's correctness claim is cross-config accuracy parity
    # (README:84-90); serial@64 vs single@128 alone confounds that with
    # 2x the optimizer steps at fixed LR. Skipped on the hermetic
    # CPU-only layout, where it would be byte-identical to the serial
    # run (same config, same 1-CPU-device env — parity trivially true).
    if accel_platform != "cpu":
        results.append(run_mode("single", accel_env, out_dir, common,
                                64, "single-b64.log", label="single-b64"))
    # Reference distributed semantics: 128 PER DEVICE (:117 + mpirun -np N).
    results.append(run_mode("distributed", dist_env, out_dir, common,
                            128 * n_dist, "distributed.log"))

    serial_t = results[0]["total_seconds"]
    serial_s = results[0]["steady_epoch_seconds"]
    for r in results:
        r["hardware"] = hw[r["mode"]]
        r["speedup_vs_serial"] = round(serial_t / r["total_seconds"], 2)
        r["steady_speedup_vs_serial"] = round(
            serial_s / r["steady_epoch_seconds"], 2)

    meta = {
        "dataset": "cifar10" if have_real else "synthetic",
        "image_size": image_size, "epochs": epochs,
        "reference": {
            # the reference's published numbers for the same comparison
            # (SURVEY.md section 6; .out logs)
            "serial_cpu_seconds": 30955.22,
            "single_v100_seconds": 10698.08,
            "dual_v100_mpi_seconds": 5220.57,
            "serial_cpu_best_acc": 0.9617,
            "single_v100_best_acc": 0.9603,
            "dual_v100_best_acc_local": 0.9558,
        },
        "results": results,
    }
    with open(os.path.join(out_dir, "COMPARE.json"), "w") as f:
        json.dump(meta, f, indent=2)

    lines = [
        "# tpunet three-config comparison (reference C16)",
        "",
        f"Dataset: **{meta['dataset']}** @ {image_size}px, "
        f"{epochs} epochs. Serial/single/distributed mirror the "
        "reference's CPU / 1-GPU / MPI+DDP configs (its numbers: "
        "30,955 s / 10,698 s / 5,221 s at ~0.96 best acc on real "
        "CIFAR-10, 20 epochs, 224px).",
        "",
        "| Training Mode | Hardware | Global batch | Total time (s) "
        "| Steady epoch (s) | Steady img/s | Best test acc "
        "| Steady speedup vs serial |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in results:
        lines.append(
            f"| {r['mode']} | {r['hardware']} | {r['global_batch']} "
            f"| {r['total_seconds']} | {r['steady_epoch_seconds']} "
            f"| {r['steady_images_per_sec']} "
            f"| {r['best_test_accuracy']:.4f} "
            f"| {r['steady_speedup_vs_serial']:.2f}x |")
    lines += ["",
              "Total time sums per-epoch seconds (train + eval, as the "
              "reference logs do); the steady columns use the fastest "
              "epoch, excluding the XLA compile a short run cannot "
              "amortize (the reference's 20-epoch totals do); accuracy "
              "is globally reduced (the reference's distributed number "
              "was rank-local).", ""]
    by = {r["mode"]: r for r in results}
    if "single-b64" in by:
        s64, c64 = by["serial"], by["single-b64"]
        gap = abs(s64["best_test_accuracy"] - c64["best_test_accuracy"])
        lines += [
            "## Matched-optimization control (execution-mode parity)",
            "",
            "`serial` and `single-b64` run the IDENTICAL optimization "
            "problem — global batch 64, same step count, same LR/"
            "schedule — on different execution modes (1 CPU device vs "
            f"1 {hw['single-b64'].split()[-1]} device). The reference's "
            "cross-config check (README:84-90) is accuracy parity; "
            "here:",
            "",
            f"- serial@64 best acc **{s64['best_test_accuracy']:.4f}**, "
            f"single-b64@64 best acc "
            f"**{c64['best_test_accuracy']:.4f}** "
            f"(|gap| {gap:.4f} — {'PARITY' if gap < 0.02 else 'MISMATCH'}"
            " at the reference's ~1-point bar).",
            f"- The serial@64 vs single@128 accuracy split is therefore "
            "an OPTIMIZATION variable (2x the optimizer steps per "
            "epoch at batch 64, fixed LR), not an execution-mode bug; "
            "single@128 == distributed@128/device remains the "
            "bitwise A/B check (AB_CHECK.json).",
            ""]
    with open(os.path.join(out_dir, "COMPARE.md"), "w") as f:
        f.write("\n".join(lines))
    print("\n".join(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
