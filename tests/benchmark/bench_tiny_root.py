"""A temporary copy of the benchmark with configurations cut to a size a
CPU test can hold. Shared by the tests of ``tests/benchmark``."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _edit(path, fn):
    with open(path) as f:
        obj = json.load(f)
    fn(obj)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make(tmp: str, dtype: str = "float32") -> str:
    """Copy ``BENCHMARK.json`` and ``benchmark/`` into ``tmp`` and shrink
    every configuration and traffic file. Returns the new root."""
    root = os.path.join(tmp, "root")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "benchmark")

    def mnv2(c):
        c["width_mult"] = 0.25
        c["image_size"] = c["augment"]["image_size"] = 32
        c["compute_dtype"] = dtype
        c["program"]["model"].update(width_mult=0.25, dtype=dtype)
        c["program"]["data"]["image_size"] = 32

    def gpt2(c):
        c.update(n_embd=32, n_layer=2, n_head=2, n_positions=64, n_ctx=64,
                 vocab_size=64, compute_dtype=dtype)
        c["train"]["overrides"] = {"n_layer": 2}
        c["program"]["model"].update(vit_hidden=32, vit_depth=2, vit_heads=2,
                                     vocab_size=64, max_seq_len=64,
                                     dtype=dtype)
        c["program"]["train_model"] = {"vit_depth": 2}
        c["program"]["data"].update(seq_len=32, vocab_size=64)

    _edit(os.path.join(b, "configs", "mobilenetv2-224.json"), mnv2)
    _edit(os.path.join(b, "configs", "gpt2-xl.json"), gpt2)
    _edit(os.path.join(b, "traffic", "train-b128.json"),
          lambda t: t.update(batch=8, steps_per_chunk=4, trace_seconds=0.5))
    _edit(os.path.join(b, "traffic", "train-b8-t1024.json"),
          lambda t: t.update(batch=8, seq_len=32, steps_per_chunk=4,
                             trace_seconds=0.5))
    _edit(os.path.join(b, "peaks.json"),
          lambda p: p["by_device_kind"].update(
              cpu={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}))

    def serve(t):
        t.update(clients=3, pool_prompt=2, pool_output=2, fill_seconds=0.2,
                 sample_requests=2, trace_seconds=0.5)
        t["prompt_len"].update(median=10, min=4, max=24)
        t["output_len"].update(median=6, min=3, max=8)

    _edit(os.path.join(b, "traffic", "serve-closed16.json"), serve)
    _edit(os.path.join(b, "workloads", "gpt2-xl.serve-closed16.json"),
          lambda w: w["program"]["serve"].update(
              slots=4, prefill_buckets=[8, 32]))
    return root


def set_limits(root: str, workload: str, limits: dict) -> None:
    _edit(os.path.join(root, "benchmark", "workloads", f"{workload}.json"),
          lambda w: w.update(limits=limits))


def context(root: str, workload: str, workdir: str, *, seed: int = 3000000019,
            seconds: float = 0.5, trace: int = 0, control: str = ""):
    """A rehearsal context: the stages run in this process, the look
    for a chip is skipped, and no timed number is reported."""
    from benchmark import harness, run

    os.makedirs(workdir, exist_ok=True)
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    if control:
        argv += ["--control", control]
    args = run.parse(argv)
    args.workdir = workdir
    return run.context(args, harness.load_cell(workload, root), rehearse=True)
