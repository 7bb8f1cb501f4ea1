#!/usr/bin/env python
"""Long-context LM training throughput (tokens/sec) per attention impl.

Measures the FULL jitted train step (forward + backward + Adam) of the
decoder-only LM family at a long sequence length, comparing the
attention cores (dense / blockwise / flash). Not driver-run (bench.py
stays the reference-workload benchmark); this is the long-context perf
evidence for the attention stack.

    python scripts/bench_lm.py [--seq-len 2048] [--batch 8] [--depth 4]

Synchronization: fetch a parameter element that is data-dependent on
the last step's update (see bench.py). Exits non-zero off the TPU or
on a chip that is not in the peak table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from _chip import require_tpu  # noqa: E402
from tpunet.utils.cache import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()


def analytic_train_flops(b: int, t: int, c: int, depth: int,
                         mlp_ratio: float, vocab: int) -> float:
    """Standard analytic model-FLOPs for one causal-LM train step
    (PaLM-style MFU accounting: matmul FLOPs only, backward = 2x
    forward, causal attention at half the full-score cost). Used for
    MFU instead of XLA cost_analysis because the Pallas flash kernel
    is a custom call whose FLOPs XLA does not count — and analytic
    model-FLOPs is the honest MFU numerator anyway (rematerialized
    recompute must not inflate utilization)."""
    per_block = (8 + 4 * mlp_ratio) * b * t * c * c   # qkv+out+mlp
    attn = 2 * b * t * t * c                          # scores+values, causal
    head = 2 * b * t * c * vocab                      # tied logits
    fwd = depth * (per_block + attn) + head
    return 3.0 * fwd                                  # fwd + 2x bwd


_PEAK_FLOPS = (       # bf16 peak per chip (same table as bench.py)
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("v6", 918e12), ("trillium", 918e12), ("v4", 275e12), ("v3", 123e12),
)


def peak_flops_per_chip() -> float:
    """Peak of this chip (``require_tpu(_PEAK_FLOPS)`` has already
    refused a kind the table lacks)."""
    kind = jax.devices()[0].device_kind.lower()
    return next(v for k, v in _PEAK_FLOPS if k in kind)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--hidden", type=int, default=512)
    p.add_argument("--depth", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--attention-block", type=int, default=None,
                   help="flash kernel block_q/block_k override")
    p.add_argument("--attention", nargs="+",
                   default=["dense", "blockwise", "flash"])
    p.add_argument("--model", choices=("lm", "lm_pp"), default="lm",
                   help="lm_pp benches the PIPELINED formulation "
                        "(stacked-scan blocks; dense attention only) — "
                        "on one chip this measures the pipe=1 overhead "
                        "of the formulation itself")
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--reps", type=int, default=2)
    p.add_argument("--remat", action="store_true",
                   help="jax.checkpoint each block (the long-context "
                        "recipe: without it, backward residuals are "
                        "O(T^2) for every attention impl)")
    args = p.parse_args()
    device = require_tpu(_PEAK_FLOPS)

    from tpunet.config import ModelConfig, OptimConfig
    from tpunet.models import create_model, init_variables
    from tpunet.train.state import TrainState, make_optimizer
    from tpunet.train.steps import make_lm_train_step
    from tpunet.utils.prng import step_key

    rng = np.random.default_rng(0)
    toks = rng.integers(0, args.vocab, (args.batch, args.seq_len))
    toks = jax.numpy.asarray(toks, jax.numpy.int32)
    if jax.default_backend() != "tpu" and "flash" in args.attention:
        print("# WARNING: not on TPU — 'flash' falls back to dense "
              "attention, so its column would just re-measure dense; "
              "skipping it", file=sys.stderr, flush=True)
        args.attention = [a for a in args.attention if a != "flash"]

    if args.model == "lm_pp" and set(args.attention) - {"dense", "flash",
                                                        "auto"}:
        args.attention = ["auto"]      # pipelined blocks: dense/flash only

    results, mfus = {}, {}
    flops_step = analytic_train_flops(args.batch, args.seq_len,
                                      args.hidden, args.depth, 4.0,
                                      args.vocab)
    peak = peak_flops_per_chip()
    for attn in args.attention:
        mcfg = ModelConfig(
            name=args.model, vit_hidden=args.hidden,
            vit_depth=args.depth,
            vit_heads=args.heads, vocab_size=args.vocab,
            max_seq_len=args.seq_len, dropout_rate=0.0, attention=attn,
            remat=args.remat and args.model == "lm",
            **({"attention_block": args.attention_block}
               if args.attention_block else {}))
        model = create_model(mcfg)
        variables = init_variables(model, jax.random.PRNGKey(0),
                                   seq_len=args.seq_len)
        state = TrainState.create(
            apply_fn=model.apply, params=variables["params"],
            batch_stats={}, ema_params={}, ema_batch_stats={},
            tx=make_optimizer(OptimConfig(), 100, 1))
        step = jax.jit(make_lm_train_step(OptimConfig(), mcfg),
                       donate_argnums=0)

        def sync(state):
            jax.block_until_ready(state)
            leaf = jax.tree_util.tree_leaves(state.params)[0]
            return float(np.asarray(leaf.ravel()[0]))

        print(f"# {attn}: compiling...", file=sys.stderr, flush=True)
        for i in range(3):
            state, m = step(state, toks, None, step_key(0, i))
        sync(state)
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            for i in range(args.steps):
                state, m = step(state, toks, None, step_key(0, i + 3))
            sync(state)
            best = min(best, (time.perf_counter() - t0) / args.steps)
        tok_s = args.batch * args.seq_len / best
        results[attn] = round(tok_s, 1)
        mfu = (flops_step / best / peak) if peak else None
        if mfu is not None:
            mfus[attn] = round(mfu, 4)
        # Cross-check only: XLA's count misses Pallas custom-call FLOPs
        # (flash) and counts remat recompute (remat), so the analytic
        # number above is the MFU numerator.
        xla_flops = 0.0
        try:
            ca = step.lower(state, toks, None,
                            step_key(0, 0)).compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            xla_flops = float(ca.get("flops", 0.0))
        except Exception:
            pass
        print(f"# {attn}: {best * 1e3:.1f} ms/step, "
              f"{tok_s:,.0f} tok/s"
              + (f", MFU {mfu:.3f} (analytic {flops_step / 1e9:.1f} "
                 f"GFLOP/step; xla counts {xla_flops / 1e9:.1f})"
                 if mfu is not None else ""),
              file=sys.stderr, flush=True)

    print(json.dumps({
        "metric": "lm_train_tokens_per_sec",
        "config": {"model": args.model, "batch": args.batch,
                   "seq_len": args.seq_len,
                   "hidden": args.hidden, "depth": args.depth,
                   "heads": args.heads, "remat": args.remat,
                   "attention_block": args.attention_block},
        **device,
        "value": results,
        "unit": "tok/s",
        "analytic_flops_per_step": flops_step,
        "peak_flops_per_chip": peak,
        "mfu": mfus,
    }))


if __name__ == "__main__":
    main()
