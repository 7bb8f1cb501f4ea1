#!/usr/bin/env python
"""Flash-kernel microbenchmark: fwd and fwd+bwd per attention impl.

Reproduces (and extends) the round-1 kernel measurement — forward at
B=4, T=4096, H=8, D=64, causal, bfloat16 on one chip — now that the
causal grid is triangular (forward/dQ) with dead copies elided
elsewhere. Round-1 recorded numbers for the same shape (rectangular
grid + @pl.when skip): flash 10.7 ms fwd vs dense 25.6 ms vs blockwise
17.1 ms (tpunet/ops/flash.py module docstring).

Prints one JSON line per (impl, mode). Synchronization fetches a value
data-dependent on the result. Exits non-zero off the TPU.

    python scripts/bench_flash.py [--t 4096] [--steps 20] [--seg]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from _chip import require_tpu  # noqa: E402
from tpunet.utils.cache import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()


def sync(x):
    # Fetch ONE element data-dependent on the result: a full-array
    # np.asarray would put the device-to-host copy of the whole tensor
    # inside the timed region.
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(np.asarray(leaf.ravel()[0]))


def bench(fn, args, steps, warmup=3, reps=3):
    for _ in range(warmup):
        out = fn(*args)
    sync(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        sync(out)
        best = min(best, time.perf_counter() - t0)
    return best / steps * 1e3  # ms


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--b", type=int, default=4)
    p.add_argument("--t", type=int, default=4096)
    p.add_argument("--h", type=int, default=8)
    p.add_argument("--d", type=int, default=64)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--block", type=int, default=512)
    p.add_argument("--seg", action="store_true",
                   help="also bench the segmented (packed) variant")
    args = p.parse_args()
    device = require_tpu()

    from tpunet.ops.attention import blockwise_attention, dense_attention
    from tpunet.ops.flash import flash_attention

    rng = np.random.default_rng(0)
    shp = (args.b, args.t, args.h, args.d)
    q = jnp.asarray(rng.standard_normal(shp), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal(shp), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shp), jnp.bfloat16)
    # 4 packed docs per row for the segmented bench (last doc absorbs
    # the t % 4 remainder)
    seg_row = np.concatenate([
        np.full(args.t // 4, i + 1, np.int32) for i in range(3)
    ] + [np.full(args.t - 3 * (args.t // 4), 4, np.int32)])
    seg = jnp.asarray(seg_row[None].repeat(args.b, 0))

    impls = {
        "flash": lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=args.block, block_k=args.block),
        "dense": lambda q, k, v: dense_attention(q, k, v, causal=True),
        "blockwise": lambda q, k, v: blockwise_attention(
            q, k, v, causal=True, block_size=args.block),
    }
    if args.seg:
        impls["flash+seg"] = lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=args.block, block_k=args.block,
            segment_ids=(seg, seg))

    meta = {"b": args.b, "t": args.t, "h": args.h, "d": args.d,
            "dtype": "bfloat16", "causal": True, **device}
    for name, f in impls.items():
        fwd = jax.jit(f)
        ms_f = bench(fwd, (q, k, v), args.steps)
        loss = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))
        ms_b = bench(loss, (q, k, v), args.steps)
        print(json.dumps({"impl": name, "fwd_ms": round(ms_f, 3),
                          "fwd_bwd_ms": round(ms_b, 3), **meta}),
              flush=True)


if __name__ == "__main__":
    main()
