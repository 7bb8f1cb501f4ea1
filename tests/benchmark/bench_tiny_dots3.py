"""``bench_tiny_root.make`` plus the dots3-note-prev configuration, its
cell and its traffic cut to a size a CPU test can hold: every layer
kind, ``index_topk`` and the window shorter than the sequences, 4 of 8
experts held."""

from __future__ import annotations

import os

import bench_tiny_root
import chip_smoke

CELL = "dots3-note-prev.serve-closed8-ctx4k"
VOCAB = 64


def make(tmp: str, dtype: str = "float32") -> str:
    root = bench_tiny_root.make(tmp, dtype)
    b = os.path.join(root, "benchmark")

    def config(c):
        small = chip_smoke.LATENT_TINY
        c.update(small, n_routed_experts=4, n_routed_experts_published=8,
                 held_experts=[0, 1, 2, 3], vocab_size=VOCAB,
                 param_dtype=dtype, compute_dtype=dtype)
        model = c["program"]["model"]
        model.update(vocab_size=VOCAB, max_seq_len=64, dtype=dtype,
                     param_dtype=dtype)
        model["latent"].update(small, n_routed_experts=8,
                               held_experts=[0, 1, 2, 3])

    def traffic(t):
        t.update(clients=3, pool_prompt=2, pool_output=2, fill_seconds=0.2,
                 sample_requests=2, trace_seconds=0.5)
        t["prompt_len"].update(median=12, min=8, max=24)
        t["output_len"].update(median=6, min=3, max=8)

    bench_tiny_root._edit(os.path.join(b, "configs", "dots3-note-prev.json"),
                          config)
    bench_tiny_root._edit(os.path.join(b, "traffic",
                                       "serve-closed8-ctx4k.json"), traffic)
    bench_tiny_root._edit(
        os.path.join(b, "workloads", f"{CELL}.json"),
        lambda w: w["program"]["serve"].update(
            slots=3, prefill_buckets=[24], kv_page_tokens=4))
    return root
