"""``bench_tiny_root.make`` plus the command-a-plus-05-2026
configuration, its cell and its traffic cut to a size a CPU test can
hold: one period (S S S F) with a window of 6 keys over 4-token pages, 2
KV heads under 8 query heads, 4 of 8 experts held beside 2 shared,
prompts past the window."""

from __future__ import annotations

import os

import bench_tiny_root

CELL = "command-a-plus-05-2026.serve-closed16-ctx8k"
VOCAB = 64
PARALLEL_TINY = dict(
    hidden_size=64, intermediate_size=32, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, sliding_window=6,
    num_experts_per_tok=2, num_shared_experts=2)


def make(tmp: str, dtype: str = "float32") -> str:
    root = bench_tiny_root.make(tmp, dtype)
    b = os.path.join(root, "benchmark")

    def config(c):
        c.update(PARALLEL_TINY, num_hidden_layers=4, num_experts=4,
                 num_experts_published=8, held_experts=[0, 1, 2, 3],
                 vocab_size=VOCAB, param_dtype=dtype, compute_dtype=dtype)
        model = c["program"]["model"]
        model.update(vocab_size=VOCAB, max_seq_len=64, dtype=dtype,
                     param_dtype=dtype)
        model["latent"].update(PARALLEL_TINY, num_experts=8,
                               held_experts=[0, 1, 2, 3])

    def traffic(t):
        t.update(clients=3, pool_prompt=2, pool_output=2, fill_seconds=0.2,
                 sample_requests=2, trace_seconds=0.5, max_requests=64)
        t["prompt_len"].update(median=12, min=8, max=24)
        t["output_len"].update(median=6, min=3, max=8)

    bench_tiny_root._edit(
        os.path.join(b, "configs", "command-a-plus-05-2026.json"), config)
    bench_tiny_root._edit(
        os.path.join(b, "traffic", "serve-closed16-ctx8k.json"), traffic)
    bench_tiny_root._edit(
        os.path.join(b, "workloads", f"{CELL}.json"),
        lambda w: w["program"]["serve"].update(
            slots=3, prefill_buckets=[24], kv_page_tokens=4))
    return root
