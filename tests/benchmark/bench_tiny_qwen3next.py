"""``bench_tiny_root.make`` plus the qwen3-next-80b-a3b configuration,
its cell and its traffic cut to a size a CPU test can hold: both layer
kinds (L L L F), 2 KV heads under 4 query heads, 4 of 8 experts held,
prompts on both sides of the delta rule's chunk."""

from __future__ import annotations

import os

import bench_tiny_root

CELL = "qwen3-next-80b-a3b.serve-closed32-ctx8k"
VOCAB = 64
HYBRID_TINY = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, num_experts_per_tok=2,
    moe_intermediate_size=32)


def make(tmp: str, dtype: str = "float32") -> str:
    root = bench_tiny_root.make(tmp, dtype)
    b = os.path.join(root, "benchmark")

    def config(c):
        c.update(HYBRID_TINY, num_hidden_layers=4, num_experts=4,
                 num_experts_published=8, held_experts=[0, 1, 2, 3],
                 shared_expert_intermediate_size=32, vocab_size=VOCAB,
                 param_dtype=dtype, compute_dtype=dtype)
        model = c["program"]["model"]
        model.update(vocab_size=VOCAB, max_seq_len=64, dtype=dtype,
                     param_dtype=dtype)
        model["latent"].update(
            HYBRID_TINY, num_hidden_layers=4, num_experts=8,
            held_experts=[0, 1, 2, 3],
            layer_types=["linear_attention"] * 3 + ["full_attention"])

    def traffic(t):
        t.update(clients=3, pool_prompt=2, pool_output=2, fill_seconds=0.2,
                 sample_requests=2, trace_seconds=0.5, max_requests=64)
        t["prompt_len"].update(median=12, min=8, max=24)
        t["output_len"].update(median=6, min=3, max=8)

    bench_tiny_root._edit(
        os.path.join(b, "configs", "qwen3-next-80b-a3b.json"), config)
    bench_tiny_root._edit(
        os.path.join(b, "traffic", "serve-closed32-ctx8k.json"), traffic)
    bench_tiny_root._edit(
        os.path.join(b, "workloads", f"{CELL}.json"),
        lambda w: w["program"]["serve"].update(
            slots=3, prefill_buckets=[24], kv_page_tokens=4))
    return root
