"""``bench_tiny_root.make`` plus the glm-4.7-flash configuration and its
train cell cut to a size a CPU test can hold: a dense layer and two
expert layers, 4 of 8 experts held, one head size for queries, keys and
values (the flash kernel's condition), the prediction module."""

from __future__ import annotations

import os

import bench_tiny_root

CELL = "glm-4.7-flash.train-b1-t8192"
VOCAB = 64
SMALL = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=24, moe_intermediate_size=32,
    num_experts_per_tok=2, num_hidden_layers=3)
HELD, PUBLISHED_E = [0, 1, 2, 3], 8


def shrink(config: dict, dtype: str = "float32") -> dict:
    """The configuration (as its file holds it) at the small sizes."""
    config.update(SMALL, n_routed_experts=len(HELD),
                  n_routed_experts_published=PUBLISHED_E, held_experts=HELD,
                  vocab_size=VOCAB, compute_dtype=dtype)
    model = config["program"]["model"]
    model.update(vocab_size=VOCAB, max_seq_len=64, dtype=dtype)
    model["latent"].update(
        SMALL, n_routed_experts=PUBLISHED_E, held_experts=HELD,
        layer_types=["full_attention"] * SMALL["num_hidden_layers"])
    config["program"]["data"].update(seq_len=32, vocab_size=VOCAB)
    return config


def make(tmp: str, dtype: str = "float32") -> str:
    root = bench_tiny_root.make(tmp, dtype)
    b = os.path.join(root, "benchmark")
    bench_tiny_root._edit(os.path.join(b, "configs", "glm-4.7-flash.json"),
                          lambda c: shrink(c, dtype))
    bench_tiny_root._edit(
        os.path.join(b, "traffic", "train-b1-t8192.json"),
        lambda t: t.update(batch=2, seq_len=32, steps_per_chunk=4,
                           trace_seconds=0.5))
    return root
