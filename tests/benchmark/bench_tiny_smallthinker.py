"""``bench_tiny_root.make`` plus the smallthinker-21ba3b configuration
and its train cell cut to a size a CPU test can hold: one period of the
layer pattern (a position-free global layer, three rotary window
layers), 6 query heads over 2 KV heads, a window shorter than the
sequence, 4 of 8 ReGLU experts held, top-3."""

from __future__ import annotations

import os

import bench_tiny_root

CELL = "smallthinker-21ba3b.train-b1-t8192"
VOCAB = 64
SMALL = dict(
    hidden_size=48, num_attention_heads=6, num_key_value_heads=2,
    head_dim=16, sliding_window_size=12, moe_ffn_hidden_size=24,
    moe_num_active_primary_experts=3, num_hidden_layers=4)
HELD, PUBLISHED_E = [0, 1, 2, 5], 8


def shrink(config: dict, dtype: str = "float32") -> dict:
    """The configuration (as its file holds it) at the small sizes."""
    config.update(SMALL, moe_num_primary_experts=len(HELD),
                  moe_num_primary_experts_published=PUBLISHED_E,
                  held_experts=HELD, vocab_size=VOCAB, compute_dtype=dtype)
    model = config["program"]["model"]
    model.update(vocab_size=VOCAB, max_seq_len=64, dtype=dtype)
    model["latent"].update(SMALL, moe_num_primary_experts=PUBLISHED_E,
                           held_experts=HELD)
    config["program"]["data"].update(seq_len=32, vocab_size=VOCAB)
    return config


def make(tmp: str, dtype: str = "float32") -> str:
    root = bench_tiny_root.make(tmp, dtype)
    b = os.path.join(root, "benchmark")
    bench_tiny_root._edit(
        os.path.join(b, "configs", "smallthinker-21ba3b.json"),
        lambda c: shrink(c, dtype))
    bench_tiny_root._edit(
        os.path.join(b, "traffic", "train-b1-t8192.json"),
        lambda t: t.update(batch=2, seq_len=32, steps_per_chunk=4,
                           trace_seconds=0.5))
    return root
