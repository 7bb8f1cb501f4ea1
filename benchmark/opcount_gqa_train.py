"""Parameters and matrix-product operations of a TRAIN step of the
grouped-query decoder whose router reads the block's input, with
windowed and position-free layers side by side and no-drop ReGLU
experts (``tpunet/models/latent_lm.py`` ``model_type`` ``smallthinker``;
the configuration ``smallthinker-21ba3b``), from the configuration's
sizes alone.

Kept with the benchmark so that no later change to the program can move
the denominator of a utilization or of a roofline share. Counted is
what THIS CHIP holds and what the algorithm requires of it: every
layer's attention and router whole, the held share of the routed
experts, the head over the vocabulary slice; scores and values over the
(query, key) pairs a query may see — ``min(t + 1, window)`` keys for
query ``t`` in a windowed layer, ``t + 1`` in a global one — whatever
blocks a kernel visits to get them. ``held_pair_share`` is the share of
the (token, expert) pairs that land on held experts: 1/4 in expectation
for 16 of 64, and measured by the run where a run is there to measure it.
"""

from __future__ import annotations

from benchmark.opcount_hybrid import roofline_seconds  # noqa: F401

# Matrix products a (query, key) pair of one head takes, each
# ``head_dim`` multiply-adds: the forward's scores and weighted values;
# the backward's scores again, dP = dO V^T, dV, dQ and dK (computed once
# each, however the kernels split them).
FORWARD_PRODUCTS, BACKWARD_PRODUCTS = 2, 5


def layer_kinds(cfg: dict) -> tuple:
    """``(windowed layers, global layers)`` of the layers that are run."""
    marks = cfg["sliding_window_layout"][:cfg["num_hidden_layers"]]
    return sum(1 for m in marks if m), sum(1 for m in marks if not m)


def layer_counts(cfg: dict) -> dict:
    """Parameters by part, as this chip holds them."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attention = c * h * d + 2 * c * hkv * d + h * d * c
    expert = 3 * c * cfg["moe_ffn_hidden_size"]
    router = c * cfg["moe_num_primary_experts_published"]
    held = len(cfg["held_experts"])
    return {"attention": attention, "expert": expert, "router": router,
            "norms": 2 * c,
            "layer": attention + router + 2 * c + held * expert,
            "embedding": v * c, "head": c * v}


def parameters(cfg: dict) -> int:
    """All parameters this chip trains: the layers, embedding, head and
    the final norm."""
    n = layer_counts(cfg)
    return (cfg["num_hidden_layers"] * n["layer"] + n["embedding"]
            + n["head"] + cfg["hidden_size"])


def attention_pairs(tokens: int, cfg: dict) -> dict:
    """(query, key) pairs of one causal row of ``tokens`` positions in
    one layer of each kind."""
    w = min(cfg["sliding_window_size"], tokens)
    return {"window": w * (w + 1) // 2 + (tokens - w) * w,
            "global": tokens * (tokens + 1) // 2}


def activated_parameters(cfg: dict, held_pair_share: float) -> float:
    """Matrix parameters one token multiplies on this chip in one
    forward pass (the embedding is a gather and counts nothing)."""
    n = layer_counts(cfg)
    routed = (cfg["moe_num_active_primary_experts"] * held_pair_share
              * n["expert"])
    return (cfg["num_hidden_layers"]
            * (n["attention"] + n["router"] + routed) + n["head"])


def attention_flops_forward(tokens: int, cfg: dict) -> float:
    """Scores and weighted values of every layer for one row, forward:
    two products of ``heads * head_dim`` multiply-adds a pair."""
    windowed, full = layer_kinds(cfg)
    pairs = attention_pairs(tokens, cfg)
    return (2.0 * FORWARD_PRODUCTS * cfg["num_attention_heads"]
            * cfg["head_dim"]
            * (windowed * pairs["window"] + full * pairs["global"]))


def train_flops_per_token(cfg: dict, seq_len: int,
                          held_pair_share: float) -> float:
    """Matrix-product FLOPs a token of one train step: forward + twice
    that backward over the activated parameters and over the attention
    pairs a row of ``seq_len`` has; recomputation does not count."""
    return 3.0 * (2.0 * activated_parameters(cfg, held_pair_share)
                  + attention_flops_forward(seq_len, cfg) / seq_len)


def flash_train(tokens: int, cfg: dict, store_bytes: int = 2) -> dict:
    """What the attention kernels of one train step on one row of
    ``tokens`` positions are required to do, all layers: the forward,
    the forward once more where the configuration's program recomputes
    each block (``program.model.remat``), and the backward's five
    products to the forward's two. ``kernels`` = kernel launches a step
    (a layer: forward, recomputed forward, dQ, dK/dV). Bytes: each
    kernel reads q, k, v (the backward dO too, and a float32 row
    statistic or two) and writes its outputs once."""
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layers = sum(layer_kinds(cfg))
    passes = 2 if cfg["program"]["model"]["remat"] else 1
    forward = attention_flops_forward(tokens, cfg)
    qo, kv = tokens * h * d * store_bytes, tokens * hkv * d * store_bytes
    stat = tokens * h * 4
    fwd_bytes = 2 * qo + 2 * kv + stat
    bwd_bytes = (3 * qo + 2 * kv + 2 * stat) + (2 * qo + 4 * kv + 2 * stat)
    return {"flops": forward * (passes + BACKWARD_PRODUCTS
                                / FORWARD_PRODUCTS),
            "bytes": layers * (passes * fwd_bytes + bwd_bytes),
            "kernels": layers * (passes + 2)}
