"""Parameters and matrix-product operations of the latent-attention
decoder with no-drop experts and a multi-token-prediction module
(``tpunet/models/latent_lm.py``; the configuration ``glm-4.7-flash``),
from the configuration's sizes alone.

Kept with the benchmark so that no later change to the program can move
the denominator of a utilization. Counted is what THIS CHIP holds and
what the algorithm requires of it: every layer's attention, router and
shared expert whole, the dense layer, the held share of the routed
experts, ``W_eh``, and the head once for the trunk and once for the
module; causal scores and values at half the square; recomputation does
not count. ``held_pair_share`` is the share of the (token, expert)
pairs that land on held experts — 1/8 in expectation for 8 of 64, and
measured by the run where a run is there to measure it.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> dict:
    """One attention layer: its matrices (which multiply) and its two
    latent norms (which do not)."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return {"matrices": (c * rq + rq * h * (dn + dr) + c * (rkv + dr)
                         + rkv * h * (dn + dv) + h * dv * c),
            "norms": rq + rkv}


def layer_counts(cfg: dict) -> dict:
    """Parameters by part, as this chip holds them."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    f, d = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    attn = attention_params(cfg)
    attention = attn["matrices"] + attn["norms"]
    expert = 3 * c * f
    held = len(cfg["held_experts"])
    router = c * cfg["n_routed_experts_published"]
    outside = (attention + expert + router
               + cfg["n_routed_experts_published"] + 2 * c)
    return {"attention": attention, "attention_matrices": attn["matrices"],
            "dense_layer": attention + 3 * c * d + 2 * c,
            "dense_mlp": 3 * c * d, "expert": expert, "router": router,
            "expert_layer_outside_routed": outside,
            "expert_layer": outside + held * expert,
            "embedding": v * c, "head": c * v, "w_eh": 2 * c * c}


def parameters(cfg: dict) -> int:
    """All parameters this chip trains: the dense layers, the expert
    layers, embedding, head, the final norm, and the module (two norms,
    ``W_eh``, one expert layer, its final norm)."""
    n = layer_counts(cfg)
    c = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    total = (dense * n["dense_layer"]
             + (cfg["num_hidden_layers"] - dense) * n["expert_layer"]
             + n["embedding"] + n["head"] + c)
    if cfg["num_nextn_predict_layers"]:
        total += 2 * c + n["w_eh"] + n["expert_layer"] + c
    return total


def activated_parameters(cfg: dict, held_pair_share: float) -> float:
    """Matrix parameters one token multiplies on this chip in one
    forward pass (the embedding is a gather and counts nothing)."""
    n = layer_counts(cfg)
    dense = cfg["first_k_dense_replace"]
    mtp = 1 if cfg["num_nextn_predict_layers"] else 0
    expert_layers = cfg["num_hidden_layers"] - dense + mtp
    routed = cfg["num_experts_per_tok"] * held_pair_share * n["expert"]
    return ((cfg["num_hidden_layers"] + mtp) * n["attention_matrices"]
            + dense * n["dense_mlp"]
            + expert_layers * (n["router"] + n["expert"] + routed)
            + mtp * n["w_eh"] + (1 + mtp) * n["head"])


def train_flops_per_token(cfg: dict, seq_len: int,
                          held_pair_share: float) -> float:
    """Matrix-product FLOPs a token of one train step: forward + twice
    that backward over the activated parameters, and over the causal
    scores and values of every attention (each ``2 * T * heads * D`` a
    token for the full square, halved)."""
    mtp = 1 if cfg["num_nextn_predict_layers"] else 0
    heads = cfg["num_attention_heads"]
    d_qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = (cfg["num_hidden_layers"] + mtp) * heads * seq_len * (
        d_qk + cfg["v_head_dim"])          # scores + values, half the square
    return 3.0 * (2.0 * activated_parameters(cfg, held_pair_share) + attn)
