"""Operations and bytes of the hybrid decoder's kernels
(``tpunet/models/hybrid_mixers.py``; the configuration
``qwen3-next-80b-a3b``), from shapes alone.

Kept with the benchmark so that no later change to the program can move
the denominator of a roofline share: the count is of what the algorithm
needs, whatever implements it.
"""

from __future__ import annotations


def full_attention_layers(cfg: dict) -> int:
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count(
        "full_attention")


def paged_decode_gqa(live_keys: int, rows: int, cfg: dict,
                     store_bytes: int = 2) -> dict:
    """One layer's width-1 grouped-query attention over the paged pool,
    summed over calls: ``live_keys`` = the sum over the calls' rows of
    the keys each attends to (its position + 1), ``rows`` = how many
    rows that is. Every live key's K row and V row is read once (a KV
    head serves its whole group of query heads from one read); each row
    reads its query and writes its output; scores and weighted values
    are two products of ``heads * head_dim`` multiply-adds a key."""
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {"bytes": live_keys * 2 * hkv * d * store_bytes
            + rows * 2 * h * d * store_bytes,
            "flops": live_keys * 2 * 2 * h * d}


def roofline_seconds(count: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations
    over the peak rate and bytes over the memory bandwidth."""
    return max(count["flops"] / peaks["bf16_flops_per_s"],
               count["bytes"] / peaks["hbm_bytes_per_s"])
