"""Operations and bytes of grouped-query attention with sliding-window
and full layers side by side (``tpunet/models/hybrid_mixers.py``
``GroupedQueryAttention``; the configuration
``command-a-plus-05-2026``), from shapes alone.

Kept with the benchmark so that no later change to the program can move
the denominator of a roofline share: the count is of what the algorithm
needs, whatever implements it.
"""

from __future__ import annotations

from benchmark.opcount_hybrid import roofline_seconds  # noqa: F401


def layer_kinds(cfg: dict) -> tuple:
    """``(sliding layers, full layers)`` of the layers that are run."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return kinds.count("sliding_attention"), kinds.count("full_attention")


def paged_decode(contexts, cfg: dict, store_bytes: int = 2) -> dict:
    """Width-1 attention over the paged pool, all layers, summed over
    rows: ``contexts`` holds, per decode row, the keys its row has
    (its position + 1). A sliding layer reads the last
    ``min(keys, sliding_window)`` of them, a full layer all. Every key
    in sight has its K row and V row read once (a KV head serves its
    whole group of query heads from one read); each row and layer reads
    its query and writes its output; scores and weighted values are two
    products of ``heads * head_dim`` multiply-adds a key."""
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    sliding, full = layer_kinds(cfg)
    window = cfg["sliding_window"]
    rows = keys = 0
    for n in contexts:
        rows += 1
        keys += sliding * min(n, window) + full * n
    return {"bytes": keys * 2 * hkv * d * store_bytes
            + rows * (sliding + full) * 2 * h * d * store_bytes,
            "flops": keys * 2 * 2 * h * d, "rows": rows, "keys": keys}


def flash_prefill(tokens: int, cfg: dict, store_bytes: int = 2) -> dict:
    """Causal attention of one row of ``tokens`` positions that starts
    at 0, all layers: query t sees ``min(t + 1, sliding_window)`` keys
    in a sliding layer and ``t + 1`` in a full one; scores and weighted
    values are two products of ``heads * head_dim`` multiply-adds a
    (query, key) pair; q and the output are read and written once, K
    and V read once."""
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    sliding, full = layer_kinds(cfg)
    w = min(cfg["sliding_window"], tokens)
    pairs_full = tokens * (tokens + 1) // 2
    pairs_window = w * (w + 1) // 2 + (tokens - w) * w
    pairs = sliding * pairs_window + full * pairs_full
    return {"flops": pairs * 2 * 2 * h * d, "pairs": pairs,
            "bytes": (sliding + full) * tokens * 2 * (h + hkv) * d
            * store_bytes, "kernels": sliding + full}
