"""Model FLOPs of one served token, from a configuration's shapes.

Counts the multiply-adds the model needs (2 FLOPs each) for one token
that attends over `context` positions (its own included): projections,
the MLP or the routed experts actually chosen (top_k of them, not every
expert), the DSA indexer and the attention over the selected rows when
the cell runs sparse, and the head. Selection itself (comparisons) and
elementwise work are not counted. This is the work any implementation of
the model has to do, so it bounds a step whatever computes it.
"""

from __future__ import annotations

from typing import Any, Dict


def sparse_cell(cfg: Dict[str, Any], max_len: int) -> bool:
    """Whether a cell runs DSA: the program's gate is on the pool's
    logical extent (max_len), not on a request's own length."""
    dsa = cfg.get("dsa") or {}
    return bool(dsa.get("enabled")) and max_len > dsa["min_n"]


def per_layer(cfg: Dict[str, Any], context: int, sparse: bool) -> float:
    d, h, kvh, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                     cfg["head_dim"])
    flops = 2 * d * (h + 2 * kvh) * hd + 2 * h * hd * d
    moe = cfg.get("moe") or {}
    if moe.get("num_experts"):
        flops += 2 * d * moe["num_experts"]
        flops += moe["top_k"] * 2 * 3 * d * moe["expert_d_ff"]
    else:
        flops += 2 * 3 * d * cfg["d_ff"]
    if sparse:
        dsa = cfg["dsa"]
        hi, di = dsa["indexer_heads"], dsa["indexer_dim"]
        flops += 2 * d * hi * di + 2 * d * di          # indexer q and k
        flops += 2 * context * hi * di + 2 * hi * context   # scores
        flops += 4 * h * hd * min(dsa["k"], context)   # attention, K rows
    else:
        flops += 4 * h * hd * context
    return float(flops)


def per_token(cfg: Dict[str, Any], context: int, sparse: bool) -> float:
    return (cfg["n_layers"] * per_layer(cfg, context, sparse)
            + 2.0 * cfg["d_model"] * cfg["vocab"])
