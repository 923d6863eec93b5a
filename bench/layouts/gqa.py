"""The layout of a decoder with grouped-query attention.

One stack of `n_layers` identical layers on a leading axis: RMSNorm
scales, `wq`/`wk`/`wv`/`wo`, a SwiGLU MLP or routed experts (every expert
held, the router as wide as the experts), and the DSA indexer with fixed
head weights; the embedding, the final norm and an untied head. Every
leaf is drawn whole (bench/weights.py), as the benchmark first drew it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """leaf -> (shape, dtype, scale); layer leaves carry the layer axis.
    scale is the standard deviation of a matrix; -1 marks a norm scale,
    -2 the indexer's positive head weights."""
    d, v, n = cfg["d_model"], cfg["vocab"], cfg["n_layers"]
    h, kvh, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    dt = cfg["dtype"]
    out = {
        # a tied embedding is the head too: drawn as a head (1/d_model),
        # or the input token's own row would outweigh the rest of the
        # residual stream and win every greedy step whatever the layers do
        "embed": ((v, d), dt, d ** -0.5 if cfg["tie_embeddings"] else 1.0),
        "final_norm": ((d,), "float32", -1.0),
        "ln1": ((n, d), "float32", -1.0),
        "ln2": ((n, d), "float32", -1.0),
        "wq": ((n, d, h * hd), dt, d ** -0.5),
        "wk": ((n, d, kvh * hd), dt, d ** -0.5),
        "wv": ((n, d, kvh * hd), dt, d ** -0.5),
        "wo": ((n, h * hd, d), dt, (h * hd) ** -0.5),
    }
    if not cfg["tie_embeddings"]:
        out["lm_head"] = ((d, v), dt, d ** -0.5)
    moe = cfg.get("moe")
    if moe and moe["num_experts"]:
        e, f = moe["num_experts"], moe["expert_d_ff"]
        out["router"] = ((n, d, e), "float32", d ** -0.5)
        out["w_gate"] = ((n, e, d, f), dt, d ** -0.5)
        out["w_up"] = ((n, e, d, f), dt, d ** -0.5)
        out["w_down"] = ((n, e, f, d), dt, f ** -0.5)
    else:
        ff = cfg["d_ff"]
        out["w_gate"] = ((n, d, ff), dt, d ** -0.5)
        out["w_up"] = ((n, d, ff), dt, d ** -0.5)
        out["w_down"] = ((n, ff, d), dt, ff ** -0.5)
    dsa = cfg.get("dsa")
    if dsa and dsa["enabled"]:
        hi, di = dsa["indexer_heads"], dsa["indexer_dim"]
        out["idx_wq"] = ((n, d, hi * di), dt, d ** -0.5)
        out["idx_wk"] = ((n, d, di), dt, d ** -0.5)
        out["idx_w"] = ((n, hi), "float32", -2.0)
    return out


def program_params(flat: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The flat leaves in the program's parameter tree (no copies)."""
    layers = {k: flat[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                   "w_gate", "w_up", "w_down", "router")
              if k in flat}
    if "idx_wq" in flat:
        layers["indexer"] = {"wq": flat["idx_wq"], "wk": flat["idx_wk"],
                             "w": flat["idx_w"]}
    params = {"embed": flat["embed"], "layers": layers,
              "final_norm": flat["final_norm"]}
    if "lm_head" in flat:
        params["lm_head"] = flat["lm_head"]
    return params


def flops_per_layer(cfg: Dict[str, Any], context: int,
                    sparse: bool) -> List[float]:
    """One token's FLOPs in each of the `n_layers` identical layers."""
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
    return [float(flops)] * cfg["n_layers"]
