"""The layout of DeepSeek-V3.2: multi-head latent attention with the V3.2
indexer, leading dense layers, then expert layers holding a share of the
routed experts.

Each kind of layer is a stack of its own (`dense_*`, `moe_*`), drawn one
layer at a time; the experts held are drawn from their global indices
(bench/weights.py:experts), so a chip's experts are that slice of what the
uncut layer draws. Every layer has the latent projections (`wq_a`,
`q_norm`, `wq_b`, `wkv_a`, `kv_norm`, `wkv_b`, `wo`) and the indexer
(`idx_wq_b` from the query latent, `idx_wk` with its LayerNorm
`idx_k_norm`/`idx_k_norm_bias`, the per-token head weights
`idx_weights_proj`); dense layers end in a SwiGLU of width d_ff, expert
layers in a router over every routed expert with its bias, a shared
expert and the held experts. The embedding and the head are untied.

Besides the FLOPs of one token, this layout gives the bytes the two
rooflines of its cell read (bench/metrics/experts_roofline.py,
attention_roofline.py).
"""

from __future__ import annotations

from typing import Any, Dict, List

from bench import weights

LAYER = (0,)
# bytes of an element: weights as served, the router and its bias float32
SERVED = {"bfloat16": 2, "float32": 4}


def _attention(cfg: Dict[str, Any], n: int) -> Dict[str, tuple]:
    d, h, dt = cfg["d_model"], cfg["n_heads"], cfg["dtype"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    hi, di = cfg["dsa"]["indexer_heads"], cfg["dsa"]["indexer_dim"]
    return {
        "ln1": ((n, d), "float32", -1.0, LAYER),
        "ln2": ((n, d), "float32", -1.0, LAYER),
        "wq_a": ((n, d, ql), dt, d ** -0.5, LAYER),
        "q_norm": ((n, ql), "float32", -1.0, LAYER),
        "wq_b": ((n, ql, h * (nope + rope)), dt, ql ** -0.5, LAYER),
        "wkv_a": ((n, d, kvl + rope), dt, d ** -0.5, LAYER),
        "kv_norm": ((n, kvl), "float32", -1.0, LAYER),
        "wkv_b": ((n, kvl, h * (nope + vd)), dt, kvl ** -0.5, LAYER),
        "wo": ((n, h * vd, d), dt, (h * vd) ** -0.5, LAYER),
        "idx_wq_b": ((n, ql, hi * di), dt, ql ** -0.5, LAYER),
        "idx_wk": ((n, d, di), dt, d ** -0.5, LAYER),
        "idx_k_norm": ((n, di), "float32", -1.0, LAYER),
        "idx_k_norm_bias": ((n, di), "float32", 0.1, LAYER),
        "idx_weights_proj": ((n, d, hi), dt, d ** -0.5, LAYER),
    }


def shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    """leaf -> (shape, dtype, scale, fold); scale as in bench/weights.py."""
    d, v, dt = cfg["d_model"], cfg["vocab"], cfg["dtype"]
    nd = cfg["first_k_dense"]
    nm = cfg["n_layers"] - nd
    moe = cfg["moe"]
    routed, _, _ = weights.expert_share(moe)
    f, fs, ff = moe["expert_d_ff"], moe["shared_d_ff"], cfg["d_ff"]
    out = {"embed": ((v, d), dt, 1.0), "final_norm": ((d,), "float32", -1.0),
           "lm_head": ((d, v), dt, d ** -0.5)}
    for prefix, n in (("dense_", nd), ("moe_", nm)):
        out.update({prefix + k: e for k, e in _attention(cfg, n).items()})
    out.update({
        "dense_w_gate": ((nd, d, ff), dt, d ** -0.5, LAYER),
        "dense_w_up": ((nd, d, ff), dt, d ** -0.5, LAYER),
        "dense_w_down": ((nd, ff, d), dt, ff ** -0.5, LAYER),
        "moe_router": ((nm, d, routed), "float32", d ** -0.5, LAYER),
        "moe_router_bias": ((nm, routed), "float32", 0.1, LAYER),
        "moe_shared_gate": ((nm, d, fs), dt, d ** -0.5, LAYER),
        "moe_shared_up": ((nm, d, fs), dt, d ** -0.5, LAYER),
        "moe_shared_down": ((nm, fs, d), dt, fs ** -0.5, LAYER),
        "moe_w_gate": weights.experts(moe, (nm,), (d, f), dt, d ** -0.5),
        "moe_w_up": weights.experts(moe, (nm,), (d, f), dt, d ** -0.5),
        "moe_w_down": weights.experts(moe, (nm,), (f, d), dt, f ** -0.5),
    })
    return out


def program_params(flat: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's tree: the drawn leaves themselves, no copies."""
    def stack(prefix):
        return {k[len(prefix):]: x for k, x in flat.items()
                if k.startswith(prefix)}
    return {"embed": flat["embed"], "dense_layers": stack("dense_"),
            "moe_layers": stack("moe_"), "final_norm": flat["final_norm"],
            "lm_head": flat["lm_head"]}


def flops_per_layer(cfg: Dict[str, Any], context: int,
                    sparse: bool) -> List[float]:
    """One token's FLOPs in each layer, in the absorbed decode form: the
    latent projections, q_nope W_ukᵀ, scores over the 576-wide latent rows
    attended and the weighted sum of their 512-wide latents, W_uv, W_o;
    with DSA the indexer's projections and scores over the whole context;
    the dense MLP, or the router, the shared expert and the routed experts
    this chip computes: top_k of the routed ones per token, held / routed
    of them here on average."""
    d, h = cfg["d_model"], cfg["n_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    attn = (2 * d * ql + 2 * ql * h * (nope + rope) + 2 * d * (kvl + rope)
            + 2 * h * nope * kvl + 2 * h * kvl * vd + 2 * h * vd * d)
    rows = context
    if sparse:
        dsa = cfg["dsa"]
        hi, di = dsa["indexer_heads"], dsa["indexer_dim"]
        attn += 2 * ql * hi * di + 2 * d * di + 2 * d * hi
        attn += 2 * context * hi * di + 2 * hi * context
        rows = min(dsa["k"], context)
    attn += 2 * h * (kvl + rope) * rows + 2 * h * kvl * rows
    moe = cfg["moe"]
    routed, held, _ = weights.expert_share(moe)
    dense = attn + 6 * d * cfg["d_ff"]
    expert = (attn + 2 * d * routed + 6 * d * moe["shared_d_ff"]
              + moe["top_k"] * held / routed * 6 * d * moe["expert_d_ff"])
    nd = cfg["first_k_dense"]
    return [float(dense)] * nd + [float(expert)] * (cfg["n_layers"] - nd)


def experts_bytes(cfg: Dict[str, Any]) -> float:
    """Bytes a step reads under `step.experts`: in each expert layer the
    held experts' weights (every one, as at decode batch sizes) and the
    router's with its bias."""
    d, moe = cfg["d_model"], cfg["moe"]
    routed, held, _ = weights.expert_share(moe)
    per_layer = (held * 3 * d * moe["expert_d_ff"] * SERVED[cfg["dtype"]]
                 + (d + 1) * routed * 4)
    return float(per_layer * (cfg["n_layers"] - cfg["first_k_dense"]))


def attention_bytes(cfg: Dict[str, Any], slots: int, max_len: int) -> float:
    """Bytes a step reads under `step.attention` with DSA on: in every
    layer each slot's K gathered latent rows (kv_lora_rank + rope wide; K
    rows whatever the context, as the step gathers them) and the weights
    W_uv and W_o."""
    h, kvl = cfg["n_heads"], cfg["kv_lora_rank"]
    rope, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    b = SERVED[cfg["dtype"]]
    rows = slots * min(cfg["dsa"]["k"], max_len) * (kvl + rope) * b
    w = (kvl * h * vd + h * vd * cfg["d_model"]) * b
    return float((rows + w) * cfg["n_layers"])
