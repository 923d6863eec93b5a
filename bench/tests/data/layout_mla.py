"""A test-only layout: latent attention with a DSA indexer that reads the
query latent and weighs its heads by the token, leading dense layers,
then expert layers with a shared expert, a router bias and a share of
the routed experts held (bench/tests/test_weights.py and
test_discovery.py copy it into a fresh root as `bench/layouts/mla.py`).

Each kind of layer is a stack of its own (`dense_*`, `moe_*`), drawn one
layer at a time; the experts held are drawn from their global indices.
"""

from __future__ import annotations

from typing import Any, Dict, List

from bench import weights

LAYER = (0,)


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
        "idx_weights_proj": ((n, d, hi), dt, d ** -0.5, LAYER),
    }


def shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
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
    def stack(prefix):
        return {k[len(prefix):]: x for k, x in flat.items()
                if k.startswith(prefix)}
    return {"embed": flat["embed"], "dense_layers": stack("dense_"),
            "moe_layers": stack("moe_"), "final_norm": flat["final_norm"],
            "lm_head": flat["lm_head"]}


def flops_per_layer(cfg: Dict[str, Any], context: int,
                    sparse: bool) -> List[float]:
    d, h = cfg["d_model"], cfg["n_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    attn = (2 * d * ql + 2 * ql * h * (nope + rope) + 2 * d * (kvl + rope)
            + 2 * kvl * h * (nope + vd) + 2 * h * vd * d)
    rows = context
    if sparse:
        dsa = cfg["dsa"]
        hi, di = dsa["indexer_heads"], dsa["indexer_dim"]
        attn += 2 * ql * hi * di + 2 * d * di + 2 * d * hi
        attn += 2 * context * hi * di + 2 * hi * context
        rows = min(dsa["k"], context)
    attn += 2 * h * (nope + rope) * rows + 2 * h * vd * rows
    moe = cfg["moe"]
    routed, held, _ = weights.expert_share(moe)
    dense = attn + 6 * d * cfg["d_ff"]
    # the routed experts this chip computes: top_k of every routed token,
    # held / routed of them on average
    expert = (attn + 2 * d * routed + 6 * d * moe["shared_d_ff"]
              + moe["top_k"] * held / routed * 6 * d * moe["expert_d_ff"])
    nd = cfg["first_k_dense"]
    return [float(dense)] * nd + [float(expert)] * (cfg["n_layers"] - nd)
