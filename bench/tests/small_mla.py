"""The committed DeepSeek-V3.2 configuration at small widths, for the CPU:
one dense layer and two expert layers, 4 of the 16 routed experts held
(from index 8), each change declared in `changed_from_registry`."""

from __future__ import annotations

from bench.registry import Registry

NAME = "deepseek-v3.2.ep32"
WIDTHS = dict(n_layers=3, first_k_dense=1, d_model=32, n_heads=2,
              q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8, d_ff=48, vocab=50)


def small_mla(dtype: str = "float32", min_n: int = 16, **moe) -> dict:
    """The configuration file's dict at small widths; `min_n` sets the DSA
    gate (K is 16), `moe` overrides the expert share."""
    spec = dict(Registry().config(NAME))
    spec.update(WIDTHS, dtype=dtype)
    spec["moe"] = dict(spec["moe"], **{
        **dict(num_experts=4, total_experts=16, first_expert=8, top_k=4,
               expert_d_ff=12, shared_d_ff=20, n_group=4, topk_group=2),
        **moe})
    spec["dsa"] = dict(spec["dsa"], k=16, indexer_heads=2, indexer_dim=8,
                       min_n=min_n)
    spec["changed_from_registry"] = sorted(
        set(spec["changed_from_registry"]) | set(WIDTHS)
        | {"dtype", "moe", "dsa"})
    return spec


def small_engine(slots: int = 4, max_len: int = 128) -> dict:
    return dict(slots=slots, max_len=max_len, page_size=16, prefill_chunk=8)
