"""Small-width copies of the benchmark's configurations, for the CPU."""

from __future__ import annotations

from bench.registry import Registry

WIDTHS = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
              vocab=300)


def small_config(name: str, dtype: str = "float32") -> dict:
    """The named configuration at small widths, every change declared."""
    spec = dict(Registry().config(name))
    spec.update(WIDTHS, dtype=dtype)
    if spec["moe"]["num_experts"]:
        spec["moe"] = dict(num_experts=8, top_k=3, expert_d_ff=32)
    else:
        spec["d_ff"] = 96
    spec["dsa"] = dict(spec["dsa"], k=16, indexer_heads=4, indexer_dim=16,
                       min_n=64)
    spec["changed_from_registry"] = sorted(
        set(spec["changed_from_registry"]) | set(WIDTHS) |
        {"dtype", "d_ff", "moe", "dsa"})
    return spec


def small_engine(slots: int = 4, max_len: int = 128) -> dict:
    return dict(slots=slots, max_len=max_len, page_size=16, prefill_chunk=8)
