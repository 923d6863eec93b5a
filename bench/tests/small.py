"""Small-width copies of the benchmark's configurations, and a test-only
layout's, for the CPU."""

from __future__ import annotations

import shutil
from pathlib import Path

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


# a test-only layout (bench/tests/data/layout_mla.py) at small widths: one
# dense layer, two expert layers, 4 experts held from index 8 of 16
MLA = dict(layout="mla", dtype="float32", n_layers=3, first_k_dense=1,
           d_model=32, vocab=50, n_heads=2, q_lora_rank=24, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, d_ff=48,
           moe=dict(num_experts=4, total_experts=16, first_expert=8,
                    top_k=2, expert_d_ff=12, shared_d_ff=20),
           dsa=dict(enabled=True, k=16, indexer_heads=2, indexer_dim=8,
                    min_n=64))


def mla_root(root: Path) -> Registry:
    """A registry over a fresh root that holds the test-only layout as
    `bench/layouts/mla.py`, and nothing else."""
    layouts = Path(root) / "bench" / "layouts"
    layouts.mkdir(parents=True, exist_ok=True)
    shutil.copy(Path(__file__).parent / "data" / "layout_mla.py",
                layouts / "mla.py")
    return Registry(root)
