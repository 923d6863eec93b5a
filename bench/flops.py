"""Model FLOPs of one served token, from a configuration's shapes.

Counts the multiply-adds the model needs (2 FLOPs each) for one token
that attends over `context` positions (its own included): projections,
the MLP or the routed experts actually chosen (top_k of them, not every
expert), the DSA indexer and the attention over the selected rows when
the cell runs sparse, and the head. Selection itself (comparisons) and
elementwise work are not counted. This is the work any implementation of
the model has to do, so it bounds a step whatever computes it. Each
layer's count is the configuration's layout's (`flops_per_layer` in
bench/layouts/<layout>.py, bench/weights.py); the head's is counted here.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from bench import weights
from bench.registry import Registry


def sparse_cell(cfg: Dict[str, Any], max_len: int) -> bool:
    """Whether a cell runs DSA: the program's gate is on the pool's
    logical extent (max_len), not on a request's own length."""
    dsa = cfg.get("dsa") or {}
    return bool(dsa.get("enabled")) and max_len > dsa["min_n"]


def per_token(cfg: Dict[str, Any], context: int, sparse: bool,
              reg: Optional[Registry] = None) -> float:
    layers = weights.layout(cfg, reg).flops_per_layer(cfg, context, sparse)
    return float(sum(layers)) + 2.0 * cfg["d_model"] * cfg["vocab"]
