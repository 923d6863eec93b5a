"""The decode step's named scopes, as the program names them
(`models/transformer.py`, `sparse/dsa.py`, `sparse/sp_dsa.py`).

A scope reaches the compiled HLO's `op_name` metadata, where a traced op
finds it through its name stack (bench/trace.py). The names are a
contract between the program and the readers here: a scope renamed in
the program leaves its reader with nothing to read.

    step.qkv        the first norm and the Q/K/V projection
    step.kv_write   the page-pool scatters of K, V and indexer-K
    step.indexer    indexer keys, the logical indexer view, the scores
    step.topk       all of the selection (GVR, extraction, radix, the
                    per-row choice); holds `jit(gvr_threshold)` and
                    `jit(radix_select_topk)`, so selector_share ⊂ topk_share
    step.attention  sparse or dense attention and the output projection
    step.mlp        the second norm and the MLP or MoE block
    step.head       the final norm and the LM head
"""

from __future__ import annotations

from typing import Dict, Optional

STEP_SCOPES = ("step.qkv", "step.kv_write", "step.indexer", "step.topk",
               "step.attention", "step.mlp", "step.head")


def time_in(tr, scope: str) -> float:
    """Device seconds of the ops under `scope` (bench/trace.py's Reduced)."""
    return tr.time_under((f"/{scope}/",))


def share(tr, scope: str) -> Optional[float]:
    """`scope`'s device time over the step's, in %; None where the trace
    holds no op under it (a program without the scope, or no trace)."""
    if tr is None or not tr.step_s:
        return None
    under = time_in(tr, scope)
    return 100.0 * under / tr.step_s if under else None


def split(tr) -> Dict[str, float]:
    """Every scope's share of the step and the unscoped rest, in %: they
    sum to 100."""
    shares = {s: share(tr, s) or 0.0 for s in STEP_SCOPES}
    shares["unscoped"] = 100.0 - sum(shares.values())
    return shares
