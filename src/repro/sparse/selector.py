"""Pluggable Top-K selector with the paper's dispatch semantics (§5.5).

The paper's two-level dispatch (Fig. 8): the GVR heuristic path takes
priority when a prediction (preIdx) is available and the `canUseHeuristic`
gate passes (K match, N < 200K, layout); otherwise radix-select handles the
request. Here the gate is resolved at trace time (shapes and availability
are static under jit) and the fallback chain is:

    gvr  (prediction available, n <= gate_max_n)
    radix (no prediction, or n beyond the gate)
    exact (lax.top_k) for tiny n — the 'insert-sort for short rows' region

`sp_gvr` selects the sequence-parallel distributed path (KV sharded rows);
it is chosen explicitly by long-context configs, not by the auto gate.

Continuous batching adds a *per-row* dimension to the gate: a serving batch
mixes warm slots (genuine previous-step feedback) with cold ones (freshly
admitted, prediction history reset). `prev_valid` (B,) carries that
row-level `canUseHeuristic` signal; under `method="auto"` the selector then
runs the GVR and radix paths and serves each row from its own path
("mixed"). Both paths are exact with identical lowest-index tie policy, so
outputs are row-for-row identical either way — the per-row dispatch is
about cost fidelity (a cold row must not be billed/telemetered as a GVR
hit) and about the feedback loop: `gvr_rows` reports which rows the GVR
path actually served, which the serving engine logs per tick. A production
kernel would partition the grid by row instead of computing both paths;
at this layer SPMD static shapes make compute-both-and-select the honest
equivalent (same semantics as a vmapped lax.cond).

Layout invariant (paged serving): every index this module consumes
(`prev_idx`) or produces lives in *logical* token space — position within
the request's own context, never a physical KV-page id. The paged decode
path (`models.transformer.serve_step_paged`) always scores over the
logical indexer view (under the default block-table-native mode only the
*attention gather* is physical — DESIGN.md §paged), so the selector is
completely layout-blind and the prev-Top-K feedback survives page-table
remaps (copy-on-write, preemption, shared-prefix admission) bit-exactly.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.gvr import extract_topk, gvr_threshold
from repro.core.topk_baselines import radix_select_topk


class SelectorOutput(NamedTuple):
    indices: jnp.ndarray         # (B, K) int32
    values: jnp.ndarray          # (B, K) f32
    method: str                  # resolved method (trace-time)
    secant_iters: Optional[jnp.ndarray] = None
    gvr_rows: Optional[jnp.ndarray] = None   # (B,) bool — rows the GVR path served
    fallback: Optional[jnp.ndarray] = None   # (B,) bool — GVR's safety net ran


def _masked_scores(scores, lengths):
    if lengths is None:
        return scores
    n = scores.shape[-1]
    pos = jnp.arange(n, dtype=jnp.int32)
    return jnp.where(pos[None, :] < lengths[:, None], scores,
                     jnp.float32(-3.4028235e38))


def select_topk(scores: jnp.ndarray, k: int, *,
                prev_idx: Optional[jnp.ndarray] = None,
                prev_valid: Optional[jnp.ndarray] = None,
                method: str = "auto",
                lengths: Optional[jnp.ndarray] = None,
                max_candidates: Optional[int] = None,
                gate_max_n: int = 200_000,
                min_n_for_selection: int = 4096,
                mesh=None, batch_axes=("pod", "data")) -> SelectorOutput:
    """Exact Top-K with the paper's dispatch policy. scores: (B, N).

    With `mesh`, the whole selection runs inside a shard_map over the batch
    axes: selection is embarrassingly row-parallel, and fencing it off stops
    the SPMD partitioner from replicating score rows to satisfy sort/scatter
    ops (EXPERIMENTS §Perf iteration 2: 282 MB -> ~0 per decode step).
    """
    if mesh is not None:
        import jax
        from jax.sharding import PartitionSpec as P
        axes = tuple(a for a in batch_axes if a in mesh.axis_names)
        ext = 1
        for a in axes:
            ext *= mesh.shape[a]
        if axes and scores.shape[0] % ext == 0 and scores.shape[0] >= ext:
            bspec = P(axes, None)
            has_prev = prev_idx is not None
            has_len = lengths is not None
            has_valid = prev_valid is not None

            def body(s_, l_, p_, v_):
                r = select_topk(s_, k, prev_idx=(p_ if has_prev else None),
                                prev_valid=(v_ if has_valid else None),
                                method=method, lengths=(l_ if has_len else None),
                                max_candidates=max_candidates,
                                gate_max_n=gate_max_n,
                                min_n_for_selection=min_n_for_selection)
                it = r.secant_iters
                if it is None:
                    it = jnp.zeros((s_.shape[0],), jnp.int32)
                g = r.gvr_rows
                if g is None:
                    g = jnp.zeros((s_.shape[0],), bool)
                fb = r.fallback
                if fb is None:
                    fb = jnp.zeros((s_.shape[0],), bool)
                return r.indices, r.values, it, g, fb

            idx, vals, iters, gvr_rows, fallback = jax.shard_map(
                body, mesh=mesh,
                in_specs=(bspec, P(axes), bspec, P(axes)),
                out_specs=(bspec, bspec, P(axes), P(axes), P(axes)),
                check_vma=False,
            )(scores,
              lengths if lengths is not None else
              jnp.full((scores.shape[0],), scores.shape[-1], jnp.int32),
              prev_idx if prev_idx is not None else
              jnp.zeros((scores.shape[0], 1), jnp.int32) - 1,
              prev_valid if prev_valid is not None else
              jnp.ones((scores.shape[0],), bool))
            resolved = ("gvr" if (prev_idx is not None
                                  and scores.shape[-1] > min_n_for_selection
                                  and scores.shape[-1] <= gate_max_n)
                        else "sharded")
            if has_valid and resolved == "gvr":
                resolved = "mixed"
            return SelectorOutput(idx, vals, resolved, iters, gvr_rows,
                                  fallback)

    n = scores.shape[-1]
    b = scores.shape[0]
    if method == "auto":
        if n <= min_n_for_selection:
            method = "exact"
        elif prev_idx is not None and n <= gate_max_n:
            # canUseHeuristic == true at trace time; a per-row validity
            # signal refines the dispatch to row granularity ("mixed")
            method = "gvr" if prev_valid is None else "mixed"
        else:
            method = "radix"               # fallback chain

    if method == "gvr":
        assert prev_idx is not None, "gvr needs a prediction signal"
        stats = gvr_threshold(scores, prev_idx, k, lengths=lengths,
                              max_candidates=max_candidates)
        vals, idx = extract_topk(scores, stats.threshold, k, lengths=lengths)
        return SelectorOutput(idx, vals, "gvr", stats.secant_iters,
                              jnp.ones((b,), bool), stats.fallback)
    if method == "mixed":
        assert prev_idx is not None, "mixed dispatch needs a prediction signal"
        assert prev_valid is not None, "mixed dispatch needs prev_valid"
        warm = prev_valid.astype(bool)
        stats = gvr_threshold(scores, prev_idx, k, lengths=lengths,
                              max_candidates=max_candidates)
        g_vals, g_idx = extract_topk(scores, stats.threshold, k,
                                     lengths=lengths)
        r_vals, r_idx, st = radix_select_topk(_masked_scores(scores, lengths), k)
        idx = jnp.where(warm[:, None], g_idx, r_idx)
        vals = jnp.where(warm[:, None], g_vals, r_vals)
        iters = jnp.where(warm, stats.secant_iters, st.passes)
        return SelectorOutput(idx, vals, "mixed", iters, warm,
                              stats.fallback & warm)
    if method == "radix":
        vals, idx, st = radix_select_topk(_masked_scores(scores, lengths), k)
        return SelectorOutput(idx, vals, "radix", st.passes,
                              jnp.zeros((b,), bool), jnp.zeros((b,), bool))
    if method == "exact":
        import jax
        vals, idx = jax.lax.top_k(_masked_scores(scores, lengths), k)
        # Canonical ascending-index order, like the extraction-based paths:
        # downstream attention then sums gathered rows in the same order no
        # matter which path served a row, so switching paths (warm/cold,
        # auto-gate) can never perturb logits even in the last float bit.
        order = jnp.argsort(idx, axis=-1)
        idx = jnp.take_along_axis(idx, order, axis=-1)
        vals = jnp.take_along_axis(vals, order, axis=-1)
        return SelectorOutput(idx.astype(jnp.int32), vals, "exact", None,
                              jnp.zeros((b,), bool), jnp.zeros((b,), bool))
    raise ValueError(f"unknown selector method {method!r}")
