"""DeepSeek Sparse Attention (DSA) decode block: indexer → Top-K → sparse MLA.

Faithful to the paper's pipeline (§2): a lightweight MQA indexer scores all
N cached tokens (Eq. 1), an exact Top-K keeps K=2048, and attention runs
over the selected rows only. The previous step's Top-K is carried as
functional state (the paper's prev_topk HBM buffer) and seeds the GVR
selector.

The XLA path here is what the distributed dry-run lowers; the Pallas
kernels (repro.kernels) are the per-device hot-spot implementations of the
same three stages, validated against the refs in kernels/ref.py.

Indices live in *logical* token space end to end — `prev_topk` (the
temporal feedback buffer) and `topk_idx` are positions within the
request's own context regardless of the physical KV layout. Do not thread
physical page ids into this pipeline: GVR's temporal-correlation warm
start is only meaningful in logical space.

Two physical forms of the sparse-attention stage share the scoring/select
front half (`dsa_select`):

* `dsa_decode` — caches arrive as contiguous logical views (the dense
  serving layout, or the paged layout's `paged_attn="gather"` oracle path
  which materializes the view first);
* `dsa_decode_paged` — block-table-native (DESIGN.md §paged): attention
  gathers exactly the Top-K rows straight from the global page pools via
  the logical→physical translation `table[b, idx // page_size]`, offset
  `idx % page_size`. The logical K/V views are never built, so per-step
  gathered KV traffic is O(K) instead of O(N). Selection itself still
  consumes logical-view indexer scores, so both forms are bit-identical.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rotary
from .selector import select_topk

NEG = -3.4028235e38


def indexer_init(key, d_model: int, heads: int, dim: int, dtype):
    k1, k2 = jax.random.split(key)
    s = d_model ** -0.5
    return {
        "wq": (jax.random.normal(k1, (d_model, heads * dim)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (d_model, dim)) * s).astype(dtype),
        "w": jnp.ones((heads,), jnp.float32) / heads,
    }


def indexer_scores(params, x: jnp.ndarray, idx_kcache: jnp.ndarray,
                   positions: jnp.ndarray, lengths: jnp.ndarray,
                   *, heads: int, dim: int, rope_base: float,
                   rules=None) -> jnp.ndarray:
    """Eq. 1: I = sum_j w_j ReLU(q_j · K_I^T). x: (B, D) one decode token.

    idx_kcache: (B, N, dim) — the indexer's own K cache (RoPE'd at write).
    Returns (B, N) f32 scores with sentinel beyond `lengths`.
    """
    from repro.parallel.sharding import constrain
    b, d = x.shape
    n = idx_kcache.shape[1]
    idx_kcache = constrain(idx_kcache, rules, "batch", None, None)
    q = (x @ params["wq"]).reshape(b, 1, heads, dim)
    q = apply_rotary(q, positions[:, None], kind="rope", base=rope_base)[:, 0]
    s = jnp.einsum("bhd,bnd->bhn", q.astype(idx_kcache.dtype), idx_kcache,
                   preferred_element_type=jnp.float32)
    s = jax.nn.relu(s)
    scores = jnp.einsum("h,bhn->bn", params["w"].astype(jnp.float32), s)
    pos = jnp.arange(n, dtype=jnp.int32)
    return jnp.where(pos[None, :] < lengths[:, None], scores, NEG)


def indexer_k(params, x: jnp.ndarray, positions: jnp.ndarray,
              *, dim: int, rope_base: float) -> jnp.ndarray:
    """Indexer key for the new token (B, dim), RoPE'd at its position."""
    kk = (x @ params["wk"]).reshape(x.shape[0], 1, 1, dim)
    return apply_rotary(kk, positions[:, None], kind="rope",
                        base=rope_base)[:, 0, 0]


class DSAOutput(NamedTuple):
    attn_out: jnp.ndarray      # (B, H, HD) f32
    topk_idx: jnp.ndarray      # (B, K) int32 — next step's prediction
    secant_iters: Optional[jnp.ndarray]
    gvr_rows: Optional[jnp.ndarray] = None   # (B,) bool — selector path taken
    fallback: Optional[jnp.ndarray] = None   # (B,) bool — GVR safety net ran


def dsa_sparse_attention(q: jnp.ndarray, kcache: jnp.ndarray, vcache: jnp.ndarray,
                         topk_idx: jnp.ndarray, lengths: jnp.ndarray,
                         *, scale: float, rules=None) -> jnp.ndarray:
    """Attention over the Top-K gathered rows only (XLA gather path).

    q: (B,H,HD); caches: (B,N,KVH,HD); topk_idx: (B,K) (may exceed length —
    masked). O(K) work independent of N (paper Table 2 'Sparse MLA').
    """
    b, h, hd = q.shape
    kvh = kcache.shape[2]
    g = h // kvh
    k = topk_idx.shape[-1]
    from repro.parallel.sharding import constrain
    # Decode-attention core is batch-parallel by construction: q is pinned
    # batch-only so the partitioner cannot back-propagate a (kvh, g) head
    # sharding through take_along_axis into the cache (which would force an
    # 8+ GB cache all-gather per step). TP lives in the projections.
    q = constrain(q, rules, "batch", None, None)
    # Pin the cache to its canonical layout (batch-sharded, kv replicated) at
    # the gather site: XLA's gather partitioner otherwise re-shards/replicates
    # the operand to satisfy head-sharding propagated from downstream matmuls.
    kcache = constrain(kcache, rules, "batch", None, None, None)
    vcache = constrain(vcache, rules, "batch", None, None, None)
    idx_safe = jnp.clip(topk_idx, 0, kcache.shape[1] - 1)
    # whole (KVH, HD) rows per index: a per-element index array would lower
    # to an element-wise gather, which on a TPU costs far more than the
    # K-row DMA this is
    rows = jax.vmap(lambda c, i: c[i])
    kg, vg = rows(kcache, idx_safe), rows(vcache, idx_safe)
    # keep the gather batch-parallel: resharding (for TP heads) must happen on
    # the small (B,K) gathered rows, never on the (B,N) cache — otherwise the
    # partitioner all-gathers the entire cache per step.
    kg = constrain(kg, rules, "batch", None, None, None)
    vg = constrain(vg, rules, "batch", None, None, None)
    logits = jnp.einsum("bkgd,bskd->bkgs", q.reshape(b, kvh, g, hd), kg,
                        preferred_element_type=jnp.float32) * scale
    valid = (topk_idx >= 0) & (topk_idx < lengths[:, None])
    logits = jnp.where(valid[:, None, None, :], logits, NEG)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, hd)


def distinct_pages(topk_idx: jnp.ndarray, *, page_size: int,
                   num_logical_pages: int) -> jnp.ndarray:
    """Per-row ascending distinct LOGICAL pages touched by the selected
    indices, padded with the sentinel `num_logical_pages` — the descriptor
    list a page-granular DMA engine would walk. `topk_idx` must already be
    clipped to [0, MP·page_size). Shape (B, S), S = min(K, MP): a row of K
    entries can never touch more than min(K, MP) distinct pages, so the
    slot scatter below cannot overflow.
    """
    b, k = topk_idx.shape
    mp = num_logical_pages
    s = min(k, mp)
    pg = jnp.sort(topk_idx // page_size, axis=1).astype(jnp.int32)
    first = jnp.concatenate(
        [jnp.ones((b, 1), bool), pg[:, 1:] > pg[:, :-1]], axis=1)
    slot = jnp.cumsum(first.astype(jnp.int32), axis=1) - 1         # (B, K)
    bi = jnp.broadcast_to(jnp.arange(b)[:, None], (b, k))
    # duplicates of a page write the same value into the same slot
    return jnp.full((b, s), mp, jnp.int32).at[bi, slot].set(pg)


def page_gather_stats(topk_idx: jnp.ndarray, *, page_size: int,
                      num_logical_pages: int) -> jnp.ndarray:
    """(B,) int32 distinct-page counts for a Top-K selection — the
    page-granular DMA descriptor count. Page-granular gather traffic is
    `count × page_size` rows vs the token-granular K rows; the roofline
    bench and the gather property test consume this."""
    n = num_logical_pages * page_size
    li = jnp.clip(topk_idx, 0, n - 1)
    up = distinct_pages(li, page_size=page_size,
                        num_logical_pages=num_logical_pages)
    return jnp.sum(up < num_logical_pages, axis=1).astype(jnp.int32)


def _gather_topk_rows_paged(pages: jnp.ndarray, table: jnp.ndarray,
                            li: jnp.ndarray, phys: jnp.ndarray,
                            *, granularity: str) -> jnp.ndarray:
    """Gather the K selected (feature...) rows from a page pool.

    "token" moves exactly K rows (one DMA descriptor per Top-K entry);
    "page" moves each *distinct* page once as a whole (`page_size` rows per
    descriptor — fewer, larger DMAs when selections cluster) and slices the
    rows out of the page buffer. Element-identical by construction: every
    entry reads physical row (clip(table[page], 0) · page_size + offset) in
    both forms, including invalid entries (unmapped pages clip to page 0
    either way), so downstream masking sees the same values bit for bit.
    """
    p, page_size = pages.shape[:2]
    if granularity == "token":
        flat = jnp.clip(phys, 0, p - 1) * page_size + li % page_size
        return pages.reshape((p * page_size,) + pages.shape[2:])[flat]
    mp = table.shape[1]
    up = distinct_pages(li, page_size=page_size, num_logical_pages=mp)
    # sentinel slot mp reads a padded -1 column → clips to page 0, but no
    # entry's searchsorted slot ever lands on it (every entry's page is in up)
    tpad = jnp.concatenate(
        [table, jnp.full((table.shape[0], 1), -1, table.dtype)], axis=1)
    uphys = jnp.take_along_axis(tpad, up, axis=1)                  # (B, S)
    page_buf = pages[jnp.clip(uphys, 0, p - 1)]        # (B, S, page_size, ...)
    slot = jax.vmap(jnp.searchsorted)(up, li // page_size)         # (B, K)
    bi = jnp.arange(li.shape[0])[:, None]
    return page_buf[bi, slot, li % page_size]


def dsa_sparse_attention_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                               v_pages: jnp.ndarray, table: jnp.ndarray,
                               topk_idx: jnp.ndarray, lengths: jnp.ndarray,
                               *, scale: float, granularity: str = "token",
                               rules=None) -> jnp.ndarray:
    """Block-table-native sparse attention (XLA gather form of the fused
    Pallas kernel `kernels.paged_sparse_decode_attn`).

    q: (B,H,HD); k/v_pages: (P, page_size, KVH, HD) global page pools;
    table: (B, MP) int32 block table (-1 = unmapped); topk_idx: (B,K)
    LOGICAL indices. The logical→physical translation is composed with the
    Top-K gather, so exactly K (KVH × HD) rows move per query — O(K)
    traffic independent of the logical extent MP·page_size — and the
    contiguous logical K/V views are never materialized.

    `granularity` picks the gather's DMA shape: "token" moves one row per
    Top-K entry; "page" moves each distinct touched page whole and slices
    rows in fast memory (`_gather_topk_rows_paged`) — coarser descriptors,
    bit-identical output.

    Masking: an entry contributes iff idx ∈ [0, length) AND its page is
    mapped. For in-length indices the page is always mapped (the serving
    layer maps pages up to `length` before the step), so for identical
    page contents this is bit-identical to `dsa_sparse_attention` over the
    materialized logical view — same gathered values at unmasked positions,
    same NEG sentinel at masked ones, same reduction shapes/order.
    """
    if granularity not in ("token", "page"):
        raise ValueError(f"granularity must be 'token' or 'page', "
                         f"got {granularity!r}")
    b, h, hd = q.shape
    p, page_size, kvh = k_pages.shape[:3]
    g = h // kvh
    n = table.shape[1] * page_size
    from repro.parallel.sharding import constrain
    # same partitioning discipline as the logical-view path: q pinned
    # batch-only so head sharding can't propagate into the (pool-global,
    # replicated) page arrays through the gather
    q = constrain(q, rules, "batch", None, None)
    li = jnp.clip(topk_idx, 0, n - 1)
    phys = jnp.take_along_axis(table, li // page_size, axis=1)     # (B, K)
    valid = ((topk_idx >= 0) & (topk_idx < lengths[:, None])
             & (phys >= 0))
    kg = _gather_topk_rows_paged(k_pages, table, li, phys,
                                 granularity=granularity)
    vg = _gather_topk_rows_paged(v_pages, table, li, phys,
                                 granularity=granularity)
    # resharding (for TP heads) happens on the small (B,K) gathered rows,
    # never on the page pool — mirrors dsa_sparse_attention
    kg = constrain(kg, rules, "batch", None, None, None)
    vg = constrain(vg, rules, "batch", None, None, None)
    logits = jnp.einsum("bkgd,bskd->bkgs", q.reshape(b, kvh, g, hd), kg,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, None, None, :], logits, NEG)
    pr = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", pr.astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, hd)


def dsa_sparse_attention_paged_mq(q: jnp.ndarray, k_pages: jnp.ndarray,
                                  v_pages: jnp.ndarray, table: jnp.ndarray,
                                  topk_idx: jnp.ndarray,
                                  lengths: jnp.ndarray,
                                  *, scale: float, granularity: str = "token",
                                  rules=None) -> jnp.ndarray:
    """Multi-query-row form of `dsa_sparse_attention_paged` — the XLA shape
    of the speculative verify tick's attention stage (the Pallas hot-spot
    form is `kernels.paged_sparse_decode_attn_mq`).

    q: (B, Q, H, HD) — the d+1 draft positions' queries; topk_idx:
    (B, Q, K) per-position LOGICAL selections; lengths: (B, Q) per-position
    causal extents (position j attends to L0 + j + 1 tokens). The Q axis
    folds into the batch of the single-row form — the pools are global and
    the block table rows repeat — so each position's bits are exactly the
    single-row path's, which is what lets the verify scan stand in for
    d+1 sequential steps without perturbing a single logit.
    """
    b, qn = q.shape[:2]
    out = dsa_sparse_attention_paged(
        q.reshape((b * qn,) + q.shape[2:]), k_pages, v_pages,
        jnp.repeat(table, qn, axis=0), topk_idx.reshape(b * qn, -1),
        lengths.reshape(b * qn), scale=scale, granularity=granularity,
        rules=rules)
    return out.reshape((b, qn) + out.shape[1:])


def dsa_select(indexer_params, x: jnp.ndarray, idx_kcache: jnp.ndarray,
               prev_topk: jnp.ndarray, lengths: jnp.ndarray,
               *, k: int, heads: int, dim: int, rope_base: float,
               selector: str = "auto",
               prev_valid: Optional[jnp.ndarray] = None,
               max_candidates: Optional[int] = None,
               gate_max_n: int = 200_000, min_n: int = 4096,
               swa_window: Optional[int] = None, rules=None, mesh=None):
    """Indexer scoring + Top-K selection (the layout-independent front half
    of the DSA pipeline — shared by the logical-view and paged attention
    forms, which is what keeps them bit-identical). The two stages carry
    the decode step's `step.indexer` and `step.topk` scopes."""
    positions = lengths - 1
    with jax.named_scope("step.indexer"):
        scores = indexer_scores(indexer_params, x, idx_kcache, positions,
                                lengths, heads=heads, dim=dim,
                                rope_base=rope_base, rules=rules)
        if swa_window is not None:
            # SWA interplay: selection restricted to the attention window
            pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)
            in_win = pos[None, :] > (lengths[:, None] - 1 - swa_window)
            scores = jnp.where(in_win, scores, NEG)
    with jax.named_scope("step.topk"):
        return select_topk(scores, k, prev_idx=prev_topk,
                           prev_valid=prev_valid, method=selector,
                           max_candidates=max_candidates,
                           gate_max_n=gate_max_n, min_n_for_selection=min_n,
                           mesh=mesh)


def dsa_decode(q: jnp.ndarray, kcache: jnp.ndarray, vcache: jnp.ndarray,
               indexer_params, x: jnp.ndarray, idx_kcache: jnp.ndarray,
               prev_topk: jnp.ndarray, lengths: jnp.ndarray,
               *, k: int, scale: float, heads: int, dim: int,
               rope_base: float, selector: str = "auto",
               prev_valid: Optional[jnp.ndarray] = None,
               max_candidates: Optional[int] = None,
               gate_max_n: int = 200_000,
               min_n: int = 4096,
               swa_window: Optional[int] = None, rules=None,
               mesh=None) -> DSAOutput:
    """Full DSA decode step for one layer (indexer → select → sparse attn)
    over contiguous logical K/V views.

    `prev_valid` (B,) marks which rows carry genuine previous-step feedback;
    under `selector="auto"` rows without it dispatch through the non-GVR
    fallback (continuous-batching cold slots — see selector.select_topk).
    """
    sel = dsa_select(indexer_params, x, idx_kcache, prev_topk, lengths,
                     k=k, heads=heads, dim=dim, rope_base=rope_base,
                     selector=selector, prev_valid=prev_valid,
                     max_candidates=max_candidates, gate_max_n=gate_max_n,
                     min_n=min_n, swa_window=swa_window, rules=rules,
                     mesh=mesh)
    with jax.named_scope("step.attention"):
        out = dsa_sparse_attention(q, kcache, vcache, sel.indices, lengths,
                                   scale=scale, rules=rules)
    return DSAOutput(out, sel.indices, sel.secant_iters, sel.gvr_rows,
                     sel.fallback)


def dsa_decode_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                     v_pages: jnp.ndarray, table: jnp.ndarray,
                     indexer_params, x: jnp.ndarray, idx_kcache: jnp.ndarray,
                     prev_topk: jnp.ndarray, lengths: jnp.ndarray,
                     *, k: int, scale: float, heads: int, dim: int,
                     rope_base: float, selector: str = "auto",
                     prev_valid: Optional[jnp.ndarray] = None,
                     max_candidates: Optional[int] = None,
                     gate_max_n: int = 200_000,
                     min_n: int = 4096,
                     swa_window: Optional[int] = None,
                     gather_granularity: str = "token", rules=None,
                     mesh=None) -> DSAOutput:
    """Block-table-native DSA decode step: identical scoring/selection to
    `dsa_decode` (bit-exact — `idx_kcache` is the logical indexer-K view,
    the paper's irreducible O(N·d_i) read), but attention gathers its K
    rows straight from the page pools. The K/V logical views are never
    built; feedback indices stay logical, so GVR's temporal warm start is
    untouched by the physical layout. `gather_granularity` selects token-
    vs page-granular DMA for the attention gather (bit-identical either
    way — see `dsa_sparse_attention_paged`).
    """
    sel = dsa_select(indexer_params, x, idx_kcache, prev_topk, lengths,
                     k=k, heads=heads, dim=dim, rope_base=rope_base,
                     selector=selector, prev_valid=prev_valid,
                     max_candidates=max_candidates, gate_max_n=gate_max_n,
                     min_n=min_n, swa_window=swa_window, rules=rules,
                     mesh=mesh)
    with jax.named_scope("step.attention"):
        out = dsa_sparse_attention_paged(q, k_pages, v_pages, table,
                                         sel.indices, lengths, scale=scale,
                                         granularity=gather_granularity,
                                         rules=rules)
    return DSAOutput(out, sel.indices, sel.secant_iters, sel.gvr_rows,
                     sel.fallback)
