"""jit'd public wrappers around the Pallas kernels (padding + dtype contracts).

Each op pads ragged/odd shapes to the kernel's tiling contract, runs the
kernel and strips padding. `interpret` defaults to None: compiled by
Mosaic when the default backend is a TPU, the Pallas interpreter on any
other backend (the CPU tests). Only an explicit `interpret=True` runs the
interpreter on a TPU. The pure-jnp oracles live in ref.py; tests assert
allclose across a shape × dtype × distribution sweep.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .gvr_topk import LANES, gvr_topk_pallas
from .indexer_topk import (indexer_topk_pallas, paged_indexer_topk_mq_pallas,
                           paged_indexer_topk_pallas)
from .paged_gather import paged_gather_pallas
from .sparse_attn import (paged_dense_decode_attn_pallas,
                          paged_sparse_decode_attn_mq_pallas,
                          paged_sparse_decode_attn_pallas,
                          paged_sparse_decode_attn_pg_pallas,
                          sparse_decode_attn_pallas)

NEG = -3.4028235e38


def _interpret(interpret: Optional[bool]) -> bool:
    """Compiled on a TPU backend unless the caller names interpret=True;
    interpreted elsewhere, where Mosaic cannot compile."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pad_rows(x: jnp.ndarray, mult: int, value) -> jnp.ndarray:
    n = x.shape[-1]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)], constant_values=value)


@partial(jax.jit, static_argnames=("k", "max_candidates",
                                   "max_secant_iters", "interpret"))
def gvr_topk(scores: jnp.ndarray, prev_idx: jnp.ndarray, k: int,
             *, lengths: Optional[jnp.ndarray] = None,
             max_candidates: Optional[int] = None,
             max_secant_iters: int = 12,
             interpret: Optional[bool] = None):
    """Exact Top-K with GVR (Pallas). scores (B,N) f32; prev_idx (B,M) i32.

    Returns (values (B,K) f32, indices (B,K) i32, stats (B,8) f32).
    stats columns: [secant_iters, bisect_iters, cand_count, fallback,
                    threshold, n_gt, n_ge, emitted].
    """
    squeeze = scores.ndim == 1
    x = scores[None] if squeeze else scores
    p = prev_idx[None] if squeeze else prev_idx
    x = x.astype(jnp.float32)
    if lengths is not None:
        ln = lengths[None] if squeeze else lengths
        pos = jnp.arange(x.shape[-1], dtype=jnp.int32)
        x = jnp.where(pos[None, :] < ln[:, None], x, NEG)
    x = _pad_rows(x, LANES, NEG)
    v, i, s = gvr_topk_pallas(x, p.astype(jnp.int32), k,
                              max_candidates=max_candidates,
                              max_secant_iters=max_secant_iters,
                              interpret=_interpret(interpret))
    if squeeze:
        return v[0], i[0], s[0]
    return v, i, s


@partial(jax.jit, static_argnames=("k", "kv_chunk", "interpret"))
def indexer_topk(q: jnp.ndarray, kcache: jnp.ndarray, w: jnp.ndarray,
                 prev_idx: jnp.ndarray, k: int,
                 *, lengths: Optional[jnp.ndarray] = None,
                 kv_chunk: int = 2048,
                 interpret: Optional[bool] = None):
    """Fused DSA indexer scoring + GVR Top-K (scores never touch HBM)."""
    b, _, _ = q.shape
    n = kcache.shape[1]
    # pad the cache length to the kv_chunk lattice (kv_chunk itself to the
    # 128 lanes); padded positions are masked by `lengths` in the kernel
    kv_chunk = -(-min(kv_chunk, n) // LANES) * LANES
    pad = (-n) % kv_chunk
    if pad:
        kcache = jnp.pad(kcache, ((0, 0), (0, pad), (0, 0)))
    if lengths is None:
        lengths = jnp.full((b,), n, jnp.int32)
    return indexer_topk_pallas(q, kcache, w, prev_idx, k, lengths=lengths,
                               kv_chunk=kv_chunk,
                               interpret=_interpret(interpret))


@partial(jax.jit, static_argnames=("interpret",))
def paged_gather(pages: jnp.ndarray, table: jnp.ndarray,
                 *, interpret: Optional[bool] = None):
    """Contiguous logical KV view from a paged pool (Pallas DMA gather).

    pages: (P, page_size, ...) — any trailing feature dims (KV heads × head
    dim, indexer dim, ...); table: (B, MP) int32 block table, -1 = unmapped
    (zero rows). Returns (B, MP * page_size, ...) — the logical view
    `serve_step_paged` consumes (there via the equivalent XLA gather).
    """
    p, page_size = pages.shape[:2]
    feat = pages.shape[2:]
    d = 1
    for f in feat:
        d *= f
    b, mp = table.shape
    out = paged_gather_pallas(pages.reshape(p, page_size, d),
                              table.astype(jnp.int32),
                              interpret=_interpret(interpret))
    return out.reshape((b, mp * page_size) + feat)


@partial(jax.jit, static_argnames=("scale", "gather_block", "gather_mode",
                                   "interpret"))
def sparse_decode_attn(q: jnp.ndarray, kcache: jnp.ndarray, vcache: jnp.ndarray,
                       idx: jnp.ndarray, *, scale: Optional[float] = None,
                       gather_block: int = 8, gather_mode: str = "pregather",
                       interpret: Optional[bool] = None):
    """Decode attention over the Top-K selected tokens only (B,H,DV)."""
    return sparse_decode_attn_pallas(q, kcache, vcache, idx, scale=scale,
                                     gather_block=gather_block,
                                     gather_mode=gather_mode,
                                     interpret=_interpret(interpret))


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_sparse_decode_attn(q: jnp.ndarray, k_pages: jnp.ndarray,
                             v_pages: jnp.ndarray, table: jnp.ndarray,
                             idx: jnp.ndarray, *,
                             scale: Optional[float] = None,
                             interpret: Optional[bool] = None):
    """Block-table-native sparse decode attention (B,H,DV).

    The Top-K gather and the logical→physical page translation are fused
    into one scalar-prefetched index_map: rows DMA straight from the
    (P, page_size, KVH, D) page pools, the logical view is never built, and
    entries that are -1-padded OR land on an unmapped (-1) table entry are
    masked out of the softmax (DESIGN.md §paged).
    """
    return paged_sparse_decode_attn_pallas(q, k_pages, v_pages, table, idx,
                                           scale=scale,
                                           interpret=_interpret(interpret))


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_sparse_decode_attn_pg(q: jnp.ndarray, k_pages: jnp.ndarray,
                                v_pages: jnp.ndarray, table: jnp.ndarray,
                                idx: jnp.ndarray, *,
                                scale: Optional[float] = None,
                                interpret: Optional[bool] = None):
    """Page-granular block-table-native sparse decode attention (B,H,DV):
    selected indices sharing a logical page move as ONE whole-page DMA
    descriptor (≤ min(K, MP) descriptors per query vs exactly K row-sized
    ones) and the unselected rows are sliced off in VMEM. Same masking
    semantics as `paged_sparse_decode_attn`; contributions match as a set
    but accumulate in page order, so it pins allclose (the bitwise
    page-vs-token guarantee lives on the XLA serving path)."""
    return paged_sparse_decode_attn_pg_pallas(q, k_pages, v_pages, table,
                                              idx, scale=scale,
                                              interpret=_interpret(interpret))


@partial(jax.jit, static_argnames=("scale", "window", "interpret"))
def paged_dense_decode_attn(q: jnp.ndarray, k_pages: jnp.ndarray,
                            v_pages: jnp.ndarray, table: jnp.ndarray,
                            lengths: jnp.ndarray, *,
                            scale: Optional[float] = None,
                            window: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """Fused paged DENSE decode attention (B,H,DV) — the pre-DSA-gate
    fallback's hot-spot form: the full causal extent is attended straight
    off the page pools (grid (B, MP), one whole-page DMA per step), never
    materializing the logical view. Causal + optional sliding-window
    masking happens on global positions inside the kernel."""
    return paged_dense_decode_attn_pallas(q, k_pages, v_pages, table,
                                          lengths, scale=scale,
                                          window=window,
                                          interpret=_interpret(interpret))


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_sparse_decode_attn_mq(q: jnp.ndarray, k_pages: jnp.ndarray,
                                v_pages: jnp.ndarray, table: jnp.ndarray,
                                idx: jnp.ndarray, *,
                                scale: Optional[float] = None,
                                interpret: Optional[bool] = None):
    """Multi-query-row block-table-native sparse decode attention
    (B,Q,H,DV) — the speculative verify tick's attention hot spot: the
    d+1 draft positions of each slot gather their own Top-K rows against
    the shared block table in ONE launch (grid gains a query-row axis;
    addressing and masking are the single-row kernel's verbatim)."""
    return paged_sparse_decode_attn_mq_pallas(q, k_pages, v_pages, table,
                                              idx, scale=scale,
                                              interpret=_interpret(interpret))


@partial(jax.jit, static_argnames=("k", "interpret"))
def paged_indexer_topk_mq(q: jnp.ndarray, k_pages: jnp.ndarray,
                          w: jnp.ndarray, table: jnp.ndarray,
                          prev_idx: jnp.ndarray, k: int, *,
                          lengths: jnp.ndarray,
                          interpret: Optional[bool] = None):
    """Fused paged indexer + GVR Top-K over Q query rows per slot, with
    the verify tick's causally-extended feedback threaded INSIDE the
    launch: row 0 warms from `prev_idx` (the previous tick's Top-K,
    exactly K entries), every later row from the row before it — the
    temporal signal never round-trips HBM between draft positions.
    `lengths` is (B, Q): row q's causal extent.

    Returns (values (B,Q,K), indices (B,Q,K) logical, stats (B,Q,8)).
    """
    return paged_indexer_topk_mq_pallas(q, k_pages, w, table, prev_idx, k,
                                        lengths=lengths,
                                        interpret=_interpret(interpret))


@partial(jax.jit, static_argnames=("k", "interpret"))
def paged_indexer_topk(q: jnp.ndarray, k_pages: jnp.ndarray, w: jnp.ndarray,
                       table: jnp.ndarray, prev_idx: jnp.ndarray, k: int,
                       *, lengths: Optional[jnp.ndarray] = None,
                       interpret: Optional[bool] = None):
    """Fused paged indexer scoring + GVR Top-K over a block table.

    The kv chunk is the logical page: the kernel scores physical pages
    addressed by the scalar-prefetched table, so neither the logical
    indexer-K view nor the score row ever touches HBM. Indices in and out
    are LOGICAL token positions.
    """
    return paged_indexer_topk_pallas(q, k_pages, w, table, prev_idx, k,
                                     lengths=lengths,
                                     interpret=_interpret(interpret))
