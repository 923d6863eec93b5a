"""Pallas TPU kernel: fused DSA indexer scoring + GVR Top-K (beyond paper).

The paper's pipeline materializes the indexer score row to HBM
(indexer MQA kernel → N·4B write) and re-reads it in the Top-K kernel
(+(I+1)·N·4B reads). On TPU, the scorer and the selector fit the same VMEM
working set, so we fuse them:

  grid = (B, N/kv_chunk). Each step DMAs one K-cache chunk
  (kv_chunk × d_i), computes the Eq.-1 scores (ReLU(q·Kᵀ) on the MXU,
  weighted over heads on the VPU), and writes them into a VMEM scores
  tile. On the final chunk the full GVR pipeline (see gvr_topk.py) runs
  over the resident tile — the scores NEVER touch HBM.

HBM traffic: N·d_i·2B (K cache, irreducible) + M·4B (prev idx) + K·8B out.
The 2·N·4B score write+read of the unfused pipeline is eliminated — at
N=128K and d_i=128 that is a 1.0 MB saving against 32 MB irreducible, but
against the *Top-K operator itself* (the paper's unit of account: (I+1)·N·4B)
it removes the entire score-read stream, i.e. the fused selector rides the
indexer's required traffic for free.

`paged_indexer_topk_pallas` is the block-table-native variant (DESIGN.md
§paged): the indexer K cache stays in the serving layer's global page pool
and the block table is scalar-prefetched, so each grid step DMAs one
physical (page_size × d_i) page — the kv chunk IS the logical page, the
index_map does the logical→physical translation, and the contiguous
logical indexer-K view is never materialized. Page j's scores become row j
of the (MP, page_size) scores tile, so GVR and the emitted Top-K indices
stay in logical token space — the feedback invariant the paged serving
layer depends on. Unmapped pages (-1) score the sentinel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gvr_topk import (LANES, gvr_on_resident_tile, gvr_params, out_rows,
                       prev_spec, topk_out_shapes, topk_out_specs,
                       unpack_topk)

NEG = -3.4028235e38  # python float: a jnp scalar would be a captured constant


def _chunk_scores(q, kc, w_col):
    """Eq. 1 over one chunk: sum_h w_h · ReLU(q_h · kc) -> (1, rows).
    q (H, D); kc (rows, D); w_col (H, 1) f32."""
    s = jax.lax.dot_general(q, kc, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return jnp.sum(w_col * jnp.maximum(s, 0.0), axis=0, keepdims=True)


def _weights(w, b, h):
    """(H,) or (B, H) head weights -> (B, H, 1) f32 (a column per slot)."""
    if w.ndim == 1:
        w = jnp.broadcast_to(w[None], (b, h))
    return w.astype(jnp.float32)[:, :, None]


def _fused_kernel(len_ref, q_ref, kv_ref, w_ref, prev_ref,
                  out_vals_ref, out_idx_ref, stats_ref, scores_scr,
                  *, kv_chunk, nkv, gvr):
    b = pl.program_id(0)
    j = pl.program_id(1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    length = len_ref[b]
    for r in range(kv_chunk // LANES):
        sc = _chunk_scores(q_ref[...], kv_ref[pl.ds(r * LANES, LANES), :],
                           w_ref[...])
        pos = lane + j * kv_chunk + r * LANES
        # ragged mask: positions beyond this row's length get the sentinel
        scores_scr[pl.ds(j * (kv_chunk // LANES) + r, 1), :] = jnp.where(
            pos < length, sc, NEG)

    @pl.when(j == nkv - 1)
    def _():
        gvr_on_resident_tile(scores_scr, prev_ref, out_vals_ref, out_idx_ref,
                             stats_ref, **gvr)


def indexer_topk_pallas(q: jnp.ndarray, kcache: jnp.ndarray, w: jnp.ndarray,
                        prev_idx: jnp.ndarray, k: int,
                        *, lengths: Optional[jnp.ndarray] = None,
                        kv_chunk: int = 2048,
                        max_candidates: Optional[int] = None,
                        max_secant_iters: int = 12,
                        f_target: Optional[int] = None,
                        interpret: bool = False):
    """Fused indexer+Top-K. q: (B,H,D); kcache: (B,N,D); w: (H,) or (B,H);
    prev_idx: (B,M) int32; lengths: (B,) int32 (defaults to N). N must be
    a multiple of kv_chunk, and kv_chunk of 128 (ops.py pads).

    Returns (values (B,K), indices (B,K), stats (B,8)).
    """
    b, h, d = q.shape
    n = kcache.shape[1]
    m = prev_idx.shape[-1]
    kv_chunk = min(kv_chunk, n)
    assert n % kv_chunk == 0 and kv_chunk % LANES == 0, (n, kv_chunk)
    nkv = n // kv_chunk
    if lengths is None:
        lengths = jnp.full((b,), n, jnp.int32)
    cmax, ft = gvr_params(k, n, max_candidates, f_target)
    gvr = dict(k=k, cmax=cmax, n=n, m=m, max_secant=max_secant_iters,
               f_target=ft)
    kern = functools.partial(_fused_kernel, kv_chunk=kv_chunk, nkv=nkv,
                             gvr=gvr)
    row = lambda i, j, ln: (i, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nkv),
        in_specs=[
            pl.BlockSpec((None, h, d), row),
            pl.BlockSpec((None, kv_chunk, d), lambda i, j, ln: (i, j, 0)),
            pl.BlockSpec((None, h, 1), row),
            prev_spec(m, row),
        ],
        out_specs=topk_out_specs(k, row),
        scratch_shapes=[pltpu.VMEM((n // LANES, LANES), jnp.float32)],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec, out_shape=topk_out_shapes(b, k),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, kcache, _weights(w, b, h),
      prev_idx.astype(jnp.int32)[:, None])
    return unpack_topk(*out, k)


# --------------------------------------------------------------------------
# Block-table-native (paged) variant — scoring reads physical pages, the
# logical indexer-K view is never materialized.
# --------------------------------------------------------------------------

def _score_page(table_ref, len_ref, q_ref, pages_ref, w_ref, scores_scr,
                *, b, j, qrow):
    """Score logical page j of slot b into row j of the scores tile. Both
    the ragged tail and an unmapped page (-1) score the sentinel, so an
    unmapped page can never be selected."""
    page_size = scores_scr.shape[1]
    sc = _chunk_scores(q_ref[...], pages_ref[...], w_ref[...])
    pos = (jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
           + j * page_size)
    ok = (pos < len_ref[b, qrow]) & (table_ref[b, j] >= 0)
    scores_scr[pl.ds(j, 1), :] = jnp.where(ok, sc, NEG)


def _paged_fused_kernel(table_ref, len_ref, q_ref, pages_ref, w_ref,
                        prev_ref, out_vals_ref, out_idx_ref, stats_ref,
                        scores_scr, *, mp, gvr):
    b = pl.program_id(0)
    j = pl.program_id(1)
    _score_page(table_ref, len_ref, q_ref, pages_ref, w_ref, scores_scr,
                b=b, j=j, qrow=0)

    @pl.when(j == mp - 1)
    def _():
        gvr_on_resident_tile(scores_scr, prev_ref, out_vals_ref, out_idx_ref,
                             stats_ref, **gvr)


def _page_spec(page_size, d, table_at):
    # the fused gather: page row index = prefetched table entry (unmapped
    # entries clip to page 0; their scores are masked)
    return pl.BlockSpec(
        (None, page_size, d),
        lambda *a: (jnp.maximum(table_at(*a), 0), 0, 0))


def paged_indexer_topk_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                              w: jnp.ndarray, table: jnp.ndarray,
                              prev_idx: jnp.ndarray, k: int,
                              *, lengths: Optional[jnp.ndarray] = None,
                              max_candidates: Optional[int] = None,
                              max_secant_iters: int = 12,
                              f_target: Optional[int] = None,
                              interpret: bool = False):
    """Fused paged indexer+Top-K. q: (B,H,D); k_pages: (P, page_size, D)
    global indexer-K page pool; table: (B, MP) int32 block table (-1 =
    unmapped); w: (H,) or (B,H); prev_idx: (B,M) int32 LOGICAL indices;
    lengths: (B,) int32 (defaults to MP·page_size).

    The grid's kv chunk is the logical page: step (b, j) DMAs physical page
    table[b, j] (scalar-prefetched index_map), scores it, and writes row j
    of the (MP, page_size) VMEM scores tile.

    Returns (values (B,K), indices (B,K) int32 — logical, stats (B,8)).
    """
    b, h, d = q.shape
    page_size = k_pages.shape[1]
    mp = table.shape[1]
    n = mp * page_size
    m = prev_idx.shape[-1]
    if lengths is None:
        lengths = jnp.full((b,), n, jnp.int32)
    cmax, ft = gvr_params(k, n, max_candidates, f_target)
    gvr = dict(k=k, cmax=cmax, n=n, m=m, max_secant=max_secant_iters,
               f_target=ft)
    kern = functools.partial(_paged_fused_kernel, mp=mp, gvr=gvr)
    row = lambda i, j, t, ln: (i, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((None, h, d), row),
            _page_spec(page_size, d, lambda i, j, t, ln: t[i, j]),
            pl.BlockSpec((None, h, 1), row),
            prev_spec(m, row),
        ],
        out_specs=topk_out_specs(k, row),
        scratch_shapes=[pltpu.VMEM((mp, page_size), jnp.float32)],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec, out_shape=topk_out_shapes(b, k),
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32)[:, None], q,
      k_pages, _weights(w, b, h), prev_idx.astype(jnp.int32)[:, None])
    return unpack_topk(*out, k)


# --------------------------------------------------------------------------
# Multi-query-row paged variant — the speculative verify tick's selection
# hot spot, with GVR's temporal feedback threaded ACROSS the query rows
# inside the kernel (DESIGN.md §spec-decode).
# --------------------------------------------------------------------------

def _paged_fused_mq_kernel(table_ref, len_ref, q_ref, pages_ref, w_ref,
                           prev_ref, out_vals_ref, out_idx_ref, stats_ref,
                           scores_scr, thread_vmem, thread_smem, sem, *, mp,
                           gvr):
    b = pl.program_id(0)
    qq = pl.program_id(1)
    j = pl.program_id(2)
    # per-query-row causal extent: verify position q masks beyond ITS
    # length (the engine passes lengths[b, q] = L0 + q + 1)
    _score_page(table_ref, len_ref, q_ref, pages_ref, w_ref, scores_scr,
                b=b, j=j, qrow=qq)

    @pl.when((j == mp - 1) & (qq == 0))
    def _():
        # query row 0 warms from the caller's prev_idx (the previous
        # TICK's selection)
        def cp(i, c):
            thread_smem[i // LANES, i % LANES] = prev_ref[0, i]
            return c
        jax.lax.fori_loop(0, prev_ref.shape[1], cp, 0)

    @pl.when(j == mp - 1)
    def _():
        # every later row warms from the row BEFORE it in this launch: its
        # emitted indices were copied VMEM→SMEM below — the temporal
        # signal never round-trips HBM between draft positions
        gvr_on_resident_tile(scores_scr, thread_smem, out_vals_ref,
                             out_idx_ref, stats_ref, **gvr)
        thread_vmem[...] = out_idx_ref[...]
        copy = pltpu.make_async_copy(thread_vmem, thread_smem, sem)
        copy.start()
        copy.wait()


def paged_indexer_topk_mq_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                                 w: jnp.ndarray, table: jnp.ndarray,
                                 prev_idx: jnp.ndarray, k: int,
                                 *, lengths: jnp.ndarray,
                                 max_candidates: Optional[int] = None,
                                 max_secant_iters: int = 12,
                                 f_target: Optional[int] = None,
                                 interpret: bool = False):
    """Fused paged indexer+GVR over Q query rows per slot (the verify
    tick's d+1 draft positions). q: (B, Q, H, D); k_pages: (P, page_size,
    D) global indexer-K page pool; table: (B, MP) int32 shared block
    table; prev_idx: (B, K) int32 LOGICAL indices — query row 0's warm
    start, i.e. the previous TICK's Top-K; lengths: (B, Q) int32 — row
    q's causal extent (position L0 + q attends to L0 + q + 1 tokens).

    `prev_idx` must carry exactly K entries: rows 1..Q-1 warm-start from
    the PREVIOUS ROW's emitted Top-K, threaded through an SMEM scratch
    inside the launch — the kernel form of the verify scan's causally-
    extended feedback, so the temporal-correlation signal never leaves
    the chip between draft positions.

    Returns (values (B, Q, K), indices (B, Q, K) int32 logical,
    stats (B, Q, 8)).
    """
    b, qn, h, d = q.shape
    page_size = k_pages.shape[1]
    mp = table.shape[1]
    n = mp * page_size
    m = prev_idx.shape[-1]
    assert m == k, ("the mq kernel threads each row's K-entry output into "
                    "the next row's warm start, so prev_idx must carry "
                    f"exactly K entries; got M={m}, K={k}")
    cmax, ft = gvr_params(k, n, max_candidates, f_target)
    gvr = dict(k=k, cmax=cmax, n=n, m=m, max_secant=max_secant_iters,
               f_target=ft)
    kern = functools.partial(_paged_fused_mq_kernel, mp=mp, gvr=gvr)
    slot = lambda i, qq, j, t, ln: (i, 0, 0)
    # outputs flattened to (B*Q, ...) rows; reshaped on return
    out_row = lambda i, qq, j, t, ln: (i * qn + qq, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, qn, mp),
        in_specs=[
            pl.BlockSpec((None, None, h, d),
                         lambda i, qq, j, t, ln: (i, qq, 0, 0)),
            _page_spec(page_size, d, lambda i, qq, j, t, ln: t[i, j]),
            pl.BlockSpec((None, h, 1), slot),
            prev_spec(m, slot),
        ],
        out_specs=topk_out_specs(k, out_row),
        scratch_shapes=[pltpu.VMEM((mp, page_size), jnp.float32),
                        pltpu.VMEM((out_rows(k), LANES), jnp.int32),
                        pltpu.SMEM((out_rows(k), LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA],
    )
    vals, idx, stats = unpack_topk(*pl.pallas_call(
        kern, grid_spec=grid_spec, out_shape=topk_out_shapes(b * qn, k),
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), q, k_pages,
      _weights(w, b, h), prev_idx.astype(jnp.int32)[:, None]), k)
    return (vals.reshape(b, qn, k), idx.reshape(b, qn, k),
            stats.reshape(b, qn, 8))
