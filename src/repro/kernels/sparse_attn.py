"""Pallas TPU kernel: sparse decode attention over Top-K gathered tokens.

The DSA "sparse MLA" stage: one query token attends over exactly the K
(=2048) KV-cache rows selected by the Top-K stage, regardless of context
length N — O(K) traffic (paper Table 2).

TPU adaptation of the GPU gather: the Top-K indices are *scalar-prefetched*
(PrefetchScalarGridSpec), so the BlockSpec index_map itself gathers — each
grid step DMAs the (gather_block × KVH × D) cache rows addressed by the
next index. Flash-style online softmax (running max / denominator / value
accumulator in VMEM scratch) accumulates across grid steps; GQA maps head
h to kv-head h // (H / KVH).

Index granularity is `gather_block` consecutive Top-K entries per grid step
(token-granular DMA when 1). Production kernels would coarsen to KV pages;
we note this in DESIGN.md §adaptation — the dry-run/roofline path uses the
XLA gather in the model layer, while this kernel is the TPU hot-spot form.

`paged_sparse_decode_attn_pallas` is the block-table-native variant
(DESIGN.md §paged): the caches stay in the serving layer's global page
pools and the index_map *composes* the logical→physical translation with
the Top-K gather — page `table[b, idx // page_size]`, offset
`idx % page_size` — so each grid step DMAs one (KVH × D) row straight out
of the page pool and the contiguous (B, MP·page_size, ...) logical view is
never built. Per-tick gathered KV traffic is O(K), independent of context
length N.

Padding contract: invalid idx entries are < 0 — the wrapper clips them for
addressing and masks their logits to -inf. The paged variant additionally
masks entries whose logical page is unmapped (table entry < 0, the -1
sentinel), so an unmapped page can never contribute to the softmax.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gqa_expand(h, kvh):
    """(H, KVH) one-hot: head h reads KV head h // (H / KVH)."""
    head = jax.lax.broadcasted_iota(jnp.int32, (h, kvh), 0)
    kv = jax.lax.broadcasted_iota(jnp.int32, (h, kvh), 1)
    return (kv == head // (h // kvh)).astype(jnp.float32)


def _rows_per_head(expand, rows):
    """(KVH, D) -> (H, D): each head's KV row, by an exact one-hot matmul
    (one non-zero term per output), in 2-D forms Mosaic lowers."""
    return jax.lax.dot(expand, rows, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _attn_kernel(idx_ref, q_ref, k_ref, v_ref, o_ref,
                 m_scr, l_scr, acc_scr, *, nsteps, kk, scale, h, kvh, dv):
    b = pl.program_id(0)
    j = pl.program_id(1)
    g = h // kvh

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0].astype(jnp.float32)                     # (H, D)
    kb = k_ref[0].astype(jnp.float32)                    # (GB, KVH, D)
    vb = v_ref[0].astype(jnp.float32)                    # (GB, KVH, DV)
    gb = kb.shape[0]

    # logits[h, t] = scale * q[h] · kb[t, h // g]
    qg = q.reshape(kvh, g, -1)
    logits = jnp.einsum("khd,tkd->kht", qg, kb).reshape(h, gb) * scale
    # mask padded entries (idx < 0) — positions beyond the valid count
    col = jax.lax.broadcasted_iota(jnp.int32, (1, gb), 1)[0] + j * gb
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, gb), 1)
    valid = jnp.zeros((1, gb), jnp.int32)
    for t in range(gb):                                   # gb is small & static
        ok = idx_ref[b, jnp.minimum(j * gb + t, kk - 1)] >= 0
        valid = jnp.where(lane == t, ok.astype(jnp.int32), valid)
    valid = (valid > 0) & (col[None, :] < kk)
    logits = jnp.where(valid, logits, -jnp.inf)

    m_prev = m_scr[...]                                   # (H, 1)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    # guard: all -inf so far -> exp(-inf - -inf); shift by finite max
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(logits), logits - m_safe, -jnp.inf))
    p = jnp.where(jnp.isfinite(logits), p, 0.0)           # (H, GB)
    l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("kgt,tkd->kgd", p.reshape(kvh, g, gb), vb).reshape(h, dv)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(j == nsteps - 1)
    def _():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def sparse_decode_attn_pallas(q: jnp.ndarray, kcache: jnp.ndarray,
                              vcache: jnp.ndarray, idx: jnp.ndarray,
                              *, scale: Optional[float] = None,
                              gather_block: int = 8,
                              gather_mode: str = "kernel",
                              interpret: bool = False):
    """q: (B,H,D); k/vcache: (B,N,KVH,D[v]); idx: (B,K) int32, -1-padded.

    gather_mode:
      "kernel"    — the BlockSpec index_map reads the scalar-prefetched
                    Top-K index for every grid step: the DMA engine itself
                    performs the gather (token-granular, gather_block=1).
                    This is the production TPU form of the GPU's scattered
                    __ldg loads.
      "pregather" — XLA take_along_axis gathers once, the kernel streams
                    contiguous (gather_block, KVH, D) tiles. Same HBM bytes;
                    faster under interpret=True (fewer grid steps).

    Returns (B, H, DV) f32 attention output over the selected tokens only.
    """
    b, h, d = q.shape
    kvh = kcache.shape[2]
    dv = vcache.shape[-1]
    kk = idx.shape[-1]
    gb = 1 if gather_mode == "kernel" else min(gather_block, kk)
    assert kk % gb == 0, (kk, gb)
    nsteps = kk // gb
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    idx_safe = jnp.where(idx >= 0, idx, 0).astype(jnp.int32)
    idx_pref = idx.astype(jnp.int32)

    kern = functools.partial(_attn_kernel, nsteps=nsteps, kk=kk, scale=scale,
                             h=h, kvh=kvh, dv=dv)

    if gather_mode == "kernel":
        # the DMA gather: block row index = prefetched Top-K entry
        kv_k_spec = pl.BlockSpec((1, 1, kvh, d),
                                 lambda i, j, idx_ref: (i, jnp.maximum(idx_ref[i, j], 0), 0, 0))
        kv_v_spec = pl.BlockSpec((1, 1, kvh, dv),
                                 lambda i, j, idx_ref: (i, jnp.maximum(idx_ref[i, j], 0), 0, 0))
        kv_in, vv_in = kcache, vcache
    else:
        kv_k_spec = pl.BlockSpec((1, gb, kvh, d), lambda i, j, idx_ref: (i, j, 0, 0))
        kv_v_spec = pl.BlockSpec((1, gb, kvh, dv), lambda i, j, idx_ref: (i, j, 0, 0))
        kv_in = jnp.take_along_axis(
            kcache, idx_safe[:, :, None, None].repeat(kvh, 2).repeat(d, 3), axis=1)
        vv_in = jnp.take_along_axis(
            vcache, idx_safe[:, :, None, None].repeat(kvh, 2).repeat(dv, 3), axis=1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nsteps),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, j, idx_ref: (i, 0, 0)),
            kv_k_spec,
            kv_v_spec,
        ],
        out_specs=pl.BlockSpec((1, h, dv), lambda i, j, idx_ref: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, dv), jnp.float32),
        ],
    )

    out_shape = jax.ShapeDtypeStruct((b, h, dv), jnp.float32)
    return pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(idx_pref, q, kv_in, vv_in)


# --------------------------------------------------------------------------
# Block-table-native (paged) variant — the page gather is fused into the
# attention DMA; the logical KV view is never materialized.
# --------------------------------------------------------------------------

def _paged_attn_kernel(table_ref, idx_ref, q_ref, k_ref, v_ref, o_ref,
                       m_scr, l_scr, acc_scr, *, nsteps, kk, scale, h, kvh,
                       dv, page_size, n_logical):
    b = pl.program_id(0)
    j = pl.program_id(1)
    g = h // kvh

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0].astype(jnp.float32)                     # (H, D)
    kb = k_ref[0, 0].astype(jnp.float32)                 # (KVH, D)
    vb = v_ref[0, 0].astype(jnp.float32)                 # (KVH, DV)

    # validity: a Top-K entry contributes iff it is non-negative AND its
    # logical page is mapped (-1 sentinel ⇒ masked, never addressed)
    li = idx_ref[b, j]
    li_safe = jnp.clip(li, 0, n_logical - 1)
    valid = (li >= 0) & (table_ref[b, li_safe // page_size] >= 0)

    # logits[h] = scale * q[h] · kb[h // g]  — one gathered token
    expand = _gqa_expand(h, kvh)
    logits = jnp.sum(q * _rows_per_head(expand, kb), axis=1,
                     keepdims=True) * scale
    logits = jnp.where(valid, logits, -jnp.inf)

    m_prev = m_scr[...]                                   # (H, 1)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, logits)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(logits), logits - m_safe, -jnp.inf))
    p = jnp.where(jnp.isfinite(logits), p, 0.0)           # (H, 1)
    l_scr[...] = l_prev * alpha + p
    pv = p * _rows_per_head(expand, vb)                   # (H, DV)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(j == nsteps - 1)
    def _():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_sparse_decode_attn_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                                    v_pages: jnp.ndarray, table: jnp.ndarray,
                                    idx: jnp.ndarray, *,
                                    scale: Optional[float] = None,
                                    interpret: bool = False):
    """q: (B,H,D); k/v_pages: (P, page_size, KVH, D[v]) global page pools;
    table: (B, MP) int32 block table (-1 = unmapped); idx: (B,K) int32
    LOGICAL Top-K indices, -1-padded.

    Both the block table and the Top-K indices are scalar-prefetched; the
    BlockSpec index_map composes the two lookups, so the DMA engine gathers
    physical row (table[b, idx // page_size], idx % page_size) directly —
    no intermediate logical view, O(K) HBM traffic per query.

    Returns (B, H, DV) f32 attention output over the selected tokens only.
    """
    b, h, d = q.shape
    p_pages, page_size, kvh = k_pages.shape[:3]
    dv = v_pages.shape[-1]
    mp = table.shape[1]
    n_logical = mp * page_size
    kk = idx.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    table = table.astype(jnp.int32)
    idx = idx.astype(jnp.int32)

    def _phys(i, j, table_ref, idx_ref):
        # logical→physical translation *inside the index_map*: the
        # prefetched table entry addresses the page, the index remainder
        # addresses the row within it (invalid entries clip to (0, 0) —
        # they are masked in the kernel body, never read semantically)
        li = jnp.clip(idx_ref[i, j], 0, n_logical - 1)
        pg = jnp.maximum(table_ref[i, li // page_size], 0)
        return pg, li % page_size

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kk),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, j, t, x: (i, 0, 0)),
            pl.BlockSpec((1, 1, kvh, d),
                         lambda i, j, t, x: _phys(i, j, t, x) + (0, 0)),
            pl.BlockSpec((1, 1, kvh, dv),
                         lambda i, j, t, x: _phys(i, j, t, x) + (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, dv), lambda i, j, t, x: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, dv), jnp.float32),
        ],
    )

    kern = functools.partial(_paged_attn_kernel, nsteps=kk, kk=kk, scale=scale,
                             h=h, kvh=kvh, dv=dv, page_size=page_size,
                             n_logical=n_logical)
    out_shape = jax.ShapeDtypeStruct((b, h, dv), jnp.float32)
    return pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(table, idx, q, k_pages, v_pages)


# --------------------------------------------------------------------------
# Multi-query-row paged variant — the speculative verify tick's hot-spot
# form: d+1 query rows per slot attend over their own Top-K selections
# against the SAME page pools/block table in one launch.
# --------------------------------------------------------------------------

def _paged_attn_mq_kernel(table_ref, idx_ref, q_ref, k_ref, v_ref, o_ref,
                          m_scr, l_scr, acc_scr, *, nsteps, scale, h, kvh,
                          dv, page_size, n_logical):
    b = pl.program_id(0)
    qq = pl.program_id(1)
    j = pl.program_id(2)
    g = h // kvh

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0, 0].astype(jnp.float32)                  # (H, D)
    kb = k_ref[0, 0].astype(jnp.float32)                 # (KVH, D)
    vb = v_ref[0, 0].astype(jnp.float32)                 # (KVH, DV)

    # validity mirrors the single-row kernel, per query row: an entry
    # contributes iff non-negative AND its logical page is mapped
    li = idx_ref[b, qq, j]
    li_safe = jnp.clip(li, 0, n_logical - 1)
    valid = (li >= 0) & (table_ref[b, li_safe // page_size] >= 0)

    expand = _gqa_expand(h, kvh)
    logits = jnp.sum(q * _rows_per_head(expand, kb), axis=1,
                     keepdims=True) * scale
    logits = jnp.where(valid, logits, -jnp.inf)

    m_prev = m_scr[...]                                   # (H, 1)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, logits)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(logits), logits - m_safe, -jnp.inf))
    p = jnp.where(jnp.isfinite(logits), p, 0.0)           # (H, 1)
    l_scr[...] = l_prev * alpha + p
    pv = p * _rows_per_head(expand, vb)                   # (H, DV)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(j == nsteps - 1)
    def _():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_sparse_decode_attn_mq_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                                       v_pages: jnp.ndarray,
                                       table: jnp.ndarray, idx: jnp.ndarray,
                                       *, scale: Optional[float] = None,
                                       interpret: bool = False):
    """q: (B, Q, H, D) — Q query rows per slot (the verify tick's d+1 draft
    positions); k/v_pages: (P, page_size, KVH, D[v]) global page pools;
    table: (B, MP) int32 block table shared by all of a slot's query rows;
    idx: (B, Q, K) int32 LOGICAL Top-K indices per query row, -1-padded.

    The grid grows a query-row axis — (B, Q, K) — and everything else is
    the single-row kernel verbatim: both lookups stay scalar-prefetched,
    the flash accumulators reset per (slot, query row), and each grid step
    DMAs one (KVH × D) row straight from the page pool. Per verify tick
    exactly (d+1)·K rows move — O(K) per position, the same bound the
    one-token step pays, amortizing the Q·H query traffic over one launch.

    Returns (B, Q, H, DV) f32.
    """
    b, qn, h, d = q.shape
    p_pages, page_size, kvh = k_pages.shape[:3]
    dv = v_pages.shape[-1]
    mp = table.shape[1]
    n_logical = mp * page_size
    kk = idx.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    table = table.astype(jnp.int32)
    idx = idx.astype(jnp.int32)

    def _phys(i, qq, j, table_ref, idx_ref):
        li = jnp.clip(idx_ref[i, qq, j], 0, n_logical - 1)
        pg = jnp.maximum(table_ref[i, li // page_size], 0)
        return pg, li % page_size

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, qn, kk),
        in_specs=[
            pl.BlockSpec((1, 1, h, d), lambda i, qq, j, t, x: (i, qq, 0, 0)),
            pl.BlockSpec((1, 1, kvh, d),
                         lambda i, qq, j, t, x: _phys(i, qq, j, t, x) + (0, 0)),
            pl.BlockSpec((1, 1, kvh, dv),
                         lambda i, qq, j, t, x: _phys(i, qq, j, t, x) + (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, h, dv),
                               lambda i, qq, j, t, x: (i, qq, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, dv), jnp.float32),
        ],
    )

    kern = functools.partial(_paged_attn_mq_kernel, nsteps=kk, scale=scale,
                             h=h, kvh=kvh, dv=dv, page_size=page_size,
                             n_logical=n_logical)
    out_shape = jax.ShapeDtypeStruct((b, qn, h, dv), jnp.float32)
    return pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(table, idx, q, k_pages, v_pages)


# --------------------------------------------------------------------------
# Page-granular variant — whole-page DMA: selected indices sharing a page
# move as ONE page-sized descriptor, rows are sliced out in VMEM.
# --------------------------------------------------------------------------

def _paged_attn_pg_kernel(tpad_ref, up_ref, q_ref, k_ref, v_ref, rv_ref,
                          o_ref, m_scr, l_scr, acc_scr, *, nsteps, scale, h,
                          kvh, dv, page_size):
    j = pl.program_id(1)
    g = h // kvh

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0].astype(jnp.float32)                     # (H, D)
    kb = k_ref[0].astype(jnp.float32)                    # (page_size, KVH, D)
    vb = v_ref[0].astype(jnp.float32)                    # (page_size, KVH, DV)
    rv = rv_ref[0]                                       # (1, page_size) int32

    # one whole gathered page per step: rows the Top-K did NOT select (and
    # every row of sentinel/unmapped pages) arrive in VMEM but are masked
    # out of the softmax here — the slice-in-fast-memory half of the
    # page-granular DMA contract
    qg = q.reshape(kvh, g, -1)
    logits = jnp.einsum("khd,tkd->kht", qg, kb).reshape(h, page_size) * scale
    logits = jnp.where(rv > 0, logits, -jnp.inf)

    m_prev = m_scr[...]                                   # (H, 1)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(logits), logits - m_safe, -jnp.inf))
    p = jnp.where(jnp.isfinite(logits), p, 0.0)           # (H, page_size)
    l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("kgt,tkd->kgd", p.reshape(kvh, g, page_size),
                    vb).reshape(h, dv)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(j == nsteps - 1)
    def _():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_sparse_decode_attn_pg_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                                       v_pages: jnp.ndarray,
                                       table: jnp.ndarray, idx: jnp.ndarray,
                                       *, scale: Optional[float] = None,
                                       interpret: bool = False):
    """Page-granular form of `paged_sparse_decode_attn_pallas`: same
    arguments and masking semantics, coarser DMA. The wrapper builds the
    per-slot DISTINCT-page descriptor list (`sparse.dsa.distinct_pages` —
    at most min(K, MP) pages, sentinel MP for unused slots) plus a
    per-(page, row) selection mask; the grid runs (B, S) steps, each
    DMA-ing one whole (page_size × KVH × D) page addressed through the
    scalar-prefetched descriptor, and the kernel slices the selected rows
    out in VMEM. Per query ≤ min(K, MP)·page_size rows move in ≤
    min(K, MP) descriptors (vs exactly K single-row descriptors for the
    token-granular kernel) — page-locality in the Top-K set turns into
    proportionally fewer, larger transfers, which is the descriptor-bound
    regime the roofline flags (EXPERIMENTS.md §Roofline).

    Contributions equal the token-granular kernel's exactly as a set; the
    flash accumulation visits them in page order rather than Top-K order,
    so outputs agree to allclose (the bit-identity pin lives on the XLA
    serving path — sparse.dsa.dsa_sparse_attention_paged, which reorders
    rows back to Top-K order).

    Returns (B, H, DV) f32.
    """
    from repro.sparse.dsa import distinct_pages

    b, h, d = q.shape
    p_pages, page_size, kvh = k_pages.shape[:3]
    dv = v_pages.shape[-1]
    mp = table.shape[1]
    n_logical = mp * page_size
    kk = idx.shape[-1]
    s_pages = min(kk, mp)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    table = table.astype(jnp.int32)
    idx = idx.astype(jnp.int32)

    # descriptor build (XLA, O(K log K) per slot): distinct touched pages,
    # padded table (sentinel page MP holds -1 = clips to page 0, masked),
    # and the per-(descriptor, row) selection mask
    li = jnp.clip(idx, 0, n_logical - 1)
    up = distinct_pages(li, page_size=page_size, num_logical_pages=mp)
    tpad = jnp.concatenate([table, jnp.full((b, 1), -1, jnp.int32)], axis=1)
    uphys = jnp.take_along_axis(tpad, up, axis=1)                 # (B, S)
    logical = (up[:, :, None] * page_size
               + jnp.arange(page_size, dtype=jnp.int32)[None, None, :])
    row_valid = ((up[:, :, None] < mp) & (uphys[:, :, None] >= 0)
                 & jnp.any((idx[:, None, None, :] == logical[..., None])
                           & (idx[:, None, None, :] >= 0), axis=-1))
    row_valid = row_valid.astype(jnp.int32)                       # (B, S, ps)

    def _page(i, j, tpad_ref, up_ref):
        # whole-page DMA: the descriptor names the logical page, the padded
        # table translates it (sentinel/unmapped clip to page 0 — every row
        # masked in the body, never read semantically)
        return (jnp.maximum(tpad_ref[i, up_ref[i, j]], 0),)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, s_pages),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, j, t, u: (i, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, d),
                         lambda i, j, t, u: _page(i, j, t, u) + (0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, dv),
                         lambda i, j, t, u: _page(i, j, t, u) + (0, 0, 0)),
            pl.BlockSpec((1, None, 1, page_size),
                         lambda i, j, t, u: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, dv), lambda i, j, t, u: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, dv), jnp.float32),
        ],
    )

    kern = functools.partial(_paged_attn_pg_kernel, nsteps=s_pages,
                             scale=scale, h=h, kvh=kvh, dv=dv,
                             page_size=page_size)
    out_shape = jax.ShapeDtypeStruct((b, h, dv), jnp.float32)
    return pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(tpad, up, q, k_pages, v_pages,
                                               row_valid[:, :, None])


# --------------------------------------------------------------------------
# Fused paged DENSE decode attention — the pre-DSA fallback's hot-spot
# form: attend the full logical extent straight off the page pools.
# --------------------------------------------------------------------------

def _paged_dense_attn_kernel(table_ref, lengths_ref, q_ref, k_ref, v_ref,
                             o_ref, m_scr, l_scr, acc_scr, *, nsteps, scale,
                             h, kvh, dv, page_size, window):
    b = pl.program_id(0)
    j = pl.program_id(1)
    g = h // kvh

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0].astype(jnp.float32)                     # (H, D)
    kb = k_ref[0].astype(jnp.float32)                    # (page_size, KVH, D)
    vb = v_ref[0].astype(jnp.float32)

    # causal/window mask over GLOBAL positions — the only validity rule
    # (mirroring layers.decode_attention_paged: unmapped pages sit beyond
    # `length`, so the length mask subsumes the -1 sentinel)
    ln = lengths_ref[b]
    gpos = (jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)[0]
            + j * page_size)
    valid = gpos < ln
    if window is not None:
        valid &= gpos > ln - 1 - window

    qg = q.reshape(kvh, g, -1)
    logits = jnp.einsum("khd,tkd->kht", qg, kb).reshape(h, page_size) * scale
    logits = jnp.where(valid[None, :], logits, -jnp.inf)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(logits), logits - m_safe, -jnp.inf))
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("kgt,tkd->kgd", p.reshape(kvh, g, page_size),
                    vb).reshape(h, dv)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(j == nsteps - 1)
    def _():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_dense_decode_attn_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                                   v_pages: jnp.ndarray, table: jnp.ndarray,
                                   lengths: jnp.ndarray, *,
                                   scale: Optional[float] = None,
                                   window: Optional[int] = None,
                                   interpret: bool = False):
    """Fused paged DENSE decode attention (the pre-DSA-gate fallback): one
    query per slot attends its full causal extent straight off the page
    pools. q: (B, H, D); k/v_pages: (P, page_size, KVH, D[v]); table:
    (B, MP) block table; lengths: (B,) causal extents; `window` an optional
    SWA width.

    Grid (B, MP): each step DMAs slot b's j-th logical page WHOLE (the
    scalar-prefetched table translates it; unmapped pages clip to page 0 —
    dead under the length mask) and flash-accumulates all page_size rows
    under the causal/window mask. Page-granular DMA is the natural shape
    here — the dense extent touches every row of every mapped page — so
    this kernel shares its descriptor economics with the pg sparse gather.

    Returns (B, H, DV) f32.
    """
    b, h, d = q.shape
    p_pages, page_size, kvh = k_pages.shape[:3]
    dv = v_pages.shape[-1]
    mp = table.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    table = table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, j, t, ln: (i, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, d),
                         lambda i, j, t, ln: (jnp.maximum(t[i, j], 0), 0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, dv),
                         lambda i, j, t, ln: (jnp.maximum(t[i, j], 0), 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, dv), lambda i, j, t, ln: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, dv), jnp.float32),
        ],
    )

    kern = functools.partial(_paged_dense_attn_kernel, nsteps=mp, scale=scale,
                             h=h, kvh=kvh, dv=dv, page_size=page_size,
                             window=window)
    out_shape = jax.ShapeDtypeStruct((b, h, dv), jnp.float32)
    return pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(table, lengths, q, k_pages,
                                               v_pages)
