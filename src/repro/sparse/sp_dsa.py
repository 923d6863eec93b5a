"""SP-DSA: sequence-parallel DSA decode layer (beyond paper — see DESIGN §2).

For 100K–500K contexts the KV cache is sharded along the sequence axis
('data' mesh axis). A naive distributed Top-K would all-gather the score row
(N·4B) every step. SP-DSA keeps everything sequence-local:

  1. cache write    — the shard owning position `length-1` writes the new
                      K/V/indexer-K row (others no-op).
  2. indexer        — each shard scores only its own cache slice (Eq. 1).
  3. SP-GVR         — exact distributed Top-K with scalar-sized collectives
                      (core.sp_gvr). Each shard keeps its own selected rows.
  4. sparse attn    — each shard attends over its local selected rows; the
                      partial (numerator, denominator) pairs combine with
                      one (H·D+H)-wide psum — flash-decoding style.
  5. feedback       — per-shard selected indices all-gather (K·4B total)
                      into the replicated prev-Top-K for the next step.

Per-step collective bill at N=512K, D=16: ~I+S scalar psums + one 2048-bin
psum + one (H·D) psum + one K-int all-gather ≈ tens of KB, vs 2 MB+ for a
score-row gather — and the attention itself never moves KV rows between
shards.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.sp_gvr import sp_canonical_topk, sp_gvr_topk_local
from repro.models.layers import apply_rotary, pool_pages, pool_read

NEG = -3.4028235e38


class SPDSAResult(NamedTuple):
    attn_out: jnp.ndarray     # (B, H_local, HD)
    new_k: jnp.ndarray        # updated local K cache shard
    new_v: jnp.ndarray
    new_ik: jnp.ndarray
    new_topk: jnp.ndarray     # (B, K) global indices (replicated)


def _write_local(cache, new, rel, in_range):
    """Write `new` (B, ...) at local position rel[b] when in_range[b]."""
    def one(c, x, r, ok):
        r = jnp.clip(r, 0, c.shape[0] - 1)
        upd = jax.lax.dynamic_update_slice(c, x[None].astype(c.dtype),
                                           (r,) + (0,) * (c.ndim - 1))
        return jnp.where(ok, upd, c)
    return jax.vmap(one)(cache, new, rel, in_range)


def sp_dsa_decode_local(q, kc, vc, ikc, h, idx_params, prev_topk, lengths,
                        knew, vnew, iknew, *, k: int, scale: float,
                        heads: int, dim: int, rope_base: float,
                        seq_axis: str = "data"):
    """Shard-local body (call inside shard_map). Shapes (per shard):

    q: (B, Hl, HD) — heads may be model-sharded; kc/vc: (B, Nl, KVH, HD);
    ikc: (B, Nl, dim); h: (B, D) replicated; prev_topk: (B, K) GLOBAL idx;
    lengths: (B,) global; knew/vnew: (B, KVH, HD); iknew: (B, dim).
    """
    b, hl, hd = q.shape
    nl = kc.shape[1]
    kvh = kc.shape[2]
    g = hl // kvh
    my = jax.lax.axis_index(seq_axis)
    d = jax.lax.axis_size(seq_axis)
    off = (my * nl).astype(jnp.int32)

    # -- 1. sequence-local cache write ---------------------------------
    pos = lengths - 1
    rel = pos - off
    in_range = (rel >= 0) & (rel < nl)
    kc = _write_local(kc, knew, rel, in_range)
    vc = _write_local(vc, vnew, rel, in_range)
    ikc = _write_local(ikc, iknew, rel, in_range)

    # -- 2. shard-local indexer scores (Eq. 1) -------------------------
    qi = (h @ idx_params["wq"]).reshape(b, 1, heads, dim)
    qi = apply_rotary(qi, pos[:, None], kind="rope", base=rope_base)[:, 0]
    s = jax.nn.relu(jnp.einsum("bhd,bnd->bhn", qi.astype(jnp.float32),
                               ikc.astype(jnp.float32)))
    scores = jnp.einsum("h,bhn->bn", idx_params["w"].astype(jnp.float32), s)
    gpos = jnp.arange(nl, dtype=jnp.int32)[None, :] + off
    scores = jnp.where(gpos < lengths[:, None], scores, NEG)

    # -- 3. SP-GVR exact distributed Top-K ------------------------------
    sel = sp_gvr_topk_local(scores, prev_topk, k, seq_axis)
    loc_idx = sel.local_indices            # (B, K) global idx, -1 padded
    loc_cnt = sel.local_count

    # -- 4. local sparse attention + flash combine ----------------------
    rel_idx = jnp.clip(loc_idx - off, 0, nl - 1)
    kg = jnp.take_along_axis(
        kc, rel_idx[:, :, None, None].repeat(kvh, 2).repeat(hd, 3), axis=1)
    vg = jnp.take_along_axis(
        vc, rel_idx[:, :, None, None].repeat(kvh, 2).repeat(hd, 3), axis=1)
    logits = jnp.einsum("bkgd,bskd->bkgs",
                        q.reshape(b, kvh, g, hd).astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    slot = jnp.arange(loc_idx.shape[-1], dtype=jnp.int32)
    valid = slot[None, :] < loc_cnt[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, NEG)
    # stable combine: global max via pmax, then psum of (num, den)
    m_loc = jnp.max(logits, axis=-1)                       # (B, KVH, G)
    m_glob = jax.lax.pmax(m_loc, seq_axis)
    p = jnp.exp(logits - m_glob[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    num = jnp.einsum("bkgs,bskd->bkgd", p, vg.astype(jnp.float32))
    den = jnp.sum(p, axis=-1)
    num = jax.lax.psum(num, seq_axis)
    den = jax.lax.psum(den, seq_axis)
    out = (num / jnp.maximum(den, 1e-30)[..., None]).reshape(b, hl, hd)

    # -- 5. feedback: assemble global Top-K for the next step -----------
    all_idx = jax.lax.all_gather(loc_idx, seq_axis, axis=1, tiled=True)  # (B, D*K)
    order = jnp.argsort(all_idx < 0, axis=-1, stable=True)  # valid first
    new_topk = jnp.take_along_axis(all_idx, order, axis=-1)[:, :k]
    return SPDSAResult(out, kc, vc, ikc, new_topk.astype(jnp.int32))


class SPDSAPagedResult(NamedTuple):
    attn_out: jnp.ndarray     # (B, H, HD) f32 — replicated across shards
    new_topk: jnp.ndarray     # (B, K) int32 global logical idx (replicated,
                              # canonical ascending order)
    secant_iters: jnp.ndarray  # (B,) int32 — SP-GVR phase-2 iterations
    gvr_rows: jnp.ndarray     # (B,) bool — rows served off the temporal prior
    fallback: jnp.ndarray     # (B,) bool — SP-GVR's safety net ran
    radix_rows: jnp.ndarray   # (B,) bool — none: SP-GVR never runs radix


def sp_dsa_decode_paged_local(q, k_pages, v_pages, table_local, idx_params, h,
                              idx_view_local, prev_topk, prev_valid, lengths,
                              *, k: int, scale: float, heads: int, dim: int,
                              rope_base: float, shard_offset,
                              page_size: int,
                              max_candidates=None,
                              swa_window=None,
                              seq_axis: str = "seq",
                              layer=None) -> SPDSAPagedResult:
    """Shard-local *paged* DSA decode stage (call inside shard_map) — the
    sequence-sharded serving engine's per-layer selection + attention core.

    Unlike `sp_dsa_decode_local` (contiguous sequence-sharded caches, flash
    partial combine), this form addresses each shard's *local page pool*
    through its slice of the block table and assembles the gathered Top-K
    rows with a single O(K) psum, so the step is **bit-identical** to the
    single-device block-table-native path
    (`sparse.dsa.dsa_sparse_attention_paged`):

      1. indexer     — each shard scores its local logical view (Eq. 1;
                       per-position math identical to `dsa.indexer_scores`).
      2. SP-GVR      — `sp_gvr_topk_local`: exact distributed Top-K with
                       scalar-sized collectives (core.sp_gvr schedule).
      3. canonical   — per-shard winners all-gather (K·D ints) and sort
                       into the ascending-index buffer the single-device
                       selector emits (`sp_canonical_topk`).
      4. paged gather— each shard pulls the selected rows IT OWNS straight
                       from its local page pool (`table[idx // page_size]`,
                       local ids); non-owned slots contribute exact zeros
                       and one (B,K,KVH,HD) psum assembles the replicated
                       gathered buffer — exactly one shard contributes per
                       slot, so the values are bit-equal to a single-device
                       pool gather, and the traffic is O(K), independent
                       of context length.
      5. attention   — replicated softmax over the assembled rows, the
                       same reduction extents/order as
                       `dsa.dsa_sparse_attention_paged` → identical bits.

    Shapes (per shard): q (B, H, HD); k/v_pages (PL+1, page_size, KVH, HD)
    local pool (last page = this shard's write sink; with `layer`, the
    layer-stacked (L, PL+1, page_size, KVH, HD) read at that layer through
    `pool_read`); table_local
    (B, MP_local) int32 LOCAL physical ids (-1 unmapped); idx_view_local
    (B, N_local, dim) the shard's logical indexer view; prev_topk (B, K)
    GLOBAL logical indices (replicated); prev_valid (B,) bool (replicated);
    lengths (B,) global; shard_offset scalar — global position of this
    shard's first token.

    `gvr_rows` mirrors the single-device mixed dispatch telemetry: the
    rows with genuine previous-step feedback are the rows the temporal
    prior actually served (SP-GVR is chosen explicitly by long-context
    configs — DESIGN.md §2 — so there is no N-gate here; the engine-level
    bit-identity pin runs below `gate_max_n` where the single-device auto
    gate resolves to the same mixed dispatch).

    Speculative verify (DESIGN.md §spec-decode): the sharded forward over
    Q query rows (`transformer._sp_paged_forward`) calls this stage once
    per row, threading `prev_topk` from each row's `new_topk` into the
    next — the collective schedule per row is exactly the decode step's,
    so a d+1-position verify tick costs d+1 of these O(1)-in-context
    schedules.
    """
    b, hl, hd = q.shape
    n_local = idx_view_local.shape[1]
    sink = pool_pages(k_pages, layer)[0] - 1

    # -- 1. shard-local indexer scores over the local logical view ------
    with jax.named_scope("step.indexer"):
        # per-position math mirrors dsa.indexer_scores bit-for-bit
        # (contraction extents are per-position, so the shard slice
        # changes nothing)
        positions = lengths - 1
        qi = (h @ idx_params["wq"]).reshape(b, 1, heads, dim)
        qi = apply_rotary(qi, positions[:, None], kind="rope",
                          base=rope_base)[:, 0]
        s = jax.nn.relu(jnp.einsum("bhd,bnd->bhn",
                                   qi.astype(idx_view_local.dtype),
                                   idx_view_local,
                                   preferred_element_type=jnp.float32))
        scores = jnp.einsum("h,bhn->bn", idx_params["w"].astype(jnp.float32), s)
        gpos = jnp.arange(n_local, dtype=jnp.int32)[None, :] + shard_offset
        scores = jnp.where(gpos < lengths[:, None], scores, NEG)
        if swa_window is not None:
            in_win = gpos > (lengths[:, None] - 1 - swa_window)
            scores = jnp.where(in_win, scores, NEG)

    # -- 2./3. SP-GVR exact distributed Top-K → canonical global buffer --
    with jax.named_scope("step.topk"):
        d = jax.lax.axis_size(seq_axis)
        n = n_local * d
        sel = sp_gvr_topk_local(scores, prev_topk, k, seq_axis,
                                max_candidates=max_candidates)
        topk = sp_canonical_topk(sel.local_indices, k, n, seq_axis)   # (B, K)

    # -- 4. owned-rows paged gather + one O(K) psum assembly -------------
    with jax.named_scope("step.attention"):
        rel = topk - shard_offset
        owned = (rel >= 0) & (rel < n_local)
        rel_c = jnp.clip(rel, 0, n_local - 1)
        phys = jnp.take_along_axis(table_local, rel_c // page_size, axis=1)
        mapped_loc = owned & (phys >= 0)
        rows = (jnp.clip(phys, 0, sink), rel_c % page_size)           # (B, K)
        kg = pool_read(k_pages, layer, *rows).reshape(topk.shape + (-1, hd))
        vg = pool_read(v_pages, layer, *rows).reshape(topk.shape + (-1, hd))
        kvh = kg.shape[2]
        g = hl // kvh
        hit = mapped_loc[:, :, None, None]
        kg = jax.lax.psum(jnp.where(hit, kg, jnp.zeros((), kg.dtype)), seq_axis)
        vg = jax.lax.psum(jnp.where(hit, vg, jnp.zeros((), vg.dtype)), seq_axis)
        mapped = jax.lax.psum(mapped_loc.astype(jnp.int32), seq_axis) > 0

        # -- 5. replicated attention over the assembled Top-K rows -----------
        # mirrors dsa.dsa_sparse_attention_paged: same einsums, same mask
        logits = jnp.einsum("bkgd,bskd->bkgs", q.reshape(b, kvh, g, hd), kg,
                            preferred_element_type=jnp.float32) * scale
        valid = (topk >= 0) & (topk < lengths[:, None]) & mapped
        logits = jnp.where(valid[:, None, None, :], logits, NEG)
        pr = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", pr.astype(vg.dtype), vg,
                         preferred_element_type=jnp.float32)
    gvr_rows = (prev_valid.astype(bool) if prev_valid is not None
                else jnp.zeros((b,), bool))
    return SPDSAPagedResult(out.reshape(b, hl, hd), topk,
                            sel.secant_iters, gvr_rows, sel.fallback,
                            jnp.zeros((b,), bool))


def make_sp_dsa(mesh, *, k: int, scale: float, heads: int, dim: int,
                rope_base: float, seq_axis: str = "data",
                head_axis: str = "model", shard_heads: bool = True):
    """shard_map-wrapped SP-DSA decode layer.

    Sharding: caches (batch=None, seq→seq_axis, kv replicated, hd), heads of
    q over head_axis when divisible, h/prev_topk/lengths replicated.
    """
    body = partial(sp_dsa_decode_local, k=k, scale=scale, heads=heads, dim=dim,
                   rope_base=rope_base, seq_axis=seq_axis)
    hspec = P(None, head_axis, None) if shard_heads else P(None, None, None)
    kv_spec = P(None, seq_axis, None, None)

    def fn(q, kc, vc, ikc, h, idx_params, prev_topk, lengths, knew, vnew, iknew):
        return body(q, kc, vc, ikc, h, idx_params, prev_topk, lengths,
                    knew, vnew, iknew)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(hspec, kv_spec, kv_spec, P(None, seq_axis, None),
                  P(None, None), P(), P(None, None), P(None),
                  P(None, None, None), P(None, None, None), P(None, None)),
        out_specs=SPDSAResult(hspec, kv_spec, kv_spec, P(None, seq_axis, None),
                              P(None, None)),
        check_vma=False,
    )
