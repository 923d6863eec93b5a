"""Pallas TPU kernel: fused Guess-Verify-Refine exact Top-K.

One program per batch row (grid=(B,)). The score row is brought HBM→VMEM
once by the BlockSpec as an (R, C) tile (C = 128 lanes for a plain score
row, C = page_size for the paged indexer), so every phase is on-chip and
the kernel's HBM traffic is the roofline minimum (N·4B in + K·8B out +
M·4B prediction):

  P1  pre-indexed statistics: each of the previous step's M Top-K indices
      (an SMEM row) addresses one tile row; a lane mask picks the entry →
      pmin/pmean/pmax. Indices outside [0, N) are ignored.
  P2  secant threshold search — each iteration is a VPU count-reduction
      over the resident tile (the paper's blockCountGE, minus the HBM
      cost) until count(x ≥ T) lands in the window [K, C_max].
  P3  exact refine by bit-space bisection on the sortable-int32 image of
      f32, from the secant's bracket to just above the row maximum: at
      most 32 exactly convergent count passes. The count at the final key
      IS n_gt/n_ge, so the tie partition follows. On a VMEM-resident row a
      full-tile count pass is a few dozen vector ops, so the refine counts
      over the tile instead of compacting a candidate buffer first (the
      GPU kernel's reason for the buffer is HBM/L2 traffic, which does not
      exist here). stats[3] still reports whether the secant left more
      than C_max candidates.
  P4  emit exactly K (all > T* plus the lowest-index ties), compacted in
      index order row by row: a row's exclusive prefix count is one
      (1,C)·(C,C) triangular matmul, and each selected entry is routed to
      its output slot by a one-hot contraction on the MXU. The payload is
      the entry's index and f32 bit pattern split into bytes, which bf16
      holds exactly, so the routing is exact at any matmul precision.

Every vector is 2-D, with no scatter and no 1-D gather: the forms Mosaic
lowers. Checked with interpret=True against kernels/ref.py (lax.top_k
oracle) and compiled for TPU v5e by tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_PAYLOAD_ROWS = 8        # index bytes (3) + f32 bytes (4), padded to a tile


def out_rows(k: int) -> int:
    """Rows of the (rows, 128) output tile that holds K results."""
    return -(-k // LANES)


def _key(bits):
    """f32 bit pattern (int32) -> int32 key with the float order."""
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _f32_key(x):
    return _key(jax.lax.bitcast_convert_type(x, jnp.int32))


def _from_key(key):
    return jax.lax.bitcast_convert_type(_key(key), jnp.float32)


def _scalar_key(x):
    """_f32_key of a scalar, through a vector: Mosaic bitcasts vectors
    only."""
    return jnp.max(_f32_key(jnp.full((1, LANES), x, jnp.float32)))


def _midpoint(lo, hi):
    """floor((lo + hi) / 2) without int32 overflow."""
    return (lo & hi) + ((lo ^ hi) >> 1)


def _prev_stats(x_ref, prev_ref, *, m, n):
    """P1: min / max / sum / count of the scores the previous Top-K points
    at. prev_ref is an SMEM (1, M) row of logical indices, or an (OR, 128)
    tile of them."""
    c = x_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)

    def body(j, s):
        vmin, vmax, vsum, vcnt = s
        idx = prev_ref[j // prev_ref.shape[1], j % prev_ref.shape[1]]
        ok = (idx >= 0) & (idx < n)
        idc = jnp.clip(idx, 0, n - 1)
        row = x_ref[pl.ds(idc // c, 1), :]
        hit = (lane == idc % c) & ok
        return (jnp.where(hit, jnp.minimum(vmin, row), vmin),
                jnp.where(hit, jnp.maximum(vmax, row), vmax),
                vsum + jnp.where(hit, row, 0.0),
                vcnt + hit.astype(jnp.float32))

    init = (jnp.full((1, c), jnp.inf, jnp.float32),
            jnp.full((1, c), -jnp.inf, jnp.float32),
            jnp.zeros((1, c), jnp.float32), jnp.zeros((1, c), jnp.float32))
    vmin, vmax, vsum, vcnt = jax.lax.fori_loop(0, m, body, init)
    return jnp.min(vmin), jnp.max(vmax), jnp.sum(vsum), jnp.sum(vcnt)


def gvr_on_resident_tile(x_ref, prev_ref, out_vals_ref, out_idx_ref,
                         stats_ref, *, k, cmax, n, m, max_secant, f_target):
    """All GVR phases over a VMEM-resident (R, C) score tile `x_ref` in
    row-major logical order (position r·C + c, N = R·C).

    Shared by the standalone Top-K kernel and the fused indexer+Top-K
    kernels (where the tile is a scores scratch that never visits HBM).
    Writes (OR, 128) value / index tiles (slot s at [s // 128, s % 128];
    the first K slots hold the result) and a (1, 8) stats row:
    [secant_iters, bisect_iters, cand_count, over_cmax, threshold, n_gt,
    n_ge, emitted].
    """
    def count_ge(t):
        return jnp.sum((x_ref[...] >= t).astype(jnp.int32))

    def count_ge_key(tk):
        return jnp.sum((_f32_key(x_ref[...]) >= tk).astype(jnp.int32))

    # ---------------- Phase 1: pre-indexed statistics -------------------
    row_max = jnp.max(x_ref[...])
    row_min = jnp.min(x_ref[...])
    p_min, p_max, p_sum, p_cnt = _prev_stats(x_ref, prev_ref, m=m, n=n)
    have = p_cnt > 0
    p_lo = jnp.where(have, p_min, row_min)
    p_hi = jnp.where(have, p_max, row_max)
    t0 = jnp.where(have, p_sum / jnp.maximum(p_cnt, 1.0),
                   0.5 * row_min + 0.5 * row_max)
    if m < k:
        p_lo = jnp.minimum(p_lo, row_min)
        p_hi = jnp.maximum(p_hi, row_max)

    # ---------------- Phase 2: secant threshold search ------------------
    ftarget = jnp.float32(f_target)

    def secant_body(s):
        (t_lo, c_lo, t_hi, c_hi, t, t_probe, cnt, hi_probed, prev_over,
         done, it) = s
        n_ge = count_ge(t)
        in_window = (n_ge >= k) & (n_ge <= cmax)
        done2 = done | in_window
        too_many = ~done & (n_ge > cmax)
        too_few = ~done & (n_ge < k)
        t_lo = jnp.where(too_many, t, t_lo)
        c_lo = jnp.where(too_many, n_ge.astype(jnp.float32), c_lo)
        t_hi = jnp.where(too_few, t, t_hi)
        c_hi = jnp.where(too_few, n_ge.astype(jnp.float32), c_hi)
        denom = c_lo - c_hi
        frac = jnp.where(denom != 0,
                         (c_lo - ftarget) / jnp.where(denom != 0, denom, 1.0),
                         jnp.float32(0.5))
        frac = jnp.where(it == 0, jnp.minimum(frac, 0.5), frac)
        t_new = t_lo + frac * (t_hi - t_lo)
        inside = (t_new > t_lo) & (t_new < t_hi)
        t_new = jnp.where(inside, t_new, 0.5 * t_lo + 0.5 * t_hi)
        probe_lo = (frac <= 0) & (t_lo != t)
        t_new = jnp.where(probe_lo, t_lo, t_new)
        probe_hi = too_many & prev_over & ~hi_probed & (t_hi != t)
        t_new = jnp.where(probe_hi, t_hi, t_new)
        collapsed = ~((t_new > t_lo) & (t_new < t_hi)) & ~probe_lo & ~probe_hi
        rescue_hi = collapsed & too_many & (row_max > t_hi)
        t_hi = jnp.where(rescue_hi, row_max, t_hi)
        c_hi = jnp.where(rescue_hi, jnp.float32(1.0), c_hi)
        rescue_lo = collapsed & too_few & (row_min < t_lo)
        t_lo = jnp.where(rescue_lo, row_min, t_lo)
        c_lo = jnp.where(rescue_lo, jnp.float32(n), c_lo)
        rescued = rescue_hi | rescue_lo
        t_new = jnp.where(rescued, 0.5 * t_lo + 0.5 * t_hi, t_new)
        collapsed = collapsed & ~rescued
        t_new = jnp.where(collapsed, t_lo, t_new)
        done2 = done2 | collapsed
        return (t_lo, c_lo, t_hi, c_hi,
                jnp.where(done2, t, t_new), t,
                n_ge,
                jnp.where(rescue_hi, False, hi_probed | probe_hi),
                too_many, done2, it + 1)

    def secant_cond(s):
        done, it = s[-2], s[-1]
        return ~done & (it < max_secant)

    t0c = jnp.clip(t0, p_lo, p_hi)
    init = (p_lo, jnp.float32(min(n, max(1.25 * m, k))),
            jnp.maximum(p_hi, p_lo), jnp.float32(1.0),
            t0c, t0c, jnp.int32(0), False, False, False, jnp.int32(0))
    (t_lo, _c_lo, _t_hi, _c_hi, _t, t_probe, cnt, _hp, _po, _done,
     secant_iters) = jax.lax.while_loop(secant_cond, secant_body, init)
    t_exit = jnp.where(cnt >= k, t_probe, t_lo)
    c_exit = count_ge(t_exit)

    # ---------------- Phase 3: exact refine (bit-bisection) -------------
    # invariant: count_ge_key(lo) >= k > count_ge_key(hi); hi starts one
    # key above the row maximum, so it holds even when the maximum ties
    lo0 = jnp.where(c_exit >= k, t_exit, row_min)

    def bis_cond(s):
        lo, hi, it = s
        return (hi > lo + 1) & (it < 34)          # hi - lo may overflow

    def bis_body(s):
        lo, hi, it = s
        mid = _midpoint(lo, hi)
        ok = count_ge_key(mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid), it + 1

    t_key, _, bisect_iters = jax.lax.while_loop(
        bis_cond, bis_body,
        (_scalar_key(lo0), _scalar_key(row_max) + 1, jnp.int32(0)))
    # count(>= t_key) >= k > count(>= t_key + 1): t_key is the K-th value
    n_ge = count_ge_key(t_key)
    n_gt = count_ge_key(t_key + 1)

    # ---------------- Phase 4: emit exactly K ---------------------------
    _emit(x_ref, t_key, k - n_gt, out_vals_ref, out_idx_ref)

    lane8 = jax.lax.broadcasted_iota(jnp.int32, (1, 8), 1)
    stats = jnp.zeros((1, 8), jnp.float32)
    for j, v in enumerate((secant_iters.astype(jnp.float32),
                           bisect_iters.astype(jnp.float32),
                           c_exit.astype(jnp.float32),
                           jnp.where(c_exit <= cmax, 0.0, 1.0),
                           _from_key(jnp.full((1, 8), t_key, jnp.int32)),
                           n_gt.astype(jnp.float32),
                           n_ge.astype(jnp.float32),
                           jnp.float32(k))):
        stats = jnp.where(lane8 == j, v, stats)
    stats_ref[...] = stats


def _emit(x_ref, t_key, quota, out_vals_ref, out_idx_ref):
    """P4: compact, in index order, every entry whose key exceeds t_key
    plus the first `quota` entries equal to it into the (OR, 128) output
    tiles."""
    r_rows, c = x_ref.shape
    n_out = out_idx_ref.shape[0]
    span = -(-c // LANES) + 1            # output rows one source row can hit
    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    upper = (ii < jj).astype(jnp.bfloat16)     # exclusive-prefix operator
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (LANES, c), 0)
    prow = jax.lax.broadcasted_iota(jnp.int32, (_PAYLOAD_ROWS, c), 0)

    out_idx_ref[...] = jnp.zeros(out_idx_ref.shape, jnp.int32)
    out_vals_ref[...] = jnp.zeros(out_vals_ref.shape, jnp.float32)

    def prefix(mask):
        return jnp.dot(mask.astype(jnp.bfloat16), upper,
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    def row_body(r, carry):
        base, eq_seen = carry
        xr = x_ref[pl.ds(r, 1), :]
        bits = jax.lax.bitcast_convert_type(xr, jnp.int32)
        kr = _key(bits)
        eq = kr == t_key
        rank = eq_seen + prefix(eq) + 1                 # 1-based tie rank
        sel = (kr > t_key) | (eq & (rank <= quota))
        cnt = jnp.sum(sel.astype(jnp.int32))

        @pl.when(cnt > 0)
        def _():
            pos = base + prefix(sel)
            gidx = r * c + lane
            parts = (gidx, gidx >> 8, gidx >> 16,
                     bits, bits >> 8, bits >> 16, bits >> 24)
            payload = jnp.zeros((_PAYLOAD_ROWS, c), jnp.int32)
            for j, part in enumerate(parts):
                payload = jnp.where(prow == j, part & 0xFF, payload)
            payload = payload.astype(jnp.float32).astype(jnp.bfloat16)
            h0 = base // LANES
            for t in range(span):
                h = h0 + t

                @pl.when(h < n_out)
                def _():
                    hit = sel & (pos // LANES == h)
                    onehot = ((slot == pos % LANES) & hit).astype(jnp.bfloat16)
                    routed = jax.lax.dot_general(
                        payload, onehot, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32
                    ).astype(jnp.int32)                          # (8, 128)
                    idx = (routed[0:1] | (routed[1:2] << 8)
                           | (routed[2:3] << 16))
                    vbits = (routed[3:4] | (routed[4:5] << 8)
                             | (routed[5:6] << 16) | (routed[6:7] << 24))
                    row = pl.ds(h, 1)
                    out_idx_ref[row, :] = out_idx_ref[row, :] | idx
                    old = jax.lax.bitcast_convert_type(out_vals_ref[row, :],
                                                       jnp.int32)
                    out_vals_ref[row, :] = jax.lax.bitcast_convert_type(
                        old | vbits, jnp.float32)
        return base + cnt, eq_seen + jnp.sum(eq.astype(jnp.int32))

    jax.lax.fori_loop(0, r_rows, row_body, (jnp.int32(0), jnp.int32(0)))


# ---- shared pallas_call plumbing of every GVR kernel ----------------------

def gvr_params(k: int, n: int, max_candidates: Optional[int],
               f_target: Optional[int]):
    """(C_max, secant target count)."""
    cmax = max_candidates if max_candidates is not None else min(3 * k, n)
    cmax = max(cmax, k)
    ft = f_target if f_target is not None else (k + cmax) // 2
    return cmax, ft


def prev_spec(m: int, index_map):
    """The previous Top-K row, in SMEM: P1 reads it one scalar at a time."""
    return pl.BlockSpec((None, 1, m), index_map, memory_space=pltpu.SMEM)


def topk_out_specs(k: int, index_map):
    """(OR, 128) value + index tiles and the (1, 8) stats row of one grid
    row; `index_map` returns (row, 0, 0)."""
    orows = out_rows(k)
    return (pl.BlockSpec((None, orows, LANES), index_map),
            pl.BlockSpec((None, orows, LANES), index_map),
            pl.BlockSpec((None, 1, 8), index_map))


def topk_out_shapes(rows: int, k: int):
    orows = out_rows(k)
    return (jax.ShapeDtypeStruct((rows, orows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, orows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, 1, 8), jnp.float32))


def unpack_topk(vals, idx, stats, k: int):
    """(rows, OR, 128) tiles -> (rows, K) values and indices, (rows, 8)."""
    rows = vals.shape[0]
    return (vals.reshape(rows, -1)[:, :k], idx.reshape(rows, -1)[:, :k],
            stats[:, 0])


def _gvr_kernel(prev_ref, scores_ref, out_vals_ref, out_idx_ref, stats_ref,
                **kw):
    gvr_on_resident_tile(scores_ref, prev_ref, out_vals_ref, out_idx_ref,
                         stats_ref, **kw)


def gvr_topk_pallas(scores: jnp.ndarray, prev_idx: jnp.ndarray, k: int,
                    *, max_candidates: Optional[int] = None,
                    max_secant_iters: int = 12,
                    f_target: Optional[int] = None,
                    interpret: bool = False):
    """pl.pallas_call wrapper. scores: (B, N) f32, N a multiple of 128
    (ops.py pads with -FLT_MAX); prev_idx: (B, M) int32.

    Returns (values (B,K) f32, indices (B,K) i32, stats (B,8) f32).
    """
    b, n = scores.shape
    m = prev_idx.shape[-1]
    assert n % LANES == 0, n
    cmax, ft = gvr_params(k, n, max_candidates, f_target)
    kern = functools.partial(_gvr_kernel, k=k, cmax=cmax, n=n, m=m,
                             max_secant=max_secant_iters, f_target=ft)
    row = lambda i: (i, 0, 0)
    out = pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[prev_spec(m, row),
                  pl.BlockSpec((None, n // LANES, LANES), row)],
        out_specs=topk_out_specs(k, row),
        out_shape=topk_out_shapes(b, k),
        interpret=interpret,
    )(prev_idx.astype(jnp.int32)[:, None],
      scores.astype(jnp.float32).reshape(b, n // LANES, LANES))
    return unpack_topk(*out, k)
