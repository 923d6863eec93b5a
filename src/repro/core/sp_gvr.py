"""SP-GVR: sequence-parallel Guess-Verify-Refine exact Top-K (beyond paper).

At 100K–500K context the KV cache (and therefore the indexer score row) is
sharded across the mesh's sequence/data axis. A distribution-agnostic Top-K
would all-gather the score row (N·4B per device per step — 2 MB at N=512K)
or run a multi-round distributed radix select (R rounds × 2^d-entry histogram
all-reduces). GVR's threshold search is precisely the part of Top-K that
distributes with O(1)-sized collectives:

  Phase 1   : local stats over the shard-resident slice of the prediction
              set → 4-scalar all-reduce (sum/count/min/max).
  Phase 2   : each secant iteration = local count + 1 scalar psum. I ≈ 1–2
              on decode workloads (temporal correlation), so the *collective
              schedule length* — not just traffic — is data-aware.
  Phase 4a/b: histogram narrowing = psum over `nbins` int32 lanes (8 KB at
              2048 bins — still ~256x smaller than a 512K-row gather).
  Phase 4d  : each snap iteration = 4-scalar all-reduce (counts + pmin/pmax
              of the snap candidates).
  Extract   : fully local. Each device keeps the selected indices that fall
              in its own shard (plus a deterministic shard-ordered tie
              quota); downstream sparse attention gathers *locally* and
              combines partial attention with a (d_model+1)-wide psum —
              the score row is never materialized globally.

Everything is exact: the threshold/count state is replicated lockstep across
devices (same psum results → same control decisions), so the selected set is
the unique deterministic exact Top-K with lowest-global-index tie policy.

Usage: call `sp_gvr_topk_local` INSIDE a shard_map whose `axis_name` shards
the score row's last dimension. Helpers at the bottom wrap a full shard_map
for convenience/testing.

Speculative verify ticks (DESIGN.md §spec-decode) run this schedule once
per draft position with the PREVIOUS POSITION's selection as `prev_idx`
(the causally-extended temporal prior): intra-tick correlation is at least
the inter-tick correlation the paper measures, so Phase 2's data-aware
iteration count — and with it the collective schedule length — carries
over to multi-token steps unchanged.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .gvr import DEFAULT_K, DEFAULT_MAX_SECANT, DEFAULT_MAX_SNAP


class SPGVRResult(NamedTuple):
    local_indices: jnp.ndarray   # (B, K) int32 — GLOBAL indices owned by this
                                 # shard, padded with -1 past local_count
    local_count: jnp.ndarray     # (B,) int32 — valid entries per row
    threshold: jnp.ndarray       # (B,) float32 — exact global K-th value
    n_gt: jnp.ndarray            # (B,) int32 — global count > threshold
    secant_iters: jnp.ndarray    # (B,) int32
    snap_iters: jnp.ndarray      # (B,) int32
    hist_levels: jnp.ndarray     # (B,) int32


def _pax(v, axis_name):
    return jax.lax.psum(v, axis_name)


def sp_gvr_topk_local(scores_local: jnp.ndarray, prev_idx: jnp.ndarray, k: int,
                      axis_name: str, *,
                      max_candidates: Optional[int] = None,
                      max_secant_iters: int = DEFAULT_MAX_SECANT,
                      max_snap_iters: int = DEFAULT_MAX_SNAP,
                      hist_bins: int = 2048,
                      max_hist_levels: int = 10,
                      f_target: Optional[int] = None) -> SPGVRResult:
    """Exact distributed Top-K over a score row sharded along `axis_name`.

    scores_local: (B, N_local) — this device's contiguous shard.
    prev_idx:     (B, M) int32 — GLOBAL indices (replicated across shards).
    """
    b, n_local = scores_local.shape
    x = scores_local.astype(jnp.float32)
    d = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    n = n_local * d
    offset = (my * n_local).astype(jnp.int32)
    cmax = max_candidates if max_candidates is not None else min(3 * k, n)
    cmax = max(cmax, k)
    ftarget = jnp.float32(f_target if f_target is not None else (k + cmax) // 2)
    m = prev_idx.shape[-1]
    fmax = jnp.finfo(jnp.float32).max

    # ---- Phase 1: distributed pre-indexed statistics (4-scalar psum) ----
    rel = prev_idx.astype(jnp.int32) - offset
    in_shard = (rel >= 0) & (rel < n_local)
    rel_safe = jnp.clip(rel, 0, n_local - 1)
    pv = jnp.take_along_axis(x, rel_safe, axis=-1)
    psum_v = _pax(jnp.sum(jnp.where(in_shard, pv, 0.0), -1), axis_name)
    pcnt = _pax(jnp.sum(in_shard, -1).astype(jnp.float32), axis_name)
    p_lo = -_pax_max(jnp.max(jnp.where(in_shard, -pv, -fmax), -1), axis_name)
    p_hi = _pax_max(jnp.max(jnp.where(in_shard, pv, -fmax), -1), axis_name)
    t0 = psum_v / jnp.maximum(pcnt, 1.0)

    row_min = -_pax_max(jnp.max(-x, -1), axis_name)
    row_max = _pax_max(jnp.max(x, -1), axis_name)
    if m < k:
        p_lo, p_hi = jnp.minimum(p_lo, row_min), jnp.maximum(p_hi, row_max)

    def gcount(t):
        """Distributed f(T): local count + scalar psum (THE collective)."""
        return _pax(jnp.sum(x >= t[:, None], -1, dtype=jnp.int32), axis_name)

    # ---- Phase 2: secant with scalar-collective counts ----
    state = dict(
        t_lo=p_lo, c_lo=jnp.full((b,), float(min(n, max(1.25 * m, k))), jnp.float32),
        t_hi=jnp.maximum(p_hi, p_lo), c_hi=jnp.ones((b,), jnp.float32),
        t=jnp.clip(t0, p_lo, p_hi), t_probe=jnp.clip(t0, p_lo, p_hi),
        cnt=jnp.zeros((b,), jnp.int32),
        hi_probed=jnp.zeros((b,), bool), prev_over=jnp.zeros((b,), bool),
        done=jnp.zeros((b,), bool), it=jnp.zeros((b,), jnp.int32),
    )

    def cond2(s):
        return jnp.any(~s["done"] & (s["it"] < max_secant_iters))

    def body2(s):
        active = ~s["done"] & (s["it"] < max_secant_iters)
        n_ge = gcount(s["t"])
        in_window = (n_ge >= k) & (n_ge <= cmax)
        done = s["done"] | (active & in_window)
        too_many = active & (n_ge > cmax)
        too_few = active & (n_ge < k)
        t_lo = jnp.where(too_many, s["t"], s["t_lo"])
        c_lo = jnp.where(too_many, n_ge.astype(jnp.float32), s["c_lo"])
        t_hi = jnp.where(too_few, s["t"], s["t_hi"])
        c_hi = jnp.where(too_few, n_ge.astype(jnp.float32), s["c_hi"])
        denom = c_lo - c_hi
        frac = jnp.where(jnp.abs(denom) > 0, (c_lo - ftarget) / denom, jnp.float32(0.5))
        frac = jnp.where(s["it"] == 0, jnp.minimum(frac, 0.5), frac)
        t_new = t_lo + frac * (t_hi - t_lo)
        inside = (t_new > t_lo) & (t_new < t_hi) & jnp.isfinite(t_new)
        t_new = jnp.where(inside, t_new, 0.5 * (t_lo + t_hi))
        probe_lo = (frac <= 0) & (t_lo != s["t"])
        t_new = jnp.where(probe_lo, t_lo, t_new)
        probe_hi = too_many & s["prev_over"] & ~s["hi_probed"] & (t_hi != s["t"])
        t_new = jnp.where(probe_hi, t_hi, t_new)
        collapsed = ~((t_new > t_lo) & (t_new < t_hi)) & ~probe_lo & ~probe_hi
        rescue_hi = collapsed & too_many & (row_max > t_hi)
        t_hi = jnp.where(rescue_hi, row_max, t_hi)
        c_hi = jnp.where(rescue_hi, jnp.ones_like(c_hi), c_hi)
        rescue_lo = collapsed & too_few & (row_min < t_lo)
        t_lo = jnp.where(rescue_lo, row_min, t_lo)
        c_lo = jnp.where(rescue_lo, jnp.full_like(c_lo, float(n)), c_lo)
        rescued = rescue_hi | rescue_lo
        t_new = jnp.where(rescued, 0.5 * (t_lo + t_hi), t_new)
        collapsed = collapsed & ~rescued
        t_new = jnp.where(collapsed, t_lo, t_new)
        done = done | (active & collapsed)
        return dict(
            t_lo=t_lo, c_lo=c_lo, t_hi=t_hi, c_hi=c_hi,
            t=jnp.where(active & ~done, t_new, s["t"]),
            t_probe=jnp.where(active, s["t"], s["t_probe"]),
            cnt=jnp.where(active, n_ge, s["cnt"]),
            hi_probed=jnp.where(rescue_hi, False, s["hi_probed"] | probe_hi),
            prev_over=jnp.where(active, too_many, s["prev_over"]),
            done=done, it=jnp.where(active, s["it"] + 1, s["it"]),
        )

    st2 = jax.lax.while_loop(cond2, body2, state)
    secant_iters = st2["it"]
    t_exit = jnp.where(st2["cnt"] >= k, st2["t_probe"], st2["t_lo"])

    # ---- Phase 4a/b: distributed histogram narrowing (nbins-wide psum) ----
    n_ge0 = gcount(t_exit)
    lo = jnp.where(n_ge0 >= k, t_exit, row_min)
    hi = row_max
    hstate = dict(lo=lo, hi=hi, done=jnp.zeros((b,), bool), it=jnp.zeros((b,), jnp.int32))

    def condh(s):
        return jnp.any(~s["done"] & (s["it"] < max_hist_levels))

    def bodyh(s):
        active = ~s["done"] & (s["it"] < max_hist_levels)
        lo, hi = s["lo"], s["hi"]
        width = (hi - lo) / hist_bins
        degenerate = ~(width > 0) | ~jnp.isfinite(width)
        safe_w = jnp.where(degenerate, 1.0, width)
        mask = x >= lo[:, None]
        bin_idx = jnp.clip(((x - lo[:, None]) / safe_w[:, None]).astype(jnp.int32),
                           0, hist_bins - 1)
        hist_local = jax.vmap(
            lambda bi, mk: jax.ops.segment_sum(mk.astype(jnp.int32), bi,
                                               num_segments=hist_bins)
        )(bin_idx, mask)
        hist = _pax(hist_local, axis_name)
        ctop = jnp.cumsum(hist[:, ::-1], axis=-1)[:, ::-1]
        jstar = jnp.maximum(jnp.sum((ctop >= k).astype(jnp.int32), -1) - 1, 0)
        new_lo = lo + jstar.astype(jnp.float32) * width
        new_hi = jnp.minimum(hi, lo + (jstar + 1).astype(jnp.float32) * width)
        in_bin = jnp.take_along_axis(hist, jstar[:, None], -1)[:, 0]
        done_now = degenerate | (in_bin <= 8) | (new_hi <= new_lo)
        return dict(
            lo=jnp.where(active & ~degenerate, new_lo, lo),
            hi=jnp.where(active & ~degenerate, new_hi, hi),
            done=s["done"] | (active & done_now),
            it=jnp.where(active, s["it"] + 1, s["it"]),
        )

    sth = jax.lax.while_loop(condh, bodyh, hstate)
    hist_levels = sth["it"]

    # ---- Phase 4d: distributed snap (4-scalar all-reduce per iteration) ----
    sstate = dict(t=sth["lo"], n_ge=jnp.zeros((b,), jnp.int32),
                  n_gt=jnp.zeros((b,), jnp.int32),
                  done=jnp.zeros((b,), bool), it=jnp.zeros((b,), jnp.int32))

    def conds(s):
        return jnp.any(~s["done"] & (s["it"] < max_snap_iters))

    def bodys(s):
        active = ~s["done"] & (s["it"] < max_snap_iters)
        tb = s["t"][:, None]
        ge, gt = x >= tb, x > tb
        n_ge = _pax(ge.sum(-1, dtype=jnp.int32), axis_name)
        n_gt = _pax(gt.sum(-1, dtype=jnp.int32), axis_name)
        up_l = jnp.min(jnp.where(gt, x, fmax), -1)
        dn_l = jnp.max(jnp.where(~ge, x, -fmax), -1)
        snap_up = -_pax_max(-up_l, axis_name)
        snap_dn = _pax_max(dn_l, axis_name)
        converged = (n_gt < k) & (n_ge >= k)
        t_next = jnp.where(n_gt >= k, snap_up, jnp.where(n_ge < k, snap_dn, s["t"]))
        return dict(
            t=jnp.where(active & ~converged, t_next, s["t"]),
            n_ge=jnp.where(active, n_ge, s["n_ge"]),
            n_gt=jnp.where(active, n_gt, s["n_gt"]),
            done=s["done"] | (active & converged),
            it=jnp.where(active & ~converged, s["it"] + 1, s["it"]),
        )

    sts = jax.lax.while_loop(conds, bodys, sstate)
    # Safety net: distributed exact K-th via local top-k + gathered merge of
    # k candidates (k·4B gather — still no full-row gather). Rare (flagged).
    fb = ~sts["done"]
    kk = min(k, n_local)
    loc_top = jax.lax.top_k(x, kk)[0]
    all_top = jax.lax.all_gather(loc_top, axis_name, axis=-1, tiled=True)
    kth = jax.lax.top_k(all_top, k)[0][:, -1]
    t_star = jnp.where(fb, kth, sts["t"])
    tb = t_star[:, None]
    n_gt = _pax(jnp.sum(x > tb, -1, dtype=jnp.int32), axis_name)

    # ---- Extraction: fully local, deterministic shard-ordered tie quota ----
    gt = x > tb
    eq = x == tb
    my_gt = gt.sum(-1, dtype=jnp.int32)
    my_eq = eq.sum(-1, dtype=jnp.int32)
    # exclusive prefix of tie counts across shards (D-scalar all-gather)
    eq_all = jax.lax.all_gather(my_eq, axis_name, axis=0)          # (D, B)
    eq_prefix = jnp.cumsum(eq_all, axis=0) - eq_all                # exclusive
    my_eq_prefix = eq_prefix[my]
    tie_budget = jnp.maximum(k - n_gt, 0)
    my_quota = jnp.clip(tie_budget - my_eq_prefix, 0, my_eq)
    my_count = my_gt + my_quota
    # local rank-key top-k: all gt first, then eq, lowest index first
    key = gt.astype(jnp.int32) * 2 + eq.astype(jnp.int32)
    _, lidx = jax.lax.top_k(key, kk)
    take = jnp.arange(kk, dtype=jnp.int32)[None, :] < my_count[:, None]
    gidx = jnp.where(take, lidx.astype(jnp.int32) + offset, -1)
    if kk < k:  # pad to fixed (B, K) contract
        gidx = jnp.pad(gidx, ((0, 0), (0, k - kk)), constant_values=-1)

    return SPGVRResult(local_indices=gidx, local_count=my_count,
                       threshold=t_star, n_gt=n_gt,
                       secant_iters=secant_iters, snap_iters=sts["it"],
                       hist_levels=hist_levels)


def _pax_max(v, axis_name):
    return jax.lax.pmax(v, axis_name)


def sp_canonical_topk(local_indices: jnp.ndarray, k: int, n: int,
                      axis_name: str) -> jnp.ndarray:
    """Assemble the replicated global Top-K buffer from per-shard results,
    in the single-device canonical order (ascending global index — the
    order `core.gvr.extract_topk`'s prefix-sum compaction emits).

    `local_indices` is `SPGVRResult.local_indices` ((B, K), -1-padded past
    the shard's own count). Cost: one K-int all-gather (K·D·4B — O(1) in
    context length). Because SP-GVR's shard-ordered tie quota implements
    the same lowest-global-index tie policy as the single-device selector
    paths, the returned (B, K) buffer is *bit-identical* to what
    `sparse.selector.select_topk` would emit for the unsharded score row —
    which is what lets a sequence-sharded serving step carry the same
    prev-Top-K feedback (and downstream attention bits) as the fused
    single-device step.
    """
    all_idx = jax.lax.all_gather(local_indices, axis_name, axis=1,
                                 tiled=True)                   # (B, D*K)
    # -1 pads sort past every valid index (valid < n); exactly K survive
    keyed = jnp.where(all_idx < 0, jnp.int32(n), all_idx)
    return jnp.sort(keyed, axis=-1)[:, :k].astype(jnp.int32)


def sp_gvr_topk(scores: jnp.ndarray, prev_idx: jnp.ndarray, k: int, mesh,
                axis_name: str = "data", **kw):
    """Convenience wrapper: shard scores over `axis_name`, run SP-GVR, and
    all-gather the per-shard index buffers into the exact global Top-K set
    (testing / non-sequence-sharded consumers)."""
    def fn(xs, pi):
        r = sp_gvr_topk_local(xs, pi, k, axis_name, **kw)
        return r.local_indices, r.local_count, r.threshold, r.secant_iters

    fn_sm = jax.shard_map(fn, mesh=mesh,
                          in_specs=(P(None, axis_name), P(None, None)),
                          out_specs=(P(axis_name, None), P(axis_name), P(axis_name),
                                     P(axis_name)),
                          check_vma=False)
    # stack per-shard outputs along a leading axis
    b = scores.shape[0]
    d = mesh.shape[axis_name]
    idx_sh, counts, thr, iters = fn_sm(scores, prev_idx)
    idx_sh = idx_sh.reshape(d, b, k)
    counts = counts.reshape(d, b)
    # compact: per row, concatenate valid entries shard by shard
    def compact(row_idx, row_cnt):
        flat = row_idx.reshape(-1)
        valid = flat >= 0
        order = jnp.argsort(~valid, stable=True)      # valid entries first
        return flat[order][:k]
    out = jax.vmap(compact, in_axes=(1, 1))(idx_sh, counts)
    return out, thr.reshape(d, b)[0], iters.reshape(d, b)[0]
