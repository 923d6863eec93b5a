"""Guess-Verify-Refine (GVR) exact Top-K — pure-JAX batched implementation.

The paper's four phases (§4.2), expressed functionally and jittable:

  Phase 1 (Guess/stats)   : gather the previous step's Top-K values; their
                            min/mean/max seed a threshold bracket.
  Phase 2 (Guess/secant)  : secant-interpolated threshold search for T with
                            K <= f(T) <= C, where f(T) = |{i : x_i >= T}|
                            (monotone non-increasing step function). Each
                            iteration costs one fused row sweep.
  Phase 3 (Verify)        : candidate collection. In this pure-JAX layer the
                            candidate set stays implicit (a mask), as in the
                            Pallas kernel (kernels/gvr_topk.py), which
                            refines over its VMEM-resident row.
  Phase 4 (Refine/snap)   : step the threshold through distinct data values
                            (fused count_ge/count_gt/snap_up/snap_down per
                            sweep) until n_gt(T) < K <= n_ge(T) — T is then
                            the exact K-th largest value (Lemma 1 containment
                            + tie partition gives the exact Top-K set).

Exactness is unconditional: if phase 2/4 iteration budgets are exhausted the
implementation falls back to a direct exact selection and flags it (the
paper's `done=2` safety net, which "never triggers" on real decode data); the
fallback affects modeled cost only, never output correctness.

Tie policy: lowest index first (deterministic; the paper's kernel is
non-deterministic on ties — ours is strictly stronger).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

# Finite sentinel for masked-out (beyond-length) elements. Using -FLT_MAX
# (not -inf) keeps secant/bisection arithmetic finite.
NEG_SENTINEL = jnp.float32(-3.4028235e38)

DEFAULT_K = 2048               # DSA Top-K size
DEFAULT_CAND_FACTOR = 3        # MAX_CANDIDATES = 3*K = 6144 (paper §5.3)
DEFAULT_MAX_SECANT = 12
DEFAULT_MAX_SNAP = 32


class GVRStats(NamedTuple):
    """Per-row phase statistics (shapes (B,))."""
    secant_iters: jnp.ndarray   # int32 — I in the paper
    hist_levels: jnp.ndarray    # int32 — phase-4b histogram narrowing levels
    snap_iters: jnp.ndarray     # int32 — S in the paper
    threshold: jnp.ndarray      # float32 — exact K-th largest value T*
    n_gt: jnp.ndarray           # int32 — |{x > T*}|  (< K)
    n_ge: jnp.ndarray           # int32 — |{x >= T*}| (>= K)
    cand_count: jnp.ndarray     # int32 — f(T) at phase-2 exit (buffer fill)
    fallback: jnp.ndarray       # bool  — safety-net path taken
    t0: jnp.ndarray             # float32 — initial guess (pmean)


class GVRResult(NamedTuple):
    values: jnp.ndarray         # (B, K) float32 — the Top-K values
    indices: jnp.ndarray        # (B, K) int32  — their positions
    stats: GVRStats


def _masked(scores: jnp.ndarray, lengths: Optional[jnp.ndarray]) -> jnp.ndarray:
    if lengths is None:
        return scores
    n = scores.shape[-1]
    pos = jnp.arange(n, dtype=jnp.int32)
    return jnp.where(pos[None, :] < lengths[:, None], scores, NEG_SENTINEL)


def _fused_pass(x: jnp.ndarray, t: jnp.ndarray):
    """One logical row sweep: (n_ge, n_gt, snap_up, snap_down).

    Mirrors the kernel's fused snap iteration (§4.2.4): all four reductions
    come out of a single traversal of the row.
    """
    tb = t[:, None]
    ge = x >= tb
    gt = x > tb
    n_ge = ge.sum(axis=-1, dtype=jnp.int32)
    n_gt = gt.sum(axis=-1, dtype=jnp.int32)
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    snap_up = jnp.min(jnp.where(gt, x, big), axis=-1)          # min{x : x > T}
    snap_dn = jnp.max(jnp.where(~ge, x, -big), axis=-1)        # max{x : x < T}
    return n_ge, n_gt, snap_up, snap_dn


def _phase1_stats(x: jnp.ndarray, prev_idx: jnp.ndarray):
    """Pre-indexed statistics over the prediction set (paper Eq. 4)."""
    pvals = jnp.take_along_axis(x, prev_idx.astype(jnp.int32), axis=-1)
    return pvals.min(axis=-1), pvals.max(axis=-1), pvals.mean(axis=-1)


def _phase2_secant(x, t0, p_lo, p_hi, k, cmax, f_target, max_iters, m):
    """Secant threshold search (paper §4.2.2, Fig. 6).

    Bracket invariant: f(t_lo) >= k is (heuristically) believed, f(t_hi) may
    undershoot; real evaluated counts replace the nominal anchors as soon as
    a point is probed. Bisection guards non-finite / out-of-bracket secant
    steps; the first iteration damps the step fraction to <= 0.5. The true
    row min/max ride along in the first sweep (free fused reductions) so a
    collapsed bracket can be *rescued* once per side when the prediction set
    failed to bracket the K-th value (duplicated / stale predictions).
    """
    b, n = x.shape
    ftarget = jnp.float32(f_target)
    fmax = jnp.finfo(jnp.float32).max

    state = dict(
        # Nominal anchors: f(pmin) >= |P| (every predicted value >= pmin), so
        # for |P| >= k the low anchor is valid; its count is seeded at 1.25|P|
        # (exact when the prediction is perfect, mild slack otherwise) rather
        # than N, which would flatten the first secant slopes; c_hi=1 is the
        # optimistic top anchor. Real evaluated counts replace both.
        t_lo=p_lo, c_lo=jnp.full((b,), float(min(n, max(1.25 * m, k))), jnp.float32),
        t_hi=jnp.maximum(p_hi, p_lo), c_hi=jnp.ones((b,), jnp.float32),
        t=jnp.clip(t0, p_lo, p_hi),                 # next probe location
        t_probe=jnp.clip(t0, p_lo, p_hi),           # last probed location
        cnt=jnp.zeros((b,), jnp.int32),             # count at t_probe
        row_min=jnp.full((b,), fmax), row_max=jnp.full((b,), -fmax),
        hi_probed=jnp.zeros((b,), bool), prev_over=jnp.zeros((b,), bool),
        done=jnp.zeros((b,), bool), it=jnp.zeros((b,), jnp.int32),
    )

    def cond_fn(s):
        return jnp.any(~s["done"] & (s["it"] < max_iters))

    def body(s):
        active = ~s["done"] & (s["it"] < max_iters)
        n_ge, _n_gt, _up, _dn = _fused_pass(x, s["t"])
        row_max = jnp.maximum(s["row_max"], jnp.max(x, axis=-1))
        row_min = jnp.minimum(s["row_min"], jnp.min(x, axis=-1))
        in_window = (n_ge >= k) & (n_ge <= cmax)
        done = s["done"] | (active & in_window)

        too_many = active & (n_ge > cmax)       # T too low — raise
        too_few = active & (n_ge < k)           # T too high — lower
        t_lo = jnp.where(too_many, s["t"], s["t_lo"])
        c_lo = jnp.where(too_many, n_ge.astype(jnp.float32), s["c_lo"])
        t_hi = jnp.where(too_few, s["t"], s["t_hi"])
        c_hi = jnp.where(too_few, n_ge.astype(jnp.float32), s["c_hi"])

        denom = c_lo - c_hi
        frac = jnp.where(jnp.abs(denom) > 0, (c_lo - ftarget) / denom, jnp.float32(0.5))
        frac = jnp.where(s["it"] == 0, jnp.minimum(frac, 0.5), frac)   # damping
        t_new = t_lo + frac * (t_hi - t_lo)
        inside = (t_new > t_lo) & (t_new < t_hi) & jnp.isfinite(t_new)
        t_new = jnp.where(inside, t_new, 0.5 * (t_lo + t_hi))          # bisection
        # Anchor probes. frac <= 0 means the target count lies at/below the
        # *nominal* low anchor (only possible while c_lo is unprobed — e.g. a
        # perfect prediction, where T* == pmin exactly): probe t_lo itself.
        probe_lo = (frac <= 0) & (t_lo != s["t"])    # don't re-probe same point
        t_new = jnp.where(probe_lo, t_lo, t_new)
        # Two consecutive overshoots against an unprobed high anchor: the
        # believed bracket top (pmax) is likely below T* — probe it so the
        # rescue can re-anchor at the true row max next iteration.
        probe_hi = too_many & s["prev_over"] & ~s["hi_probed"] & (t_hi != s["t"])
        t_new = jnp.where(probe_hi, t_hi, t_new)
        collapsed = ~((t_new > t_lo) & (t_new < t_hi)) & ~probe_lo & ~probe_hi

        # Bracket rescue (once per side): the prediction-derived bracket did
        # not contain a valid threshold — fall back to the true row extrema.
        # (a collapse against an already-probed high anchor counts too)
        rescue_hi = collapsed & too_many & (row_max > t_hi)
        t_hi = jnp.where(rescue_hi, row_max, t_hi)
        c_hi = jnp.where(rescue_hi, jnp.ones_like(c_hi), c_hi)
        rescue_lo = collapsed & too_few & (row_min < t_lo)
        t_lo = jnp.where(rescue_lo, row_min, t_lo)
        c_lo = jnp.where(rescue_lo, jnp.full_like(c_lo, float(n)), c_lo)
        rescued = rescue_hi | rescue_lo
        t_new = jnp.where(rescued, 0.5 * (t_lo + t_hi), t_new)
        collapsed = collapsed & ~rescued

        # Float-precision floor: genuinely collapsed — park at t_lo (count
        # >= k there, up to anchor nominality) and let snap/fallback finish.
        t_new = jnp.where(collapsed, t_lo, t_new)
        done = done | (active & collapsed)

        return dict(
            t_lo=t_lo, c_lo=c_lo, t_hi=t_hi, c_hi=c_hi,
            t=jnp.where(active & ~done, t_new, s["t"]),
            t_probe=jnp.where(active, s["t"], s["t_probe"]),
            cnt=jnp.where(active, n_ge, s["cnt"]),
            row_min=row_min, row_max=row_max,
            hi_probed=jnp.where(rescue_hi, False, s["hi_probed"] | probe_hi),
            prev_over=jnp.where(active, too_many, s["prev_over"]),
            done=done,
            it=jnp.where(active, s["it"] + 1, s["it"]),
        )

    state = jax.lax.while_loop(cond_fn, body, state)
    # Start snap from the last probed point if it still covers K, else from
    # the low bracket end (believed count >= k). Snap repairs either way.
    t_exit = jnp.where(state["cnt"] >= k, state["t_probe"], state["t_lo"])
    window_ok = (state["cnt"] >= k) & (state["cnt"] <= cmax)
    return t_exit, state["cnt"], state["it"], window_ok


def _phase4_histogram(x, t_init, k, nbins, max_levels):
    """Phase 4a/4b: histogram narrowing to the K-th bin (paper Fig. 7).

    Repeatedly bins the candidates {x >= lo} over [lo, hi] into `nbins`
    uniform bins, finds the bin containing the K-th largest (cumulative
    count from the top), and narrows [lo, hi] to that bin. Invariant:
    n_ge(lo) >= k. In the kernel this is SMEM-only work over the candidate
    buffer; here the candidate set stays implicit.
    """
    b, n = x.shape
    fmax = jnp.finfo(jnp.float32).max
    row_min = jnp.min(x, axis=-1)
    row_max = jnp.max(x, axis=-1)

    # Establish the invariant: if the phase-2 exit point undercounts
    # (nominal-anchor lie), rescue to the row min where n_ge = N >= k.
    n_ge0 = (x >= t_init[:, None]).sum(-1, dtype=jnp.int32)
    lo = jnp.where(n_ge0 >= k, t_init, row_min)
    hi = row_max

    state = dict(lo=lo, hi=hi, done=jnp.zeros((b,), bool), it=jnp.zeros((b,), jnp.int32))

    def cond_fn(s):
        return jnp.any(~s["done"] & (s["it"] < max_levels))

    def body(s):
        active = ~s["done"] & (s["it"] < max_levels)
        lo, hi = s["lo"], s["hi"]
        width = (hi - lo) / nbins
        degenerate = ~(width > 0) | ~jnp.isfinite(width)
        safe_w = jnp.where(degenerate, 1.0, width)
        mask = x >= lo[:, None]
        bin_idx = jnp.clip(((x - lo[:, None]) / safe_w[:, None]).astype(jnp.int32), 0, nbins - 1)
        hist = jax.vmap(
            lambda bi, m: jax.ops.segment_sum(m.astype(jnp.int32), bi, num_segments=nbins)
        )(bin_idx, mask)
        ctop = jnp.cumsum(hist[:, ::-1], axis=-1)[:, ::-1]      # count in bins >= j
        jstar = jnp.sum((ctop >= k).astype(jnp.int32), axis=-1) - 1   # max j: ctop[j] >= k
        jstar = jnp.maximum(jstar, 0)
        new_lo = lo + jstar.astype(jnp.float32) * width
        new_hi = jnp.minimum(hi, lo + (jstar + 1).astype(jnp.float32) * width)
        in_bin = jnp.take_along_axis(hist, jstar[:, None], axis=-1)[:, 0]
        done_now = degenerate | (in_bin <= 8) | (new_hi <= new_lo)
        return dict(
            lo=jnp.where(active & ~degenerate, new_lo, lo),
            hi=jnp.where(active & ~degenerate, new_hi, hi),
            done=s["done"] | (active & done_now),
            it=jnp.where(active, s["it"] + 1, s["it"]),
        )

    state = jax.lax.while_loop(cond_fn, body, state)
    return state["lo"], state["it"]


def _phase4_snap(x, t_init, k, max_iters):
    """Snap to the exact K-th value (paper §4.2.4 step 3).

    Convergence: n_gt(T) < K <= n_ge(T). Each iteration is one fused sweep.
    """
    b = x.shape[0]
    state = dict(t=t_init, n_ge=jnp.zeros((b,), jnp.int32), n_gt=jnp.zeros((b,), jnp.int32),
                 done=jnp.zeros((b,), bool), it=jnp.zeros((b,), jnp.int32))

    def cond_fn(s):
        return jnp.any(~s["done"] & (s["it"] < max_iters))

    def body(s):
        active = ~s["done"] & (s["it"] < max_iters)
        n_ge, n_gt, snap_up, snap_dn = _fused_pass(x, s["t"])
        converged = (n_gt < k) & (n_ge >= k)
        t_next = jnp.where(n_gt >= k, snap_up, jnp.where(n_ge < k, snap_dn, s["t"]))
        return dict(
            t=jnp.where(active & ~converged, t_next, s["t"]),
            n_ge=jnp.where(active, n_ge, s["n_ge"]),
            n_gt=jnp.where(active, n_gt, s["n_gt"]),
            done=s["done"] | (active & converged),
            it=jnp.where(active & ~converged, s["it"] + 1, s["it"]),
        )

    state = jax.lax.while_loop(cond_fn, body, state)
    return state["t"], state["n_gt"], state["n_ge"], state["it"], state["done"]


@partial(jax.jit, static_argnames=("k", "max_candidates", "max_secant_iters",
                                   "max_snap_iters", "f_target", "hist_bins",
                                   "max_hist_levels"))
def gvr_threshold(scores: jnp.ndarray, prev_idx: jnp.ndarray, k: int = DEFAULT_K,
                  *, lengths: Optional[jnp.ndarray] = None,
                  max_candidates: Optional[int] = None,
                  max_secant_iters: int = DEFAULT_MAX_SECANT,
                  max_snap_iters: int = DEFAULT_MAX_SNAP,
                  f_target: Optional[int] = None,
                  hist_bins: int = 2048,
                  max_hist_levels: int = 10) -> GVRStats:
    """Phases 1+2+4: exact K-th-largest threshold without extraction.

    This is the piece SP-GVR distributes with scalar collectives — the
    threshold (plus n_gt/n_ge) fully determines the exact Top-K set.
    """
    squeeze = scores.ndim == 1
    if squeeze:
        scores, prev_idx = scores[None], prev_idx[None]
        if lengths is not None:
            lengths = lengths[None]
    x = _masked(scores.astype(jnp.float32), lengths)
    b, n = x.shape
    if k > n:
        raise ValueError(f"k={k} > n={n}")
    cmax = max_candidates if max_candidates is not None else min(DEFAULT_CAND_FACTOR * k, n)
    cmax = max(cmax, k)
    ft = f_target if f_target is not None else (k + cmax) // 2

    p_lo, p_hi, t0 = _phase1_stats(x, prev_idx)
    if prev_idx.shape[-1] < k:
        # Prediction set smaller than K: f(pmin) >= |P| no longer covers K —
        # fall back to the true row extrema for the bracket (one extra fused
        # sweep, accounted to phase 2).
        p_lo = jnp.minimum(p_lo, jnp.min(x, axis=-1))
        p_hi = jnp.maximum(p_hi, jnp.max(x, axis=-1))

    t_exit, cand_count, secant_iters, _ok = _phase2_secant(
        x, t0, p_lo, p_hi, k, cmax, ft, max_secant_iters, prev_idx.shape[-1])
    t_hist, hist_levels = _phase4_histogram(x, t_exit, k, nbins=hist_bins,
                                            max_levels=max_hist_levels)
    t_star, n_gt, n_ge, snap_iters, snap_done = _phase4_snap(x, t_hist, k, max_snap_iters)

    # Safety net (paper's done=2): exact K-th via direct selection, taken
    # only when snap exhausted its budget — lax.cond keeps the common path
    # free of the full top_k.
    fallback = ~snap_done

    def _with_fallback(_):
        kth = jax.lax.top_k(x, k)[0][:, -1]
        t2 = jnp.where(fallback, kth, t_star)
        ge2, gt2, _, _ = _fused_pass(x, t2)
        return t2, jnp.where(fallback, gt2, n_gt), jnp.where(fallback, ge2, n_ge)

    t_star, n_gt, n_ge = jax.lax.cond(
        jnp.any(fallback), _with_fallback, lambda _: (t_star, n_gt, n_ge), None)

    stats = GVRStats(secant_iters=secant_iters, hist_levels=hist_levels,
                     snap_iters=snap_iters, threshold=t_star, n_gt=n_gt, n_ge=n_ge,
                     cand_count=cand_count, fallback=fallback, t0=t0)
    if squeeze:
        stats = GVRStats(*[s[0] for s in stats])
    return stats


def extract_topk(scores: jnp.ndarray, t_star: jnp.ndarray, k: int,
                 *, lengths: Optional[jnp.ndarray] = None):
    """Exact Top-K set from the exact threshold: all x > T* plus the
    lowest-index ties x == T* (paper §4.2.4 step 4, deterministic ties).

    Implemented as mask → prefix-sum → scatter compaction (the kernel's
    Phase-5 in XLA form). Unlike a rank-key lax.top_k, every op here
    partitions along the batch dimension, so under pjit the extraction stays
    fully batch-parallel (no score-row all-gather — see EXPERIMENTS §Perf
    iteration 2).
    """
    x = _masked(scores.astype(jnp.float32), lengths)
    b, n = x.shape
    tb = t_star[..., None]
    gt = x > tb
    eq = x == tb
    eq_rank = jnp.cumsum(eq.astype(jnp.int32), axis=-1)       # inclusive
    n_gt = jnp.sum(gt, axis=-1, dtype=jnp.int32)
    quota = jnp.maximum(k - n_gt, 0)[:, None]
    sel = gt | (eq & (eq_rank <= quota))                      # exactly k/row
    pos = jnp.cumsum(sel.astype(jnp.int32), axis=-1) - 1      # target slot
    slot = jnp.where(sel & (pos < k), pos, k)                 # k = drop bucket
    col = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (b, n))
    idx = jnp.zeros((b, k + 1), jnp.int32).at[
        jnp.arange(b, dtype=jnp.int32)[:, None], slot].set(col)[:, :k]
    vals = jnp.take_along_axis(x, idx, axis=-1)
    return vals, idx


@partial(jax.jit, static_argnames=("k", "max_candidates", "max_secant_iters",
                                   "max_snap_iters", "f_target", "sort_values"))
def gvr_topk(scores: jnp.ndarray, prev_idx: jnp.ndarray, k: int = DEFAULT_K,
             *, lengths: Optional[jnp.ndarray] = None,
             max_candidates: Optional[int] = None,
             max_secant_iters: int = DEFAULT_MAX_SECANT,
             max_snap_iters: int = DEFAULT_MAX_SNAP,
             f_target: Optional[int] = None,
             sort_values: bool = False) -> GVRResult:
    """Full GVR exact Top-K. scores: (B, N) or (N,); prev_idx: (B, M) or (M,).

    Returns the exact Top-K (values, indices) — identical as a multiset of
    values to jax.lax.top_k — plus per-row phase statistics.
    """
    squeeze = scores.ndim == 1
    sb = scores if not squeeze else scores[None]
    pb = prev_idx if not squeeze else prev_idx[None]
    lb = lengths if (lengths is None or not squeeze) else lengths[None]

    stats = gvr_threshold(sb, pb, k, lengths=lb, max_candidates=max_candidates,
                          max_secant_iters=max_secant_iters,
                          max_snap_iters=max_snap_iters, f_target=f_target)
    vals, idx = extract_topk(sb, stats.threshold, k, lengths=lb)
    if sort_values:
        order = jnp.argsort(-vals, axis=-1, stable=True)
        vals = jnp.take_along_axis(vals, order, axis=-1)
        idx = jnp.take_along_axis(idx, order, axis=-1)
    if squeeze:
        return GVRResult(vals[0], idx[0], GVRStats(*[s[0] for s in stats]))
    return GVRResult(vals, idx, stats)


def uniform_pre_idx(n: int, m: int = DEFAULT_K, batch: Optional[int] = None) -> jnp.ndarray:
    """Evenly-spaced prediction set — the 'no temporal signal' warm start
    (a uniform value sample still seeds Phase 1 better than a blind radix
    decomposition; paper Table 9 row (b))."""
    idx = jnp.linspace(0, n - 1, m).astype(jnp.int32)
    if batch is not None:
        idx = jnp.broadcast_to(idx[None], (batch, m))
    return idx


def global_passes(stats: GVRStats) -> jnp.ndarray:
    """Modeled full-row global-memory passes: I + 1 (paper Table 1; the +1 is
    the collect pass — the count sub-pass is cache-eliminated §4.2.3).
    Snap passes touch only the candidate buffer (<= C), not the row."""
    return stats.secant_iters + 1
