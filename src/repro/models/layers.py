"""Shared model layers: norms, rotary variants, blockwise attention, MLP/MoE.

Everything is pjit-friendly pure JAX with scan-compatible shapes. Memory
discipline for the dry-run: train attention is blockwise (flash-style online
softmax over KV chunks) so no (S × S) logits buffer ever materializes; MoE
uses expert-parallel all_to_all via shard_map (Switch-style), so dispatch is
scatter/gather, not one-hot einsums — cost_analysis FLOPs stay 'useful'.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return y * scale.astype(x.dtype) + bias.astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings: rope / yarn / rope2d (chatglm) / mrope (qwen2vl)
# --------------------------------------------------------------------------

def _rope_freqs(dim: int, base: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_freqs(dim: int, base: float, yarn) -> jnp.ndarray:
    """YaRN's rotary frequencies (DeepSeek-V3's `precompute_freqs_cis`):
    the plain frequencies, divided by `yarn.factor` below the correction
    range that beta_fast/beta_slow set over the original context, kept
    above it, and ramped linearly between. `yarn` is a YaRNConfig."""
    def corr(rot):
        return (dim * math.log(yarn.original_max_position
                               / (rot * 2 * math.pi)) / (2 * math.log(base)))
    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    smooth = 1.0 - ramp
    freqs = _rope_freqs(dim, base)
    return freqs / yarn.factor * (1.0 - smooth) + freqs * smooth


def yarn_mscale(yarn) -> float:
    """YaRN's attention temperature m (softmax scale × m²)."""
    return 0.1 * yarn.mscale_all_dim * math.log(yarn.factor) + 1.0


def apply_rotary(x: jnp.ndarray, positions: jnp.ndarray, *, kind: str = "rope",
                 base: float = 10000.0, fraction: float = 1.0,
                 mrope_sections=(16, 24, 24),
                 freqs: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) int32 (or (B, S, 3) for mrope).
    `freqs` (rot_d/2,) replaces the plain frequencies (YaRN: yarn_freqs)."""
    d = x.shape[-1]
    rot_d = int(d * fraction) // 2 * 2
    xr, xp = x[..., :rot_d], x[..., rot_d:]

    if kind == "mrope":
        # sectioned M-RoPE: head-dim pairs are split into (temporal, h, w)
        # sections, each rotated by its own position stream. Text tokens use
        # identical streams, recovering 1-D RoPE.
        if positions.ndim == 2:
            positions = jnp.broadcast_to(positions[..., None],
                                         positions.shape + (3,))
        freqs = _rope_freqs(rot_d, base)                      # (rot_d/2,)
        sec = jnp.cumsum(jnp.asarray(mrope_sections))
        sec_id = jnp.searchsorted(sec, jnp.arange(rot_d // 2), side="right")
        pos_per_freq = jnp.take_along_axis(
            positions.astype(jnp.float32),                    # (B, S, 3)
            jnp.broadcast_to(sec_id[None, None, :],
                             positions.shape[:2] + (rot_d // 2,)).astype(jnp.int32) % 3,
            axis=-1)                                          # (B, S, rot_d/2)
        ang = pos_per_freq * freqs[None, None, :]
    else:
        if freqs is None:
            freqs = _rope_freqs(rot_d, base)
        ang = positions.astype(jnp.float32)[..., None] * freqs[None, None, :]

    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)          # (B, S, 1, rot_d/2)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    x1, x2 = xr[..., ::2], xr[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    xr = jnp.stack([r1, r2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([xr, xp], axis=-1) if rot_d < d else xr


# --------------------------------------------------------------------------
# Blockwise (flash-style) causal attention — train path
# --------------------------------------------------------------------------

def blockwise_causal_attention(q, k, v, *, scale: float,
                               q_block: int = 512, kv_block: int = 1024,
                               window: Optional[int] = None):
    """q: (B,S,H,D); k,v: (B,S,KVH,D). Online-softmax over KV blocks: no
    (S,S) buffer. GQA via head grouping. `window` = SWA width (None = full
    causal)."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qb = min(q_block, s)
    kb = min(kv_block, s)
    assert s % qb == 0 and s % kb == 0
    nq, nk = s // qb, s // kb

    q = q.reshape(b, nq, qb, kvh, g, d)
    k = k.reshape(b, nk, kb, kvh, d)
    v = v.reshape(b, nk, kb, kvh, d)

    def q_step(_, qi):
        qblk = qi["q"]                                    # (B, qb, KVH, G, D)
        q_pos = qi["pos"]                                 # (qb,)

        def kv_step(carry, kv):
            m, l, acc = carry
            kblk, vblk, k_pos = kv["k"], kv["v"], kv["pos"]
            logits = jnp.einsum("bqkgd,bskd->bqkgs", qblk.astype(jnp.float32),
                                kblk.astype(jnp.float32)) * scale
            mask = k_pos[None, None, None, None, :] <= q_pos[None, :, None, None, None]
            if window is not None:
                mask &= k_pos[None, None, None, None, :] > (
                    q_pos[None, :, None, None, None] - window)
            logits = jnp.where(mask, logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqkgs,bskd->bqkgd", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, qb, kvh, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, qb, kvh, g), jnp.float32)
        a0 = jnp.zeros((b, qb, kvh, g, d), jnp.float32)
        kv_pos = (jnp.arange(nk * kb).reshape(nk, kb))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            {"k": k.swapaxes(0, 1), "v": v.swapaxes(0, 1), "pos": kv_pos})
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out

    q_pos = jnp.arange(nq * qb).reshape(nq, qb)
    _, out = jax.lax.scan(q_step, None, {"q": q.swapaxes(0, 1), "pos": q_pos})
    # out: (nq, B, qb, KVH, G, D) -> (B, S, H, D)
    out = out.swapaxes(0, 1).reshape(b, s, kvh, g, d).reshape(b, s, h, d)
    return out.astype(jnp.float32)


def decode_attention(q, kcache, vcache, length, *, scale: float,
                     window: Optional[int] = None, rules=None):
    """One-token decode attention over a full cache (exact, non-sparse path).

    q: (B,H,D); caches: (B,N,KVH,D); length: (B,) valid prefix lengths.
    Batch-parallel core (see dsa_sparse_attention for rationale).
    """
    from repro.parallel.sharding import constrain
    q = constrain(q, rules, "batch", None, None)
    b, h, d = q.shape
    n, kvh = kcache.shape[1], kcache.shape[2]
    g = h // kvh
    logits = jnp.einsum("bkgd,bskd->bkgs",
                        q.reshape(b, kvh, g, d).astype(kcache.dtype), kcache,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(n)[None, None, None, :]
    mask = pos < length[:, None, None, None]
    if window is not None:
        mask &= pos > (length[:, None, None, None] - 1 - window)
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(vcache.dtype), vcache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, d)


def pool_read(pages, layer, *idx):
    """Rows of a page pool: `pages[idx]` of one layer's pool (P, page_size,
    ...) where `layer` is None, else `pages[layer, idx]` of a layer-stacked
    pool (L, P, page_size, ...). The layer index joins the gather's own
    indices, so the layer's pool is never sliced out of the stack."""
    return pages[idx if layer is None else (layer,) + idx]


def pool_pages(pages, layer) -> tuple:
    """(P, page_size) of a page pool read as `pool_read` reads it."""
    lead = 0 if layer is None else 1
    return pages.shape[lead:lead + 2]


def decode_attention_paged(q, k_pages, v_pages, table, length, *, scale: float,
                           window: Optional[int] = None, rules=None,
                           layer=None):
    """Fused paged form of `decode_attention` — the dense pre-DSA fallback
    without a caller-materialized logical view.

    q: (B,H,D); k/v_pages: (P, page_size, ...) global page pools whose
    rows hold KVH heads of q's width D, as (KVH, D) or flat (KVH·D) — a
    latent pool's row is one head; with `layer`, layer `layer` of
    layer-stacked pools (L, P, page_size, ...) (`pool_read`).
    table: (B, MP) int32 block table (-1 = unmapped); length: (B,).
    The logical view is built from the block table here (unmapped entries
    clip to page 0 — their positions lie at or beyond `length`, so the
    length/window mask kills them) and runs through the exact
    `decode_attention` reduction, so it is bit-identical to calling
    `decode_attention` over a caller-gathered view of the same pools. The
    Pallas hot-spot form (whole-page DMA + flash accumulation) is
    `kernels.paged_dense_decode_attn`.
    """
    from repro.parallel.sharding import constrain
    p, page_size = pool_pages(k_pages, layer)
    b, mp = table.shape
    gather = jnp.clip(table, 0, p - 1)
    view = (b, mp * page_size, -1, q.shape[-1])
    kc = pool_read(k_pages, layer, gather).reshape(view)
    vc = pool_read(v_pages, layer, gather).reshape(view)
    kc = constrain(kc, rules, "batch", None, None, None)
    vc = constrain(vc, rules, "batch", None, None, None)
    return decode_attention(q, kc, vc, length, scale=scale, window=window,
                            rules=rules)


# --------------------------------------------------------------------------
# MLP + MoE (expert-parallel all_to_all)
# --------------------------------------------------------------------------

def swiglu_mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def gelu_mlp(x, w_up, b_up, w_down, b_down):
    h = jax.nn.gelu(x @ w_up + b_up.astype(x.dtype))
    return h @ w_down + b_down.astype(x.dtype)


def moe_mlp_dense_fallback(x, router_w, w_gate, w_up, w_down, *, top_k: int):
    """Reference/smoke MoE: computes every expert densely then combines the
    top-k — O(E) compute, used only at toy sizes and as the test oracle."""
    b, s, dm = x.shape
    e = w_gate.shape[0]
    logits = x @ router_w                                 # (B, S, E)
    gates, eidx = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(gates, axis=-1)
    all_out = jnp.einsum("bsd,edf->bsef", x, w_gate)
    all_up = jnp.einsum("bsd,edf->bsef", x, w_up)
    h = jax.nn.silu(all_out) * all_up
    all_down = jnp.einsum("bsef,efd->bsed", h, w_down)    # (B, S, E, D)
    sel = jnp.take_along_axis(all_down, eidx[..., None], axis=2)  # (B, S, K, D)
    return jnp.einsum("bsk,bskd->bsd", gates.astype(sel.dtype), sel)


def moe_mlp_ep(x, router_w, w_gate, w_up, w_down, *, top_k: int,
               capacity_factor: float = 1.25,
               mesh=None, expert_axis: str = "model",
               token_axes=("pod", "data")):
    """Expert-parallel MoE FFN (Switch-style, scatter/gather dispatch).

    Inside shard_map over the full mesh: tokens arrive sharded over
    `token_axes` and are further split over `expert_axis`; each sub-shard
    routes, scatters into per-global-expert capacity buffers, all_to_all
    exchanges over `expert_axis` (THE EP collective), runs its local experts
    as batched matmuls (exact useful FLOPs), reverses the exchange, and
    combines with gate weights. Overflow beyond capacity drops (standard).

    x: (B, S, D); router_w: (D, E); w_*: (E, D, F) / (E, F, D).
    """
    if mesh is None:
        return moe_mlp_dense_fallback(x, router_w, w_gate, w_up, w_down,
                                      top_k=top_k)
    token_axes = tuple(a for a in token_axes if a in mesh.axis_names)
    e = w_gate.shape[0]
    ep = mesh.shape[expert_axis]
    assert e % ep == 0

    def body(xb, rw, wg, wu, wd):
        # xb: (b_loc, S, D) — replicated over expert_axis; take our slice of
        # tokens so routing work is divided across the EP axis.
        my = jax.lax.axis_index(expert_axis)
        bl, s, dm = xb.shape
        t = bl * s
        xt = xb.reshape(t, dm)
        # pad so the token shard divides the EP axis (decode-sized batches)
        t_pad = ((t + ep - 1) // ep) * ep
        if t_pad != t:
            xt = jnp.pad(xt, ((0, t_pad - t), (0, 0)))
        tm = t_pad // ep
        xt = jax.lax.dynamic_slice(xt, (my * tm, 0), (tm, dm))

        logits = xt @ rw                                   # (tm, E)
        gates, eidx = jax.lax.top_k(logits, top_k)
        gates = jax.nn.softmax(gates.astype(jnp.float32), axis=-1)
        a = tm * top_k
        flat_e = eidx.reshape(a)
        flat_tok = jnp.repeat(jnp.arange(tm, dtype=jnp.int32), top_k)
        flat_g = gates.reshape(a)

        cap = max(int(a / e * capacity_factor), 4)
        # rank of each assignment within its expert (stable by token order)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(e))
        rank_sorted = jnp.arange(a, dtype=jnp.int32) - seg_start[sorted_e]
        rank = jnp.zeros(a, jnp.int32).at[order].set(rank_sorted)
        keep = rank < cap
        slot = jnp.where(keep, flat_e * cap + rank, e * cap)  # drop bucket

        send = jnp.zeros((e * cap + 1, dm), xt.dtype).at[slot].set(xt[flat_tok])
        send = send[:-1].reshape(e, cap, dm)
        # EP exchange: every sub-shard sends expert-e rows to e's owner
        recv = jax.lax.all_to_all(send, expert_axis, split_axis=0,
                                  concat_axis=1, tiled=True)  # (E/ep, ep*cap, D)
        h = jnp.einsum("ecd,edf->ecf", recv, wg)
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", recv, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd)              # (E/ep, ep*cap, D)
        back = jax.lax.all_to_all(out, expert_axis, split_axis=1,
                                  concat_axis=0, tiled=True)  # (E, cap, D)
        back = back.reshape(e * cap, dm)
        back = jnp.concatenate([back, jnp.zeros((1, dm), back.dtype)], axis=0)
        gathered = back[slot] * flat_g[:, None].astype(back.dtype)
        yt = jnp.zeros((tm, dm), back.dtype).at[flat_tok].add(gathered)
        # reassemble the token shard across the EP axis
        y = jax.lax.all_gather(yt, expert_axis, axis=0, tiled=True)  # (t_pad, D)
        return y[:t].reshape(bl, s, dm)

    tok_extent = 1
    for a in token_axes:
        tok_extent *= mesh.shape[a]
    if token_axes and x.shape[0] % tok_extent == 0:
        tok_spec = P(token_axes, None, None)
    else:
        tok_spec = P(None, None, None)   # tiny decode batch: replicate tokens
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(), P(expert_axis), P(expert_axis), P(expert_axis)),
        out_specs=tok_spec, check_vma=False,
    )(x, router_w, w_gate, w_up, w_down)


def route_grouped_sigmoid(x, router_w, router_bias, *, top_k: int,
                          n_group: int, topk_group: int,
                          routed_scaling: float):
    """DeepSeek-V3's noaux_tc routing over every routed expert.

    x: (T, D); router_w: (D, E) and router_bias: (E,), float32. Scores are
    sigmoid(x W) in float32; the choice adds the bias. Each of `n_group`
    groups of E / n_group experts scores by its two largest choices, the
    `topk_group` best groups stay, and the `top_k` largest choices within
    them are the token's experts. Their gates are the unbiased scores,
    normalised to sum 1, times `routed_scaling`. The scores are float32
    at full precision: a choice near a tie must not flip on a bfloat16
    pass of the matmul. Returns (idx (T, top_k)
    int32, gates (T, top_k) float32)."""
    t = x.shape[0]
    e = router_w.shape[-1]
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                    router_w.astype(jnp.float32),
                                    precision=jax.lax.Precision.HIGHEST))
    choice = scores + router_bias.astype(jnp.float32)
    grouped = choice.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)   # (T, G)
    _, keep = jax.lax.top_k(group_score, topk_group)
    kept = jnp.any(keep[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)                                          # (T, G)
    masked = jnp.where(jnp.repeat(kept, e // n_group, axis=1), choice,
                       -jnp.inf)
    _, idx = jax.lax.top_k(masked, top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * routed_scaling
    return idx.astype(jnp.int32), gates


def held_experts_mlp(x, idx, gates, w_gate, w_up, w_down, *,
                     first_expert: int):
    """This chip's part of a routed expert layer: the experts it holds,
    [first_expert, first_expert + E_held), for every token routed to them.

    x: (T, D); idx/gates: (T, top_k) from the router over all experts;
    w_gate/w_up: (E_held, D, F), w_down: (E_held, F, D). Each held expert
    runs on every token, weighted by the token's gate for it (zero where
    the token is not routed there): at decode batch sizes an expert reads
    its weights whatever the number of its rows, and nothing is dropped.
    What experts held elsewhere add is not computed here. Returns (y
    (T, D), held (T,) int32: the token's routed experts held here)."""
    held = w_gate.shape[0]
    local = idx - first_expert                                      # (T, k)
    hit = local[..., None] == jnp.arange(held)[None, None, :]      # (T, k, E)
    mix = jnp.sum(jnp.where(hit, gates[..., None], 0.0), axis=1)    # (T, E)
    g = jnp.einsum("td,edf->tef", x, w_gate)
    u = jnp.einsum("td,edf->tef", x, w_up)
    h = jax.nn.silu(g) * u
    h = h * mix[..., None].astype(h.dtype)
    y = jnp.einsum("tef,efd->td", h, w_down)
    return y, jnp.sum(hit, axis=(1, 2), dtype=jnp.int32)
