"""Unified decoder-only transformer LM (dense / GQA / SWA / MoE / VLM).

Covers: h2o-danube-3-4b, granite-34b, chatglm3-6b, llama3.2-1b,
granite-moe-1b-a400m, moonshot-v1-16b-a3b, qwen2-vl-7b (with the stubbed
patch-embedding prefix), and the attention sub-blocks reused by jamba and
whisper.

Design for the 512-chip dry-run: parameters are stacked over layers and the
forward is a lax.scan over the stack — HLO size is O(1) in depth. Train
attention is blockwise (no S×S buffer); MoE goes through the expert-parallel
all_to_all (layers.moe_mlp_ep) when a mesh is provided.

serve_step carries functional decode state (KV caches, DSA indexer cache,
prev-Top-K feedback, lengths) and runs the paper's DSA pipeline per layer
when enabled.

Every decode body names its stages with `jax.named_scope`: `step.qkv`,
`step.kv_write`, `step.indexer`, `step.topk`, `step.attention`,
`step.mlp`, `step.head` (the indexer and selection scopes sit in
`sparse/dsa.py` and `sparse/sp_dsa.py`). The names reach the compiled
HLO's op_name metadata, where a profiler trace's ops find them; the
benchmark's readers (bench/scopes.py) depend on them.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.parallel.sharding import MeshRules, constrain
from repro.sparse import dsa as dsa_mod
from .config import ModelConfig
from .layers import (apply_rotary, blockwise_causal_attention, decode_attention,
                     decode_attention_paged, moe_mlp_ep, rms_norm, swiglu_mlp)


def _norm_init(d):
    return jnp.ones((d,), jnp.float32)


def _dense(key, shape, dtype, scale=None):
    scale = scale if scale is not None else shape[0] ** -0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_layer_params(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.hd
    keys = jax.random.split(key, 12)
    p = {
        "ln1": _norm_init(d),
        "ln2": _norm_init(d),
        "wq": _dense(keys[0], (d, cfg.n_heads * hd), dtype),
        "wk": _dense(keys[1], (d, cfg.n_kv_heads * hd), dtype),
        "wv": _dense(keys[2], (d, cfg.n_kv_heads * hd), dtype),
        "wo": _dense(keys[3], (cfg.n_heads * hd, d), dtype),
    }
    if cfg.moe.num_experts:
        e, f = cfg.moe.num_experts, cfg.moe.expert_d_ff
        p["router"] = _dense(keys[4], (d, e), jnp.float32)
        p["w_gate"] = _dense(keys[5], (e, d, f), dtype)
        p["w_up"] = _dense(keys[6], (e, d, f), dtype)
        p["w_down"] = _dense(keys[7], (e, f, d), dtype, scale=f ** -0.5)
    else:
        p["w_gate"] = _dense(keys[5], (d, cfg.d_ff), dtype)
        p["w_up"] = _dense(keys[6], (d, cfg.d_ff), dtype)
        p["w_down"] = _dense(keys[7], (cfg.d_ff, d), dtype, scale=cfg.d_ff ** -0.5)
    if cfg.dsa.enabled:
        p["indexer"] = dsa_mod.indexer_init(keys[8], d, cfg.dsa.indexer_heads,
                                            cfg.dsa.indexer_dim, dtype)
    return p


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer_params(k, cfg, dtype))(layer_keys)
    params = {
        "embed": _dense(k_emb, (cfg.vocab, cfg.d_model), dtype, scale=1.0),
        "layers": layers,
        "final_norm": _norm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(k_head, (cfg.d_model, cfg.vocab), dtype)
    if cfg.num_patches:
        params["patch_proj"] = _dense(k_head, (cfg.d_model, cfg.d_model), dtype)
    return params


def param_specs(cfg: ModelConfig, rules: MeshRules) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.hd
    sp = rules.spec
    lp = {
        "ln1": P(None), "ln2": P(None),
        "wq": sp("d_model", "heads", sizes=(d, cfg.n_heads * hd)),
        "wk": sp("d_model", "kv_heads", sizes=(d, cfg.n_kv_heads * hd)),
        "wv": sp("d_model", "kv_heads", sizes=(d, cfg.n_kv_heads * hd)),
        "wo": sp("heads", "d_model", sizes=(cfg.n_heads * hd, d)),
    }
    if cfg.moe.num_experts:
        e, f = cfg.moe.num_experts, cfg.moe.expert_d_ff
        lp["router"] = P(None, None)
        lp["w_gate"] = sp("experts", None, None, sizes=(e, d, f))
        lp["w_up"] = sp("experts", None, None, sizes=(e, d, f))
        lp["w_down"] = sp("experts", None, None, sizes=(e, f, d))
    else:
        lp["w_gate"] = sp("d_model", "d_ff", sizes=(d, cfg.d_ff))
        lp["w_up"] = sp("d_model", "d_ff", sizes=(d, cfg.d_ff))
        lp["w_down"] = sp("d_ff", "d_model", sizes=(cfg.d_ff, d))
    if cfg.dsa.enabled:
        di = cfg.dsa.indexer_dim
        hi = cfg.dsa.indexer_heads
        lp["indexer"] = {
            "wq": sp("d_model", "indexer", sizes=(d, hi * di)),
            "wk": P(None, None),
            "w": P(None),
        }
    # prepend the stacked-layer axis (never sharded)
    lp = jax.tree.map(lambda s: P(*((None,) + tuple(s))), lp,
                      is_leaf=lambda x: isinstance(x, P))
    specs = {
        "embed": sp("vocab", "d_model", sizes=(cfg.vocab, d)),
        "layers": lp,
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = sp("d_model", "vocab", sizes=(d, cfg.vocab))
    if cfg.num_patches:
        specs["patch_proj"] = P(None, None)
    return specs


# --------------------------------------------------------------------------
# Train forward
# --------------------------------------------------------------------------

def _attention_train(p, x, cfg: ModelConfig, positions, rules):
    b, s, d = x.shape
    hd = cfg.hd
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
    q = apply_rotary(q, positions, kind=cfg.rope_kind, base=cfg.rope_base,
                     fraction=cfg.rope_fraction)
    k = apply_rotary(k, positions, kind=cfg.rope_kind, base=cfg.rope_base,
                     fraction=cfg.rope_fraction)
    out = blockwise_causal_attention(q, k, v, scale=hd ** -0.5,
                                     window=cfg.swa_window)
    out = out.reshape(b, s, cfg.n_heads * hd).astype(x.dtype)
    return out @ p["wo"]


def _mlp(p, x, cfg: ModelConfig, mesh):
    if cfg.moe.num_experts:
        return moe_mlp_ep(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                          top_k=cfg.moe.top_k,
                          capacity_factor=cfg.moe.capacity_factor, mesh=mesh)
    return swiglu_mlp(x, p["w_gate"], p["w_up"], p["w_down"])


def forward_train(params, tokens, cfg: ModelConfig, *, mesh=None,
                  rules: Optional[MeshRules] = None,
                  patch_embeds: Optional[jnp.ndarray] = None,
                  remat: bool = True):
    """tokens: (B, S) int32 → logits (B, S, V). VLM: the first num_patches
    positions take the stubbed patch embeddings instead of token embeds."""
    b, s = tokens.shape
    x = params["embed"][tokens]
    if cfg.num_patches and patch_embeds is not None:
        pe = (patch_embeds @ params["patch_proj"]).astype(x.dtype)
        x = jnp.concatenate([pe, x[:, cfg.num_patches:]], axis=1)
    x = constrain(x, rules, "batch", "seq", "d_model")
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def layer(x, p):
        h = _attention_train(p, rms_norm(x, p["ln1"]), cfg, positions, rules)
        x = x + h
        x = constrain(x, rules, "batch", "seq", "d_model")
        h = _mlp(p, rms_norm(x, p["ln2"]), cfg, mesh)
        x = x + h
        x = constrain(x, rules, "batch", "seq", "d_model")
        return x, None

    if remat:
        layer = jax.checkpoint(layer, prevent_cse=False)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return constrain(logits, rules, "batch", "seq", "vocab")


def loss_fn(params, batch, cfg: ModelConfig, *, mesh=None, rules=None):
    tokens, targets = batch["tokens"], batch["targets"]
    logits = forward_train(params, tokens, cfg, mesh=mesh, rules=rules,
                           patch_embeds=batch.get("patch_embeds"))
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(targets, jnp.float32))
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --------------------------------------------------------------------------
# Decode (serve) path
# --------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=None) -> Dict[str, jnp.ndarray]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    l, hd = cfg.n_layers, cfg.hd
    state = {
        "k": jnp.zeros((l, batch, max_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((l, batch, max_len, cfg.n_kv_heads, hd), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }
    if cfg.dsa.enabled:
        from repro.core.temporal import seed_slot_idx
        state["idx_k"] = jnp.zeros((l, batch, max_len, cfg.dsa.indexer_dim), dtype)
        kk = min(cfg.dsa.k, max_len)
        base = seed_slot_idx(kk, max_len)
        state["prev_topk"] = jnp.broadcast_to(base[None, None], (l, batch, kk))
        # Validity of the prediction signal, per layer × slot: False until a
        # DSA step has written genuine feedback (the even-spacing seed above
        # is a warm-start hint, not history). The selector's per-row dispatch
        # sends invalid rows through the non-GVR fallback.
        state["topk_valid"] = jnp.zeros((l, batch), bool)
        # Telemetry: which rows the selector's GVR path actually served on
        # the last step (the serving engine's per-slot method log).
        state["sel_gvr"] = jnp.zeros((l, batch), bool)
    return state


def state_batch_axes(cfg: ModelConfig) -> Dict[str, int]:
    """Batch (slot) axis of every decode-state leaf — the serving engine's
    contract for per-slot slicing/merging (continuous batching)."""
    axes = {"k": 1, "v": 1, "length": 0}
    if cfg.dsa.enabled:
        axes.update(idx_k=1, prev_topk=1, topk_valid=1, sel_gvr=1)
    return axes


def reset_slot_state(cfg: ModelConfig, state: Dict[str, jnp.ndarray], slot,
                     seq_len_hint: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Slot admission hook: zero one slot's length and re-seed its GVR
    feedback (even spacing over `seq_len_hint`, invalid until the first DSA
    step — paper Table 9 row b). KV rows need no clearing: every consumer
    masks beyond `length`."""
    state = dict(state)
    state["length"] = state["length"].at[slot].set(0)
    if cfg.dsa.enabled:
        from repro.core.temporal import reset_slot_arrays
        prev, valid = reset_slot_arrays(state["prev_topk"], state["topk_valid"],
                                        slot, seq_len_hint)
        state["prev_topk"], state["topk_valid"] = prev, valid
        state["sel_gvr"] = state["sel_gvr"].at[:, slot].set(False)
    return state


def recycle_slot_state(cfg: ModelConfig, state: Dict[str, jnp.ndarray],
                       slot) -> Dict[str, jnp.ndarray]:
    """Slot eviction hook: poison the slot's predictions so they can never
    leak into the next admitted request (see temporal.recycle_slot_arrays)."""
    state = dict(state)
    if cfg.dsa.enabled:
        from repro.core.temporal import recycle_slot_arrays
        prev, valid = recycle_slot_arrays(state["prev_topk"],
                                          state["topk_valid"], slot)
        state["prev_topk"], state["topk_valid"] = prev, valid
        state["sel_gvr"] = state["sel_gvr"].at[:, slot].set(False)
    return state


def state_specs(cfg: ModelConfig, rules: MeshRules, *, batch: int, max_len: int,
                seq_sharded: bool = False) -> Dict[str, Any]:
    seq_ax = "seq_shard" if seq_sharded else None
    sp = rules.spec
    hd = cfg.hd
    specs = {
        "k": sp(None, "batch", seq_ax, "kv_heads", None,
                sizes=(cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd)),
        "v": sp(None, "batch", seq_ax, "kv_heads", None,
                sizes=(cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd)),
        "length": P(None),
    }
    if cfg.dsa.enabled:
        specs["idx_k"] = sp(None, "batch", seq_ax, None,
                            sizes=(cfg.n_layers, batch, max_len, cfg.dsa.indexer_dim))
        specs["prev_topk"] = sp(None, "batch", None,
                                sizes=(cfg.n_layers, batch, min(cfg.dsa.k, max_len)))
        specs["topk_valid"] = sp(None, "batch", sizes=(cfg.n_layers, batch))
        specs["sel_gvr"] = sp(None, "batch", sizes=(cfg.n_layers, batch))
    return specs


def _write_row(cache, new, lengths):
    """cache: (B, N, ...); new: (B, ...) inserted at position lengths[b]."""
    def one(c, x, p):
        return jax.lax.dynamic_update_slice(c, x[None], (p,) + (0,) * (c.ndim - 1))
    return jax.vmap(one)(cache, new.astype(cache.dtype), lengths)


def _project_qkv(p, h, b, positions, cfg: ModelConfig, rules):
    """Per-layer decode projections + RoPE, shared by the dense and paged
    cache layouts. h: (B, D) normed input. Returns q (B,H,HD), kn (B,KVH,HD),
    vn (B,KVH,HD) — the new token's rows, ready for the cache write."""
    hd = cfg.hd
    q = (h @ p["wq"]).reshape(b, 1, cfg.n_heads, hd)
    kn = (h @ p["wk"]).reshape(b, 1, cfg.n_kv_heads, hd)
    vn = (h @ p["wv"]).reshape(b, 1, cfg.n_kv_heads, hd)
    q = apply_rotary(q, positions[:, None], kind=cfg.rope_kind,
                     base=cfg.rope_base, fraction=cfg.rope_fraction)[:, 0]
    kn = apply_rotary(kn, positions[:, None], kind=cfg.rope_kind,
                      base=cfg.rope_base, fraction=cfg.rope_fraction)[:, 0]
    kn = constrain(kn, rules, "batch", None, None)
    vn = constrain(vn[:, 0], rules, "batch", None, None)
    return q, kn, vn


def _attend_decode(p, h, q, kc, vc, idx_kc, prev_topk, topk_valid, new_len,
                   cfg: ModelConfig, use_dsa: bool, rules, mesh, paged=None,
                   gather_granularity: str = "token"):
    """Shared decode-attention core.

    Scoring/selection always run over a *logical* contiguous indexer view:
    everything downstream of this point — indexer scores, Top-K selection,
    the prev-Top-K feedback and the sel_gvr telemetry — lives in logical
    token space and never sees a physical page id (the layout invariant
    GVR's temporal prediction depends on). The attention gather has two
    physical forms: the dense layout (and the paged "gather" oracle) passes
    contiguous K/V views via `kc`/`vc`; the paged "fused" path passes
    `paged=(k_pages, v_pages, page_table)` instead and attention pulls its
    Top-K rows straight from the page pools (`dsa_decode_paged`) — same
    bits, O(K) instead of O(N) gathered KV traffic."""
    hd = cfg.hd
    out = {}
    if use_dsa:
        dsa_kw = dict(
            k=prev_topk.shape[-1], scale=hd ** -0.5,
            heads=cfg.dsa.indexer_heads, dim=cfg.dsa.indexer_dim,
            rope_base=cfg.rope_base, selector=cfg.dsa.selector,
            prev_valid=topk_valid,
            max_candidates=cfg.dsa.max_candidates,
            gate_max_n=cfg.dsa.gate_max_n, min_n=cfg.dsa.min_n,
            swa_window=cfg.swa_window, rules=rules, mesh=mesh)
        if paged is not None:
            kp, vp, table = paged
            res = dsa_mod.dsa_decode_paged(
                q, kp, vp, table, p["indexer"], h, idx_kc, prev_topk,
                new_len, gather_granularity=gather_granularity, **dsa_kw)
        else:
            res = dsa_mod.dsa_decode(
                q, kc, vc, p["indexer"], h, idx_kc, prev_topk, new_len,
                **dsa_kw)
        attn = res.attn_out
        out["prev_topk"] = res.topk_idx
        if topk_valid is not None:
            # a DSA step just wrote genuine feedback → rows become warm
            out["topk_valid"] = jnp.ones_like(topk_valid)
            out.update(sel_telemetry(res, topk_valid))
        return attn, out
    with jax.named_scope("step.attention"):
        if paged is not None:
            # fused dense pre-DSA fallback: attend over the full logical
            # extent straight off the page pools (bit-identical to
            # gathering the view first — see layers.decode_attention_paged)
            kp, vp, table = paged
            attn = decode_attention_paged(q, kp, vp, table, new_len,
                                          scale=hd ** -0.5,
                                          window=cfg.swa_window, rules=rules)
        else:
            attn = decode_attention(q, kc, vc, new_len, scale=hd ** -0.5,
                                    window=cfg.swa_window)
    if prev_topk is not None:
        out["prev_topk"] = prev_topk
        if topk_valid is not None:
            out["topk_valid"] = topk_valid
            out.update(sel_telemetry(None, topk_valid))
    return attn, out


def _decode_out_mlp(p, x, attn, cfg: ModelConfig, mesh, rules):
    """The rest of one decode layer: the attention output projection and
    the residual MLP/MoE block. attn: (B, H, HD); x: (B, D)."""
    with jax.named_scope("step.attention"):
        attn = attn.reshape(x.shape[0], cfg.n_heads * cfg.hd).astype(x.dtype)
        x = x + attn @ p["wo"]
    with jax.named_scope("step.mlp"):
        h = rms_norm(x, p["ln2"])
        if cfg.moe.num_experts:
            m = _mlp(p, h[:, None, :], cfg, mesh)[:, 0]
        else:
            m = _mlp(p, h, cfg, mesh)
        x = x + m
    return constrain(x, rules, "batch", "d_model")


def _lm_head(params, x, cfg: ModelConfig):
    """The final norm and the LM head: float32 logits."""
    with jax.named_scope("step.head"):
        x = rms_norm(x, params["final_norm"])
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return (x @ head).astype(jnp.float32)


def sel_telemetry(res, valid) -> Dict[str, jnp.ndarray]:
    """One layer's selector telemetry, (B,) each: which rows the GVR path
    served (`sel_gvr`, kept in the decode state for the engine's method
    log), and for those rows GVR's secant iterations and whether its
    safety net ran (`sel_iters`, `sel_fallback`: values of other rows
    mean nothing; they leave the step only as `sel_counts`). `res` is the
    layer's DSAOutput, SelectorOutput or SPDSAPagedResult; None where no
    selection ran (the dense fallback). `valid` is the (B,) bool feedback
    validity, for shape."""
    none = jnp.zeros_like(valid)
    if res is None:
        return {"sel_gvr": none, "sel_iters": none.astype(jnp.int32),
                "sel_fallback": none}
    iters = res.secant_iters       # None where lax.top_k served every row
    return {"sel_gvr": res.gvr_rows,
            "sel_iters": (none if iters is None else iters).astype(jnp.int32),
            "sel_fallback": res.fallback}


def sel_counts(outs, b: int) -> jnp.ndarray:
    """Per row, summed over the layer scan's leading (layer) axis of its
    `sel_telemetry` stacks in `outs` ((L, ..., B) each): [GVR-served
    row-layers, their secant iterations, their safety-net fallbacks] —
    (..., B, 3) int32; zeros (B, 3) where no layer carries telemetry (DSA
    off). The counts a step returns with `with_counts=True`."""
    if "sel_gvr" not in outs:
        return jnp.zeros((b, 3), jnp.int32)
    gvr = outs["sel_gvr"]
    return jnp.stack(
        [jnp.sum(gvr, axis=0, dtype=jnp.int32),
         jnp.sum(jnp.where(gvr, outs["sel_iters"], 0), axis=0,
                 dtype=jnp.int32),
         jnp.sum(gvr & outs["sel_fallback"], axis=0, dtype=jnp.int32)],
        axis=-1)


def serve_step(params, state, tokens, cfg: ModelConfig, *, mesh=None,
               rules: Optional[MeshRules] = None, with_counts: bool = False):
    """One decode step. tokens: (B,) int32. Returns (logits (B,V), state),
    and with `with_counts` the rows' GVR counts (B, 3) third (`sel_counts`).

    Per layer: append KV (and indexer K) at position `length`, then attend —
    DSA sparse path when enabled and the cache is long enough, dense
    otherwise. prev-Top-K feedback is updated in place (the paper's
    per-layer prev_topk buffer).
    """
    b = tokens.shape[0]
    hd = cfg.hd
    x = params["embed"][tokens]                          # (B, D)
    x = constrain(x, rules, "batch", "d_model")
    new_len = state["length"] + 1
    positions = state["length"]                          # 0-based write pos
    n = state["k"].shape[2]

    use_dsa = cfg.dsa.enabled and n > cfg.dsa.min_n

    def layer(x, carry):
        p, kc, vc, idx_kc, prev_topk = (carry["p"], carry["k"], carry["v"],
                                        carry.get("idx_k"), carry.get("prev_topk"))
        topk_valid = carry.get("topk_valid")
        # pin cache layouts at loop entry — scatter/gather partitioners
        # otherwise adopt head-sharding propagated from the projections and
        # re-gather the full cache every step
        kc = constrain(kc, rules, "batch", None, None, None)
        vc = constrain(vc, rules, "batch", None, None, None)
        if idx_kc is not None:
            idx_kc = constrain(idx_kc, rules, "batch", None, None)
        with jax.named_scope("step.qkv"):
            h = rms_norm(x, p["ln1"])
            q, kn, vn = _project_qkv(p, h, b, positions, cfg, rules)
        with jax.named_scope("step.kv_write"):
            kc = _write_row(kc, kn, positions)
            vc = _write_row(vc, vn, positions)
            kc = constrain(kc, rules, "batch", None, None, None)
            vc = constrain(vc, rules, "batch", None, None, None)

        out = {"k": kc, "v": vc, "p": p}
        if use_dsa:
            with jax.named_scope("step.indexer"):
                ik = dsa_mod.indexer_k(p["indexer"], h, positions,
                                       dim=cfg.dsa.indexer_dim,
                                       rope_base=cfg.rope_base)
            with jax.named_scope("step.kv_write"):
                idx_kc = _write_row(idx_kc, ik, positions)
        if idx_kc is not None:
            out["idx_k"] = idx_kc
        attn, extras = _attend_decode(p, h, q, kc, vc, idx_kc, prev_topk,
                                      topk_valid, new_len, cfg, use_dsa,
                                      rules, mesh)
        out.update(extras)
        x = _decode_out_mlp(p, x, attn, cfg, mesh, rules)
        return x, out

    carry_in = {"p": params["layers"], "k": state["k"], "v": state["v"]}
    if cfg.dsa.enabled:
        carry_in["idx_k"] = state["idx_k"]
        carry_in["prev_topk"] = state["prev_topk"]
        if "topk_valid" in state:
            carry_in["topk_valid"] = state["topk_valid"]
    x, outs = jax.lax.scan(layer, x, carry_in)

    new_state = dict(state)
    new_state["k"], new_state["v"] = outs["k"], outs["v"]
    if cfg.dsa.enabled:
        new_state["idx_k"] = outs["idx_k"]
        new_state["prev_topk"] = outs["prev_topk"]
        if "topk_valid" in state:
            new_state["topk_valid"] = outs["topk_valid"]
            new_state["sel_gvr"] = outs["sel_gvr"]
    new_state["length"] = new_len
    logits = constrain(_lm_head(params, x, cfg), rules, "batch", "vocab")
    if with_counts:
        return logits, new_state, sel_counts(outs, b)
    return logits, new_state


# --------------------------------------------------------------------------
# Sequence-sharded paged decode — SP-GVR serving path (DESIGN.md §sp-serving)
# --------------------------------------------------------------------------
#
# For 500K-context slots no single device holds a slot's KV pages, so the
# page pools shard over a 1-D sequence mesh: shard s owns the pages whose
# LOGICAL token range falls in [s·N/S, (s+1)·N/S), each shard has its own
# `num_pages_per_shard`-page pool (plus its own write-sink page), and the
# replicated block table stores SHARD-LOCAL physical ids (the logical page
# index determines the owner, so no shard field is needed). Everything the
# GVR feedback loop touches — prev_topk, topk_valid, sel_gvr, lengths —
# stays replicated in GLOBAL logical token space (sp_gvr_topk_local's
# contract), so admission/eviction/preemption hooks and the warm/cold
# dispatch are byte-for-byte the single-device ones. Selection runs through
# SP-GVR's O(1)-collective schedule and attention assembles exactly the K
# selected rows with one O(K) psum (sparse/sp_dsa.py), so a 512K-token slot
# never materializes a global score row or logical KV view: per-device KV
# residency is N/S and per-tick collective traffic is independent of N.


def init_sp_paged_decode_state(cfg: ModelConfig, batch: int, max_len: int, *,
                               num_pages_per_shard: int, page_size: int,
                               seq_shards: int, dtype=None, mesh=None
                               ) -> Dict[str, jnp.ndarray]:
    """Sequence-sharded variant of `init_paged_decode_state`.

    Page pools gain a leading shard axis — (L, S, PL+1, page_size, ...) —
    which `serve_step_sp_paged` shards over the mesh's "seq" axis; each
    shard's extra final page is its own write sink. `max_len` must divide
    into `seq_shards` page-aligned spans so logical-page ownership is
    whole-page. The block table holds shard-local physical ids.

    With `mesh`, every leaf is created in place: each pool split over the
    mesh's "seq" axis (device s allocates only shard s), the rest
    replicated — no device ever holds the whole pool.
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    if max_len % (page_size * seq_shards) != 0:
        raise ValueError(
            f"max_len ({max_len}) must be a multiple of page_size × "
            f"seq_shards ({page_size}×{seq_shards}) — shard token spans "
            f"must be page-aligned for whole-page ownership")
    l, hd = cfg.n_layers, cfg.hd
    mp = max_len // page_size
    pool_at = rep_at = None
    if mesh is not None:
        pool_at = NamedSharding(mesh, P(None, "seq"))
        rep_at = NamedSharding(mesh, P())
    pool = (l, seq_shards, num_pages_per_shard + 1, page_size)
    state = {
        "k_pages": jnp.zeros(pool + (cfg.n_kv_heads, hd), dtype,
                             device=pool_at),
        "v_pages": jnp.zeros(pool + (cfg.n_kv_heads, hd), dtype,
                             device=pool_at),
        "page_table": jnp.full((batch, mp), -1, jnp.int32, device=rep_at),
        "length": jnp.zeros((batch,), jnp.int32, device=rep_at),
    }
    if cfg.dsa.enabled:
        from repro.core.temporal import seed_slot_idx
        state["idx_k_pages"] = jnp.zeros(pool + (cfg.dsa.indexer_dim,),
                                         dtype, device=pool_at)
        kk = min(cfg.dsa.k, max_len)
        base = seed_slot_idx(kk, max_len)
        state["prev_topk"] = jnp.broadcast_to(base[None, None], (l, batch, kk))
        state["topk_valid"] = jnp.zeros((l, batch), bool, device=rep_at)
        state["sel_gvr"] = jnp.zeros((l, batch), bool, device=rep_at)
        if rep_at is not None:
            state["prev_topk"] = jax.device_put(state["prev_topk"], rep_at)
    return state


def sp_paged_state_batch_axes(cfg: ModelConfig) -> Dict[str, int]:
    """Slot-axis map of the sequence-sharded paged state — identical to the
    single-device paged map (the sharded page pools are likewise pool-global
    per shard and must pass through the engine's row merge unmerged)."""
    return paged_state_batch_axes(cfg)


def _sp_paged_validate(state, cfg: ModelConfig, mesh, seq_axis: str) -> None:
    """Shared entry validation of the sequence-sharded paged steps
    (`serve_step_sp_paged` and the speculative `serve_step_sp_spec_paged`)."""
    num_shards = state["k_pages"].shape[1]
    page_size = state["k_pages"].shape[3]
    mp = state["page_table"].shape[1]
    n = mp * page_size
    if mp % num_shards != 0:
        raise ValueError(f"logical pages ({mp}) must divide over "
                         f"{num_shards} shards")
    if not (cfg.dsa.enabled and n > cfg.dsa.min_n):
        raise ValueError(
            "sequence-sharded paged decode requires the DSA gate open "
            f"(dsa.enabled and max_len > dsa.min_n={cfg.dsa.min_n}): the "
            "sequence-sharded path has no dense fallback attention")
    if mesh.shape[seq_axis] != num_shards:
        raise ValueError(
            f"state carries {num_shards} shards but mesh axis "
            f"{seq_axis!r} has {mesh.shape[seq_axis]} devices")


def _sp_paged_token_body(params, state, tokens, mwp, cfg: ModelConfig, *,
                         seq_axis: str):
    """Per-device body of ONE sequence-sharded paged decode step — executes
    inside a shard_map over `seq_axis` (state's page-pool leaves arrive as
    this device's shard slice, everything else replicated). Factored to
    module level so the speculative verify step can scan it over the d+1
    draft positions of a verify tick within a single shard_map
    (`serve_step_sp_spec_paged`); `serve_step_sp_paged` wraps exactly one
    invocation. Returns (logits, new_state, counts) with the shard axis
    restored on the pool leaves; counts: `sel_counts` (B, 3)."""
    from repro.sparse import sp_dsa as sp_dsa_mod

    b = tokens.shape[0]
    hd = cfg.hd
    ppl = state["k_pages"].shape[2] - 1                  # pages per shard
    page_size = state["k_pages"].shape[3]
    mp = state["page_table"].shape[1]
    num_shards = jax.lax.axis_size(seq_axis)
    mp_local = mp // num_shards
    n_local = mp_local * page_size
    kk = state["prev_topk"].shape[-1]

    my = jax.lax.axis_index(seq_axis)
    shard_offset = (my * n_local).astype(jnp.int32)
    table = state["page_table"]                      # (B, MP) replicated
    table_local = jax.lax.dynamic_slice_in_dim(
        table, my * mp_local, mp_local, axis=1)      # shard-local slice
    positions = state["length"]
    new_len = state["length"] + 1
    sink = ppl                                       # local sink page id

    # this shard writes iff it owns the write position
    owner = (positions >= shard_offset) & (positions < shard_offset + n_local)
    rel = jnp.clip(positions - shard_offset, 0, n_local - 1)
    phys = jnp.take_along_axis(table_local,
                               (rel // page_size)[:, None], axis=1)[:, 0]
    writable = owner & (phys >= 0) & (positions >= mwp)
    dest = jnp.where(writable, phys, sink)
    off = positions % page_size                      # page-aligned spans
    gather_local = jnp.clip(table_local, 0, sink)

    x = params["embed"][tokens]

    def layer(x, carry):
        p = carry["p"]
        kp, vp = carry["k_pages"], carry["v_pages"]
        idx_kp = carry["idx_k_pages"]
        prev_topk = carry["prev_topk"]
        topk_valid = carry.get("topk_valid")
        with jax.named_scope("step.qkv"):
            h = rms_norm(x, p["ln1"])
            q, kn, vn = _project_qkv(p, h, b, positions, cfg, None)
        with jax.named_scope("step.indexer"):
            ik = dsa_mod.indexer_k(p["indexer"], h, positions,
                                   dim=cfg.dsa.indexer_dim,
                                   rope_base=cfg.rope_base)
        with jax.named_scope("step.kv_write"):
            kp = kp.at[dest, off].set(kn.astype(kp.dtype))
            vp = vp.at[dest, off].set(vn.astype(vp.dtype))
            idx_kp = idx_kp.at[dest, off].set(ik.astype(idx_kp.dtype))
        with jax.named_scope("step.indexer"):
            # shard-local logical indexer view: N/S × d_i per device — the
            # irreducible indexer read, now split across the mesh
            idx_kc = idx_kp[gather_local].reshape(b, n_local,
                                                  cfg.dsa.indexer_dim)
        res = sp_dsa_mod.sp_dsa_decode_paged_local(
            q, kp, vp, table_local, p["indexer"], h, idx_kc,
            prev_topk, topk_valid, new_len,
            k=kk, scale=hd ** -0.5, heads=cfg.dsa.indexer_heads,
            dim=cfg.dsa.indexer_dim, rope_base=cfg.rope_base,
            shard_offset=shard_offset, page_size=page_size,
            max_candidates=cfg.dsa.max_candidates,
            swa_window=cfg.swa_window, seq_axis=seq_axis)
        out = {"k_pages": kp, "v_pages": vp, "idx_k_pages": idx_kp,
               "p": p, "prev_topk": res.new_topk}
        if topk_valid is not None:
            out["topk_valid"] = jnp.ones_like(topk_valid)
            out.update(sel_telemetry(res, topk_valid))
        x = _decode_out_mlp(p, x, res.attn_out, cfg, None, None)
        return x, out

    carry_in = {"p": params["layers"],
                "k_pages": state["k_pages"][:, 0],
                "v_pages": state["v_pages"][:, 0],
                "idx_k_pages": state["idx_k_pages"][:, 0],
                "prev_topk": state["prev_topk"]}
    if "topk_valid" in state:
        carry_in["topk_valid"] = state["topk_valid"]
    x, outs = jax.lax.scan(layer, x, carry_in)

    new_state = dict(state)
    for key in ("k_pages", "v_pages", "idx_k_pages"):
        new_state[key] = outs[key][:, None]          # restore shard axis
    new_state["prev_topk"] = outs["prev_topk"]
    if "topk_valid" in state:
        new_state["topk_valid"] = outs["topk_valid"]
        new_state["sel_gvr"] = outs["sel_gvr"]
    new_state["length"] = new_len
    return _lm_head(params, x, cfg), new_state, sel_counts(outs, b)


def serve_step_sp_paged(params, state, tokens, cfg: ModelConfig, *, mesh,
                        min_write_pos: Optional[jnp.ndarray] = None,
                        seq_axis: str = "seq",
                        rules: Optional[MeshRules] = None,
                        with_counts: bool = False):
    """One sequence-sharded paged decode step (inside a shard_map over the
    mesh's `seq_axis`). tokens: (B,) int32. Returns (logits, state), and
    with `with_counts` the rows' GVR counts (B, 3) third (`sel_counts`).

    Per shard and per layer: the shard owning logical position `length`
    scatters the new token's K/V/indexer-K rows into ITS page pool (every
    other shard writes its own sink page — scatter shapes stay static and
    replay masking via `min_write_pos` works exactly as in the single-
    device paged step); each shard scores its local logical indexer view;
    `sp_gvr_topk_local` selects the exact global Top-K with O(1)-sized
    collectives; attention assembles exactly the K selected rows with one
    O(K) psum and runs replicated (`sp_dsa_decode_paged_local`). The
    result is bit-identical to `serve_step_paged(..., paged_attn="fused")`
    over the same logical cache content — tokens, logits, feedback buffer
    and telemetry alike — which `tests/test_sp_engine.py` pins.

    Requires an active DSA gate (`cfg.dsa.enabled` and
    `max_len > cfg.dsa.min_n`): sequence sharding exists for long contexts,
    and the dense fallback attention has no sharded form here.
    """
    b = tokens.shape[0]
    _sp_paged_validate(state, cfg, mesh, seq_axis)
    mwp = (min_write_pos if min_write_pos is not None
           else jnp.zeros((b,), jnp.int32))

    def body(params, state, tokens, mwp):
        out = _sp_paged_token_body(params, state, tokens, mwp, cfg,
                                   seq_axis=seq_axis)
        return out if with_counts else out[:2]

    pool_spec = P(None, seq_axis)
    st_spec = {key: (pool_spec if key in ("k_pages", "v_pages", "idx_k_pages")
                     else P()) for key in state}
    param_spec = jax.tree.map(lambda _: P(), params)
    fn = jax.shard_map(body, mesh=mesh,
                   in_specs=(param_spec, st_spec, P(), P()),
                   out_specs=(P(), st_spec) + ((P(),) if with_counts else ()),
                   check_vma=False)
    return fn(params, state, tokens, mwp)


# --------------------------------------------------------------------------
# Paged decode (serve) path — pool-of-pages KV layout
# --------------------------------------------------------------------------
#
# The paged layout replaces the dense per-slot (B, max_len, ...) caches with
# a global pool of `num_pages` pages of `page_size` tokens plus a per-slot
# page table translating logical token positions to physical pages
# (serve.paged owns allocation, ref-counts and shared-prefix admission).
# Each step scatters the new token's K/V (and indexer-K) rows into the
# slot's current page and runs the same `_attend_decode` core as the dense
# layout. The sparse-attention stage is block-table-native by default
# (`paged_attn="fused"`): Top-K selection happens on the logical indexer
# view, then attention gathers exactly the selected rows straight from the
# page pools — the big K/V logical views are never materialized
# (`paged_attn="gather"` keeps the PR-2 materialize-then-attend oracle).
# Either way Top-K indices, the prev-Top-K feedback buffer and all
# selector telemetry stay in logical token space, and a request decodes
# bit-identically under either layout (and either paged_attn mode). All
# shapes are static: the tick never recompiles across admissions,
# evictions or page-table changes.

# min_write_pos sentinel larger than any position: the row never writes.
# Rows whose write is masked (inactive slots, shared-prefix replay over
# already-materialized pages) scatter into a dedicated sink page instead —
# that keeps the scatter shape static and shared pages copy-free.
PAGED_NEVER_WRITE = 2 ** 30


def init_paged_decode_state(cfg: ModelConfig, batch: int, max_len: int, *,
                            num_pages: int, page_size: int,
                            dtype=None) -> Dict[str, jnp.ndarray]:
    """Paged decode-state variant of `init_decode_state`.

    K/V (and DSA indexer-K) caches live in `num_pages` + 1 pages of
    `page_size` tokens — the extra final page is the write sink for masked
    rows. `page_table` (batch, max_len // page_size) maps each slot's
    logical pages to physical ids (-1 = unmapped). `max_len` must be a
    multiple of `page_size` so the gathered logical view has exactly the
    dense layout's shape (bit-exactness depends on identical reduction
    extents, not just identical values).
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    if max_len % page_size != 0:
        raise ValueError(f"max_len ({max_len}) must be a multiple of "
                         f"page_size ({page_size})")
    l, hd = cfg.n_layers, cfg.hd
    mp = max_len // page_size
    state = {
        "k_pages": jnp.zeros((l, num_pages + 1, page_size, cfg.n_kv_heads, hd),
                             dtype),
        "v_pages": jnp.zeros((l, num_pages + 1, page_size, cfg.n_kv_heads, hd),
                             dtype),
        "page_table": jnp.full((batch, mp), -1, jnp.int32),
        "length": jnp.zeros((batch,), jnp.int32),
    }
    if cfg.dsa.enabled:
        from repro.core.temporal import seed_slot_idx
        state["idx_k_pages"] = jnp.zeros(
            (l, num_pages + 1, page_size, cfg.dsa.indexer_dim), dtype)
        kk = min(cfg.dsa.k, max_len)
        base = seed_slot_idx(kk, max_len)
        state["prev_topk"] = jnp.broadcast_to(base[None, None], (l, batch, kk))
        state["topk_valid"] = jnp.zeros((l, batch), bool)
        state["sel_gvr"] = jnp.zeros((l, batch), bool)
    return state


def paged_state_batch_axes(cfg: ModelConfig) -> Dict[str, int]:
    """Slot-axis map of the paged decode state. Page-pool leaves (k_pages /
    v_pages / idx_k_pages) are intentionally absent: they are pool-global,
    and masked rows already write to the sink page inside the step — the
    engine must pass them through unmerged."""
    axes = {"page_table": 0, "length": 0}
    if cfg.dsa.enabled:
        axes.update(prev_topk=1, topk_valid=1, sel_gvr=1)
    return axes


def serve_step_paged(params, state, tokens, cfg: ModelConfig, *,
                     min_write_pos: Optional[jnp.ndarray] = None,
                     paged_attn: str = "fused",
                     gather_granularity: str = "token",
                     mesh=None, rules: Optional[MeshRules] = None,
                     with_counts: bool = False):
    """One paged decode step. tokens: (B,) int32. Returns (logits, state),
    and with `with_counts` the rows' GVR counts (B, 3) third (`sel_counts`).

    Mirrors `serve_step` exactly, with the logical→physical translation at
    the cache boundary: the new token's rows scatter into
    `page_table[b, length // page_size]` at offset `length % page_size`.
    `min_write_pos` (B,) suppresses the cache write for rows whose
    position is below it (redirected to the sink page): the engine uses it
    to mask inactive slots and to replay the last prompt token over a
    shared prefix without copy-on-writing the shared page.

    `paged_attn` picks the physical form of the sparse-attention stage
    (DESIGN.md §paged) — both are bit-identical in tokens, logits, Top-K
    indices and selector telemetry:

    * "fused" (default) — block-table-native: Top-K selection runs on the
      logical indexer view (O(N·d_i), the irreducible indexer read), then
      attention gathers exactly the K selected rows straight from the
      global K/V page pools via `table[b, idx // page_size]` — the
      (B, MP·page_size, KVH, HD) logical K/V views are never built, so
      per-tick gathered KV traffic is O(K), independent of context length.
    * "gather" — the PR-2 oracle path: materialize the full logical K/V
      views first (O(N) traffic), then run the identical logical-view
      attention. Kept as the reference the fused path is pinned against.

    Either way the prev-Top-K feedback stays in logical token space, so
    warm/cold dispatch and the dense-layout bit-exactness are untouched.

    `gather_granularity` ("token" | "page") picks the DMA shape of the
    fused sparse gather: token-granular moves one row per Top-K entry,
    page-granular moves each distinct touched page whole and slices rows
    out in fast memory — coarser descriptors, bit-identical output
    (sparse.dsa.dsa_sparse_attention_paged).
    """
    b = tokens.shape[0]
    hd = cfg.hd
    x = params["embed"][tokens]                          # (B, D)
    x = constrain(x, rules, "batch", "d_model")
    positions = state["length"]                          # 0-based write pos
    new_len = state["length"] + 1
    table = state["page_table"]
    page_size = state["k_pages"].shape[2]
    sink = state["k_pages"].shape[1] - 1                 # last physical page
    mp = table.shape[1]
    n = mp * page_size                                   # logical extent

    lp = positions // page_size
    off = positions % page_size
    phys = jnp.take_along_axis(table, lp[:, None], axis=1)[:, 0]
    writable = phys >= 0
    if min_write_pos is not None:
        writable &= positions >= min_write_pos
    dest = jnp.where(writable, phys, sink)
    # `gather` materializes a logical view: unmapped pages clip to page 0 —
    # garbage rows, dead beyond `length` under the NEG_SENTINEL masking
    # convention (finite values, so their post-mask contribution is exactly
    # zero, as in the dense layout). Under the default fused path this is
    # only used for the indexer-K view (and the dense pre-DSA fallback);
    # attention itself never builds a logical view — it addresses the page
    # pools through the raw table, masking the -1 sentinel explicitly
    # (dsa_sparse_attention_paged / kernels.paged_sparse_decode_attn).
    gather = jnp.clip(table, 0, sink)

    if paged_attn not in ("fused", "gather"):
        raise ValueError(f"unknown paged_attn {paged_attn!r} "
                         f"(expected 'fused' or 'gather')")
    use_dsa = cfg.dsa.enabled and n > cfg.dsa.min_n
    # fused covers both attention forms: the sparse (DSA) stage gathers its
    # Top-K rows from the pools, and the dense pre-DSA fallback attends the
    # full logical extent through decode_attention_paged — either way the
    # step never materializes the K/V logical views itself
    fused = paged_attn == "fused"

    def layer(x, carry):
        p = carry["p"]
        kp, vp = carry["k_pages"], carry["v_pages"]
        idx_kp = carry.get("idx_k_pages")
        prev_topk = carry.get("prev_topk")
        topk_valid = carry.get("topk_valid")
        with jax.named_scope("step.qkv"):
            h = rms_norm(x, p["ln1"])
            q, kn, vn = _project_qkv(p, h, b, positions, cfg, rules)
        with jax.named_scope("step.kv_write"):
            kp = kp.at[dest, off].set(kn.astype(kp.dtype))
            vp = vp.at[dest, off].set(vn.astype(vp.dtype))
        if fused:
            kc = vc = None            # K/V logical views intentionally unbuilt
        else:
            with jax.named_scope("step.attention"):
                kc = kp[gather].reshape(b, n, cfg.n_kv_heads, hd)
                vc = vp[gather].reshape(b, n, cfg.n_kv_heads, hd)
                kc = constrain(kc, rules, "batch", None, None, None)
                vc = constrain(vc, rules, "batch", None, None, None)

        out = {"k_pages": kp, "v_pages": vp, "p": p}
        idx_kc = None
        if use_dsa:
            with jax.named_scope("step.indexer"):
                ik = dsa_mod.indexer_k(p["indexer"], h, positions,
                                       dim=cfg.dsa.indexer_dim,
                                       rope_base=cfg.rope_base)
            with jax.named_scope("step.kv_write"):
                idx_kp = idx_kp.at[dest, off].set(ik.astype(idx_kp.dtype))
            with jax.named_scope("step.indexer"):
                # the indexer scores all N tokens (paper Table 2:
                # irreducible O(N·d_i)), so its logical view costs what
                # scoring in page space would — and keeps scores/Top-K in
                # logical order
                idx_kc = idx_kp[gather].reshape(b, n, cfg.dsa.indexer_dim)
        if idx_kp is not None:
            out["idx_k_pages"] = idx_kp
        attn, extras = _attend_decode(p, h, q, kc, vc, idx_kc, prev_topk,
                                      topk_valid, new_len, cfg, use_dsa,
                                      rules, mesh,
                                      paged=(kp, vp, table) if fused else None,
                                      gather_granularity=gather_granularity)
        out.update(extras)
        x = _decode_out_mlp(p, x, attn, cfg, mesh, rules)
        return x, out

    carry_in = {"p": params["layers"], "k_pages": state["k_pages"],
                "v_pages": state["v_pages"]}
    if cfg.dsa.enabled:
        carry_in["idx_k_pages"] = state["idx_k_pages"]
        carry_in["prev_topk"] = state["prev_topk"]
        if "topk_valid" in state:
            carry_in["topk_valid"] = state["topk_valid"]
    x, outs = jax.lax.scan(layer, x, carry_in)

    new_state = dict(state)
    new_state["k_pages"], new_state["v_pages"] = outs["k_pages"], outs["v_pages"]
    if cfg.dsa.enabled:
        new_state["idx_k_pages"] = outs["idx_k_pages"]
        new_state["prev_topk"] = outs["prev_topk"]
        if "topk_valid" in state:
            new_state["topk_valid"] = outs["topk_valid"]
            new_state["sel_gvr"] = outs["sel_gvr"]
    new_state["length"] = new_len
    logits = constrain(_lm_head(params, x, cfg), rules, "batch", "vocab")
    if with_counts:
        return logits, new_state, sel_counts(outs, b)
    return logits, new_state


# --------------------------------------------------------------------------
# Speculative verify step — draft–verify–rollback over the paged layouts
# (DESIGN.md §spec-decode)
# --------------------------------------------------------------------------
#
# One verify tick scores all d+1 draft positions of each slot through the
# SAME per-token paged step the engine already runs, scanned inside one jit:
# position j writes its K/V at `length + j` and attends with per-position
# causal extent `length + j + 1`, so every position reproduces the exact
# bits of the non-speculative step it stands in for. The GVR feedback is
# causally extended WITHIN the tick: position j's selection warm-starts
# position j+1 (the scan threads `prev_topk`/`topk_valid` through the
# per-token steps), which is precisely the paper's temporal-correlation
# signal stretched across a multi-token step ("Learn from the Past" argues
# the correlation survives; the per-position `sel_gvr` stack lets the
# engine measure how the hit rate degrades with draft depth).
#
# Greedy acceptance and EXACT rollback both happen in-graph: draft token j
# is accepted iff it matches position j-1's argmax (and every earlier draft
# was accepted); the final state then takes `length = L0 + a + 1` and the
# feedback buffers (`prev_topk`/`topk_valid`/`sel_gvr`) from position a's
# stack entry — bit-identical to what a non-speculative engine would hold
# after emitting the same a+1 tokens. KV rows written by rejected positions
# need no clearing (every consumer masks beyond `length`, the same
# convention that leaves evicted dense-slot rows dirty); the HOST-side page
# rollback (block table + ref-counts) is `PagedAdmissionCore.rewind_slot`.


def _spec_verify_scan(step_fn, state, tokens, draft_len, max_accept,
                      eos_id: int, base_mwp, axes, dsa_enabled: bool):
    """Shared multi-position verify scan + greedy acceptance + exact
    in-graph rollback (used by `serve_step_spec_paged` and, inside the
    shard_map, by `serve_step_sp_spec_paged`).

    step_fn(state, tok (B,), mwp (B,)) -> (logits (B, V), new_state,
    counts (B, 3)) — one per-token paged decode step with its `sel_counts`. tokens: (B, D+1) — column 0 is the last
    emitted token, columns 1..D the draft. draft_len: (B,) in [0, D] — rows
    verify positions 0..draft_len (position j > draft_len is frozen: state
    row kept, cache write redirected to the sink page). max_accept: (B,)
    caps accepted DRAFT tokens (the engine's max_new_tokens budget).
    eos_id: emission truncates at (and includes) the first eos argmax
    (-1 = disabled; vocab ids are non-negative so it never matches).

    Returns (out_tokens (B, D+1), accept_len (B,), logits_all (B, D+1, V),
    sel_gvr_pos (B, D+1), gvr_counts (B, 3), new_state): `out_tokens[:, j]`
    is position j's argmax, the engine appends columns 0..accept_len;
    `sel_gvr_pos` is the layer-0 per-position GVR telemetry (column j valid
    iff j <= draft_len); `gvr_counts` is `sel_counts` over the executed
    positions (j <= draft_len) of every layer.
    """
    b, d1 = tokens.shape
    never = jnp.int32(PAGED_NEVER_WRITE)
    length0 = state["length"]

    def body(st, inp):
        j, tok = inp
        live = j <= draft_len                          # (B,)
        mwp = jnp.where(live, base_mwp, never)
        logits, st2, counts = step_fn(st, tok, mwp)
        merged = {}
        for key, arr in st2.items():
            ax = axes.get(key)
            if ax is None:          # pool-global leaf: sink writes already
                merged[key] = arr   # keep frozen rows untouched
                continue
            shape = [1] * arr.ndim
            shape[ax] = b
            merged[key] = jnp.where(live.reshape(shape), arr, st[key])
        ys = {"logits": logits}
        if dsa_enabled:
            # raw (unmerged) per-position stacks: entry j is only ever
            # selected for rows with accept_len <= draft_len, i.e. rows
            # for which position j really executed
            for key in ("prev_topk", "topk_valid", "sel_gvr"):
                ys[key] = st2[key]
            ys["sel_counts"] = counts
        return merged, ys

    xs = (jnp.arange(d1, dtype=jnp.int32), tokens.T)
    end_state, ys = jax.lax.scan(body, state, xs)
    return _spec_accept_rollback(length0, end_state, ys, tokens, draft_len,
                                 max_accept, eos_id, dsa_enabled)


def _spec_accept_rollback(length0, end_state, ys, tokens, draft_len,
                          max_accept, eos_id: int, dsa_enabled: bool):
    """Greedy acceptance + exact in-graph rollback from the per-position
    verify stacks. Shared verbatim by the scan and mq verify forms — having
    ONE copy of this arithmetic is what guarantees the two verify kernels
    agree on every accept/reject/eos trace whenever their stacks agree.

    ys: {"logits": (D+1, B, V)} plus, when DSA state is carried,
    per-position stacks "prev_topk" (D+1, L, B, K), "topk_valid" and
    "sel_gvr" (D+1, L, B) — RAW (unmerged) values; entry j is only ever
    selected for rows whose position j really executed (accept_len <=
    draft_len) — and "sel_counts" (D+1, B, 3), each position's
    `sel_counts`, summed over the executed positions only. Returns the
    serve_step_spec_paged 6-tuple.
    """
    b, d1 = tokens.shape
    logits_all = ys["logits"]                          # (D+1, B, V)
    argmax_all = jnp.argmax(logits_all, axis=-1).astype(jnp.int32)
    if d1 > 1:
        # draft token j (1-based) is accepted iff it matches position
        # j-1's argmax, it exists (j <= draft_len), and every earlier
        # draft was accepted — the standard greedy-spec prefix rule
        match = ((tokens[:, 1:].T == argmax_all[:-1])
                 & (jnp.arange(1, d1, dtype=jnp.int32)[:, None]
                    <= draft_len[None, :]))
        raw = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=0),
                      axis=0).astype(jnp.int32)
    else:
        raw = jnp.zeros((b,), jnp.int32)
    a = jnp.minimum(raw, jnp.maximum(max_accept, 0))
    # emission stops at (and includes) the first eos the verify emitted
    is_eos = argmax_all == jnp.int32(eos_id)           # (D+1, B)
    first_eos = jnp.argmax(is_eos, axis=0).astype(jnp.int32)
    a = jnp.where(jnp.any(is_eos, axis=0), jnp.minimum(a, first_eos), a)

    new_state = dict(end_state)
    new_state["length"] = length0 + a + 1
    if dsa_enabled:
        # roll the feedback back to position a's selection — exactly the
        # buffer a non-speculative engine holds after the same tokens
        pt = ys["prev_topk"]                           # (D+1, L, B, K)
        gi = jnp.broadcast_to(a[None, None, :, None], (1,) + pt.shape[1:])
        new_state["prev_topk"] = jnp.take_along_axis(pt, gi, axis=0)[0]
        for key in ("topk_valid", "sel_gvr"):
            stk = ys[key]                              # (D+1, L, B)
            gi = jnp.broadcast_to(a[None, None, :], (1,) + stk.shape[1:])
            new_state[key] = jnp.take_along_axis(stk, gi, axis=0)[0]
        sel_pos = jnp.transpose(ys["sel_gvr"][:, 0, :])   # (B, D+1), layer 0
        executed = (jnp.arange(d1, dtype=jnp.int32)[:, None]
                    <= draft_len[None, :])             # (D+1, B)
        counts = jnp.sum(jnp.where(executed[:, :, None], ys["sel_counts"],
                                   0), axis=0)
    else:
        sel_pos = jnp.zeros((b, d1), bool)
        counts = jnp.zeros((b, 3), jnp.int32)
    return (argmax_all.T, a, jnp.transpose(logits_all, (1, 0, 2)),
            sel_pos, counts, new_state)


def _paged_verify_mq(params, state, tokens, cfg: ModelConfig, *, draft_len,
                     base_mwp, paged_attn: str, gather_granularity: str,
                     mesh, rules):
    """Multi-query-row verify body (`verify_kernel="mq"`): all d+1 verify
    positions of every slot run as one batched forward instead of a scan of
    d+1 single-token steps — the XLA form of the Pallas mq hot-spot kernels
    (`kernels.paged_sparse_decode_attn_mq` / `paged_indexer_topk_mq`).

    Per layer: every position's K/V/indexer-K rows scatter FIRST (position
    j at `length0 + j`; frozen/masked rows to the sink page), then Top-K
    selection runs as a chain over the Q axis — row 0 warms from the
    incoming prev-Top-K, row j+1 from row j's selection, exactly the
    causally-extended GVR feedback the scan threads through its carry —
    and attention over all (B, Q) selections is ONE multi-query launch
    (`dsa_sparse_attention_paged_mq`).

    Bit-identity with the scan form: position j's consumers all mask
    beyond their own causal extent `length0 + j + 1` (indexer scores,
    sparse-attention validity, the dense fallback's length mask), and the
    NEG/-inf sentinels zero masked contributions exactly in f32, so the
    rows written by later positions — fresh here, stale under the scan —
    are arithmetically invisible; everything inside the extent was written
    by earlier positions identically in both forms. Frozen rows (j >
    draft_len) compute garbage at advanced positions (the scan computes
    different garbage at frozen positions) — their stack entries are never
    selected by the rollback (accept_len <= draft_len) and frozen eos
    argmaxes can never lower accept_len below a live position's, so the
    accept/rollback arithmetic sees identical inputs wherever it looks.

    Returns (ys, end_state) in `_spec_verify_scan`'s stack format, ready
    for `_spec_accept_rollback`.
    """
    b, d1 = tokens.shape
    hd = cfg.hd
    length0 = state["length"]
    table = state["page_table"]
    page_size = state["k_pages"].shape[2]
    sink = state["k_pages"].shape[1] - 1
    mp = table.shape[1]
    n = mp * page_size
    use_dsa = cfg.dsa.enabled and n > cfg.dsa.min_n
    if paged_attn not in ("fused", "gather"):
        raise ValueError(f"unknown paged_attn {paged_attn!r} "
                         f"(expected 'fused' or 'gather')")
    fused = paged_attn == "fused"

    jj = jnp.arange(d1, dtype=jnp.int32)
    positions = length0[:, None] + jj[None, :]           # (B, Q)
    lengths_q = positions + 1                            # causal extents
    live = jj[None, :] <= draft_len[:, None]             # (B, Q)
    flat_pos = positions.reshape(b * d1)

    off = positions % page_size
    phys = jnp.take_along_axis(table, positions // page_size, axis=1)
    writable = live & (phys >= 0) & (positions >= base_mwp[:, None])
    dest = jnp.where(writable, phys, sink)
    gather = jnp.clip(table, 0, sink)

    x = params["embed"][tokens]                          # (B, Q, D)
    x = constrain(x, rules, "batch", None, "d_model")

    def layer(x, carry):
        p = carry["p"]
        kp, vp = carry["k_pages"], carry["v_pages"]
        idx_kp = carry.get("idx_k_pages")
        prev_topk = carry.get("prev_topk")               # (B, K)
        topk_valid = carry.get("topk_valid")             # (B,)
        with jax.named_scope("step.qkv"):
            h = rms_norm(x, p["ln1"])                    # (B, Q, D)
            hf = h.reshape(b * d1, -1)
            q, kn, vn = _project_qkv(p, hf, b * d1, flat_pos, cfg, rules)
            q = q.reshape(b, d1, cfg.n_heads, hd)
            kn = kn.reshape(b, d1, cfg.n_kv_heads, hd)
            vn = vn.reshape(b, d1, cfg.n_kv_heads, hd)
        # all Q rows write before anything attends — safe because every
        # consumer masks beyond its own extent (see docstring)
        with jax.named_scope("step.kv_write"):
            kp = kp.at[dest, off].set(kn.astype(kp.dtype))
            vp = vp.at[dest, off].set(vn.astype(vp.dtype))

        out = {"k_pages": kp, "v_pages": vp}
        if use_dsa:
            with jax.named_scope("step.indexer"):
                ik = dsa_mod.indexer_k(p["indexer"], hf, flat_pos,
                                       dim=cfg.dsa.indexer_dim,
                                       rope_base=cfg.rope_base)
                ik = ik.reshape(b, d1, cfg.dsa.indexer_dim)
            with jax.named_scope("step.kv_write"):
                idx_kp = idx_kp.at[dest, off].set(ik.astype(idx_kp.dtype))
            with jax.named_scope("step.indexer"):
                idx_kc = idx_kp[gather].reshape(b, n, cfg.dsa.indexer_dim)

            # the per-row Top-K chain: the mq indexer kernel's VMEM
            # feedback threading, in XLA form — selection is inherently
            # sequential over Q (row j warms row j+1)
            def sel_row(cr, inp):
                prev, valid = cr
                h_j, len_j = inp
                sel = dsa_mod.dsa_select(
                    p["indexer"], h_j, idx_kc, prev, len_j,
                    k=prev.shape[-1], heads=cfg.dsa.indexer_heads,
                    dim=cfg.dsa.indexer_dim, rope_base=cfg.rope_base,
                    selector=cfg.dsa.selector, prev_valid=valid,
                    max_candidates=cfg.dsa.max_candidates,
                    gate_max_n=cfg.dsa.gate_max_n, min_n=cfg.dsa.min_n,
                    swa_window=cfg.swa_window, rules=rules, mesh=mesh)
                return ((sel.indices, jnp.ones_like(valid)),
                        (sel.indices, sel_telemetry(sel, valid)))

            _, (idx_all, tele) = jax.lax.scan(
                sel_row, (prev_topk, topk_valid),
                (jnp.swapaxes(h, 0, 1), jnp.swapaxes(lengths_q, 0, 1)))
            idx_q = jnp.swapaxes(idx_all, 0, 1)          # (B, Q, K)
            with jax.named_scope("step.attention"):
                if fused:
                    attn = dsa_mod.dsa_sparse_attention_paged_mq(
                        q, kp, vp, table, idx_q, lengths_q,
                        scale=hd ** -0.5, granularity=gather_granularity,
                        rules=rules)
                else:
                    kc = kp[gather].reshape(b, n, cfg.n_kv_heads, hd)
                    vc = vp[gather].reshape(b, n, cfg.n_kv_heads, hd)
                    attn = dsa_mod.dsa_sparse_attention(
                        q.reshape(b * d1, cfg.n_heads, hd),
                        jnp.repeat(kc, d1, axis=0),
                        jnp.repeat(vc, d1, axis=0),
                        idx_q.reshape(b * d1, -1), lengths_q.reshape(b * d1),
                        scale=hd ** -0.5, rules=rules)
                    attn = attn.reshape(b, d1, cfg.n_heads, hd)
            out["sel_idx"] = idx_all                      # (Q, B, K)
            out.update(tele)                              # (Q, B) each
            out["sel_valid"] = jnp.ones((d1,) + topk_valid.shape, bool)
        else:
            qf = q.reshape(b * d1, cfg.n_heads, hd)
            lf = lengths_q.reshape(b * d1)
            with jax.named_scope("step.attention"):
                if fused:
                    attn = decode_attention_paged(
                        qf, kp, vp, jnp.repeat(table, d1, axis=0), lf,
                        scale=hd ** -0.5, window=cfg.swa_window, rules=rules)
                else:
                    kc = kp[gather].reshape(b, n, cfg.n_kv_heads, hd)
                    vc = vp[gather].reshape(b, n, cfg.n_kv_heads, hd)
                    attn = decode_attention(
                        qf, jnp.repeat(kc, d1, axis=0),
                        jnp.repeat(vc, d1, axis=0), lf,
                        scale=hd ** -0.5, window=cfg.swa_window)
                attn = attn.reshape(b, d1, cfg.n_heads, hd)
            if prev_topk is not None:
                # pre-gate passthrough: the scan stacks the same incoming
                # feedback at every position
                out["sel_idx"] = jnp.broadcast_to(
                    prev_topk[None], (d1,) + prev_topk.shape)
                out["sel_valid"] = jnp.broadcast_to(
                    topk_valid[None], (d1,) + topk_valid.shape)
                none = sel_telemetry(None, topk_valid)
                out.update({k: jnp.broadcast_to(v[None], (d1,) + v.shape)
                            for k, v in none.items()})
        if idx_kp is not None:
            out["idx_k_pages"] = idx_kp

        with jax.named_scope("step.attention"):
            attn = attn.reshape(b, d1, cfg.n_heads * hd).astype(x.dtype)
            x = x + attn @ p["wo"]
        with jax.named_scope("step.mlp"):
            h2 = rms_norm(x, p["ln2"])
            if cfg.moe.num_experts:
                # MoE per position with the scan's (B, 1, D) call shape —
                # routing/capacity must see the same token batch per call
                mo = jax.lax.map(
                    lambda hh: _mlp(p, hh[:, None, :], cfg, mesh)[:, 0],
                    jnp.swapaxes(h2, 0, 1))
                m = jnp.swapaxes(mo, 0, 1)
            else:
                m = _mlp(p, h2, cfg, mesh)
            x = x + m
        x = constrain(x, rules, "batch", None, "d_model")
        return x, out

    carry_in = {"p": params["layers"], "k_pages": state["k_pages"],
                "v_pages": state["v_pages"]}
    if cfg.dsa.enabled:
        carry_in["idx_k_pages"] = state["idx_k_pages"]
        carry_in["prev_topk"] = state["prev_topk"]
        carry_in["topk_valid"] = state["topk_valid"]
    x, outs = jax.lax.scan(layer, x, carry_in)
    logits = _lm_head(params, x, cfg)                    # (B, Q, V)

    ys = {"logits": jnp.transpose(logits, (1, 0, 2))}    # (D+1, B, V)
    if cfg.dsa.enabled:
        ys["prev_topk"] = jnp.swapaxes(outs["sel_idx"], 0, 1)   # (Q, L, B, K)
        ys["topk_valid"] = jnp.swapaxes(outs["sel_valid"], 0, 1)
        ys["sel_gvr"] = jnp.swapaxes(outs["sel_gvr"], 0, 1)
        ys["sel_counts"] = sel_counts(outs, b)            # (Q, B, 3)
    end_state = dict(state)
    end_state["k_pages"] = outs["k_pages"]
    end_state["v_pages"] = outs["v_pages"]
    if cfg.dsa.enabled:
        end_state["idx_k_pages"] = outs["idx_k_pages"]
    return ys, end_state


def serve_step_spec_paged(params, state, tokens, cfg: ModelConfig, *,
                          draft_len, max_accept, eos_id: int = -1,
                          min_write_pos: Optional[jnp.ndarray] = None,
                          paged_attn: str = "fused",
                          verify_kernel: str = "scan",
                          gather_granularity: str = "token",
                          mesh=None, rules: Optional[MeshRules] = None):
    """Speculative verify tick over the paged layout: score all d+1 draft
    positions, accept the longest matching greedy prefix, and roll the
    decode state back to the accepted point in-graph (see the section
    comment above for the exact semantics and the bit-identity argument).
    tokens: (B, D+1) int32.

    `verify_kernel` picks the verify body — both are bit-identical in
    tokens, accept traces, feedback buffers and telemetry (shared
    `_spec_accept_rollback` arithmetic over provably-equal stacks):

    * "scan" — d+1 sequential `serve_step_paged` calls inside one jitted
      lax.scan (the PR-5 form; the reference).
    * "mq" — one multi-query-row forward: batched writes, the chained
      Top-K warm start, and ONE mq attention launch per layer
      (`_paged_verify_mq` — the served form of the Pallas mq kernels).

    Returns (out_tokens (B, D+1), accept_len (B,), logits_all (B, D+1, V),
    sel_gvr_pos (B, D+1), gvr_counts (B, 3), new_state) — see
    `_spec_verify_scan`.
    """
    b = tokens.shape[0]
    base_mwp = (min_write_pos if min_write_pos is not None
                else jnp.zeros((b,), jnp.int32))
    if verify_kernel not in ("scan", "mq"):
        raise ValueError(f"unknown verify_kernel {verify_kernel!r} "
                         f"(expected 'scan' or 'mq')")
    draft_len = jnp.asarray(draft_len, jnp.int32)
    max_accept = jnp.asarray(max_accept, jnp.int32)

    if verify_kernel == "mq":
        ys, end_state = _paged_verify_mq(
            params, state, tokens, cfg, draft_len=draft_len,
            base_mwp=base_mwp, paged_attn=paged_attn,
            gather_granularity=gather_granularity, mesh=mesh, rules=rules)
        return _spec_accept_rollback(state["length"], end_state, ys, tokens,
                                     draft_len, max_accept, int(eos_id),
                                     cfg.dsa.enabled)

    def step_fn(st, tok, mwp):
        return serve_step_paged(params, st, tok, cfg, min_write_pos=mwp,
                                paged_attn=paged_attn,
                                gather_granularity=gather_granularity,
                                mesh=mesh, rules=rules, with_counts=True)

    return _spec_verify_scan(step_fn, state, tokens, draft_len, max_accept,
                             int(eos_id), base_mwp,
                             paged_state_batch_axes(cfg), cfg.dsa.enabled)


def _sp_paged_verify_mq_body(params, state, tokens, draft_len, max_accept,
                             base_mwp, cfg: ModelConfig, *, eos_id: int,
                             seq_axis: str):
    """Per-device mq verify body (`verify_kernel="mq"` under sequence
    sharding) — `_paged_verify_mq` restructured over the shard-local page
    pools, running inside the `serve_step_sp_spec_paged` shard_map.

    Per layer: ALL d+1 positions' projections run batched and their
    K/V/indexer-K rows scatter into whichever shard owns each position
    (frozen/masked rows to the local sink), then the shard-local logical
    indexer view is built once and the Top-K chain + attention run per
    query row (`sp_dsa_decode_paged_local` — selection is inherently
    sequential over Q, and the O(K)-psum collective schedule is per-row,
    so the tick's collective count matches the scan form's d+1 schedules;
    the win is the batched projection/write work). Bit-identity with the
    scan form follows the single-device mq argument: every consumer masks
    beyond its own causal extent, so later-position rows — fresh here,
    stale under the scan — contribute exactly zero, and frozen rows'
    garbage stacks are never selected by the shared rollback arithmetic.

    Returns the serve_step_spec_paged 6-tuple (replicated outputs + the
    per-shard end state), via `_spec_accept_rollback`.
    """
    from repro.sparse import sp_dsa as sp_dsa_mod

    b, d1 = tokens.shape
    hd = cfg.hd
    never = jnp.int32(PAGED_NEVER_WRITE)
    length0 = state["length"]
    ppl = state["k_pages"].shape[2] - 1                  # pages per shard
    page_size = state["k_pages"].shape[3]
    mp = state["page_table"].shape[1]
    num_shards = jax.lax.axis_size(seq_axis)
    mp_local = mp // num_shards
    n_local = mp_local * page_size
    kk = state["prev_topk"].shape[-1]

    my = jax.lax.axis_index(seq_axis)
    shard_offset = (my * n_local).astype(jnp.int32)
    table = state["page_table"]
    table_local = jax.lax.dynamic_slice_in_dim(
        table, my * mp_local, mp_local, axis=1)
    sink = ppl

    jj = jnp.arange(d1, dtype=jnp.int32)
    positions = length0[:, None] + jj[None, :]           # (B, Q)
    lengths_q = positions + 1
    live = jj[None, :] <= draft_len[:, None]
    mwp_q = jnp.where(live, base_mwp[:, None], never)
    flat_pos = positions.reshape(b * d1)

    owner = ((positions >= shard_offset)
             & (positions < shard_offset + n_local))
    rel = jnp.clip(positions - shard_offset, 0, n_local - 1)
    phys = jnp.take_along_axis(table_local, rel // page_size, axis=1)
    writable = owner & (phys >= 0) & (positions >= mwp_q)
    dest = jnp.where(writable, phys, sink)
    off = positions % page_size
    gather_local = jnp.clip(table_local, 0, sink)

    x = params["embed"][tokens]                          # (B, Q, D)

    def layer(x, carry):
        p = carry["p"]
        kp, vp = carry["k_pages"], carry["v_pages"]
        idx_kp = carry["idx_k_pages"]
        prev_topk = carry["prev_topk"]                   # (B, K)
        topk_valid = carry.get("topk_valid")             # (B,)
        with jax.named_scope("step.qkv"):
            h = rms_norm(x, p["ln1"])                    # (B, Q, D)
            hf = h.reshape(b * d1, -1)
            q, kn, vn = _project_qkv(p, hf, b * d1, flat_pos, cfg, None)
            q = q.reshape(b, d1, cfg.n_heads, hd)
            kn = kn.reshape(b, d1, cfg.n_kv_heads, hd)
            vn = vn.reshape(b, d1, cfg.n_kv_heads, hd)
        with jax.named_scope("step.indexer"):
            ik = dsa_mod.indexer_k(p["indexer"], hf, flat_pos,
                                   dim=cfg.dsa.indexer_dim,
                                   rope_base=cfg.rope_base)
            ik = ik.reshape(b, d1, cfg.dsa.indexer_dim)
        with jax.named_scope("step.kv_write"):
            kp = kp.at[dest, off].set(kn.astype(kp.dtype))
            vp = vp.at[dest, off].set(vn.astype(vp.dtype))
            idx_kp = idx_kp.at[dest, off].set(ik.astype(idx_kp.dtype))
        with jax.named_scope("step.indexer"):
            idx_kc = idx_kp[gather_local].reshape(b, n_local,
                                                  cfg.dsa.indexer_dim)

        def sel_row(cr, inp):
            prev, valid = cr
            q_j, h_j, len_j = inp
            res = sp_dsa_mod.sp_dsa_decode_paged_local(
                q_j, kp, vp, table_local, p["indexer"], h_j, idx_kc,
                prev, valid, len_j,
                k=kk, scale=hd ** -0.5, heads=cfg.dsa.indexer_heads,
                dim=cfg.dsa.indexer_dim, rope_base=cfg.rope_base,
                shard_offset=shard_offset, page_size=page_size,
                max_candidates=cfg.dsa.max_candidates,
                swa_window=cfg.swa_window, seq_axis=seq_axis)
            return ((res.new_topk, jnp.ones_like(valid)),
                    (res.attn_out, res.new_topk, sel_telemetry(res, valid)))

        valid0 = (topk_valid if topk_valid is not None
                  else jnp.ones((b,), bool))
        _, (attn_all, idx_all, tele) = jax.lax.scan(
            sel_row, (prev_topk, valid0),
            (jnp.swapaxes(q, 0, 1), jnp.swapaxes(h, 0, 1),
             jnp.swapaxes(lengths_q, 0, 1)))

        out = {"k_pages": kp, "v_pages": vp, "idx_k_pages": idx_kp,
               "sel_idx": idx_all,                       # (Q, B, K)
               "sel_valid": jnp.ones((d1, b), bool), **tele}   # (Q, B)
        with jax.named_scope("step.attention"):
            attn = jnp.swapaxes(attn_all, 0, 1)          # (B, Q, H, HD)
            attn = attn.reshape(b, d1, cfg.n_heads * hd).astype(x.dtype)
            x = x + attn @ p["wo"]
        with jax.named_scope("step.mlp"):
            h2 = rms_norm(x, p["ln2"])
            if cfg.moe.num_experts:
                mo = jax.lax.map(
                    lambda hh: _mlp(p, hh[:, None, :], cfg, None)[:, 0],
                    jnp.swapaxes(h2, 0, 1))
                m = jnp.swapaxes(mo, 0, 1)
            else:
                m = _mlp(p, h2, cfg, None)
            x = x + m
        return x, out

    carry_in = {"p": params["layers"],
                "k_pages": state["k_pages"][:, 0],
                "v_pages": state["v_pages"][:, 0],
                "idx_k_pages": state["idx_k_pages"][:, 0],
                "prev_topk": state["prev_topk"]}
    if "topk_valid" in state:
        carry_in["topk_valid"] = state["topk_valid"]
    x, outs = jax.lax.scan(layer, x, carry_in)
    logits = _lm_head(params, x, cfg)                    # (B, Q, V)

    ys = {"logits": jnp.transpose(logits, (1, 0, 2)),
          "prev_topk": jnp.swapaxes(outs["sel_idx"], 0, 1),
          "topk_valid": jnp.swapaxes(outs["sel_valid"], 0, 1),
          "sel_gvr": jnp.swapaxes(outs["sel_gvr"], 0, 1),
          "sel_counts": sel_counts(outs, b)}              # (Q, B, 3)
    end_state = dict(state)
    for key in ("k_pages", "v_pages", "idx_k_pages"):
        end_state[key] = outs[key][:, None]              # restore shard axis
    return _spec_accept_rollback(length0, end_state, ys, tokens, draft_len,
                                 max_accept, eos_id, True)


def serve_step_sp_spec_paged(params, state, tokens, cfg: ModelConfig, *,
                             mesh, draft_len, max_accept, eos_id: int = -1,
                             min_write_pos: Optional[jnp.ndarray] = None,
                             verify_kernel: str = "scan",
                             seq_axis: str = "seq",
                             rules: Optional[MeshRules] = None):
    """Sequence-sharded speculative verify tick: the same verify semantics
    as `serve_step_spec_paged`, with the per-device sharded body
    (`_sp_paged_token_body`) and the whole verify — including the in-graph
    acceptance/rollback, which is replicated arithmetic — inside ONE
    shard_map over the mesh's `seq_axis`. Per position the collective
    schedule is exactly the non-speculative sharded step's (O(1) in
    context length), so a verify tick costs d+1 of those schedules and
    nothing more. Bit-identical to the single-device
    `serve_step_spec_paged` over the same logical cache content, which is
    what pins spec == non-spec on sharded meshes (tests/test_spec.py).

    `verify_kernel` picks the verify body, as in the single-device step:
    "scan" runs d+1 sequential sharded token steps; "mq" batches each
    layer's projections/writes across all positions and chains selection
    per row (`_sp_paged_verify_mq_body`) — bit-identical in tokens,
    accept traces, feedback and telemetry.
    """
    b = tokens.shape[0]
    _sp_paged_validate(state, cfg, mesh, seq_axis)
    if verify_kernel not in ("scan", "mq"):
        raise ValueError(f"unknown verify_kernel {verify_kernel!r} "
                         f"(expected 'scan' or 'mq')")
    base_mwp = (min_write_pos if min_write_pos is not None
                else jnp.zeros((b,), jnp.int32))
    axes = sp_paged_state_batch_axes(cfg)

    def body(params, state, tokens, draft_len, max_accept, base_mwp):
        if verify_kernel == "mq":
            return _sp_paged_verify_mq_body(params, state, tokens,
                                            draft_len, max_accept, base_mwp,
                                            cfg, eos_id=int(eos_id),
                                            seq_axis=seq_axis)

        def step_fn(st, tok, mwp):
            return _sp_paged_token_body(params, st, tok, mwp, cfg,
                                        seq_axis=seq_axis)
        return _spec_verify_scan(step_fn, state, tokens, draft_len,
                                 max_accept, int(eos_id), base_mwp, axes,
                                 cfg.dsa.enabled)

    pool_spec = P(None, seq_axis)
    st_spec = {key: (pool_spec if key in ("k_pages", "v_pages", "idx_k_pages")
                     else P()) for key in state}
    param_spec = jax.tree.map(lambda _: P(), params)
    fn = jax.shard_map(body, mesh=mesh,
                   in_specs=(param_spec, st_spec, P(), P(), P(), P()),
                   out_specs=(P(), P(), P(), P(), P(), st_spec),
                   check_vma=False)
    return fn(params, state, tokens, jnp.asarray(draft_len, jnp.int32),
              jnp.asarray(max_accept, jnp.int32), base_mwp)
