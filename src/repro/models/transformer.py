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
when enabled. The paged layouts decode through one forward over Q query
rows a slot per placement — `_paged_forward` on one device,
`_sp_paged_forward` per shard of a sequence mesh: Q = 1 is a decode step,
Q = d+1 a speculative verify tick. Latent attention (MLA) has its own
paged body, `_mla_serve_step_paged`.

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
from .layers import (_rope_freqs, apply_rotary, blockwise_causal_attention,
                     decode_attention, decode_attention_paged,
                     held_experts_mlp, moe_mlp_ep, pool_read, rms_norm,
                     route_grouped_sigmoid, swiglu_mlp, yarn_freqs,
                     yarn_mscale)


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
    if cfg.is_mla:
        return _init_mla_params(key, cfg)
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
    _mla_refuse("param_specs (the sharded parameter layout)", cfg)
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
    if cfg.is_mla:
        return _mla_forward_train(params, tokens, cfg, rules=rules,
                                  remat=remat)
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
    _mla_refuse("init_decode_state (the dense-layout decode state)", cfg)
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
    _mla_refuse("state_specs (the dense-layout decode state)", cfg)
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
                   cfg: ModelConfig, use_dsa: bool, rules, mesh):
    """The dense layout's decode-attention core (`serve_step`).

    Scoring/selection run over the *logical* contiguous indexer view:
    indexer scores, Top-K selection, the prev-Top-K feedback and the
    sel_gvr telemetry live in logical token space and never see a physical
    page id (the layout invariant GVR's temporal prediction depends on);
    attention reads the contiguous K/V views `kc`/`vc`. The paged forms
    are `_paged_forward` and `_sp_paged_forward`."""
    hd = cfg.hd
    out = {}
    if use_dsa:
        res = dsa_mod.dsa_decode(
            q, kc, vc, p["indexer"], h, idx_kc, prev_topk, new_len,
            k=prev_topk.shape[-1], scale=hd ** -0.5,
            heads=cfg.dsa.indexer_heads, dim=cfg.dsa.indexer_dim,
            rope_base=cfg.rope_base, selector=cfg.dsa.selector,
            prev_valid=topk_valid, max_candidates=cfg.dsa.max_candidates,
            gate_max_n=cfg.dsa.gate_max_n, min_n=cfg.dsa.min_n,
            swa_window=cfg.swa_window, rules=rules, mesh=mesh)
        out["prev_topk"] = res.topk_idx
        if topk_valid is not None:
            # a DSA step just wrote genuine feedback → rows become warm
            out["topk_valid"] = jnp.ones_like(topk_valid)
            out.update(sel_telemetry(res, topk_valid))
        return res.attn_out, out
    with jax.named_scope("step.attention"):
        attn = decode_attention(q, kc, vc, new_len, scale=hd ** -0.5,
                                window=cfg.swa_window)
    if prev_topk is not None:
        out["prev_topk"] = prev_topk
        if topk_valid is not None:
            out["topk_valid"] = topk_valid
            out.update(sel_telemetry(None, topk_valid))
    return attn, out


# A paged forward runs Q query rows a slot: row j of slot b sits at
# position length[b] + j, and the B·Q rows are laid out slot-major, (B·Q,
# ...). Q = 1 is a decode step; every helper below then returns its input
# or calls its function once, so the one-row program has no row axis, no
# repeat and no loop of trip count 1.

def _by_row(a, q: int):
    """Slot-major rows (B·Q, ...) → (Q, B, ...), one entry a query row."""
    return jnp.swapaxes(a.reshape((-1, q) + a.shape[1:]), 0, 1)


def _flat_rows(a, q: int):
    """(Q, B, ...) → slot-major rows (B·Q, ...); Q = 1: `a` is (B, ...)."""
    if q == 1:
        return a
    return jnp.swapaxes(a, 0, 1).reshape((-1,) + a.shape[2:])


def _per_row(a, q: int):
    """A slot's array (B, ...) repeated for each of its Q rows (B·Q, ...)."""
    return a if q == 1 else jnp.repeat(a, q, axis=0)


def _row_stack(a, q: int):
    """The same (B, ...) value at each of the Q rows: (Q, B, ...)."""
    return a if q == 1 else jnp.broadcast_to(a[None], (q,) + a.shape)


def _row_positions(length, q: int):
    """The positions (B·Q,) of the rows: length[b] + j, slot-major."""
    if q == 1:
        return length
    return (length[:, None] + jnp.arange(q, dtype=length.dtype)).reshape(-1)


def _rows_chain(fn, carry, rows, q: int):
    """`fn(carry, row) -> (carry, out)` over the Q rows in order, each
    row's carry feeding the next (the Top-K warm start: row j+1 warms
    from row j). rows: slot-major (B·Q, ...) arrays. Returns the outs,
    (Q, B, ...) each; for Q = 1 fn's one call, (B, ...)."""
    if q == 1:
        return fn(carry, rows)[1]
    return jax.lax.scan(fn, carry,
                        jax.tree.map(lambda a: _by_row(a, q), rows))[1]


def _project_rows(p, x, positions, cfg: ModelConfig, rules, indexer: bool):
    """A paged layer's projections of its rows x (T, D) at `positions`
    (T,): the normed input h, the queries (T, H, HD), the new K and V rows
    (T, KVH, HD) and, with `indexer`, the new indexer keys (T, d_i)."""
    with jax.named_scope("step.qkv"):
        h = rms_norm(x, p["ln1"])
        q, kn, vn = _project_qkv(p, h, x.shape[0], positions, cfg, rules)
    ik = None
    if indexer:
        with jax.named_scope("step.indexer"):
            ik = dsa_mod.indexer_k(p["indexer"], h, positions,
                                   dim=cfg.dsa.indexer_dim,
                                   rope_base=cfg.rope_base)
    return h, q, kn, vn, ik


def _decode_out_mlp(p, x, attn, cfg: ModelConfig, mesh, rules, q: int = 1):
    """The rest of one decode layer over its rows: the attention output
    projection and the residual MLP/MoE block. attn: (T, H, HD); x: (T, D),
    slot-major rows of Q a slot. The expert block runs one (B, 1, D) call
    a row position, the call a one-row step makes, so that routing (and
    an expert-parallel capacity) sees the same token batch."""
    with jax.named_scope("step.attention"):
        attn = attn.reshape(x.shape[0], cfg.n_heads * cfg.hd).astype(x.dtype)
        x = x + attn @ p["wo"]
    with jax.named_scope("step.mlp"):
        h = rms_norm(x, p["ln2"])
        if cfg.moe.num_experts:
            def moe(hh):
                return _mlp(p, hh[:, None, :], cfg, mesh)[:, 0]
            m = (moe(h) if q == 1
                 else _flat_rows(jax.lax.map(moe, _by_row(h, q)), q))
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
    log), for those rows GVR's secant iterations and whether its safety
    net ran (`sel_iters`, `sel_fallback`: values of other rows mean
    nothing), and which rows had their radix path computed (`sel_radix`);
    all but `sel_gvr` leave the step only as `sel_counts`. `res` is the
    layer's DSAOutput, SelectorOutput or SPDSAPagedResult; None where no
    selection ran (the dense fallback). `valid` is the (B,) bool feedback
    validity, for shape."""
    none = jnp.zeros_like(valid)
    if res is None:
        return {"sel_gvr": none, "sel_iters": none.astype(jnp.int32),
                "sel_fallback": none, "sel_radix": none}
    iters = res.secant_iters       # None where lax.top_k served every row
    return {"sel_gvr": res.gvr_rows,
            "sel_iters": (none if iters is None else iters).astype(jnp.int32),
            "sel_fallback": res.fallback, "sel_radix": res.radix_rows}


# the columns of the counts a step returns with `with_counts`: the
# selector's (`sel_counts`), then, where the step's expert layers hold a
# share of the routed experts, the routed (token, expert) assignments held
GVR_COUNTERS = ("gvr_row_layers", "gvr_secant_iters", "gvr_fallbacks",
                "radix_row_layers")
HELD_EXPERT_COUNTERS = ("held_expert_rows",)


def step_counters(cfg: ModelConfig) -> Tuple[str, ...]:
    """The names of the counts' columns, in order: the MLA step's expert
    layers (`held_experts_mlp`) add HELD_EXPERT_COUNTERS."""
    return GVR_COUNTERS + (HELD_EXPERT_COUNTERS if cfg.is_mla else ())


def sel_counts(outs, b: int) -> jnp.ndarray:
    """Per row, summed over the layer scan's leading (layer) axis of its
    `sel_telemetry` stacks in `outs` ((L, ..., B) each): [GVR-served
    row-layers, their secant iterations, their safety-net fallbacks,
    row-layers whose radix path was computed] — (..., B, 4) int32; zeros
    (B, 4) where no layer carries telemetry (DSA off). The counts a step
    returns with `with_counts=True`."""
    if "sel_gvr" not in outs:
        return jnp.zeros((b, 4), jnp.int32)
    gvr = outs["sel_gvr"]
    return jnp.stack(
        [jnp.sum(gvr, axis=0, dtype=jnp.int32),
         jnp.sum(jnp.where(gvr, outs["sel_iters"], 0), axis=0,
                 dtype=jnp.int32),
         jnp.sum(gvr & outs["sel_fallback"], axis=0, dtype=jnp.int32),
         jnp.sum(outs["sel_radix"], axis=0, dtype=jnp.int32)],
        axis=-1)


def serve_step(params, state, tokens, cfg: ModelConfig, *, mesh=None,
               rules: Optional[MeshRules] = None, with_counts: bool = False):
    """One decode step. tokens: (B,) int32. Returns (logits (B,V), state),
    and with `with_counts` the rows' GVR counts (B, 4) third (`sel_counts`).

    Per layer: append KV (and indexer K) at position `length`, then attend —
    DSA sparse path when enabled and the cache is long enough, dense
    otherwise. prev-Top-K feedback is updated in place (the paper's
    per-layer prev_topk buffer).
    """
    _mla_refuse("serve_step (the dense-layout decode body)", cfg)
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
    _mla_refuse("init_sp_paged_decode_state (the sequence-sharded decode "
                "state)", cfg)
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


_SP_POOLS = ("k_pages", "v_pages", "idx_k_pages")


def _sp_specs(params, state, seq_axis: str):
    """shard_map specs of the sharded step's parameters (replicated) and
    state (the pools split over `seq_axis`, the rest replicated)."""
    st = {key: P(None, seq_axis) if key in _SP_POOLS else P()
          for key in state}
    return jax.tree.map(lambda _: P(), params), st


def _sp_paged_forward(params, state, tokens, min_write_pos, cfg: ModelConfig,
                      *, q: int, seq_axis: str):
    """The sequence-sharded paged forward over Q query rows a slot, per
    device, inside a shard_map over `seq_axis`: the pools arrive as this
    device's shard (L, 1, PL + 1, page_size, ...), everything else
    replicated. tokens, min_write_pos: (B·Q,) slot-major.

    The single-device forward (`_paged_forward`) with two differences: a
    row writes where this shard owns its position (else to the shard's
    sink page), and each row's selection and attention run in
    `sp_dsa_decode_paged_local` (SP-GVR's O(1)-collective Top-K, the
    selected rows assembled with one O(K) psum), row j+1 warm-starting
    from row j. The pools ride the layer scan's carry as one shard's
    stacks (L, PL + 1, page_size, ...). Returns what `_paged_forward`
    returns, the pools' shard axis restored."""
    from repro.sparse import sp_dsa as sp_dsa_mod

    b = state["length"].shape[0]
    hd = cfg.hd
    sink = state["k_pages"].shape[2] - 1                # local sink page
    page_size = state["k_pages"].shape[3]
    mp_local = state["page_table"].shape[1] // jax.lax.axis_size(seq_axis)
    n_local = mp_local * page_size

    my = jax.lax.axis_index(seq_axis)
    shard_offset = (my * n_local).astype(jnp.int32)
    table_local = jax.lax.dynamic_slice_in_dim(
        state["page_table"], my * mp_local, mp_local, axis=1)
    positions = _row_positions(state["length"], q)
    lengths = positions + 1
    # this shard writes a row iff it owns the row's position
    owner = (positions >= shard_offset) & (positions < shard_offset + n_local)
    rel = jnp.clip(positions - shard_offset, 0, n_local - 1)
    phys = jnp.take_along_axis(table_local, (rel // page_size).reshape(b, q),
                               axis=1).reshape(-1)
    writable = owner & (phys >= 0) & (positions >= min_write_pos)
    dest = jnp.where(writable, phys, sink)
    off = positions % page_size                      # page-aligned spans
    gather_local = jnp.clip(table_local, 0, sink)

    def layer(carry, xs):
        x, pools = carry
        p, li = xs["p"], xs["layer"]
        h, qh, kn, vn, ik = _project_rows(p, x, positions, cfg, None, True)
        with jax.named_scope("step.kv_write"):
            pools = {key: _pool_write(pools[key], li, dest, off, row)
                     for key, row in (("k_pages", kn), ("v_pages", vn),
                                      ("idx_k_pages", ik))}
        with jax.named_scope("step.indexer"):
            # shard-local logical indexer view: N/S × d_i per device — the
            # irreducible indexer read, split across the mesh
            idx_kc = _pool_view(pools["idx_k_pages"], li, gather_local,
                                n_local)

        def select_attend(carry, row):
            prev, valid = carry
            q_j, h_j, len_j = row
            res = sp_dsa_mod.sp_dsa_decode_paged_local(
                q_j, pools["k_pages"], pools["v_pages"], table_local,
                p["indexer"], h_j, idx_kc, prev, valid, len_j,
                k=prev.shape[-1], scale=hd ** -0.5,
                heads=cfg.dsa.indexer_heads, dim=cfg.dsa.indexer_dim,
                rope_base=cfg.rope_base, shard_offset=shard_offset,
                page_size=page_size, max_candidates=cfg.dsa.max_candidates,
                swa_window=cfg.swa_window, seq_axis=seq_axis, layer=li)
            warm = jnp.ones_like(valid)
            return (res.new_topk, warm), dict(
                attn=res.attn_out, prev_topk=res.new_topk, topk_valid=warm,
                **sel_telemetry(res, valid))

        out = _rows_chain(select_attend, (xs["prev_topk"], xs["topk_valid"]),
                          (qh, h, lengths), q)
        attn = _flat_rows(out.pop("attn"), q)
        x = _decode_out_mlp(p, x, attn, cfg, None, None, q)
        return (x, pools), out

    pools = {key: state[key][:, 0] for key in _SP_POOLS}
    xs = {"p": params["layers"], "layer": jnp.arange(cfg.n_layers),
          "prev_topk": state["prev_topk"], "topk_valid": state["topk_valid"]}
    (x, pools), outs = jax.lax.scan(layer, (params["embed"][tokens], pools),
                                    xs)
    new_state = dict(state, **{key: v[:, None] for key, v in pools.items()})
    return _lm_head(params, x, cfg), new_state, outs


def serve_step_sp_paged(params, state, tokens, cfg: ModelConfig, *, mesh,
                        min_write_pos: Optional[jnp.ndarray] = None,
                        seq_axis: str = "seq",
                        rules: Optional[MeshRules] = None,
                        with_counts: bool = False):
    """One sequence-sharded paged decode step (inside a shard_map over the
    mesh's `seq_axis`): the Q = 1 call of `_sp_paged_forward`. tokens:
    (B,) int32. Returns (logits, state), and with `with_counts` the rows'
    GVR counts (B, 4) third (`sel_counts`).

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
    _mla_refuse("serve_step_sp_paged (the sequence-sharded decode body)", cfg)
    b = tokens.shape[0]
    _sp_paged_validate(state, cfg, mesh, seq_axis)
    mwp = (min_write_pos if min_write_pos is not None
           else jnp.zeros((b,), jnp.int32))

    def body(params, state, tokens, mwp):
        logits, new_state, outs = _sp_paged_forward(
            params, state, tokens, mwp, cfg, q=1, seq_axis=seq_axis)
        return _decode_result(logits, new_state, outs, with_counts)

    param_spec, st_spec = _sp_specs(params, state, seq_axis)
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
# One forward over Q query rows a slot (`_paged_forward`) serves the decode
# step (Q = 1) and the speculative verify tick (Q = d+1): each layer
# scatters every row's K/V (and indexer-K) entries into the slots' pages,
# selects per row and attends once over all rows. The sequence-sharded
# placement is the same forward per shard (`_sp_paged_forward`). The
# sparse-attention stage is block-table-native by default
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

    Each pool is (L, num_pages + 1, page_size, row width), a GQA K/V row
    flattened to one KVH·HD axis. The step updates a pool in place only
    in the chip's default layout for its shape, and a row as one minor
    axis keeps that layout row-major: with (KVH, HD) = (8, 64) minor the
    chip lays the page axis out minor, and every step would relayout the
    whole pool on entry and exit.
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    if max_len % page_size != 0:
        raise ValueError(f"max_len ({max_len}) must be a multiple of "
                         f"page_size ({page_size})")
    l, hd = cfg.n_layers, cfg.hd
    mp = max_len // page_size
    state = {
        "page_table": jnp.full((batch, mp), -1, jnp.int32),
        "length": jnp.zeros((batch,), jnp.int32),
    }
    if cfg.is_mla:
        # one latent pool: each row [c_kv | k_r], shared by every head
        state["kv_pages"] = jnp.zeros(
            (l, num_pages + 1, page_size,
             cfg.kv_lora_rank + cfg.qk_rope_head_dim), dtype)
    else:
        shape = (l, num_pages + 1, page_size, cfg.n_kv_heads * hd)
        state["k_pages"] = jnp.zeros(shape, dtype)
        state["v_pages"] = jnp.zeros(shape, dtype)
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


def _paged_targets(state, pool: str, min_write_pos, q: int = 1):
    """Where a paged step writes the new tokens of its B·Q query rows
    (slot-major, row j of slot b at length[b] + j): (positions, causal
    extents, dest page (the sink page for masked rows), offset, the clipped
    table for logical views, logical extent)."""
    positions = _row_positions(state["length"], q)
    new_len = positions + 1
    table = state["page_table"]
    page_size = state[pool].shape[2]
    sink = state[pool].shape[1] - 1
    lp = positions // page_size
    off = positions % page_size
    phys = jnp.take_along_axis(table, lp.reshape(table.shape[0], q),
                               axis=1).reshape(-1)
    writable = phys >= 0
    if min_write_pos is not None:
        writable &= positions >= min_write_pos
    dest = jnp.where(writable, phys, sink)
    return (positions, new_len, dest, off, jnp.clip(table, 0, sink),
            table.shape[1] * page_size)


# The paged forwards carry the layer-stacked page pools (L, P + 1,
# page_size, row) whole through their layer scans and touch them only by
# one scatter of all the rows' new entries at [layer, dest, off]
# (`_pool_write`) and by reads that fold the layer into their gather
# indices (`layers.pool_read`, `_pool_view`). A layer slice `pool[layer]`,
# or a pool in the scan's xs/ys, would copy the layer's pool on every step.

def _pool_write(pool, layer, dest, off, row):
    """Scatter the T new rows (T, ...) into layer `layer` of a stacked pool."""
    row = row.reshape(row.shape[:1] + pool.shape[3:])
    return pool.at[layer, dest, off].set(row.astype(pool.dtype))


def _pool_view(pool, layer, gather, n):
    """Layer `layer`'s logical view (B, n, ...) of a stacked pool, read
    through the clipped block table `gather` (B, MP)."""
    return pool_read(pool, layer, gather).reshape(
        (gather.shape[0], n) + pool.shape[3:])


def _paged_forward(params, state, tokens, cfg: ModelConfig, *, q: int,
                   min_write_pos, paged_attn: str, gather_granularity: str,
                   mesh, rules):
    """The one-device paged forward over Q query rows a slot: a decode step
    is Q = 1 (`serve_step_paged`), a speculative verify tick Q = d+1
    (`serve_step_spec_paged`). tokens, min_write_pos: (B·Q,) slot-major,
    row j of slot b at position length[b] + j.

    Per layer: project all rows; write every row's K/V (and indexer-K)
    before anything attends, in one scatter a pool; build the logical
    indexer view once; run the Top-K chain over the Q rows, row 0 warm
    from the incoming `prev_topk`, row j+1 from row j; then one attention
    call over every row's selection (or the dense pre-gate fallback over
    every row's extent); the out-projection and MLP. Row j's consumers mask
    beyond its own causal extent length + j + 1, so the rows written by
    later rows of the tick contribute exactly zero.

    Returns (logits (B·Q, V) f32, the state with the written pools, outs):
    outs holds the layer scan's feedback stacks — `prev_topk` (L, B, K),
    `topk_valid` and the `sel_telemetry` entries (L, B), with a row axis
    (L, Q, B, ...) where Q > 1 — where the DSA state is carried.
    """
    b = state["length"].shape[0]
    hd = cfg.hd
    x = constrain(params["embed"][tokens], rules, "batch", "d_model")
    # `gather` reads logical views: unmapped pages clip to page 0 —
    # garbage rows, dead beyond `length` under the NEG_SENTINEL masking
    # convention (finite values, so their post-mask contribution is exactly
    # zero, as in the dense layout). The fused forms read the indexer-K
    # view alone (and the dense pre-DSA fallback its view); sparse
    # attention addresses the page pools through the raw table, masking
    # the -1 sentinel explicitly (dsa_sparse_attention_paged).
    positions, lengths, dest, off, gather, n = _paged_targets(
        state, "k_pages", min_write_pos, q)
    table = _per_row(state["page_table"], q)

    if paged_attn not in ("fused", "gather"):
        raise ValueError(f"unknown paged_attn {paged_attn!r} "
                         f"(expected 'fused' or 'gather')")
    use_dsa = cfg.dsa.enabled and n > cfg.dsa.min_n
    # fused covers both attention forms: the sparse (DSA) stage gathers its
    # Top-K rows from the pools, and the dense pre-DSA fallback attends the
    # full logical extent through decode_attention_paged — either way the
    # forward never materializes the K/V logical views itself
    fused = paged_attn == "fused"

    def layer(carry, xs):
        x, pools = carry
        p, li = xs["p"], xs["layer"]
        h, qh, kn, vn, ik = _project_rows(p, x, positions, cfg, rules,
                                          use_dsa)
        with jax.named_scope("step.kv_write"):
            pools = dict(pools,
                         k_pages=_pool_write(pools["k_pages"], li, dest, off,
                                             kn),
                         v_pages=_pool_write(pools["v_pages"], li, dest, off,
                                             vn))
            if use_dsa:
                pools["idx_k_pages"] = _pool_write(
                    pools["idx_k_pages"], li, dest, off, ik)
        kp, vp = pools["k_pages"], pools["v_pages"]
        if not fused:
            with jax.named_scope("step.attention"):
                heads = (b, n, cfg.n_kv_heads, hd)
                kc, vc = (_per_row(constrain(
                    _pool_view(pool, li, gather, n).reshape(heads), rules,
                    "batch", None, None, None), q) for pool in (kp, vp))

        out = {}
        if use_dsa:
            with jax.named_scope("step.indexer"):
                # the indexer scores all N tokens (paper Table 2:
                # irreducible O(N·d_i)), so its logical view costs what
                # scoring in page space would — and keeps scores/Top-K in
                # logical order
                idx_kc = _pool_view(pools["idx_k_pages"], li, gather, n)

            def select(carry, row):
                prev, valid = carry
                h_j, len_j = row
                sel = dsa_mod.dsa_select(
                    p["indexer"], h_j, idx_kc, prev, len_j, k=prev.shape[-1],
                    heads=cfg.dsa.indexer_heads, dim=cfg.dsa.indexer_dim,
                    rope_base=cfg.rope_base, selector=cfg.dsa.selector,
                    prev_valid=valid, max_candidates=cfg.dsa.max_candidates,
                    gate_max_n=cfg.dsa.gate_max_n, min_n=cfg.dsa.min_n,
                    swa_window=cfg.swa_window, rules=rules, mesh=mesh)
                # a DSA step just wrote genuine feedback → rows become warm
                warm = jnp.ones_like(valid)
                return (sel.indices, warm), dict(
                    prev_topk=sel.indices, topk_valid=warm,
                    **sel_telemetry(sel, valid))

            out = _rows_chain(select, (xs["prev_topk"], xs["topk_valid"]),
                              (h, lengths), q)
            idx = _flat_rows(out["prev_topk"], q)
            with jax.named_scope("step.attention"):
                if fused:
                    attn = dsa_mod.dsa_sparse_attention_paged(
                        qh, kp, vp, table, idx, lengths, scale=hd ** -0.5,
                        granularity=gather_granularity, rules=rules,
                        layer=li)
                else:
                    attn = dsa_mod.dsa_sparse_attention(
                        qh, kc, vc, idx, lengths, scale=hd ** -0.5,
                        rules=rules)
        else:
            with jax.named_scope("step.attention"):
                if fused:
                    attn = decode_attention_paged(
                        qh, kp, vp, table, lengths, scale=hd ** -0.5,
                        window=cfg.swa_window, rules=rules, layer=li)
                else:
                    attn = decode_attention(qh, kc, vc, lengths,
                                            scale=hd ** -0.5,
                                            window=cfg.swa_window)
            if cfg.dsa.enabled:
                # before the gate opens the feedback passes through
                valid = xs["topk_valid"]
                out = dict(prev_topk=xs["prev_topk"], topk_valid=valid,
                           **sel_telemetry(None, valid))
                out = {key: _row_stack(v, q) for key, v in out.items()}
        x = _decode_out_mlp(p, x, attn, cfg, mesh, rules, q)
        return (x, pools), out

    pools = {key: state[key] for key in ("k_pages", "v_pages")}
    if use_dsa:
        pools["idx_k_pages"] = state["idx_k_pages"]
    xs = {"p": params["layers"], "layer": jnp.arange(cfg.n_layers)}
    if cfg.dsa.enabled:
        xs["prev_topk"] = state["prev_topk"]
        xs["topk_valid"] = state["topk_valid"]
    (x, pools), outs = jax.lax.scan(layer, (x, pools), xs)
    return _lm_head(params, x, cfg), dict(state, **pools), outs


def _decode_result(logits, state, outs, with_counts: bool):
    """A one-row forward as a decode step: the state takes the feedback the
    layer scan left and length + 1; `with_counts` adds the rows'
    `sel_counts` (B, 4) third."""
    state = dict(state)
    state.update({key: outs[key] for key in ("prev_topk", "topk_valid",
                                             "sel_gvr") if key in outs})
    state["length"] = state["length"] + 1
    if with_counts:
        return logits, state, sel_counts(outs, logits.shape[0])
    return logits, state


def serve_step_paged(params, state, tokens, cfg: ModelConfig, *,
                     min_write_pos: Optional[jnp.ndarray] = None,
                     paged_attn: str = "fused",
                     gather_granularity: str = "token",
                     mesh=None, rules: Optional[MeshRules] = None,
                     with_counts: bool = False):
    """One paged decode step: the Q = 1 call of the paged forward
    (`_paged_forward`). tokens: (B,) int32. Returns (logits, state), and
    with `with_counts` the rows' GVR counts (B, 4) third (`sel_counts`).

    Mirrors `serve_step` exactly, with the logical→physical translation at
    the cache boundary: the new token's rows scatter into
    `page_table[b, length // page_size]` at offset `length % page_size`.
    `min_write_pos` (B,) suppresses the cache write for rows whose
    position is below it (redirected to the sink page): the engine uses it
    to mask inactive slots and to replay the last prompt token over a
    shared prefix without copy-on-writing the shared page.

    `paged_attn` picks the physical form of the attention stage
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

    The layer-stacked pools (`k_pages`, `v_pages`, and `idx_k_pages` where
    the DSA gate is open) ride the layer scan's carry whole, never its
    xs/ys, and the step's outputs alias its donated inputs: each layer
    scatters its new rows at [layer, dest, off] (`_pool_write`) and every
    reader folds the layer into its gather (`_pool_view`, the `layer`
    argument of the attention forms). Nothing in the step may take a layer
    slice `pool[layer]` of a pool: the compiler would copy the layer's
    pool, and its write-back into the stack, on every step.

    An MLA configuration runs its latent form (`_mla_serve_step_paged`).
    """
    if cfg.is_mla:
        return _mla_serve_step_paged(
            params, state, tokens, cfg, min_write_pos=min_write_pos,
            paged_attn=paged_attn, gather_granularity=gather_granularity,
            mesh=mesh, rules=rules, with_counts=with_counts)
    logits, new_state, outs = _paged_forward(
        params, state, tokens, cfg, q=1, min_write_pos=min_write_pos,
        paged_attn=paged_attn, gather_granularity=gather_granularity,
        mesh=mesh, rules=rules)
    logits = constrain(logits, rules, "batch", "vocab")
    return _decode_result(logits, new_state, outs, with_counts)


# --------------------------------------------------------------------------
# Multi-head latent attention with the V3.2 indexer and held experts
# (DeepSeek-V3.2; ModelConfig.is_mla)
# --------------------------------------------------------------------------
#
# Per layer, h = rms_norm(x) · ln1:
#
#   c_q = rms_norm(h W_qa) · q_norm;  q = c_q W_qb → H × (nope | rope)
#   [c_kv | k_r] = h W_kva;  c_kv = rms_norm(c_kv) · kv_norm;  k_r shared by
#       every head; q's and k_r's rope dims YaRN-RoPE'd
#   W_kvb: kv_lora_rank → H × (nope k | v) = [W_uk | W_uv]
#   attention score (nope + rope)^-0.5 · m² (q_nope·k_nope + q_rope·k_r)
#
# Decode runs the absorbed form: the cache row is [c_kv | k_r], one latent
# "KV head" for all H heads; q̃ = q_nope W_ukᵀ scores c_kv directly, and the
# softmax-weighted sum of c_kv goes through W_uv and W_o. Training runs the
# plain form (keys and values per head). The indexer reads the query
# latent (`sparse.dsa.indexer_query_latent`). The first `first_k_dense`
# layers (`dense_layers`) end in a SwiGLU of width d_ff; the rest
# (`moe_layers`) in a shared expert plus the experts this chip holds of
# the routed ones (`layers.held_experts_mlp`).

def _mla_refuse(body: str, cfg: ModelConfig) -> None:
    """The decode bodies that have no latent-attention form say so."""
    if cfg.is_mla:
        raise ValueError(
            f"{body} has no multi-head latent attention form ({cfg.name}): "
            f"MLA configurations decode through serve_step_paged "
            f"(paged_attn='fused')")


def _mla_freqs(cfg: ModelConfig) -> jnp.ndarray:
    """The rotary frequencies of the rope dims, YaRN-scaled where set."""
    if cfg.yarn is not None:
        return yarn_freqs(cfg.qk_rope_head_dim, cfg.rope_base, cfg.yarn)
    return _rope_freqs(cfg.qk_rope_head_dim, cfg.rope_base)


def _mla_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn is not None:
        scale *= yarn_mscale(cfg.yarn) ** 2
    return scale


def _init_mla_layer(key, cfg: ModelConfig, dtype, moe: bool):
    d, h = cfg.d_model, cfg.n_heads
    ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    hi, di = cfg.dsa.indexer_heads, cfg.dsa.indexer_dim
    ks = jax.random.split(key, 16)
    p = {
        "ln1": _norm_init(d), "ln2": _norm_init(d),
        "wq_a": _dense(ks[0], (d, ql), dtype),
        "q_norm": _norm_init(ql),
        "wq_b": _dense(ks[1], (ql, h * (nope + rope)), dtype),
        "wkv_a": _dense(ks[2], (d, kvl + rope), dtype),
        "kv_norm": _norm_init(kvl),
        "wkv_b": _dense(ks[3], (kvl, h * (nope + vd)), dtype),
        "wo": _dense(ks[4], (h * vd, d), dtype),
        "idx_wq_b": _dense(ks[5], (ql, hi * di), dtype),
        "idx_wk": _dense(ks[6], (d, di), dtype),
        "idx_k_norm": _norm_init(di),
        "idx_k_norm_bias": jnp.zeros((di,), jnp.float32),
        "idx_weights_proj": _dense(ks[7], (d, hi), dtype),
    }
    if not moe:
        p["w_gate"] = _dense(ks[8], (d, cfg.d_ff), dtype)
        p["w_up"] = _dense(ks[9], (d, cfg.d_ff), dtype)
        p["w_down"] = _dense(ks[10], (cfg.d_ff, d), dtype)
        return p
    m = cfg.moe
    e, f, fs = m.num_experts, m.expert_d_ff, m.shared_d_ff
    p["router"] = _dense(ks[11], (d, m.routed_experts), jnp.float32)
    p["router_bias"] = jnp.zeros((m.routed_experts,), jnp.float32)
    p["shared_gate"] = _dense(ks[12], (d, fs), dtype)
    p["shared_up"] = _dense(ks[13], (d, fs), dtype)
    p["shared_down"] = _dense(ks[14], (fs, d), dtype)
    kg, ku, kd = jax.random.split(ks[15], 3)
    p["w_gate"] = _dense(kg, (e, d, f), dtype, scale=d ** -0.5)
    p["w_up"] = _dense(ku, (e, d, f), dtype, scale=d ** -0.5)
    p["w_down"] = _dense(kd, (e, f, d), dtype, scale=f ** -0.5)
    return p


def _mla_stacks(cfg: ModelConfig):
    """(stack name, first layer, layer count, expert layer?) of each
    non-empty layer stack, in order."""
    nd = cfg.first_k_dense
    out = [("dense_layers", 0, nd, False),
           ("moe_layers", nd, cfg.n_layers - nd, True)]
    return [s for s in out if s[2] > 0]


def _init_mla_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_head, *k_stacks = jax.random.split(key, 4)
    params = {
        "embed": _dense(k_emb, (cfg.vocab, cfg.d_model), dtype, scale=1.0),
        "final_norm": _norm_init(cfg.d_model),
        "lm_head": _dense(k_head, (cfg.d_model, cfg.vocab), dtype),
    }
    for (name, _, n, moe), k in zip(_mla_stacks(cfg), k_stacks):
        params[name] = jax.vmap(
            lambda kk: _init_mla_layer(kk, cfg, dtype, moe))(
                jax.random.split(k, n))
    return params


def _mla_moe(p, h, cfg: ModelConfig):
    """The expert block of an MLA expert layer on rows h (T, D): the shared
    expert once, plus the routed experts held here (`step.experts`).
    Returns (y (T, D), held (T,): each row's routed experts held here)."""
    m = cfg.moe
    y = swiglu_mlp(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    with jax.named_scope("step.experts"):
        idx, gates = route_grouped_sigmoid(
            h, p["router"], p["router_bias"], top_k=m.top_k,
            n_group=m.n_group, topk_group=m.topk_group,
            routed_scaling=m.routed_scaling)
        routed, held = held_experts_mlp(h, idx, gates, p["w_gate"],
                                        p["w_up"], p["w_down"],
                                        first_expert=m.first_expert)
        y = y + routed
    return y, held


def _mla_mlp(p, h, cfg: ModelConfig, moe: bool):
    """The MLP of an MLA layer on rows h (T, D): (y, held rows or None)."""
    if moe:
        return _mla_moe(p, h, cfg)
    return swiglu_mlp(h, p["w_gate"], p["w_up"], p["w_down"]), None


def _mla_attention_train(p, h, cfg: ModelConfig, positions):
    """Plain (non-absorbed) causal MLA over whole sequences. h: (B, S, D)."""
    b, s, _ = h.shape
    nh, kvl = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    freqs = _mla_freqs(cfg)
    cq = rms_norm(h @ p["wq_a"], p["q_norm"])
    q = (cq @ p["wq_b"]).reshape(b, s, nh, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], apply_rotary(q[..., nope:], positions, freqs=freqs)],
        axis=-1)
    kv = h @ p["wkv_a"]
    ckv = rms_norm(kv[..., :kvl], p["kv_norm"])
    k_r = apply_rotary(kv[..., None, kvl:], positions, freqs=freqs)
    kvb = (ckv @ p["wkv_b"]).reshape(b, s, nh, nope + vd)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_r, (b, s, nh, rope))], axis=-1)
    # the blockwise kernel takes one head width: values padded to the
    # query's, the padding sliced off again
    v = jnp.pad(kvb[..., nope:], ((0, 0),) * 3 + ((0, nope + rope - vd),))
    out = blockwise_causal_attention(q, k, v, scale=_mla_scale(cfg))
    out = out[..., :vd].reshape(b, s, nh * vd).astype(h.dtype)
    return out @ p["wo"]


def _mla_forward_train(params, tokens, cfg: ModelConfig, *, rules, remat):
    b, s = tokens.shape
    x = constrain(params["embed"][tokens], rules, "batch", "seq", "d_model")
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    for name, _, _, moe in _mla_stacks(cfg):
        def layer(x, p, moe=moe):
            x = x + _mla_attention_train(p, rms_norm(x, p["ln1"]), cfg,
                                         positions)
            h = rms_norm(x, p["ln2"]).reshape(b * s, -1)
            m, _ = _mla_mlp(p, h, cfg, moe)
            x = x + m.reshape(b, s, -1)
            return constrain(x, rules, "batch", "seq", "d_model"), None
        if remat:
            layer = jax.checkpoint(layer, prevent_cse=False)
        x, _ = jax.lax.scan(layer, x, params[name])
    x = rms_norm(x, params["final_norm"])
    return constrain(x @ params["lm_head"], rules, "batch", "seq", "vocab")


def _mla_project(p, h, positions, cfg: ModelConfig, freqs):
    """One decode token's latent projections (`step.qkv`). h: (B, D).
    Returns the absorbed queries (B, H, kv_lora_rank + rope) —
    [q_nope W_ukᵀ | q_rope] — the cache row (B, kv_lora_rank + rope) —
    [c_kv | k_r] — and the normed query latent c_q (B, q_lora_rank)."""
    b = h.shape[0]
    nh, kvl = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cq = rms_norm(h @ p["wq_a"], p["q_norm"])
    q = (cq @ p["wq_b"]).reshape(b, 1, nh, nope + rope)
    q_r = apply_rotary(q[..., nope:], positions[:, None], freqs=freqs)[:, 0]
    kv = h @ p["wkv_a"]
    ckv = rms_norm(kv[:, :kvl], p["kv_norm"])
    k_r = apply_rotary(kv[:, None, None, kvl:], positions[:, None],
                       freqs=freqs)[:, 0, 0]
    w_uk = p["wkv_b"].reshape(kvl, nh, nope + vd)[..., :nope]
    q_abs = jnp.einsum("bhn,chn->bhc", q[:, 0, :, :nope], w_uk,
                       preferred_element_type=jnp.float32)
    qf = jnp.concatenate([q_abs.astype(q_r.dtype), q_r], axis=-1)
    return qf, jnp.concatenate([ckv, k_r], axis=-1), cq


def _mla_out(p, o_lat, cfg: ModelConfig):
    """Σ p c_kv (B, H, kv_lora_rank) through W_uv and W_o (`step.attention`)."""
    b = o_lat.shape[0]
    nh, kvl = cfg.n_heads, cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    w_uv = p["wkv_b"].reshape(kvl, nh, nope + vd)[..., nope:]
    o = jnp.einsum("bhc,chv->bhv", o_lat.astype(w_uv.dtype), w_uv)
    return o.reshape(b, nh * vd) @ p["wo"]


def _mla_serve_step_paged(params, state, tokens, cfg: ModelConfig, *,
                          min_write_pos, paged_attn: str,
                          gather_granularity: str, mesh, rules,
                          with_counts: bool):
    """`serve_step_paged` for an MLA configuration: the absorbed latent
    decode over one latent page pool (`kv_pages`, rows [c_kv | k_r]), the
    dense then the expert layer stack each scanned over its own layers.
    The stacked pools (`kv_pages`, and `idx_k_pages` where the gate is
    open) pass whole through both scans' carries, the expert stack's at
    layer offset `lo`, as in `serve_step_paged`: written by `_pool_write`,
    read through the layer index, never sliced per stack or per layer nor
    joined again. The small per-layer leaves (`prev_topk`, `topk_valid`)
    are sliced per stack and joined. With the DSA gate open the V3.2 indexer
    selects through `dsa_select` and the selector, and attention gathers
    the K latent rows from the pool (`mla_sparse_attention_paged`);
    closed, it attends the whole logical extent through
    `decode_attention_paged` with the pool as its one KV head. Counts: the
    selector's (`sel_counts`) and, fifth, the row's routed experts held
    here, summed over the expert layers (HELD_EXPERT_COUNTERS)."""
    if paged_attn != "fused":
        raise ValueError(
            f"serve_step_paged(paged_attn={paged_attn!r}) has no multi-head "
            f"latent attention form ({cfg.name}): use 'fused'")
    b = tokens.shape[0]
    kvl, di = cfg.kv_lora_rank, cfg.dsa.indexer_dim
    x = constrain(params["embed"][tokens], rules, "batch", "d_model")
    positions, new_len, dest, off, gather, n = _paged_targets(
        state, "kv_pages", min_write_pos)
    table = state["page_table"]
    use_dsa = cfg.dsa.enabled and n > cfg.dsa.min_n
    freqs = _mla_freqs(cfg)
    scale = _mla_scale(cfg)

    def layer(carry, xs, moe):
        x, pools = carry
        p, li = xs["p"], xs["layer"]
        prev_topk, topk_valid = xs.get("prev_topk"), xs.get("topk_valid")
        with jax.named_scope("step.qkv"):
            h = rms_norm(x, p["ln1"])
            q, row, cq = _mla_project(p, h, positions, cfg, freqs)
        with jax.named_scope("step.kv_write"):
            kvp = _pool_write(pools["kv_pages"], li, dest, off, row)
        pools = dict(pools, kv_pages=kvp)
        out = {}
        if use_dsa:
            with jax.named_scope("step.indexer"):
                ik = dsa_mod.indexer_k_latent(p, h, positions, dim=di,
                                              freqs=freqs)
                query = dsa_mod.indexer_query_latent(
                    p, cq, h, positions, heads=cfg.dsa.indexer_heads,
                    dim=di, freqs=freqs)
            with jax.named_scope("step.kv_write"):
                pools["idx_k_pages"] = _pool_write(
                    pools["idx_k_pages"], li, dest, off, ik)
            with jax.named_scope("step.indexer"):
                idx_kc = _pool_view(pools["idx_k_pages"], li, gather, n)
            sel = dsa_mod.dsa_select(
                None, h, idx_kc, prev_topk, new_len, k=prev_topk.shape[-1],
                heads=cfg.dsa.indexer_heads, dim=di, rope_base=cfg.rope_base,
                selector=cfg.dsa.selector, prev_valid=topk_valid,
                max_candidates=cfg.dsa.max_candidates,
                gate_max_n=cfg.dsa.gate_max_n, min_n=cfg.dsa.min_n,
                rules=rules, mesh=mesh, query=query)
            with jax.named_scope("step.attention"):
                o_lat = dsa_mod.mla_sparse_attention_paged(
                    q, kvp, table, sel.indices, new_len, scale=scale,
                    v_dim=kvl, granularity=gather_granularity, rules=rules,
                    layer=li)
            out["prev_topk"] = sel.indices
            out["topk_valid"] = jnp.ones_like(topk_valid)
            out.update(sel_telemetry(sel, topk_valid))
        else:
            with jax.named_scope("step.attention"):
                # the latent pool reads as one KV head of q's width
                o_lat = decode_attention_paged(q, kvp, kvp, table, new_len,
                                               scale=scale, rules=rules,
                                               layer=li)[..., :kvl]
            if prev_topk is not None:
                out["prev_topk"], out["topk_valid"] = prev_topk, topk_valid
                out.update(sel_telemetry(None, topk_valid))
        with jax.named_scope("step.attention"):
            x = x + _mla_out(p, o_lat, cfg)
        with jax.named_scope("step.mlp"):
            m, held = _mla_mlp(p, rms_norm(x, p["ln2"]), cfg, moe)
            x = x + m
        if held is not None:
            out["held_rows"] = held
        return (constrain(x, rules, "batch", "d_model"), pools), out

    pools = {"kv_pages": state["kv_pages"]}
    if use_dsa:
        pools["idx_k_pages"] = state["idx_k_pages"]
    layered = ["prev_topk", "topk_valid"] if cfg.dsa.enabled else []
    outs = []
    for name, lo, cnt, moe in _mla_stacks(cfg):
        xs = {"p": params[name], "layer": jnp.arange(lo, lo + cnt)}
        xs.update({k: state[k][lo:lo + cnt] for k in layered})
        (x, pools), o = jax.lax.scan(partial(layer, moe=moe), (x, pools), xs)
        outs.append(o)
    cat = {k: jnp.concatenate([o[k] for o in outs], axis=0)
           for k in outs[0] if k != "held_rows"}

    new_state = dict(state, **pools)
    for k in layered + (["sel_gvr"] if cfg.dsa.enabled else []):
        new_state[k] = cat[k]
    new_state["length"] = new_len
    logits = constrain(_lm_head(params, x, cfg), rules, "batch", "vocab")
    if not with_counts:
        return logits, new_state
    held = jnp.zeros((b,), jnp.int32)
    for o in outs:
        if "held_rows" in o:
            held = held + jnp.sum(o["held_rows"], axis=0)
    counts = jnp.concatenate([sel_counts(cat, b), held[:, None]], axis=-1)
    return logits, new_state, counts


# --------------------------------------------------------------------------
# Speculative verify step — draft–verify–rollback over the paged layouts
# (DESIGN.md §spec-decode)
# --------------------------------------------------------------------------
#
# One verify tick scores all d+1 draft positions of each slot: position j
# writes its K/V at `length + j` and attends with per-position causal
# extent `length + j + 1`, so every position reproduces the exact bits of
# the non-speculative step it stands in for. Two bodies do it: "mq", the
# paged forward's Q = d+1 call (`_paged_forward`, `_sp_paged_forward`),
# and "scan", d+1 of its Q = 1 calls scanned inside one jit — the
# reference mq is pinned against. The GVR feedback is causally extended
# WITHIN the tick: position j's selection warm-starts position j+1 (the
# forward's Top-K chain, the scan's carry), which is precisely the paper's
# temporal-correlation signal stretched across a multi-token step ("Learn
# from the Past" argues the correlation survives; the per-position
# `sel_gvr` stack lets the engine measure how the hit rate degrades with
# draft depth).
#
# mq is bit-identical to scan: position j's consumers all mask beyond its
# own causal extent (indexer scores, sparse-attention validity, the dense
# fallback's length mask), and the NEG/-inf sentinels zero masked
# contributions exactly in f32, so the rows later positions write — fresh
# under mq, stale under scan — are arithmetically invisible. Frozen rows
# (j > draft_len) compute garbage at advanced positions under mq (scan
# computes other garbage at frozen ones); the rollback never selects their
# stack entries (accept_len <= draft_len), and a frozen eos argmax can
# never lower accept_len below a live position's.
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
    counts (B, 4)) — one per-token paged decode step with its `sel_counts`. tokens: (B, D+1) — column 0 is the last
    emitted token, columns 1..D the draft. draft_len: (B,) in [0, D] — rows
    verify positions 0..draft_len (position j > draft_len is frozen: state
    row kept, cache write redirected to the sink page). max_accept: (B,)
    caps accepted DRAFT tokens (the engine's max_new_tokens budget).
    eos_id: emission truncates at (and includes) the first eos argmax
    (-1 = disabled; vocab ids are non-negative so it never matches).

    Returns (out_tokens (B, D+1), accept_len (B,), logits_all (B, D+1, V),
    sel_gvr_pos (B, D+1), gvr_counts (B, 4), new_state): `out_tokens[:, j]`
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
    draft_len) — and "sel_counts" (D+1, B, 4), each position's
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
        counts = jnp.zeros((b, 4), jnp.int32)
    return (argmax_all.T, a, jnp.transpose(logits_all, (1, 0, 2)),
            sel_pos, counts, new_state)


def _verify_mwp(draft_len, base_mwp, q: int):
    """min_write_pos of a verify tick's B·Q rows: row j > draft_len is
    frozen (its write goes to the sink page, as the scan form's frozen
    position's does)."""
    live = jnp.arange(q, dtype=jnp.int32)[None, :] <= draft_len[:, None]
    return jnp.where(live, base_mwp[:, None],
                     jnp.int32(PAGED_NEVER_WRITE)).reshape(-1)


def _verify_result(length0, logits, end_state, outs, tokens, draft_len,
                   max_accept, eos_id: int, dsa_enabled: bool):
    """A Q = d+1 row forward as a verify tick: its outputs turned into the
    per-position stacks `_spec_accept_rollback` reads, then acceptance and
    rollback. Row j of the forward is verify position j."""
    b, d1 = tokens.shape
    ys = {"logits": _by_row(logits, d1)}                  # (D+1, B, V)
    if dsa_enabled:
        for key in ("prev_topk", "topk_valid", "sel_gvr"):
            stk = outs[key] if d1 > 1 else outs[key][:, None]
            ys[key] = jnp.swapaxes(stk, 0, 1)             # (D+1, L, B, ...)
        ys["sel_counts"] = sel_counts(outs, b).reshape(d1, b, -1)
    return _spec_accept_rollback(length0, end_state, ys, tokens, draft_len,
                                 max_accept, eos_id, dsa_enabled)


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
      lax.scan (the PR-5 form): the reference mq is pinned against.
    * "mq" — the paged forward's Q = d+1 call (`_paged_forward`, the one
      `serve_step_paged` makes with Q = 1): one scatter of all rows a
      pool, the chained Top-K warm start, one attention call over all
      (B, Q) selections a layer.

    Returns (out_tokens (B, D+1), accept_len (B,), logits_all (B, D+1, V),
    sel_gvr_pos (B, D+1), gvr_counts (B, 4), new_state) — see
    `_spec_verify_scan`.
    """
    _mla_refuse(f"serve_step_spec_paged (the {verify_kernel!r} verify body)",
                cfg)
    b, d1 = tokens.shape
    base_mwp = (min_write_pos if min_write_pos is not None
                else jnp.zeros((b,), jnp.int32))
    if verify_kernel not in ("scan", "mq"):
        raise ValueError(f"unknown verify_kernel {verify_kernel!r} "
                         f"(expected 'scan' or 'mq')")
    draft_len = jnp.asarray(draft_len, jnp.int32)
    max_accept = jnp.asarray(max_accept, jnp.int32)

    if verify_kernel == "mq":
        logits, end_state, outs = _paged_forward(
            params, state, tokens.reshape(-1), cfg, q=d1,
            min_write_pos=_verify_mwp(draft_len, base_mwp, d1),
            paged_attn=paged_attn, gather_granularity=gather_granularity,
            mesh=mesh, rules=rules)
        return _verify_result(state["length"], logits, end_state, outs,
                              tokens, draft_len, max_accept, int(eos_id),
                              cfg.dsa.enabled)

    def step_fn(st, tok, mwp):
        return serve_step_paged(params, st, tok, cfg, min_write_pos=mwp,
                                paged_attn=paged_attn,
                                gather_granularity=gather_granularity,
                                mesh=mesh, rules=rules, with_counts=True)

    return _spec_verify_scan(step_fn, state, tokens, draft_len, max_accept,
                             int(eos_id), base_mwp,
                             paged_state_batch_axes(cfg), cfg.dsa.enabled)


def serve_step_sp_spec_paged(params, state, tokens, cfg: ModelConfig, *,
                             mesh, draft_len, max_accept, eos_id: int = -1,
                             min_write_pos: Optional[jnp.ndarray] = None,
                             verify_kernel: str = "scan",
                             seq_axis: str = "seq",
                             rules: Optional[MeshRules] = None):
    """Sequence-sharded speculative verify tick: the same verify semantics
    as `serve_step_spec_paged`, with the whole verify — including the
    in-graph acceptance/rollback, which is replicated arithmetic — inside
    ONE shard_map over the mesh's `seq_axis`. Per position the collective
    schedule is exactly the non-speculative sharded step's (O(1) in
    context length), so a verify tick costs d+1 of those schedules and
    nothing more. Bit-identical to the single-device
    `serve_step_spec_paged` over the same logical cache content, which is
    what pins spec == non-spec on sharded meshes (tests/test_spec.py).

    `verify_kernel` picks the verify body, as in the single-device step:
    "scan" runs d+1 sequential Q = 1 calls of the sharded forward
    (`_sp_paged_forward`), the reference; "mq" is its one Q = d+1 call —
    each layer's projections and writes batched over every position,
    selection and attention chained per row — bit-identical in tokens,
    accept traces, feedback and telemetry.
    """
    _mla_refuse(f"serve_step_sp_spec_paged (the sequence-sharded "
                f"{verify_kernel!r} verify body)", cfg)
    b, d1 = tokens.shape
    _sp_paged_validate(state, cfg, mesh, seq_axis)
    if verify_kernel not in ("scan", "mq"):
        raise ValueError(f"unknown verify_kernel {verify_kernel!r} "
                         f"(expected 'scan' or 'mq')")
    base_mwp = (min_write_pos if min_write_pos is not None
                else jnp.zeros((b,), jnp.int32))
    axes = sp_paged_state_batch_axes(cfg)

    def body(params, state, tokens, draft_len, max_accept, base_mwp):
        if verify_kernel == "mq":
            logits, end_state, outs = _sp_paged_forward(
                params, state, tokens.reshape(-1),
                _verify_mwp(draft_len, base_mwp, d1), cfg, q=d1,
                seq_axis=seq_axis)
            return _verify_result(state["length"], logits, end_state, outs,
                                  tokens, draft_len, max_accept, int(eos_id),
                                  True)

        def step_fn(st, tok, mwp):
            logits, new_state, outs = _sp_paged_forward(
                params, st, tok, mwp, cfg, q=1, seq_axis=seq_axis)
            return _decode_result(logits, new_state, outs, True)
        return _spec_verify_scan(step_fn, state, tokens, draft_len,
                                 max_accept, int(eos_id), base_mwp, axes,
                                 cfg.dsa.enabled)

    param_spec, st_spec = _sp_specs(params, state, seq_axis)
    fn = jax.shard_map(body, mesh=mesh,
                   in_specs=(param_spec, st_spec, P(), P(), P(), P()),
                   out_specs=(P(), P(), P(), P(), P(), st_spec),
                   check_vma=False)
    return fn(params, state, tokens, jnp.asarray(draft_len, jnp.int32),
              jnp.asarray(max_accept, jnp.int32), base_mwp)
