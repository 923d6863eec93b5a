"""Plain float32 reference of DeepSeek-V3.2: multi-head latent attention,
the V3.2 indexer with exact Top-K, leading dense layers, then expert
layers of which a share of the routed experts is held.

Full causal forward over whole sequences in jax.numpy at `highest`
matmul precision, with nothing from the program: no cache, no paging, no
absorption, no kernels, no selector. Per layer, h = rmsnorm(x) * ln1:

    c_q = rmsnorm(h Wqa) * q_norm;  q = c_q Wqb -> H x (128 nope | 64 rope)
    [c_kv | k_r] = h Wkva (512 | 64);  c_kv = rmsnorm(c_kv) * kv_norm
    k_nope, v = c_kv Wkvb -> H x (128 | 128);  q_rope and k_r YaRN-RoPE'd
        (k_r one for all heads), in interleaved pairs
    score_h(t, s) = (q_h(t) . k_h(s)) * 192^-0.5 * m^2,  m = 0.1 ln 40 + 1,
        causal; where the cell runs sparse, over the K positions s <= t of
        largest indexer score only (all of them while t < K; ties to the
        lowest s):
            qI = c_q WIq (64 x 128), kI = LayerNorm(h WIk), RoPE on the
            first 64 dims of each; w = h WIw * 64^-0.5
            I(t, s) = 128^-0.5 sum_j w_j(t) ReLU(qI_j(t) . kI(s))
    x = x + (softmax . v) Wo
    h2 = rmsnorm(x) * ln2; dense layers: x + SwiGLU_18432(h2); expert
    layers: sigma = sigmoid(h2 Wr) over every routed expert; choice
        sigma + bias; each group of routed/n_group scores by its two
        largest choices, the topk_group best groups stay, the top_k
        largest choices in them are the token's experts; gates sigma at
        those, normalised to sum 1, times routed_scaling;
        x + SwiGLU_shared(h2) + sum over held e of g_e SwiGLU_e(h2)
        (a routed expert held on another chip adds nothing here)

then rmsnorm * final_norm and the untied head. `quant="fp8"` is the
control of bench/reference/dense.py: float8_e4m3fn operands for every
matmul and the residual stream between layers.

It fits beside the program's resident weights (9.7 GB at the cell's
size): sequences run one at a time (all padded to one length); a layer
reads its leaves from the stacks as stored and casts each where it is
used; attention runs HEAD_GROUP heads at a time in query blocks over a
selection mask made once per layer; the MLPs run in token blocks, the
held experts one at a time, and the head in vocabulary blocks whose
logits go to the host. A layer at 8192 positions compiles for one v5e
to 2.2 GB of temporaries. The logits come back as a numpy array.

`row_logits` recomputes one position's logits from a layer on, over the
residual stream `stream` keeps, with the position's Top-K selection or
choice of experts in some layers set from outside: what the reference
would read on the program's selection, or had a routing near-tie gone
the other way (bench/flips.py).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import (F8_MAX, _round, fake_quant, matmul,
                                   rms_norm)

Q_BLOCK = 64
TOKEN_BLOCK = 1024
HEAD_GROUP = 32
VOCAB_BLOCK = 16384

_ATTN = ("ln1", "ln2", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
         "wkv_b", "wo", "idx_wq_b", "idx_wk", "idx_k_norm",
         "idx_k_norm_bias", "idx_weights_proj")
_DENSE = _ATTN + ("w_gate", "w_up", "w_down")
_MOE = _ATTN + ("router", "router_bias", "shared_gate", "shared_up",
                "shared_down", "w_gate", "w_up", "w_down")


def yarn_freqs(spec) -> jnp.ndarray:
    """DeepSeek-V3's YaRN frequencies over the rope dims."""
    dim, base, y = spec["qk_rope_head_dim"], spec["rope_base"], spec["yarn"]

    def corr(rot):
        return (dim * math.log(y["original_max_position"] / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = freqs / y["factor"] * ramp + freqs * (1.0 - ramp)
    return jnp.asarray(freqs, jnp.float32)


def rope(x, pos, freqs):
    """Rotate the first 2 len(freqs) dims of x (S, heads, D) in
    interleaved pairs (0,1), (2,3), ... by pos * freqs."""
    rot = 2 * freqs.shape[0]
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([r.reshape(x[..., :rot].shape), x[..., rot:]],
                           axis=-1)


def layer_norm(x, scale, bias, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _f32(w, quant):
    """A weight as float32, rounded to float8 (per tensor) under the
    control."""
    w = w.astype(jnp.float32)
    return fake_quant(w) if quant == "fp8" else w


def _act(x, quant):
    """An activation as a matmul reads it: per row in float8 under the
    control."""
    return fake_quant(x, axis=-1) if quant == "fp8" else x


def _mlp(h, wg, wu, wd, quant):
    """SwiGLU on h (S, D) in token blocks, its weights cast as read."""
    n, d = h.shape
    tb = math.gcd(n, TOKEN_BLOCK)
    wg, wu, wd = _f32(wg, quant), _f32(wu, quant), _f32(wd, quant)

    def block(hb):
        a = _act(hb, quant)
        return _act(jax.nn.silu(a @ wg) * (a @ wu), quant) @ wd
    return jax.lax.map(block, h.reshape(n // tb, tb, d)).reshape(n, -1)


def _selection(c_q, h, w, pos, spec, quant, freqs):
    """The K keys of largest indexer score for every query, ties to the
    lowest position, causal: (S, S) bool."""
    dsa = spec["dsa"]
    s = h.shape[0]
    hi, di, k = dsa["indexer_heads"], dsa["indexer_dim"], dsa["k"]
    qi = rope(matmul(c_q, _f32(w["idx_wq_b"], None), quant)
              .reshape(s, hi, di), pos, freqs)
    ki = layer_norm(matmul(h, _f32(w["idx_wk"], None), quant),
                    w["idx_k_norm"], w["idx_k_norm_bias"])
    ki = rope(ki[:, None], pos, freqs)[:, 0]
    hw = (matmul(h, _f32(w["idx_weights_proj"], None), quant)
          * hi ** -0.5 * di ** -0.5)
    if quant == "fp8":
        qi, ki = fake_quant(qi, axis=-1), fake_quant(ki, axis=-1)
    keys = jnp.arange(s)

    def sel(i0):
        qq = jax.lax.dynamic_slice_in_dim(qi, i0, Q_BLOCK, axis=0)
        ww = jax.lax.dynamic_slice_in_dim(hw, i0, Q_BLOCK, axis=0)
        score = jax.nn.relu(jnp.einsum("qhd,sd->qhs", qq, ki))
        score = jnp.einsum("qh,qhs->qs", ww, score)
        causal = keys[None, :] <= (i0 + jnp.arange(Q_BLOCK))[:, None]
        score = jnp.where(causal, score, -jnp.inf)
        kth = jnp.sort(score, axis=-1)[:, max(s - k, 0), None]
        # ties at the K-th score go to the lowest positions
        above = jnp.sum(score > kth, axis=-1, keepdims=True)
        tied = score == kth
        first = jnp.cumsum(tied, axis=-1) <= k - above
        return causal & ((score > kth) | (tied & first))
    return jax.lax.map(sel, jnp.arange(0, s, Q_BLOCK)).reshape(s, s)


def _attention(h, w, pos, spec, sparse, quant, freqs):
    """Plain MLA over one sequence h (S, D), causal, HEAD_GROUP heads at a
    time and in query blocks."""
    s = h.shape[0]
    nh, ql, kvl = spec["n_heads"], spec["q_lora_rank"], spec["kv_lora_rank"]
    nope, rp, vd = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                    spec["v_head_dim"])
    scale = (nope + rp) ** -0.5
    if spec.get("yarn"):
        m = 0.1 * spec["yarn"]["mscale_all_dim"] * math.log(
            spec["yarn"]["factor"]) + 1.0
        scale *= m * m
    c_q = rms_norm(matmul(h, _f32(w["wq_a"], None), quant), w["q_norm"],
                   spec["norm_eps"])
    kv = matmul(h, _f32(w["wkv_a"], None), quant)
    c_kv = rms_norm(kv[:, :kvl], w["kv_norm"], spec["norm_eps"])
    k_r = rope(kv[:, None, kvl:], pos, freqs)                   # (S, 1, rope)
    keys = jnp.arange(s)
    chosen = keys[None, :] <= keys[:, None]
    if sparse:
        chosen = _selection(c_q, h, w, pos, spec, quant, freqs)
    wq_b = _f32(w["wq_b"], quant).reshape(ql, nh, nope + rp)
    wkv_b = _f32(w["wkv_b"], quant).reshape(kvl, nh, nope + vd)
    if quant == "fp8":
        c_q, c_kv = fake_quant(c_q, axis=-1), fake_quant(c_kv, axis=-1)
    g = math.gcd(nh, HEAD_GROUP)

    def heads(h0):
        q = jnp.einsum("sc,chd->shd", c_q,
                       jax.lax.dynamic_slice_in_dim(wq_b, h0, g, axis=1))
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, freqs)],
                            -1)
        kvb = jnp.einsum("sc,chd->shd", c_kv,
                         jax.lax.dynamic_slice_in_dim(wkv_b, h0, g, axis=1))
        k = jnp.concatenate([kvb[..., :nope],
                             jnp.broadcast_to(k_r, (s, g, rp))], -1)
        v = kvb[..., nope:]
        if quant == "fp8":
            q, k, v = (fake_quant(q, axis=-1), fake_quant(k, axis=-1),
                       fake_quant(v, axis=-1))

        def block(i0):
            qi = jax.lax.dynamic_slice_in_dim(q, i0, Q_BLOCK, axis=0)
            logits = jnp.einsum("qhd,shd->hqs", qi, k) * scale
            mask = jax.lax.dynamic_slice_in_dim(chosen, i0, Q_BLOCK, axis=0)
            p = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf),
                               axis=-1)
            if quant == "fp8":
                p = fake_quant(p, axis=-1)
            return jnp.einsum("hqs,shv->qhv", p, v)
        return jax.lax.map(block, jnp.arange(0, s, Q_BLOCK)).reshape(s, g, vd)

    out = jax.lax.map(heads, jnp.arange(0, nh, g))          # (nh/g, S, g, vd)
    out = jnp.moveaxis(out, 0, 1).reshape(s, nh * vd)
    return matmul(out, _f32(w["wo"], None), quant)


def _route(h, w, moe):
    """noaux_tc on h (S, D): the sigmoid scores (S, routed) and each
    token's top_k experts (S, top_k)."""
    routed = moe.get("total_experts", moe["num_experts"])
    groups, top_g = moe["n_group"], moe["topk_group"]
    sig = jax.nn.sigmoid(h @ w["router"])                      # (S, routed)
    choice = (sig + w["router_bias"]).reshape(-1, groups, routed // groups)
    group_score = jnp.sum(jnp.sort(choice, axis=-1)[..., -2:], axis=-1)
    kept = group_score >= jnp.sort(group_score, axis=-1)[:, -top_g, None]
    choice = jnp.where(kept[..., None], choice, -jnp.inf).reshape(-1, routed)
    return sig, jax.lax.top_k(choice, moe["top_k"])[1]


def _experts(h, w, spec, quant, idx=None):
    """The shared expert plus the routed experts held here, on h (S, D),
    one held expert at a time; `idx` (S, top_k) overrides the routing's
    choice of experts."""
    moe = spec["moe"]
    first = moe.get("first_expert", 0)
    held = moe["num_experts"]
    sig, chosen = _route(h, w, moe)
    idx = chosen if idx is None else idx
    gates = jnp.take_along_axis(sig, idx, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * moe[
        "routed_scaling"]
    mix = jnp.sum(jax.nn.one_hot(idx - first, held) * gates[..., None],
                  axis=1)                                       # (S, held)

    def expert(y, e):
        return y + mix[:, e, None] * _mlp(h, w["w_gate"][e], w["w_up"][e],
                                          w["w_down"][e], quant), None

    shared = _mlp(h, w["shared_gate"], w["shared_up"], w["shared_down"],
                  quant)
    return jax.lax.scan(expert, shared, jnp.arange(held))[0]


@partial(jax.jit, static_argnames=("spec_key", "sparse", "quant", "moe"))
def _layer(x, stack, i, pos, *, spec_key, sparse, quant, moe):
    """Layer i of a stack (its leaves as stored, sliced and cast here)."""
    spec = _spec(spec_key)
    w = {k: v[i] for k, v in stack.items()}
    for k in ("ln1", "ln2", "q_norm", "kv_norm", "idx_k_norm",
              "idx_k_norm_bias"):
        w[k] = w[k].astype(jnp.float32)
    freqs = yarn_freqs(spec)
    h = rms_norm(x, w["ln1"], spec["norm_eps"])
    x = _round(x + _attention(h, w, pos, spec, sparse, quant, freqs), quant)
    h = rms_norm(x, w["ln2"], spec["norm_eps"])
    mlp = (_experts(h, w, spec, quant) if moe else
           _mlp(h, w["w_gate"], w["w_up"], w["w_down"], quant))
    return _round(x + mlp, quant)


@partial(jax.jit, static_argnames=("quant",))
def _head_block(h, head, head_max, *, quant):
    """One vocabulary block of the head; under the control its weights
    round to float8 with the whole head's scale (`head_max`)."""
    head = head.astype(jnp.float32)
    if quant == "fp8":
        scale = jnp.where(head_max > 0, head_max / F8_MAX, 1.0)
        head = (head / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
        h = fake_quant(h, axis=-1)
    return h @ head


def _spec_key(spec):
    keys = ("n_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_base", "norm_eps")
    groups = tuple((g, tuple(sorted(spec[g].items())))
                   for g in ("dsa", "moe", "yarn"))
    return tuple((k, spec[k]) for k in keys) + groups


def _spec(key):
    spec = dict(key)
    for g in ("dsa", "moe", "yarn"):
        spec[g] = dict(spec[g])
    return spec


def _stack(spec, flat, layer):
    """(the leaves of layer `layer`'s stack, its index there, expert
    layer or not)."""
    nd = spec["first_k_dense"]
    moe = layer >= nd
    prefix, i = ("moe_", layer - nd) if moe else ("dense_", layer)
    return {k: flat[prefix + k] for k in (_MOE if moe else _DENSE)}, i, moe


def stream(spec: Dict[str, Any], flat: Dict[str, jax.Array], seq, s: int,
           *, sparse: bool, quant: Optional[str] = None):
    """The residual stream (s, D) of sequence `seq` padded at its end to
    `s` positions: the embedding, then each layer's output in turn (call
    under `highest` matmul precision)."""
    tokens = np.zeros((s,), np.int32)
    tokens[:len(seq)] = seq
    x = _round(flat["embed"][jnp.asarray(tokens)].astype(jnp.float32), quant)
    yield x
    for layer in range(spec["n_layers"]):
        stack, i, moe = _stack(spec, flat, layer)
        x = _layer(x, stack, i, jnp.arange(s), spec_key=_spec_key(spec),
                   sparse=sparse, quant=quant, moe=moe)
        yield x


def head(spec, flat, x, quant=None):
    """Logits (N, V) on the host of the last layer's output rows x (N, D),
    in vocabulary blocks."""
    h = rms_norm(x, flat["final_norm"].astype(jnp.float32), spec["norm_eps"])
    vocab = flat["lm_head"].shape[1]
    head_max = jnp.max(jnp.abs(flat["lm_head"])).astype(jnp.float32)
    out = np.empty((h.shape[0], vocab), np.float32)
    for v0 in range(0, vocab, VOCAB_BLOCK):
        block = flat["lm_head"][:, v0:v0 + VOCAB_BLOCK]
        out[:, v0:v0 + block.shape[1]] = np.asarray(_head_block(
            h, block, head_max, quant=quant))
    return out


def logits(spec: Dict[str, Any], flat: Dict[str, jax.Array],
           seqs: List[np.ndarray], rows: List[np.ndarray], *, sparse: bool,
           quant: Optional[str] = None, pad_to: int = 512) -> np.ndarray:
    """Reference logits (sum(len(r) for r in rows), V), float32, at
    positions `rows[i]` of sequence `seqs[i]`, for the weights `flat`
    (bench/weights.py leaves of bench/layouts/mla.py). Every sequence is
    padded at its end to one length, a multiple of `pad_to` (the padding
    sits after every real position, so causality keeps it out)."""
    s = -(-max(len(q) for q in seqs) // pad_to) * pad_to
    with jax.default_matmul_precision("highest"):
        xs = []
        for q, r in zip(seqs, rows):
            *_, x = stream(spec, flat, q, s, sparse=sparse, quant=quant)
            xs.append(x[jnp.asarray(np.asarray(r, np.int32))])
        return head(spec, flat, jnp.concatenate(xs), quant)


@partial(jax.jit, static_argnames=("spec_key", "sparse", "moe"))
def _row_layer(x, stack, i, pos, p, pick, route, *, spec_key, sparse, moe):
    """Layer i of a stack on row p alone of x (S, D), the layer's input,
    in float32: the same layer as `_layer`, its attention in the absorbed
    form (q_nope W_uk against the latent, then W_uv), over one query.
    `pick` (K,) sets row p's Top-K selection, `route` (top_k,) its experts
    (-1 first: as the layer chooses). Returns the row's output (D,), its
    router's sigmoid scores (routed,) (empty in a dense layer) and the
    K positions of its own Top-K selection (empty where dense)."""
    spec = _spec(spec_key)
    w = {k: v[i] for k, v in stack.items()}
    for k in ("ln1", "ln2", "q_norm", "kv_norm", "idx_k_norm",
              "idx_k_norm_bias"):
        w[k] = w[k].astype(jnp.float32)
    freqs, eps = yarn_freqs(spec), spec["norm_eps"]
    nh, ql, kvl = spec["n_heads"], spec["q_lora_rank"], spec["kv_lora_rank"]
    nope, rp, vd = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                    spec["v_head_dim"])
    dsa = spec["dsa"]
    hi, di = dsa["indexer_heads"], dsa["indexer_dim"]
    scale = (nope + rp) ** -0.5
    if spec.get("yarn"):
        m = 0.1 * spec["yarn"]["mscale_all_dim"] * math.log(
            spec["yarn"]["factor"]) + 1.0
        scale *= m * m
    s = x.shape[0]
    keys = jnp.arange(s)
    at = jax.lax.dynamic_slice_in_dim(pos, p, 1)
    h = rms_norm(x, w["ln1"], eps)
    hp = jax.lax.dynamic_slice_in_dim(h, p, 1)                      # (1, D)
    c_q = rms_norm(hp @ _f32(w["wq_a"], None), w["q_norm"], eps)
    kv = h @ _f32(w["wkv_a"], None)
    c_kv = rms_norm(kv[:, :kvl], w["kv_norm"], eps)                 # (S, kvl)
    k_r = rope(kv[:, None, kvl:], pos, freqs)[:, 0]                 # (S, rp)
    chosen = keys <= p
    own = jnp.zeros((0,), jnp.int32)
    if sparse:
        qi = rope((c_q @ _f32(w["idx_wq_b"], None)).reshape(1, hi, di), at,
                  freqs)[0]
        ki = layer_norm(h @ _f32(w["idx_wk"], None), w["idx_k_norm"],
                        w["idx_k_norm_bias"])
        ki = rope(ki[:, None], pos, freqs)[:, 0]
        hw = ((hp @ _f32(w["idx_weights_proj"], None))[0]
              * hi ** -0.5 * di ** -0.5)
        score = jnp.where(chosen, hw @ jax.nn.relu(qi @ ki.T), -jnp.inf)
        # the K largest, ties to the lowest position
        order = jnp.argsort(-score, stable=True)
        rank = jnp.zeros((s,), jnp.int32).at[order].set(keys)
        own = order[:dsa["k"]]
        given = jnp.zeros((s,), bool).at[pick].set(True)
        chosen &= jnp.where(pick[0] >= 0, given, rank < dsa["k"])
    q = (c_q @ _f32(w["wq_b"], None)).reshape(nh, nope + rp)
    q_rope = rope(q[None, :, nope:], at, freqs)[0]
    wkv_b = _f32(w["wkv_b"], None).reshape(kvl, nh, nope + vd)
    q_lat = jnp.einsum("hd,chd->hc", q[:, :nope], wkv_b[..., :nope])
    att = (q_lat @ c_kv.T + q_rope @ k_r.T) * scale                 # (nh, S)
    prob = jax.nn.softmax(jnp.where(chosen[None], att, -jnp.inf), axis=-1)
    o = jnp.einsum("hc,chv->hv", prob @ c_kv, wkv_b[..., nope:])
    xp = (jax.lax.dynamic_slice_in_dim(x, p, 1)
          + o.reshape(1, nh * vd) @ _f32(w["wo"], None))
    h2 = rms_norm(xp, w["ln2"], eps)
    if moe:
        sig, idx = _route(h2, w, spec["moe"])
        idx = jnp.where(route[0] >= 0, route[None], idx)
        mlp, sig = _experts(h2, w, spec, None, idx=idx), sig[0]
    else:
        mlp = _mlp(h2, w["w_gate"], w["w_up"], w["w_down"], None)
        sig = jnp.zeros((0,), jnp.float32)
    return (xp + mlp)[0], sig, own


def row_logits(spec: Dict[str, Any], flat: Dict[str, jax.Array], xs, p: int,
               *, sparse: bool, start: int = 0, topk=None, route=None):
    """Row p's logits (V,) on the host, recomputed from layer `start` on
    over the stream `xs` (`stream`'s arrays, which hold every other row),
    with row p's Top-K selection set to `topk[layer]` (K positions) and
    its experts to `route[layer]` (top_k experts) where these name a
    layer. Also, for each recomputed layer, the row's router sigmoid
    scores and the positions of the layer's own Top-K selection (host
    arrays, empty where the layer has none). Call under `highest` matmul
    precision."""
    topk, route = topk or {}, route or {}
    free_k = np.full((spec["dsa"]["k"],), -1, np.int32)
    free_e = np.full((spec["moe"]["top_k"],), -1, np.int32)
    x, sigs, own = xs[start], [], []
    for layer in range(start, spec["n_layers"]):
        stack, i, moe = _stack(spec, flat, layer)
        row, sig, sel = _row_layer(
            x, stack, i, jnp.arange(x.shape[0]), p,
            jnp.asarray(topk.get(layer, free_k), jnp.int32),
            jnp.asarray(route.get(layer, free_e), jnp.int32),
            spec_key=_spec_key(spec), sparse=sparse, moe=moe)
        sigs.append(np.asarray(sig))
        own.append(np.asarray(sel))
        if layer + 1 < spec["n_layers"]:
            x = xs[layer + 1].at[p].set(row)
    return head(spec, flat, row[None])[0], sigs, own
