"""Plain float32 reference of the decoder the dense configurations run.

Full causal forward over whole sequences, layer by layer, in jax.numpy at
`highest` matmul precision, with nothing from the program: no cache, no
paging, no kernels, no selector. Per layer:

    h = rmsnorm(x) * ln1
    q, k, v = h Wq, h Wk, h Wv; RoPE on the first `rope_fraction` of each
        head's dims, in interleaved pairs, at the token's position
    attention, grouped-query, scale head_dim^-0.5, causal; where the cell
        runs sparse (DSA: the pool's extent passes min_n), a query at
        position t attends only the K positions s <= t whose indexer score
            I(t, s) = sum_j w_j ReLU(qI_j(t) . kI(s))
        is largest (all of them while t < K; ties to the lowest s), qI
        and kI RoPE'd over all their dims
    x = x + attn Wo
    x = x + (silu(h Wg) * (h Wu)) Wd, h = rmsnorm(x) * ln2

then rmsnorm * final_norm and the head (the embedding's transpose where
tied). `quant="fp8"` computes in float8_e4m3fn where the program computes
in its served type: the operands of every matmul (weights per tensor;
activations, queries, keys, values and attention weights per row) and the
residual stream between layers (per row), each scaled to the format's
largest value; norms, softmax and sums stay float32. That is the control
`correct` must fail (bench/calibrate.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0


def fake_quant(x, axis=None):
    """Round to float8_e4m3fn after scaling the largest |x| (over `axis`,
    or the whole tensor) to the format's largest value."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def matmul(a, w, quant: Optional[str]):
    """a (..., din) @ w (din, dout), both float32."""
    if quant == "fp8":
        a, w = fake_quant(a, axis=-1), fake_quant(w)
    return a @ w


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, pos, fraction: float, base: float):
    """x (B, S, heads, D); pos (S,). Interleaved pairs (0,1), (2,3), ... of
    the first int(D * fraction) dims rotate by pos * base^(-2i/rot)."""
    d = x.shape[-1]
    rot = int(d * fraction) // 2 * 2
    inv = 1.0 / (base ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]     # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([r.reshape(x[..., :rot].shape), x[..., rot:]],
                           axis=-1)


def dense_mlp(h, w, spec, quant):
    g = matmul(h, w["w_gate"], quant)
    u = matmul(h, w["w_up"], quant)
    return matmul(jax.nn.silu(g) * u, w["w_down"], quant)


def _attention(q, k, v, sel_fn, qb: int, quant):
    """q (B,S,H,D), k/v (B,S,KVH,D); causal, in query blocks of qb rows.
    sel_fn(i0) -> (B, qb, S) bool mask of the selected keys, or None."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    keys = jnp.arange(s)
    if quant == "fp8":
        q, k, v = (fake_quant(q, axis=-1), fake_quant(k, axis=-1),
                   fake_quant(v, axis=-1))

    def block(i0):
        qi = jax.lax.dynamic_slice_in_dim(q, i0, qb, axis=1)
        qi = qi.reshape(b, qb, kvh, g, d)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qi, k) * d ** -0.5
        mask = keys[None, :] <= (i0 + jnp.arange(qb))[:, None]    # (qb, S)
        mask = jnp.broadcast_to(mask[None], (b, qb, s))
        sel = sel_fn(i0)
        if sel is not None:
            mask = mask & sel
        logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        if quant == "fp8":
            p = fake_quant(p, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
        return out.reshape(b, qb, h * d)

    outs = jax.lax.map(block, jnp.arange(0, s, qb))          # (nb, B, qb, HD)
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h * d)


def _indexer_select(h, w, pos, spec, quant, qb: int):
    """The K keys of largest indexer score per query (all while fewer than
    K keys precede it), as a function of the query block's first row."""
    dsa = spec["dsa"]
    b, s, _ = h.shape
    hi, di, k = dsa["indexer_heads"], dsa["indexer_dim"], dsa["k"]
    qi = matmul(h, w["idx_wq"], quant).reshape(b, s, hi, di)
    qi = rope(qi, pos, 1.0, spec["rope_base"])
    ki = matmul(h, w["idx_wk"], quant).reshape(b, s, 1, di)
    ki = rope(ki, pos, 1.0, spec["rope_base"])[:, :, 0]
    if quant == "fp8":
        qi, ki = fake_quant(qi, axis=-1), fake_quant(ki, axis=-1)
    keys = jnp.arange(s)

    def sel(i0):
        qq = jax.lax.dynamic_slice_in_dim(qi, i0, qb, axis=1)
        score = jnp.einsum("bqhd,bsd->bqhs", qq, ki)
        score = jnp.einsum("h,bqhs->bqs", w["idx_w"], jax.nn.relu(score))
        causal = keys[None, :] <= (i0 + jnp.arange(qb))[:, None]
        score = jnp.where(causal[None], score, -jnp.inf)
        kth = jnp.sort(score, axis=-1)[..., max(s - k, 0), None]
        # ties at the K-th score go to the lowest positions
        above = jnp.sum(score > kth, axis=-1, keepdims=True)
        tied = score == kth
        first = jnp.cumsum(tied, axis=-1) <= k - above
        return causal[None] & ((score > kth) | (tied & first))
    return sel


@partial(jax.jit, static_argnames=("spec_key", "sparse", "quant", "mlp"))
def _layer(x, w, pos, *, spec_key, sparse, quant, mlp):
    spec = dict(spec_key)
    spec["dsa"], spec["moe"] = dict(spec["dsa"]), dict(spec["moe"])
    b, s, _ = x.shape
    nh, kvh, hd = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    qb = min(64 if sparse else 128, s)
    h = rms_norm(x, w["ln1"], spec["norm_eps"])
    q = matmul(h, w["wq"], quant).reshape(b, s, nh, hd)
    k = matmul(h, w["wk"], quant).reshape(b, s, kvh, hd)
    v = matmul(h, w["wv"], quant).reshape(b, s, kvh, hd)
    q = rope(q, pos, spec["rope_fraction"], spec["rope_base"])
    k = rope(k, pos, spec["rope_fraction"], spec["rope_base"])
    sel = (_indexer_select(h, w, pos, spec, quant, qb) if sparse
           else (lambda i0: None))
    x = _round(x + matmul(_attention(q, k, v, sel, qb, quant), w["wo"], quant),
               quant)
    h = rms_norm(x, w["ln2"], spec["norm_eps"])
    return _round(x + MLPS[mlp](h, w, spec, quant), quant)


def _round(x, quant):
    """The residual stream in the control's precision, as the program keeps
    its own in the served one."""
    return fake_quant(x, axis=-1) if quant == "fp8" else x


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, rows, final_norm, head, *, eps, quant):
    """Logits (N, V) at the flat (batch, position) rows of x."""
    h = rms_norm(x.reshape(-1, x.shape[-1])[rows], final_norm
                 .astype(jnp.float32), eps)
    return matmul(h, head.astype(jnp.float32), quant)


MLPS: Dict[str, Callable] = {"dense": dense_mlp}

_LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
               "w_down", "router", "idx_wq", "idx_wk", "idx_w")


def _spec_key(spec):
    keys = ("n_heads", "n_kv_heads", "head_dim", "rope_fraction", "rope_base",
            "norm_eps")
    dsa = tuple(sorted((k, v) for k, v in spec["dsa"].items()))
    moe = tuple(sorted((k, v) for k, v in (spec.get("moe") or {}).items()))
    return tuple((k, spec[k]) for k in keys) + (("dsa", dsa), ("moe", moe))


def logits(spec: Dict[str, Any], flat: Dict[str, jax.Array],
           seqs: List[np.ndarray], rows: List[np.ndarray], *, sparse: bool,
           quant: Optional[str] = None, mlp: str = "dense",
           pad_to: int = 512) -> jax.Array:
    """Reference logits (sum(len(r) for r in rows), V), float32, at
    positions `rows[i]` of sequence `seqs[i]`, for the weights `flat`
    (bench/weights.py leaves). Sequences run together, padded at the end
    to a multiple of `pad_to` (padding sits after every real position, so
    causality keeps it out)."""
    s = -(-max(len(q) for q in seqs) // pad_to) * pad_to
    tokens = np.zeros((len(seqs), s), np.int32)
    for i, q in enumerate(seqs):
        tokens[i, :len(q)] = q
    flat_rows = np.concatenate([i * s + np.asarray(r, np.int64)
                                for i, r in enumerate(rows)]).astype(np.int32)
    key = _spec_key(spec)
    with jax.default_matmul_precision("highest"):
        x = _round(flat["embed"][jnp.asarray(tokens)].astype(jnp.float32),
                   quant)
        pos = jnp.arange(s)
        for layer in range(spec["n_layers"]):
            w = {k: flat[k][layer] for k in _LAYER_KEYS if k in flat}
            x = _layer(x, w, pos, spec_key=key, sparse=sparse, quant=quant,
                       mlp=mlp)
        head = flat["embed"].T if spec["tie_embeddings"] else flat["lm_head"]
        return _head(x, jnp.asarray(flat_rows), flat["final_norm"], head,
                     eps=spec["norm_eps"], quant=quant)
