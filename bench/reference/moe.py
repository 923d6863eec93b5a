"""Plain float32 reference of the decoder with routed experts.

The layer of bench/reference/dense.py with its MLP replaced: the router
(float32) scores every expert, each token keeps its top_k experts by score
and weighs them by a softmax over those top_k scores, and each kept expert
is a SwiGLU of width expert_d_ff:

    y = sum over kept e of softmax(r_kept)_e * (silu(h Wg_e) * (h Wu_e)) Wd_e

Every expert is computed for every token here and the kept ones summed
with their weights (zero for the rest): plain, not fast.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import dense

TOKEN_BLOCK = 1024


def moe_mlp(h, w, spec, quant):
    b, s, d = h.shape
    top_k = spec["moe"]["top_k"]
    t = h.reshape(b * s, d)
    r = t @ w["router"]                                     # (T, E)
    vals, idx = jax.lax.top_k(r, top_k)
    gates = jax.nn.softmax(vals, axis=-1)
    mix = jnp.sum(jax.nn.one_hot(idx, r.shape[-1]) * gates[..., None],
                  axis=1)                                   # (T, E)
    wg, wu, wd = w["w_gate"], w["w_up"], w["w_down"]
    if quant == "fp8":
        wg, wu, wd = (dense.fake_quant(wg), dense.fake_quant(wu),
                      dense.fake_quant(wd))

    def block(args):
        tb, mb = args
        a = dense.fake_quant(tb, axis=-1) if quant == "fp8" else tb
        g = jnp.einsum("td,edf->tef", a, wg)
        u = jnp.einsum("td,edf->tef", a, wu)
        hh = jax.nn.silu(g) * u
        if quant == "fp8":
            hh = dense.fake_quant(hh, axis=-1)
        y = jnp.einsum("tef,efd->ted", hh, wd)
        return jnp.einsum("te,ted->td", mb, y)

    n = t.shape[0]
    tb = math.gcd(n, TOKEN_BLOCK)
    out = jax.lax.map(block, (t.reshape(n // tb, tb, d),
                              mix.reshape(n // tb, tb, -1)))
    return out.reshape(b, s, d)


dense.MLPS["moe"] = moe_mlp


def logits(spec, flat, seqs, rows, *, sparse, quant=None, pad_to=512):
    """As dense.logits, with routed experts in place of the MLP."""
    return dense.logits(spec, flat, seqs, rows, sparse=sparse, quant=quant,
                        mlp="moe", pad_to=pad_to)
