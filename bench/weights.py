"""Weights drawn from the seed, on the device, in one jitted call.

The benchmark makes the weights itself, in the layout the program's
transformer takes (embedding, layers stacked on a leading axis, final
norm, head), so that the reference can draw the very same values again
from the seed without taking anything the program made. Matrices are
normal with variance 1/fan_in, norm scales 1 + N(0, 0.1^2), the router
in float32 (the program keeps it so), everything else in the served type.
"""

from __future__ import annotations

import json
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# leaf name -> fixed stream id: a leaf's values never depend on which
# other leaves the configuration has
_STREAMS = {name: i for i, name in enumerate((
    "embed", "final_norm", "lm_head", "ln1", "ln2", "wq", "wk", "wv", "wo",
    "router", "w_gate", "w_up", "w_down", "idx_wq", "idx_wk", "idx_w"))}


def seed_key(seed: int) -> jax.Array:
    """A key for any whole seed, however large (the high bits fold in)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """leaf -> (shape, dtype, scale); layer leaves carry the layer axis.
    scale is the standard deviation of a matrix; -1 marks a norm scale,
    -2 the indexer's positive head weights."""
    d, v, n = cfg["d_model"], cfg["vocab"], cfg["n_layers"]
    h, kvh, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    dt = cfg["dtype"]
    out = {
        # a tied embedding is the head too: drawn as a head (1/d_model),
        # or the input token's own row would outweigh the rest of the
        # residual stream and win every greedy step whatever the layers do
        "embed": ((v, d), dt, d ** -0.5 if cfg["tie_embeddings"] else 1.0),
        "final_norm": ((d,), "float32", -1.0),
        "ln1": ((n, d), "float32", -1.0),
        "ln2": ((n, d), "float32", -1.0),
        "wq": ((n, d, h * hd), dt, d ** -0.5),
        "wk": ((n, d, kvh * hd), dt, d ** -0.5),
        "wv": ((n, d, kvh * hd), dt, d ** -0.5),
        "wo": ((n, h * hd, d), dt, (h * hd) ** -0.5),
    }
    if not cfg["tie_embeddings"]:
        out["lm_head"] = ((d, v), dt, d ** -0.5)
    moe = cfg.get("moe")
    if moe and moe["num_experts"]:
        e, f = moe["num_experts"], moe["expert_d_ff"]
        out["router"] = ((n, d, e), "float32", d ** -0.5)
        out["w_gate"] = ((n, e, d, f), dt, d ** -0.5)
        out["w_up"] = ((n, e, d, f), dt, d ** -0.5)
        out["w_down"] = ((n, e, f, d), dt, f ** -0.5)
    else:
        ff = cfg["d_ff"]
        out["w_gate"] = ((n, d, ff), dt, d ** -0.5)
        out["w_up"] = ((n, d, ff), dt, d ** -0.5)
        out["w_down"] = ((n, ff, d), dt, ff ** -0.5)
    dsa = cfg.get("dsa")
    if dsa and dsa["enabled"]:
        hi, di = dsa["indexer_heads"], dsa["indexer_dim"]
        out["idx_wq"] = ((n, d, hi * di), dt, d ** -0.5)
        out["idx_wk"] = ((n, d, di), dt, d ** -0.5)
        out["idx_w"] = ((n, hi), "float32", -2.0)
    return out


def _draw(key, shape, dtype, scale):
    if scale == -1.0:                       # norm scale around one
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif scale == -2.0:                     # indexer head weights, positive
        x = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5) / shape[-1]
    else:
        x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(dtype)


_KEYS = ("d_model", "vocab", "n_layers", "n_heads", "n_kv_heads", "head_dim",
         "dtype", "tie_embeddings", "d_ff", "moe", "dsa")


@partial(jax.jit, static_argnums=0)
def _make_flat(cfg_json: str, key):
    return {name: _draw(jax.random.fold_in(key, _STREAMS[name]), shape,
                        jnp.dtype(dt), scale)
            for name, (shape, dt, scale) in shapes(json.loads(cfg_json)).items()}


def make_flat(cfg: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """Every leaf by its flat name, drawn in one jitted call."""
    cfg_json = json.dumps({k: cfg[k] for k in _KEYS if k in cfg},
                          sort_keys=True)
    return _make_flat(cfg_json, seed_key(seed))


def program_params(flat: Dict[str, jax.Array]) -> Dict[str, Any]:
    """The flat leaves in the program's parameter tree (no copies)."""
    layers = {k: flat[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                   "w_gate", "w_up", "w_down", "router")
              if k in flat}
    if "idx_wq" in flat:
        layers["indexer"] = {"wq": flat["idx_wq"], "wk": flat["idx_wk"],
                             "w": flat["idx_w"]}
    params = {"embed": flat["embed"], "layers": layers,
              "final_norm": flat["final_norm"]}
    if "lm_head" in flat:
        params["lm_head"] = flat["lm_head"]
    return params
