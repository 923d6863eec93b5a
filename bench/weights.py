"""Weights drawn from the seed, on the device, one leaf per jitted call.

The benchmark makes the weights itself, in the layout the program takes,
so that the reference can draw the very same values again from the seed
without taking anything the program made. Which leaves a configuration
has, and how they make the program's parameter tree, is its layout's:
`bench/layouts/<name>.py`, named by the configuration's `"layout"` (`gqa`
where it names none), with

    shapes(spec) -> {leaf: (shape, dtype, scale[, fold])}
    program_params(flat, spec) -> the program's parameter tree
    flops_per_layer(spec, context, sparse) -> [one token's FLOPs, a layer]

This module draws. Each leaf comes from a stream of the seed that its
name alone fixes (`stream`), so its values never depend on which other
leaves the layout has, and each is drawn in a jitted call of its own, so
the peak while drawing passes the leaves by one leaf's float32 temporary
at most. Matrices are
normal with standard deviation `scale`, norm scales 1 + N(0, 0.1^2)
(scale -1), the indexer's positive head weights uniform in [0.5, 1.5]
over their count (scale -2); the layout gives each leaf's type.

`fold` draws the leading axes key by key: slice i of the j-th leading
axis comes from `fold_in(<key of the slice around it>, fold[j] + i)`. A
stack drawn one layer at a time has `fold` (0,), and the bound is then
one layer's temporary. `experts` gives an expert leaf the global index of
each expert held, so the experts a chip holds are exactly that slice of
what the uncut layer would draw.
"""

from __future__ import annotations

import zlib
from functools import partial
from types import ModuleType
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from bench.registry import Registry

# fixed stream ids of the `gqa` leaves: the committed configurations'
# weights depend on them; any other leaf's id comes from its name
_STREAMS = {name: i for i, name in enumerate((
    "embed", "final_norm", "lm_head", "ln1", "ln2", "wq", "wk", "wv", "wo",
    "router", "w_gate", "w_up", "w_down", "idx_wq", "idx_wk", "idx_w"))}


def stream(name: str) -> int:
    """A leaf's stream id, from its name alone."""
    if name in _STREAMS:
        return _STREAMS[name]
    return len(_STREAMS) + zlib.crc32(name.encode()) % 2 ** 31


def seed_key(seed: int) -> jax.Array:
    """A key for any whole seed, however large (the high bits fold in)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def expert_share(moe: Dict[str, Any]) -> Tuple[int, int, int]:
    """(routed, held, first): the experts the router scores (`total_experts`,
    the published count), those held on this chip (`num_experts`) and the
    global index of the first held (`first_expert`). A configuration that
    names neither holds every expert."""
    held = moe["num_experts"]
    return moe.get("total_experts", held), held, moe.get("first_expert", 0)


def experts(moe: Dict[str, Any], lead: Sequence[int], shape: Sequence[int],
            dtype: str, scale: float):
    """The entry of an expert leaf: `lead` leading axes (a layer stack,
    drawn a slice at a time), the experts held, then each expert's
    `shape`; expert e is drawn from its global index."""
    _, held, first = expert_share(moe)
    return ((*lead, held, *shape), dtype, scale, (0,) * len(lead) + (first,))


def _draw(key, shape, dtype, scale):
    if scale == -1.0:                       # norm scale around one
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif scale == -2.0:                     # indexer head weights, positive
        x = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5) / shape[-1]
    else:
        x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(dtype)


def _folded(key, shape, dtype, scale, fold):
    if not fold:
        return _draw(key, shape, dtype, scale)
    return jax.vmap(lambda i: _folded(jax.random.fold_in(key, i), shape[1:],
                                      dtype, scale, fold[1:]))(
        fold[0] + jnp.arange(shape[0]))


@partial(jax.jit, static_argnums=(1, 2, 3))
def _whole(key, shape, dtype, scale):
    return _draw(key, shape, jnp.dtype(dtype), scale)


@partial(jax.jit, static_argnums=(3, 4, 5, 6), donate_argnums=0)
def _into(out, key, i, shape, dtype, scale, fold):
    x = _folded(jax.random.fold_in(key, fold[0] + i), shape[1:],
                jnp.dtype(dtype), scale, fold[1:])
    return jax.lax.dynamic_update_index_in_dim(out, x, i, 0)


def draw(key, shape, dtype, scale, fold=()) -> jax.Array:
    """One leaf from its key: whole, or its first axis a slice a call."""
    shape = tuple(shape)
    if not fold:
        return _whole(key, shape, dtype, scale)
    out = jnp.zeros(shape, dtype)
    for i in range(shape[0]):
        out = _into(out, key, i, shape, dtype, scale, tuple(fold))
    return out


def layout(spec: Dict[str, Any], reg: Optional[Registry] = None) -> ModuleType:
    """The configuration's layout module."""
    return (reg or Registry()).layout(spec)


def make_flat(spec: Dict[str, Any], seed: int,
              reg: Optional[Registry] = None) -> Dict[str, jax.Array]:
    """Every leaf of the configuration's layout by its flat name."""
    key = seed_key(seed)
    return {name: draw(jax.random.fold_in(key, stream(name)), *entry)
            for name, entry in layout(spec, reg).shapes(spec).items()}


def program_params(flat: Dict[str, jax.Array], spec: Dict[str, Any],
                   reg: Optional[Registry] = None) -> Dict[str, Any]:
    """The flat leaves in the program's parameter tree (no copies)."""
    return layout(spec, reg).program_params(flat, spec)
