"""Production mesh construction (single-pod 16x16 and multi-pod 2x16x16).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax


def _auto(n_axes: int):
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / small runs)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)))


def make_seq_mesh(seq_shards: int):
    """1-D sequence mesh for the sequence-sharded decode engine
    (`DecodeEngine(kv_layout="paged", seq_shards=S)`): each device owns the
    KV pages of one contiguous span of the logical token range, and
    `serve_step_sp_paged` shard_maps over the "seq" axis."""
    if seq_shards > len(jax.devices()):
        raise ValueError(
            f"seq_shards={seq_shards} exceeds the {len(jax.devices())} "
            f"available device(s) — on CPU hosts force more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            f"the first jax call")
    return jax.make_mesh((seq_shards,), ("seq",), axis_types=_auto(1))
