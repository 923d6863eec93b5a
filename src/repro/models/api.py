"""Unified model API: family dispatch + shape-cell input specs."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import encdec, hybrid, ssm, transformer

_FAMILY_MODULES = {
    "dense": transformer, "moe": transformer, "vlm": transformer,
    "hybrid": hybrid, "ssm": ssm, "audio": encdec,
}

# the assigned shape cells (system-prompt table)
SHAPES = {
    "train_4k": dict(kind="train", seq_len=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq_len=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq_len=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq_len=524288, global_batch=1,
                      seq_sharded=True),
}


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    mod: Any

    def init_params(self, key):
        return self.mod.init_params(key, self.cfg)

    def param_specs(self, rules):
        return self.mod.param_specs(self.cfg, rules)

    def loss_fn(self, params, batch, *, mesh=None, rules=None):
        return self.mod.loss_fn(params, batch, self.cfg, mesh=mesh, rules=rules)

    def forward_train(self, params, tokens, **kw):
        return self.mod.forward_train(params, tokens, self.cfg, **kw)

    def init_decode_state(self, batch, max_len, dtype=None):
        return self.mod.init_decode_state(self.cfg, batch, max_len, dtype=dtype)

    def state_specs(self, rules, *, batch, max_len, seq_sharded=False):
        return self.mod.state_specs(self.cfg, rules, batch=batch,
                                    max_len=max_len, seq_sharded=seq_sharded)

    # ---- slot-wise decode-state hooks (continuous-batching engine) ------
    def state_batch_axes(self) -> Optional[Dict[str, int]]:
        """Batch(slot)-axis map of the decode-state leaves, or None when the
        family doesn't expose slot-wise state (engine unsupported)."""
        fn = getattr(self.mod, "state_batch_axes", None)
        return fn(self.cfg) if fn is not None else None

    def reset_slot_state(self, state, slot, *, seq_len_hint=None):
        """Reset one slot for admission: zero length, re-seed GVR feedback."""
        fn = getattr(self.mod, "reset_slot_state", None)
        if fn is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no slot-wise state reset")
        return fn(self.cfg, state, slot, seq_len_hint=seq_len_hint)

    def recycle_slot_state(self, state, slot):
        """Recycle one slot on eviction: poison stale prediction feedback."""
        fn = getattr(self.mod, "recycle_slot_state", None)
        if fn is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no slot-wise state recycle")
        return fn(self.cfg, state, slot)

    # ---- paged decode-state variant (serve.paged subsystem) -------------
    def init_paged_decode_state(self, batch, max_len, *, num_pages, page_size,
                                dtype=None):
        """Paged KV layout: pool-of-pages caches + per-slot page tables.
        Raises for families without a paged decode path."""
        fn = getattr(self.mod, "init_paged_decode_state", None)
        if fn is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no paged decode state")
        return fn(self.cfg, batch, max_len, num_pages=num_pages,
                  page_size=page_size, dtype=dtype)

    def paged_state_batch_axes(self) -> Optional[Dict[str, int]]:
        """Slot-axis map of the paged decode-state leaves (page-pool leaves
        are absent — they are pool-global), or None when the family has no
        paged decode path."""
        fn = getattr(self.mod, "paged_state_batch_axes", None)
        return fn(self.cfg) if fn is not None else None

    def step_counters(self) -> tuple:
        """The names of the columns of the counts a step returns with
        `with_counts`, in order."""
        fn = getattr(self.mod, "step_counters", None)
        return fn(self.cfg) if fn is not None else transformer.GVR_COUNTERS

    def serve_step_paged(self, params, state, tokens, *, min_write_pos=None,
                         paged_attn="fused", gather_granularity="token",
                         mesh=None, rules=None, with_counts=False):
        """One paged decode step: the Q = 1 call of the paged forward over
        Q query rows a slot. `paged_attn` selects the sparse-attention
        form: "fused" (block-table-native, O(K) gathered KV traffic —
        default) or "gather" (materialize the logical view first; the PR-2
        oracle). `gather_granularity` ("token" | "page") picks the DMA
        shape of the fused sparse gather. All combinations are
        bit-identical — see transformer.serve_step_paged. `with_counts`
        adds the rows' GVR counts as a third result.
        """
        fn = getattr(self.mod, "serve_step_paged", None)
        if fn is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no paged serve_step")
        return fn(params, state, tokens, self.cfg,
                  min_write_pos=min_write_pos, paged_attn=paged_attn,
                  gather_granularity=gather_granularity,
                  mesh=mesh, rules=rules, with_counts=with_counts)

    def serve_step_spec_paged(self, params, state, tokens, *, draft_len,
                              max_accept, eos_id=-1, min_write_pos=None,
                              paged_attn="fused", verify_kernel="scan",
                              gather_granularity="token",
                              mesh=None, rules=None):
        """Speculative verify tick (serve.spec subsystem): score all d+1
        draft positions, greedy-accept the longest matching prefix, and
        roll the decode state back to the accepted point in-graph.
        `verify_kernel` picks the verify body: "mq" (the paged forward's
        Q = d+1 call, the forward `serve_step_paged` runs with Q = 1) or
        "scan" (d+1 sequential paged steps in one jitted scan, the
        reference mq is pinned against; bit-identical) — see
        transformer.serve_step_spec_paged."""
        fn = getattr(self.mod, "serve_step_spec_paged", None)
        if fn is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no speculative paged "
                f"serve_step")
        return fn(params, state, tokens, self.cfg, draft_len=draft_len,
                  max_accept=max_accept, eos_id=eos_id,
                  min_write_pos=min_write_pos, paged_attn=paged_attn,
                  verify_kernel=verify_kernel,
                  gather_granularity=gather_granularity,
                  mesh=mesh, rules=rules)

    # ---- sequence-sharded paged decode (SP-GVR serving path) ------------
    def init_sp_paged_decode_state(self, batch, max_len, *,
                                   num_pages_per_shard, page_size,
                                   seq_shards, dtype=None, mesh=None):
        """Sequence-sharded paged layout: per-shard page pools (leading
        shard axis) + shard-local block tables, placed on `mesh` when
        given. Raises for families without the sharded decode path."""
        fn = getattr(self.mod, "init_sp_paged_decode_state", None)
        if fn is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no sequence-sharded "
                f"paged decode state")
        return fn(self.cfg, batch, max_len,
                  num_pages_per_shard=num_pages_per_shard,
                  page_size=page_size, seq_shards=seq_shards, dtype=dtype,
                  mesh=mesh)

    def sp_paged_state_batch_axes(self) -> Optional[Dict[str, int]]:
        """Slot-axis map of the sequence-sharded paged decode state
        (sharded page pools absent — pool-global per shard), or None."""
        fn = getattr(self.mod, "sp_paged_state_batch_axes", None)
        return fn(self.cfg) if fn is not None else None

    def serve_step_sp_paged(self, params, state, tokens, *, mesh,
                            min_write_pos=None, rules=None,
                            with_counts=False):
        """One sequence-sharded paged decode step (shard_map over the
        mesh's "seq" axis; SP-GVR selection + O(K)-psum paged attention):
        the Q = 1 call of the sharded forward over Q query rows.
        Bit-identical to `serve_step_paged(paged_attn="fused")` — see
        transformer.serve_step_sp_paged. `with_counts` adds the rows' GVR
        counts as a third result."""
        fn = getattr(self.mod, "serve_step_sp_paged", None)
        if fn is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no sequence-sharded "
                f"paged serve_step")
        return fn(params, state, tokens, self.cfg, mesh=mesh,
                  min_write_pos=min_write_pos, rules=rules,
                  with_counts=with_counts)

    def serve_step_sp_spec_paged(self, params, state, tokens, *, mesh,
                                 draft_len, max_accept, eos_id=-1,
                                 min_write_pos=None, verify_kernel="scan",
                                 rules=None):
        """Sequence-sharded speculative verify tick (one shard_map over
        the d+1 draft positions; `verify_kernel` picks "mq", the sharded
        forward's Q = d+1 call, or "scan", its reference of d+1 Q = 1
        calls; bit-identical) — see transformer.serve_step_sp_spec_paged."""
        fn = getattr(self.mod, "serve_step_sp_spec_paged", None)
        if fn is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no sequence-sharded "
                f"speculative paged serve_step")
        return fn(params, state, tokens, self.cfg, mesh=mesh,
                  draft_len=draft_len, max_accept=max_accept, eos_id=eos_id,
                  min_write_pos=min_write_pos, verify_kernel=verify_kernel,
                  rules=rules)

    def serve_step(self, params, state, tokens, *, mesh=None, rules=None,
                   seq_sharded: bool = False, with_counts: bool = False):
        """One decode step. `with_counts` (families with a DSA selector:
        the transformer) adds the rows' GVR counts as a third result."""
        if self.cfg.family == "hybrid":
            return self.mod.serve_step(params, state, tokens, self.cfg,
                                       mesh=mesh, rules=rules,
                                       seq_sharded=seq_sharded)
        counts = {"with_counts": True} if with_counts else {}
        return self.mod.serve_step(params, state, tokens, self.cfg,
                                   mesh=mesh, rules=rules, **counts)

    # ---- dry-run stand-ins (ShapeDtypeStruct; no allocation) ------------
    def input_specs(self, shape: str) -> Dict[str, Any]:
        s = SHAPES[shape]
        b, sl = s["global_batch"], s["seq_len"]
        i32 = jnp.int32
        if s["kind"] in ("train", "prefill"):
            specs = {
                "tokens": jax.ShapeDtypeStruct((b, sl), i32),
                "targets": jax.ShapeDtypeStruct((b, sl), i32),
            }
            if self.cfg.family == "audio":
                specs["frames"] = jax.ShapeDtypeStruct(
                    (b, self.cfg.encoder_frames, self.cfg.d_model),
                    jnp.dtype(self.cfg.dtype))
            if self.cfg.num_patches:
                specs["patch_embeds"] = jax.ShapeDtypeStruct(
                    (b, self.cfg.num_patches, self.cfg.d_model),
                    jnp.dtype(self.cfg.dtype))
            return specs
        # decode: one new token; the KV/state cache is part of the state specs
        return {"tokens": jax.ShapeDtypeStruct((b,), i32)}

    def decode_state_specs(self, shape: str):
        s = SHAPES[shape]
        assert s["kind"] == "decode"
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.eval_shape(lambda: self.init_decode_state(
                s["global_batch"], s["seq_len"])))


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg=cfg, mod=_FAMILY_MODULES[cfg.family])


def supported_shapes(cfg: ModelConfig) -> list:
    """Which of the 4 assigned shape cells apply to this arch (DESIGN
    §Arch-applicability): long_500k only for sub-quadratic families;
    decode skipped for encoder-only archs (none assigned)."""
    shapes = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.family in ("ssm", "hybrid"):
        shapes.append("long_500k")
    return shapes
