"""Logical-axis → mesh-axis sharding rules (DP/TP/EP/SP).

Rules map logical tensor axes to mesh axes, with a divisibility guard: a
logical axis whose size does not divide the assigned mesh-axis extent falls
back to replication (e.g. qwen2-vl's 28 heads on a 16-way model axis, or
whisper's 51865 vocab). This is the MaxText-style behavior and keeps every
assigned architecture shardable on the fixed production mesh without
padding weights.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
from jax.sharding import PartitionSpec as P


# default logical rules; "batch" spans both pod and data for multi-pod DP
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,                 # sequence replicated in train (no SP default)
    "seq_shard": ("data",),      # SP: long-context decode KV sharding
    "heads": ("model",),
    "kv_heads": ("model",),
    "d_model": None,
    "d_ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_ff": None,
    "indexer": None,
    "state": None,
}


@dataclasses.dataclass
class MeshRules:
    mesh: jax.sharding.Mesh
    rules: dict

    def axes(self, logical: Optional[str]) -> Optional[Union[str, tuple]]:
        if logical is None:
            return None
        r = self.rules.get(logical)
        if r is None:
            return None
        present = tuple(a for a in (r if isinstance(r, tuple) else (r,))
                        if a in self.mesh.axis_names)
        if not present:
            return None
        return present if len(present) > 1 else present[0]

    def _extent(self, axes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            return self.mesh.shape[axes]
        e = 1
        for a in axes:
            e *= self.mesh.shape[a]
        return e

    def spec(self, *logical: Optional[str], sizes: Optional[Sequence[int]] = None) -> P:
        """PartitionSpec for logical axes; replicates non-divisible dims."""
        out = []
        for i, name in enumerate(logical):
            axes = self.axes(name)
            if axes is not None and sizes is not None:
                if sizes[i] % self._extent(axes) != 0:
                    axes = None              # divisibility fallback
            out.append(axes)
        return P(*out)


def make_rules(mesh, overrides: Optional[dict] = None) -> MeshRules:
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    return MeshRules(mesh=mesh, rules=rules)


def overrides_for(cfg, shape_kind: str) -> dict:
    """Per-(arch × shape) parallelism policy (perf iteration #1, see
    EXPERIMENTS.md §Perf).

    The production mesh is fixed at (pod)×16×16, but TP width must follow
    model width: Megatron-style TP=16 on a ≤4K-wide model moves ~6 activation
    all-reduces of (B_loc·S·d) per layer per step — far more traffic than its
    entire gradient. Policy for train/prefill:

      * wide dense models (d_model ≥ 6144: granite-34b, jamba): keep TP=16
        (parameter memory forces it);
      * MoE models: experts → model axis (EP all-to-all), attention/embed
        replicated over model, batch → (pod, data);
      * everything else: pure DP — batch spans (pod, data, model); optimizer
        state ZeRO-shards over the same axes; no activation collectives.

    Decode keeps the default rules: one token per step means param-read
    bandwidth dominates, and TP=16 divides exactly that.
    """
    if shape_kind not in ("train", "prefill"):
        return {}
    if cfg.moe.num_experts and not cfg.attn_every:
        return {"batch": ("pod", "data"), "heads": None, "kv_heads": None,
                "d_ff": None, "vocab": None}
    if cfg.d_model >= 6144 or cfg.attn_every or cfg.family == "ssm":
        # wide models: TP is forced by memory. SSM: the recurrence's time
        # scan places DP gradient reductions inside a 4096-trip loop under
        # pure DP (measured 27 s -> 346 s collective) — TP keeps them out.
        return {}
    return {"batch": ("pod", "data", "model"), "heads": None,
            "kv_heads": None, "d_ff": None, "vocab": None}


def constrain(x, rules: Optional[MeshRules], *logical):
    """with_sharding_constraint via logical names (no-op without rules)."""
    if rules is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(rules.mesh,
                                      rules.spec(*logical, sizes=x.shape)))
