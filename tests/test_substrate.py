"""Substrate tests: checkpoint atomicity/resume, data determinism/elasticity,
optimizer, fault-tolerance units, sharding rules."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.data.pipeline import batch_for_step
from repro.optim import adamw
from repro.runtime import fault_tolerance as ft

RNG = np.random.default_rng(4)


# ------------------------------ checkpoint --------------------------------

def _tree():
    return {"w": jnp.asarray(RNG.normal(size=(4, 8)), jnp.float32),
            "b": {"x": jnp.arange(5, dtype=jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), t, 7)
    out, step = ckpt.restore_latest(str(tmp_path), t)
    assert step == 7
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b), t, out)


def test_checkpoint_latest_and_retention(tmp_path):
    t = _tree()
    for s in [1, 2, 3, 4, 5]:
        ckpt.save(str(tmp_path), t, s, keep_last=2)
    assert ckpt.all_steps(str(tmp_path)) == [4, 5]


def test_checkpoint_atomicity_tmp_never_restored(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), t, 1)
    # simulate a crash mid-write: stale tmp dir must be ignored
    os.makedirs(tmp_path / "step_2.tmp")
    out, step = ckpt.restore_latest(str(tmp_path), t)
    assert step == 1


def test_checkpoint_structure_validation(tmp_path):
    ckpt.save(str(tmp_path), _tree(), 1)
    bad = {"w": jnp.zeros((4, 8)), "b": {"y": jnp.zeros(5)}}
    with pytest.raises(ValueError):
        ckpt.restore(str(tmp_path), 1, bad)


def test_checkpoint_async(tmp_path):
    t = _tree()
    th = ckpt.save(str(tmp_path), t, 3, block=False)
    th.join()
    assert ckpt.all_steps(str(tmp_path)) == [3]


# ------------------------------ data --------------------------------------

def test_data_determinism_across_restart():
    a = batch_for_step(11, vocab=1000, batch=8, seq=16, seed=5)
    b = batch_for_step(11, vocab=1000, batch=8, seq=16, seed=5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])


def test_data_elastic_resharding_preserves_global_stream():
    """The global batch is identical whether read by 4 hosts or 2 (after a
    failure) — the elasticity contract."""
    g4 = np.concatenate([batch_for_step(3, vocab=50, batch=8, seq=4, seed=0,
                                        host_id=h, num_hosts=4)["tokens"]
                         for h in range(4)])
    g2 = np.concatenate([batch_for_step(3, vocab=50, batch=8, seq=4, seed=0,
                                        host_id=h, num_hosts=2)["tokens"]
                         for h in range(2)])
    np.testing.assert_array_equal(g4, g2)


def test_targets_are_shifted_tokens():
    b = batch_for_step(0, vocab=50, batch=2, seq=8, seed=0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])


# ------------------------------ optimizer ---------------------------------

def test_adamw_reduces_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = adamw.init(params)
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=1, total_steps=200,
                            weight_decay=0.0)
    for _ in range(100):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, opt, m = adamw.update(g, opt, params, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_grad_clip_bounds_update():
    params = {"w": jnp.zeros(3)}
    opt = adamw.init(params)
    cfg = adamw.AdamWConfig(lr=1.0, clip_norm=1e-3, warmup_steps=1,
                            weight_decay=0.0)
    g = {"w": jnp.asarray([1e9, -1e9, 1e9])}
    _, _, m = adamw.update(g, opt, params, cfg)
    assert float(m["grad_norm"]) > 1e8   # raw norm reported pre-clip


def test_zero1_specs_shard_moments():
    from jax.sharding import AbstractMesh
    from repro.parallel.sharding import make_rules
    from jax.sharding import PartitionSpec as P
    mesh = AbstractMesh((2, 2), ("data", "model"))
    rules = make_rules(mesh)
    pspecs = {"w": P(None, "model"), "tiny": P(None)}
    shapes = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
              "tiny": jax.ShapeDtypeStruct((3,), jnp.float32)}
    out = adamw.zero1_specs(pspecs, rules, sizes_tree=shapes)
    assert out["w"] == P("data", "model")     # free dim picked up ZeRO shard
    assert out["tiny"] == P(None)             # non-divisible stays replicated


# --------------------------- fault tolerance ------------------------------

def test_resilient_step_retries_then_succeeds():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    wrapped = ft.resilient_step(flaky, max_retries=3, backoff_s=0.0)
    assert wrapped(10, 5) == "ok"
    assert calls["n"] == 3


def test_resilient_step_raises_stepfailed_with_rollback_info():
    def always_fails():
        raise RuntimeError("hard fault")

    wrapped = ft.resilient_step(always_fails, max_retries=1, backoff_s=0.0)
    with pytest.raises(ft.StepFailed) as ei:
        wrapped(42, 40)
    assert ei.value.last_good_step == 40


def test_elastic_plan_rebalance():
    plan = ft.ElasticPlan(alive_hosts=list(range(8)), global_batch=64)
    plan2 = plan.rebalanced(lost=[3])
    assert len(plan2.alive_hosts) in (4, 7)   # divisor of 64
    assert 3 not in plan2.alive_hosts
    rank, n = plan2.shard_for(plan2.alive_hosts[-1])
    assert 0 <= rank < n


def test_shard_owner_deterministic_and_covering():
    alive = [0, 2, 5]
    owners = {ft.shard_owner(7, s, alive) for s in range(30)}
    assert owners <= set(alive)
    assert ft.shard_owner(7, 3, alive) == ft.shard_owner(7, 3, alive)


def test_straggler_monitor_flags_outliers():
    mon = ft.StragglerMonitor(threshold=2.0, warmup=3)
    for i in range(10):
        assert not mon.record(i, 1.0)
    assert mon.record(10, 5.0)
    assert 10 in mon.flagged


# ---------------------------- sharding rules -------------------------------

def test_divisibility_fallback():
    from jax.sharding import AbstractMesh
    from repro.parallel.sharding import make_rules
    from jax.sharding import PartitionSpec as P
    mesh = AbstractMesh((2, 8), ("data", "model"))
    rules = make_rules(mesh)
    # 28 heads on an 8-way model axis -> replicate; 32 -> shard
    assert rules.spec("d_model", "heads", sizes=(64, 28)) == P(None, None)
    assert rules.spec("d_model", "heads", sizes=(64, 32)) == P(None, "model")
