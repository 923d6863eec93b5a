"""Sequence-sharded paged serving: the DecodeEngine over the SP-GVR path.

Pins `DecodeEngine(kv_layout="paged", seq_shards=S)` bit-identical —
tokens, per-tick method log, GVR hit rate, prefix-cache hits — to the
single-device `paged_attn="fused"` engine on the same traces, for S=2 and
S=4, including a cross-shard shared-prefix trace and a preemption trace
(page pressure confined to shard 0 with matched per-pool capacity, so both
engines preempt the same victim at the same tick).

Multi-device CPU meshes require forcing the host device count before the
first jax call, so the sharded runs happen in a subprocess (same harness as
tests/test_sp_gvr.py); the tests skip cleanly when the runner cannot
provide the forced mesh."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.mesh, pytest.mark.slow]

_SCRIPT = r"""
import jax, numpy as np, json
from repro.configs.registry import get_config
from repro.models.api import build_model
from repro.serve import DecodeEngine, Request

cfg = get_config("llama3.2-1b", smoke=True)
model = build_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))

def mk_cov(seed=5):
    # two prompts share a 24-token (3-page) prefix that SPANS the shard
    # boundary at S=4 (n_local = 16 tokens); the sharer arrives after the
    # first request's prefill commit so the chain actually hits
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, (24,))
    return [Request(uid=0, prompt=np.concatenate(
                        [shared, rng.integers(0, cfg.vocab, (13,))]),
                    max_new_tokens=8, arrival=0),
            Request(uid=1, prompt=np.concatenate(
                        [shared, rng.integers(0, cfg.vocab, (6,))]),
                    max_new_tokens=6, arrival=20),
            Request(uid=2, prompt=rng.integers(0, cfg.vocab, (40,)),
                    max_new_tokens=10, arrival=6)]

def mk_pre(seed=9):
    # both requests' pages stay in shard 0's span ([0, 32) at S=2), and the
    # long-running second request holds pages when the first crosses into
    # logical page 3 — pool pressure, then preemption, in both layouts
    rng = np.random.default_rng(seed)
    return [Request(uid=0, prompt=rng.integers(0, cfg.vocab, (20,)),
                    max_new_tokens=8, arrival=0),
            Request(uid=1, prompt=rng.integers(0, cfg.vocab, (12,)),
                    max_new_tokens=16, arrival=0)]

def run(reqs, **kw):
    eng = DecodeEngine(model, params, num_slots=2, max_len=64,
                       prefill_chunk=4, kv_layout="paged", page_size=8, **kw)
    rep = eng.run(reqs, max_ticks=500)
    if hasattr(eng.kv, "assert_consistent"):
        eng.kv.assert_consistent()
    return {
        "tokens": [r.generated for r in reqs],
        "log": {str(u): v for u, v in sorted(eng.method_log.items())},
        "hit": rep.gvr_hit_rate,
        "decode_counts": rep.decode_method_counts,
        "prefix": rep.prefix_hit_tokens,
        "preempt": rep.preemptions,
        "completed": rep.completed,
        "step_signatures": eng._tick_fn._cache_size(),
    }

out = {"cov": {}, "pre": {}}
out["cov"]["single"] = run(mk_cov(), paged_attn="fused")
for s in (2, 4):
    out["cov"][f"sp{s}"] = run(mk_cov(), seq_shards=s)
out["pre"]["single"] = run(mk_pre(), num_pages=5, paged_attn="fused")
out["pre"]["sp2"] = run(mk_pre(), num_pages=5, seq_shards=2)
print("RESULT:" + json.dumps(out))
"""


from _mesh_compat import REPO_ROOT, forced_mesh_env, probe_forced_mesh


@pytest.fixture(scope="module")
def sp_engine_results():
    if not probe_forced_mesh(4):
        pytest.skip("runner cannot force a 4-device CPU mesh")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, env=forced_mesh_env(4), timeout=900,
                       cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


@pytest.mark.parametrize("shards", ["sp2", "sp4"])
def test_sp_engine_bit_identical_to_fused(sp_engine_results, shards):
    """Same ragged staggered trace with a cross-shard shared prefix: the
    sequence-sharded engine must reproduce the single-device fused run
    verbatim — generated tokens, per-tick (tick, phase, method) log, GVR
    hit rate and prefix-cache hit accounting."""
    single = sp_engine_results["cov"]["single"]
    sharded = sp_engine_results["cov"][shards]
    assert sharded["completed"] == single["completed"] == 3
    assert sharded["tokens"] == single["tokens"]
    assert sharded["log"] == single["log"]
    assert sharded["hit"] == single["hit"]
    assert sharded["decode_counts"] == single["decode_counts"]
    assert sharded["prefix"] == single["prefix"]


def test_sp_engine_coverage_trace_is_meaningful(sp_engine_results):
    """The pin must exercise what it claims to: warm GVR decode ticks, a
    non-trivial shared-prefix hit (3 pages — spanning the shard boundary
    at S=4), and no accidental preemptions muddying the comparison."""
    single = sp_engine_results["cov"]["single"]
    assert single["prefix"] == 24
    assert single["preempt"] == 0
    assert single["decode_counts"].get("gvr", 0) > 0
    assert 0.0 < single["hit"] <= 1.0


def test_sp_engine_preemption_trace_bit_identical(sp_engine_results):
    """Page pressure confined to shard 0 with per-shard capacity equal to
    the single-pool run's: both engines must preempt (at least once), pick
    the same victim at the same tick, and replay to identical tokens."""
    single = sp_engine_results["pre"]["single"]
    sharded = sp_engine_results["pre"]["sp2"]
    assert single["preempt"] >= 1
    assert sharded["preempt"] == single["preempt"]
    assert sharded["tokens"] == single["tokens"]
    assert sharded["log"] == single["log"]
    assert sharded["hit"] == single["hit"]


@pytest.mark.parametrize("trace,layout", [("cov", "single"), ("cov", "sp2"),
                                          ("cov", "sp4"), ("pre", "single"),
                                          ("pre", "sp2")])
def test_engine_step_compiles_once(sp_engine_results, trace, layout):
    """Host-side state updates (block-table pushes, admission, eviction,
    copy-on-write) keep every leaf's placement, so the pool-wide step has
    one signature for the whole run: on a chip each extra one is another
    full-width compile."""
    assert sp_engine_results[trace][layout]["step_signatures"] == 1


# ---- shard-aware preemption victim choice (host-side, no mesh) ------------


def test_preempt_victim_prefers_pressured_shard_holders():
    """ROADMAP open item, now pinned: when a PoolExhausted names a
    pressured shard, the victim must actually HOLD pages in that shard —
    the old shard-blind order would preempt the PREFILL slot with the most
    remaining prompt even when all its pages live elsewhere, destroying
    its work without freeing a single page where the allocation failed."""
    from types import SimpleNamespace
    import numpy as np
    from repro.serve import DecodeEngine
    from repro.serve.scheduler import DECODE, PREFILL

    class KV:
        def __init__(self, holdings):
            self._h = holdings

        def pages_in_shard(self, slot, shard):
            return self._h[slot].get(shard, 0)

    # slot 0: PREFILL, most remaining prompt (old-policy victim) but all
    # pages in shard 1; slot 1: PREFILL holding shard-0 pages; slot 2:
    # DECODE holding shard-0 pages
    slots = [
        SimpleNamespace(phase=PREFILL, prompt=np.zeros(40), prefill_pos=0,
                        admitted_at=5, generated=[]),
        SimpleNamespace(phase=PREFILL, prompt=np.zeros(10), prefill_pos=0,
                        admitted_at=1, generated=[]),
        SimpleNamespace(phase=DECODE, prompt=np.zeros(8), prefill_pos=8,
                        admitted_at=0, generated=[1, 2]),
    ]
    stub = SimpleNamespace(slots=slots,
                           kv=KV({0: {1: 4}, 1: {0: 2}, 2: {0: 1}}))
    pick = DecodeEngine._preempt_victim
    # pressured shard 0: slot 1 is the only PREFILL holder → victim
    assert pick(stub, exclude=None, shard=0) == 1
    # shard-blind (single pool / no shard info): old order unchanged
    assert pick(stub, exclude=None, shard=None) == 0
    # only the DECODE slot holds shard-0 pages → PREFILL order falls
    # through to it
    stub2 = SimpleNamespace(slots=slots,
                            kv=KV({0: {1: 4}, 1: {1: 2}, 2: {0: 1}}))
    assert pick(stub2, exclude=None, shard=0) == 2
    # nobody holds pages in the pressured shard: preempting anyone would
    # be pure waste → None (the engine then reports the per-shard squeeze)
    assert pick(stub, exclude=None, shard=3) is None


# ---- constructor contracts (no multi-device mesh needed) ------------------

def _smoke_model():
    import jax
    from repro.configs.registry import get_config
    from repro.models.api import build_model
    cfg = get_config("llama3.2-1b", smoke=True)
    model = build_model(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def test_seq_shards_requires_paged_layout():
    from repro.serve import DecodeEngine
    model, params = _smoke_model()
    with pytest.raises(ValueError, match="paged"):
        DecodeEngine(model, params, num_slots=2, max_len=64,
                     kv_layout="dense", seq_shards=2)


def test_seq_shards_requires_fused_paged_attn():
    from repro.serve import DecodeEngine
    model, params = _smoke_model()
    with pytest.raises(ValueError, match="fused"):
        DecodeEngine(model, params, num_slots=2, max_len=64,
                     kv_layout="paged", page_size=8, seq_shards=2,
                     paged_attn="gather")


def test_seq_shards_requires_page_aligned_spans():
    from repro.serve import DecodeEngine
    model, params = _smoke_model()
    with pytest.raises(ValueError, match="page_size"):
        DecodeEngine(model, params, num_slots=2, max_len=40,
                     kv_layout="paged", page_size=8, seq_shards=4)


def test_seq_shards_single_device_fails_with_actionable_error():
    """On a runner without enough devices the engine must fail (or build)
    with a clear message naming the XLA_FLAGS escape hatch, never an
    opaque mesh assertion — the single-device-runner contract."""
    import jax
    from repro.serve import DecodeEngine
    model, params = _smoke_model()
    want = len(jax.devices()) + 1
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        DecodeEngine(model, params, num_slots=2, max_len=64 * want,
                     kv_layout="paged", page_size=8, seq_shards=want)
