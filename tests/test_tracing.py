"""Tracing inside the program: the decode step's named scopes in the
compiled HLO, the engine's on-device GVR counters, and the engine's
tick-phase spans in a profiler trace (CPU, smoke configs)."""

import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.gvr import DEFAULT_MAX_SECANT
from repro.models.api import build_model
from repro.models.config import DSAConfig
from repro.serve import DecodeEngine, Request
from repro.serve.engine import GVR_COUNTERS

STEP_SCOPES = ("step.qkv", "step.kv_write", "step.indexer", "step.topk",
               "step.attention", "step.mlp", "step.head")
RNG = np.random.default_rng(11)


def _engine(cfg, **kw):
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_chunk", 4)
    return DecodeEngine(model, params, kv_layout="paged", page_size=16,
                        paged_attn="fused", **kw)


def _step_hlo(eng) -> str:
    b = eng.num_slots
    return eng._tick_fn.lower(
        eng.params, eng.state, jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), bool), jnp.zeros((b,), jnp.int32)).compile().as_text()


def _scopes_in(hlo: str):
    return {s for s in STEP_SCOPES if f"/{s}/" in hlo}


def _warm_decode(eng, reqs):
    """Submit, then tick until every request decodes warm (its last tick
    served by GVR)."""
    for r in reqs:
        eng.submit(r)
    while not all(r.phase == "DECODE" and eng.method_log[r.uid][-1][2] == "gvr"
                  for r in reqs):
        eng.tick()


def test_compiled_step_carries_every_scope_dsa():
    """The paged step of a DSA config (gate open: max_len 64 > min_n 8)
    names every stage in its compiled op_name metadata; the selector's
    own jits stay nested inside `step.topk`."""
    eng = _engine(get_config("llama3.2-1b", smoke=True))
    hlo = _step_hlo(eng)
    assert _scopes_in(hlo) == set(STEP_SCOPES)
    # the selector's batch-level switch puts them under `cond/branch_<i>_fun`
    for jit in ("gvr_threshold", "radix_select_topk"):
        assert re.search(rf"/step\.topk/(cond/branch_\d_fun/)?jit\({jit}\)/",
                         hlo), jit


def test_compiled_step_carries_its_scopes_moe_without_dsa():
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    cfg = dataclasses.replace(cfg, dsa=DSAConfig(enabled=False))
    hlo = _step_hlo(_engine(cfg))
    assert _scopes_in(hlo) == set(STEP_SCOPES) - {"step.indexer",
                                                  "step.topk"}


def test_gvr_counters_match_the_method_log():
    """Between two reads that bracket only warm decode ticks, the counted
    GVR row-layers are n_layers × the decode `gvr` entries of the method
    log; iterations lie within the secant budget; a read resets them and
    changes no step signature."""
    cfg = get_config("llama3.2-1b", smoke=True)
    eng = _engine(cfg)
    reqs = [Request(uid=i, prompt=RNG.integers(0, cfg.vocab, (p,)),
                    max_new_tokens=24) for i, p in enumerate((5, 9))]
    _warm_decode(eng, reqs)
    eng.counters()
    first = eng.tick_count
    for _ in range(6):
        eng.tick()
    got = eng.counters()
    assert set(got) == set(GVR_COUNTERS)
    served = sum(1 for entries in eng.method_log.values()
                 for tick, phase, method in entries
                 if tick >= first and phase == "DECODE" and method == "gvr")
    assert served == 6 * len(reqs)
    assert got["gvr_row_layers"] == cfg.n_layers * served
    assert 0 <= got["gvr_secant_iters"] <= (DEFAULT_MAX_SECANT
                                            * got["gvr_row_layers"])
    assert got["gvr_fallbacks"] == 0
    assert got["radix_row_layers"] == 0        # every row warm: GVR alone
    assert eng.counters() == dict.fromkeys(GVR_COUNTERS, 0)
    eng.tick()
    assert eng._tick_fn._cache_size() == 1


@pytest.mark.parametrize("neighbour", ["warm", "admitted_too"])
def test_radix_row_layers_count_the_admission_step(neighbour):
    """Radix runs only on a step whose batch holds a cold row. On the tick
    a slot is admitted, its first prefill step counts n_layers radix
    row-layers for each active row: beside a warm decoding slot (a mixed
    batch, the admitted slot the only active row), or with the other slot
    admitted in the same tick (every row cold, both active). The chunk's
    later steps and the decode step, every row warm by then, count none,
    and GVR counts its warm row-layers as before."""
    cfg = get_config("llama3.2-1b", smoke=True)
    eng = _engine(cfg)
    chunk = eng.prefill_chunk
    prompt = lambda: RNG.integers(0, cfg.vocab, (2 * chunk,))
    new = [Request(uid=0, prompt=prompt(), max_new_tokens=8)]
    if neighbour == "warm":
        old = Request(uid=1, prompt=RNG.integers(0, cfg.vocab, (5,)),
                      max_new_tokens=40)
        _warm_decode(eng, [old])
        # the free slot's row is cold until it has served a request
        eng.submit(Request(uid=2, prompt=RNG.integers(0, cfg.vocab, (3,)),
                           max_new_tokens=2))
        eng.tick()
        while eng.slots[1] is not None:
            eng.tick()
        warm_decoding = 1
    else:
        new.append(Request(uid=1, prompt=prompt(), max_new_tokens=8))
        warm_decoding = 0
    eng.counters()
    for r in new:
        eng.submit(r)
    eng.tick()
    got = eng.counters()
    assert all(r.phase == "PREFILL" for r in new)
    assert got["radix_row_layers"] == cfg.n_layers * len(new)
    assert got["gvr_row_layers"] == cfg.n_layers * (
        len(new) * (chunk - 1) + warm_decoding)
    assert 0 <= got["gvr_secant_iters"] <= (DEFAULT_MAX_SECANT
                                            * got["gvr_row_layers"])
    assert got["gvr_fallbacks"] == 0
    eng.tick()                          # the second chunk: every row warm
    assert eng.counters()["radix_row_layers"] == 0


def test_spec_counters_count_every_executed_gvr_position():
    """Verify ticks count each executed draft position of every layer,
    rejected ones included, and no frozen position past a row's draft:
    between two reads over warm decode ticks, GVR row-layers are n_layers
    × the executed positions that GVR served (the engine's per-position
    telemetry). Drafts are wrong and of every length 0..depth."""
    from repro.serve import ScriptedDrafter
    cfg = get_config("llama3.2-1b", smoke=True)
    depth = 3

    def draft(req, d):
        return [(req.generated[-1] + 1) % cfg.vocab] * (
            len(req.generated) % (d + 1))
    eng = _engine(cfg, spec_depth=depth, drafter=ScriptedDrafter(draft))
    reqs = [Request(uid=i, prompt=RNG.integers(0, cfg.vocab, (p,)),
                    max_new_tokens=40) for i, p in enumerate((5, 9))]
    _warm_decode(eng, reqs)
    eng.counters()
    hits0, total0 = sum(eng._spec_pos_hits), sum(eng._spec_pos_total)
    for _ in range(6):
        eng.tick()
    got = eng.counters()
    executed = sum(eng._spec_pos_total) - total0
    served = sum(eng._spec_pos_hits) - hits0
    assert 6 * len(reqs) < executed < 6 * len(reqs) * (depth + 1)
    assert served == executed
    assert got["gvr_row_layers"] == cfg.n_layers * served
    assert got["radix_row_layers"] == 0
    assert 0 <= got["gvr_secant_iters"] <= (DEFAULT_MAX_SECANT
                                            * got["gvr_row_layers"])
    assert got["gvr_fallbacks"] == 0


@pytest.mark.parametrize("spec", [False, True])
def test_counters_skip_inactive_rows(spec):
    """A warm row that sits a step out (a prefilling slot during a decode
    step) is computed but not counted."""
    cfg = get_config("llama3.2-1b", smoke=True)
    eng = _engine(cfg, **(dict(spec_depth=3) if spec else {}))
    reqs = [Request(uid=i, prompt=RNG.integers(0, cfg.vocab, (p,)),
                    max_new_tokens=40) for i, p in enumerate((5, 9))]
    _warm_decode(eng, reqs)
    eng.counters()
    b = eng.num_slots
    active = jnp.asarray([True] + [False] * (b - 1))
    if spec:
        draft_len = jnp.full((b,), 2, jnp.int32)
        eng.state, *_, sel_pos = eng._spec_fn(
            eng.params, eng.state, jnp.zeros((b, 4), jnp.int32), active,
            draft_len, jnp.zeros((b,), jnp.int32))
        served = int(np.asarray(sel_pos)[0, :3].sum())
    else:
        eng.state, _, _, gvr = eng._tick_fn(
            eng.params, eng.state, jnp.zeros((b,), jnp.int32), active,
            jnp.zeros((b,), jnp.int32))
        served = int(np.asarray(gvr).sum())
    assert served == (3 if spec else 1)
    assert eng.counters()["gvr_row_layers"] == cfg.n_layers * served


_SP_SCRIPT = r"""
import json
import jax, numpy as np
from repro.configs.registry import get_config
from repro.models.api import build_model
from repro.serve import DecodeEngine, Request, ScriptedDrafter

cfg = get_config("llama3.2-1b", smoke=True)
model = build_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))
rng = np.random.default_rng(11)
prompts = [rng.integers(0, cfg.vocab, (p,)) for p in (20, 12)]

def draft(req, d):
    return [(req.generated[-1] + 1) % cfg.vocab] * (len(req.generated) % (d + 1))

def run(**kw):
    eng = DecodeEngine(model, params, num_slots=2, max_len=64,
                       prefill_chunk=4, kv_layout="paged", page_size=8,
                       seq_shards=2, **kw)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=24)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    while not all(r.phase == "DECODE" and eng.method_log[r.uid][-1][2] == "gvr"
                  for r in reqs):
        eng.tick()
    eng.counters()
    first, hits0 = eng.tick_count, int(sum(eng._spec_pos_hits))
    for _ in range(6):
        eng.tick()
    got = eng.counters()
    served = sum(1 for e in eng.method_log.values() for t, ph, m in e
                 if t >= first and ph == "DECODE" and m == "gvr")
    return dict(got, served=served,
                executed_gvr=int(sum(eng._spec_pos_hits)) - hits0,
                again=eng.counters(), signatures=eng._tick_fn._cache_size())

out = {"plain": run(),
       "spec": run(spec_depth=3, drafter=ScriptedDrafter(draft))}
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.mesh
@pytest.mark.slow
def test_sequence_sharded_counters():
    """The replicated counter leaf through the sequence-sharded step and
    verify tick (2 forced CPU devices): n_layers × the GVR-served decode
    positions, and a read resets it."""
    import json
    import subprocess
    import sys
    from _mesh_compat import REPO_ROOT, forced_mesh_env, probe_forced_mesh
    if not probe_forced_mesh(2):
        pytest.skip("runner cannot force a 2-device CPU mesh")
    r = subprocess.run([sys.executable, "-c", _SP_SCRIPT],
                       capture_output=True, text=True,
                       env=forced_mesh_env(2), timeout=900, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    cfg = get_config("llama3.2-1b", smoke=True)
    zeros = dict.fromkeys(GVR_COUNTERS, 0)
    plain, spec = out["plain"], out["spec"]
    assert plain["served"] == 12
    assert plain["gvr_row_layers"] == cfg.n_layers * plain["served"]
    assert spec["executed_gvr"] > spec["served"]        # rejected drafts
    assert spec["gvr_row_layers"] == cfg.n_layers * spec["executed_gvr"]
    for leg in (plain, spec):
        assert 0 <= leg["gvr_secant_iters"] <= (DEFAULT_MAX_SECANT
                                                * leg["gvr_row_layers"])
        assert leg["gvr_fallbacks"] == 0
        assert leg["radix_row_layers"] == 0     # SP-GVR runs no radix
        assert leg["again"] == zeros
        assert leg["signatures"] == 1


def test_report_carries_the_counters():
    cfg = get_config("llama3.2-1b", smoke=True)
    eng = _engine(cfg)
    rep = eng.run([Request(uid=0, prompt=RNG.integers(0, cfg.vocab, (6,)),
                           max_new_tokens=10)])
    assert 0.0 < rep.gvr_secant_iters_mean <= DEFAULT_MAX_SECANT
    assert rep.gvr_fallbacks == 0
    assert eng.counters() == dict.fromkeys(GVR_COUNTERS, 0)


def test_tick_phase_spans_nest_in_the_tick(tmp_path):
    """A profiler trace of three engine ticks holds one `engine.tick` step
    span per tick on one host line, with the admit, dispatch, readback and
    emit phases inside it."""
    cfg = get_config("llama3.2-1b", smoke=True)
    eng = _engine(cfg)
    eng.submit(Request(uid=0, prompt=RNG.integers(0, cfg.vocab, (3,)),
                       max_new_tokens=16))
    for _ in range(3):                 # compile, prefill, first decode
        eng.tick()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        eng.tick()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    lines = [list(line.events) for plane in pd.planes
             if plane.name == "/host:CPU" for line in plane.lines]
    line = next(evs for evs in lines if any(e.name == "engine.tick"
                                            for e in evs))
    ticks = [e for e in line if e.name == "engine.tick"]
    assert len(ticks) == 3
    for t in ticks:
        inside = {e.name for e in line
                  if e.start_ns >= t.start_ns
                  and e.start_ns + e.duration_ns <= t.start_ns + t.duration_ns}
        assert {"engine.admit", "engine.pages", "engine.dispatch",
                "engine.readback", "engine.emit"} <= inside
