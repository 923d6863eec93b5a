"""Sequence-sharded paged serving benchmark: the engine over SP-GVR.

    PYTHONPATH=src python -m benchmarks.run sp_engine          # smoke (CPU)
    SP_ENGINE_BENCH_FULL=1 PYTHONPATH=src python -m benchmarks.run sp_engine

`DecodeEngine(kv_layout="paged", seq_shards=S)` runs `serve_step` inside a
shard_map over a 1-D sequence mesh: each device owns the pages of one
logical token span, selection goes through SP-GVR's O(1)-collective
schedule (core/sp_gvr.py) and attention assembles exactly the K selected
rows with one O(K) psum (sparse/sp_dsa.py). This section pins three things
into BENCH_sp_engine.json:

1. **Per-tick collective bytes** — two groundings. (a) The schedule
   model (derived exactly from shapes, the repo's traffic-model idiom):
   SP-GVR's scalar/histogram psums + the K-index all-gather + the
   (K,KVH,HD) row-assembly psum vs. the naive distributed-Top-K
   baseline's N·4B score-row all-gather per device per layer, computed
   at two context lengths — sharded bytes EQUAL (O(1) in N), baseline
   linear. (b) The *implementation*: the actual `serve_step_sp_paged` is
   compiled at two context lengths and every collective op's result
   bytes are summed from the optimized HLO — asserted identical across a
   4× context jump, so a regression that sneaks an N-sized collective
   into the step fails the section, not just the hand model.
2. **Context capacity at fixed per-device KV budget**: per-device page
   residency is N/S, so S shards hold an S× longer context on the same
   per-device page pool — computed from the page-row byte layout.
3. **Engine tokens/s** for the sharded engine vs the single-device fused
   engine on the same trace (in a subprocess with a forced multi-device
   CPU mesh), with the built-in acceptance that the generated tokens are
   identical — sharding changes residency and traffic, never the bits.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from .common import emit

BENCH_JSON = "BENCH_sp_engine.json"

# SP-GVR iteration budgets (core/sp_gvr.py defaults) — the collective
# schedule's worst case; measured decode workloads exit in 1-2 secant
# iterations (temporal correlation), so these bound, not estimate
MAX_SECANT = 12
MAX_SNAP = 32
HIST_BINS = 2048
MAX_HIST_LEVELS = 10


def _per_tick_collective_bytes(cfg, *, n: int, batch: int, shards: int,
                               mode: str) -> dict:
    """Exact per-tick per-device collective payload accounting (all layers,
    one decode tick). `mode="sp"` is the SP-GVR schedule; `"allgather"` is
    the naive distributed Top-K that gathers the full score row."""
    k = cfg.dsa.k
    kvh, hd = cfg.n_kv_heads, cfg.hd
    b4 = 4 * batch                                  # one f32/i32 scalar per row
    if mode == "sp":
        selection = (
            4 * b4                                  # phase 1: 4-scalar psum
            + MAX_SECANT * b4                       # phase 2: 1 scalar/iter
            + MAX_HIST_LEVELS * HIST_BINS * batch * 4   # phase 4a/b psums
            + MAX_SNAP * 4 * b4                     # phase 4d: 4-scalar/iter
            + shards * batch * 4                    # tie-prefix all-gather
            + shards * k * batch * 4                # canonical idx all-gather
        )
        attention = (
            2 * k * kvh * hd * batch * 4            # K/V row-assembly psum
            + k * batch * 4                         # mapped-indicator psum
        )
    elif mode == "allgather":
        selection = shards * n * batch * 4          # full score-row gather
        attention = 2 * k * kvh * hd * batch * 4    # selected rows still move
    else:
        raise ValueError(mode)
    return {
        "selection_bytes": cfg.n_layers * selection,
        "attention_bytes": cfg.n_layers * attention,
        "total_bytes": cfg.n_layers * (selection + attention),
    }


def _kv_row_bytes(cfg) -> int:
    el = np.dtype(cfg.dtype).itemsize
    return (2 * cfg.n_kv_heads * cfg.hd + cfg.dsa.indexer_dim) * el


_ENGINE_SCRIPT = r"""
import json, re, time
import jax, numpy as np
from repro.configs.registry import get_config
from repro.models.api import build_model
from repro.launch.mesh import make_seq_mesh
from repro.serve import DecodeEngine, Request

shards = %(shards)d
cfg = get_config("llama3.2-1b", smoke=True)
model = build_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))

_DT_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2,
             "f64": 8, "s64": 8, "u64": 8}
_COLL_RE = re.compile(
    r"=\s*(\(?[a-z0-9\[\]{}, ]*\)?)\s*"
    r"(?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")

def collective_bytes_from_hlo(max_len):
    # ground the O(1)-in-N claim in the IMPLEMENTATION: compile the actual
    # sharded step at this context length and sum the result bytes of
    # every collective op in the optimized HLO
    span = max_len // 8 // shards
    state = jax.eval_shape(lambda: model.init_sp_paged_decode_state(
        2, max_len, num_pages_per_shard=2 * span, page_size=8,
        seq_shards=shards))
    i32 = jax.ShapeDtypeStruct((2,), jax.numpy.int32)
    mesh = make_seq_mesh(shards)
    fn = jax.jit(lambda p, s, t, m: model.serve_step_sp_paged(
        p, s, t, mesh=mesh, min_write_pos=m))
    psds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        params)
    txt = fn.lower(psds, state, i32, i32).compile().as_text()
    total, ops = 0, 0
    for line in txt.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        ops += 1
        for dt, dims in _SHAPE_RE.findall(m.group(1)):
            nelem = 1
            for d in dims.split(","):
                if d:
                    nelem *= int(d)
            total += nelem * _DT_BYTES.get(dt, 4)
    return {"bytes": total, "ops": ops}

def mk_reqs(seed=5):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, (24,))
    return [Request(uid=0, prompt=np.concatenate(
                        [shared, rng.integers(0, cfg.vocab, (13,))]),
                    max_new_tokens=%(gen)d, arrival=0),
            Request(uid=1, prompt=np.concatenate(
                        [shared, rng.integers(0, cfg.vocab, (6,))]),
                    max_new_tokens=%(gen)d, arrival=20),
            Request(uid=2, prompt=rng.integers(0, cfg.vocab, (40,)),
                    max_new_tokens=%(gen)d, arrival=6)]

out = {"collective_hlo": {str(n): collective_bytes_from_hlo(n)
                          for n in (%(hlo_lo)d, %(hlo_hi)d)}}
for name, kw in (("single", dict(paged_attn="fused")),
                 (f"sp{shards}", dict(seq_shards=shards))):
    eng = DecodeEngine(model, params, num_slots=2, max_len=64,
                       prefill_chunk=4, kv_layout="paged", page_size=8, **kw)
    # warm the jit caches outside the measured window
    eng.run([Request(uid=-1, prompt=np.zeros((9,), np.int32),
                     max_new_tokens=2)], max_ticks=100)
    reqs = mk_reqs()
    t0 = time.perf_counter()
    rep = eng.run(reqs, max_ticks=5000)
    wall = time.perf_counter() - t0
    assert rep.completed == 3, (name, rep.completed)
    out[name] = {
        "tokens": [r.generated for r in reqs],
        "tokens_per_s": round(rep.decoded_tokens / wall, 1),
        "ticks": rep.ticks,
        "gvr_hit_rate": round(rep.gvr_hit_rate, 4),
        "prefix_hit_tokens": rep.prefix_hit_tokens,
    }
print("RESULT:" + json.dumps(out))
"""


def bench_sp_engine():
    import jax
    from repro.configs.registry import get_config

    full = bool(os.environ.get("SP_ENGINE_BENCH_FULL"))
    shards = 4 if full else 2
    gen = 16 if full else 8
    ctx_lens = (65536, 524288) if full else (8192, 65536)

    cfg = get_config("llama3.2-1b", smoke=True)
    batch = 2
    rows = []
    results = {"config": {"arch": cfg.name, "k": cfg.dsa.k, "batch": batch,
                          "seq_shards": shards,
                          "context_lens": list(ctx_lens), "full": full}}

    # ---- 1. per-tick collective bytes: O(1) in N vs the O(N) baseline ----
    traffic = {}
    for n in ctx_lens:
        traffic[n] = {m: _per_tick_collective_bytes(
            cfg, n=n, batch=batch, shards=shards, mode=m)
            for m in ("sp", "allgather")}
        rows.append((f"sp_engine/sp_collective_bytes_per_tick/n={n}",
                     traffic[n]["sp"]["total_bytes"], "derived_model"))
        rows.append((f"sp_engine/allgather_bytes_per_tick/n={n}",
                     traffic[n]["allgather"]["total_bytes"], "derived_model"))
    n_lo, n_hi = ctx_lens
    # the acceptance: SP-GVR's per-tick collective payload is O(1) in
    # context length — bit-equal across a (n_hi/n_lo)x context jump
    assert (traffic[n_hi]["sp"]["total_bytes"]
            == traffic[n_lo]["sp"]["total_bytes"]), traffic
    # while the score-row all-gather baseline grows linearly with N
    assert (traffic[n_hi]["allgather"]["selection_bytes"]
            == traffic[n_lo]["allgather"]["selection_bytes"]
            * n_hi // n_lo), traffic
    assert (traffic[n_hi]["allgather"]["total_bytes"]
            > traffic[n_hi]["sp"]["total_bytes"]), traffic
    results["per_tick_collective_bytes"] = {
        str(n): traffic[n] for n in ctx_lens}
    results["collective_bytes_o1_in_context"] = True
    rows.append(("sp_engine/collective_bytes_o1_in_context", 1,
                 "asserted_from_traffic_model"))
    rows.append(("sp_engine/allgather_vs_sp_bytes_ratio",
                 round(traffic[n_hi]["allgather"]["total_bytes"]
                       / traffic[n_hi]["sp"]["total_bytes"], 1),
                 f"n={n_hi}"))

    # ---- 2. max context at fixed per-device KV page budget ---------------
    row_bytes = _kv_row_bytes(cfg)
    budget_tokens = n_hi // shards                  # per-device page budget
    budget_bytes = budget_tokens * row_bytes * cfg.n_layers
    results["context_capacity"] = {
        "per_device_kv_budget_bytes": budget_bytes,
        "max_context_single_device": budget_tokens,
        "max_context_sharded": budget_tokens * shards,
        "capacity_multiplier": shards,
    }
    rows.append(("sp_engine/max_context_at_fixed_device_budget",
                 budget_tokens * shards,
                 f"derived_model_{shards}x_single_device"))

    # ---- 3. engine tokens/s, sharded vs single, identical tokens, and ----
    # the HLO-grounded collective check (forced multi-device subprocess)
    if jax.default_backend() != "cpu":
        # this process already holds the accelerator; the child below
        # forces a CPU mesh, so what it measures is the CPU, not the chip
        raise RuntimeError(
            "sp_engine runs a forced multi-device CPU mesh in a child "
            "process: run it with JAX_PLATFORMS=cpu (it is not a device "
            "measurement; `python chip_smoke.py --chips 4` runs the "
            "sharded engine on four chips)")
    print("sp_engine: engine rows come from a forced "
          f"{shards}-device CPU mesh in a child process (CPU walls)",
          file=sys.stderr)
    hlo_lens = (512, 2048) if full else (256, 1024)
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={shards}"
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", "src")
    script = _ENGINE_SCRIPT % {"shards": shards, "gen": gen,
                               "hlo_lo": hlo_lens[0], "hlo_hi": hlo_lens[1]}
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=1800)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT:")][0]
    eng = json.loads(line[len("RESULT:"):])

    # ground the O(1)-in-N claim in the implementation, not just the
    # schedule model: the compiled sharded step's collective ops and their
    # result bytes must be IDENTICAL across a 4x context-length jump (a
    # regression that adds an N-sized all-gather changes this total)
    hlo = eng.pop("collective_hlo")
    lo, hi = (hlo[str(n)] for n in hlo_lens)
    assert lo["ops"] > 0, "no collective ops found in the lowered step?"
    assert lo == hi, f"collective schedule grew with context: {hlo}"
    results["per_tick_collective_hlo"] = {
        "context_lens": list(hlo_lens), "per_step": lo}
    rows.append(("sp_engine/hlo_collective_bytes_per_step", lo["bytes"],
                 f"asserted_equal_n={hlo_lens[0]}..{hlo_lens[1]}"))
    rows.append(("sp_engine/hlo_collective_ops_per_step", lo["ops"],
                 "compiled_step"))
    sp = eng[f"sp{shards}"]
    # built-in acceptance: sharding changes residency/traffic, not bits
    assert sp["tokens"] == eng["single"]["tokens"], \
        "sequence-sharded decode diverged from the single-device fused path"
    assert sp["gvr_hit_rate"] == eng["single"]["gvr_hit_rate"]
    for name in ("single", f"sp{shards}"):
        e = dict(eng[name])
        e.pop("tokens")
        results.setdefault("engine", {})[name] = e
        rows.append((f"sp_engine/{name}/tokens_per_s",
                     eng[name]["tokens_per_s"], "cpu_wall"))
    results["sharded_tokens_identical_to_single_device"] = True
    rows.append(("sp_engine/sharded_tokens_identical", 1,
                 "asserted_bit_identity"))

    with open(BENCH_JSON, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    return rows


if __name__ == "__main__":
    emit(bench_sp_engine())
