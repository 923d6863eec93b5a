#!/usr/bin/env python3
"""Bring-up check of the paged GVR decode engine on a TPU.

Runs the serving path the README describes — `DecodeEngine(kv_layout=
"paged", paged_attn="fused")` — once, at the full published width of
llama3.2-1b (16 layers, d_model 2048, 32/8 heads × 64, d_ff 8192, vocab
128256, DSA K=2048 with 64 indexer heads × 128), bf16 weights drawn from
`--seed`, and checks what comes out:

  kernels  gvr_topk, paged_indexer_topk and paged_sparse_decode_attn,
           compiled by Mosaic (interpret=False) at those widths, against
           kernels/ref.py: Top-K index sets equal lax.top_k exactly, the
           attention output within a stated tolerance.
  engine   4 slots, staggered requests with prompts longer than K (so the
           selector really selects) past the DSA gate (max_len > min_n):
           compile seconds, wall, tokens, decode GVR hit rate (must be
           > 0), peak device bytes, every logit finite.
  oracle   every request's logits against the dense-layout serve_step
           loop fed the same tokens (one request per batch row).

`--chips 4` runs only the sequence-sharded engine (seq_shards=4 over the
four chips of a v5e 2x2 host) against the single-device paged engine on
the same trace and seed: tokens must agree and logits within tolerance.
Its prompts are longer than K and cross every shard boundary, so every
shard owns selected rows; the model keeps its full width but only
`SHARDED["layers"]` of its 16 layers (see SHARDED below).

Usage:  python chip_smoke.py [--chips 1|4] [--seed N]

It refuses to run without a TPU. The last line of standard output is one
JSON object, {"ok": true, "device": {...}}, printed only when every phase
passed; otherwise the exit code is non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Logits of the engine and of its oracle are compared relative to the row's
# largest logit. Both run the same bf16 model at batch 4, but as different
# programs (paged vs dense cache, one device vs a sharded psum): XLA may
# fuse and order bf16/f32 reductions differently in each, and bf16 keeps 8
# significant bits (unit roundoff 2**-8 ≈ 3.9e-3), so activations may
# drift by a few roundoffs over 16 layers. 2e-2 of the row's scale leaves
# room for that and is far
# below what a wrong cache row or a wrong Top-K set does to a row (O(1)).
LOGIT_RTOL = 2e-2
# The flash-style kernel accumulates 2048 rows online in f32 in another
# order than the softmax oracle, and the TPU's exp differs from XLA's by a
# few f32 ulps: outputs of magnitude ~0.1 agree to ~1e-6. A gather or mask
# error moves them by O(0.1).
ATTN_ATOL, ATTN_RTOL = 1e-4, 1e-3

# Sizes. The kernels run at the engine's widths over a 32K context. The
# engine's max_len passes the DSA gate (min_n 4096) and every prompt is
# longer than K=2048, so the selector selects from more than K candidates.
# Prefill streams one token per pool-wide step, and on v5e a step of this
# model took ~135 ms at max_len 16384 and ~69 ms at 8192 (the selector's
# radix + GVR passes over every row of every layer), so the longest prompt
# sets the run time, and the dense oracle's (~62 ms a step) as well: about
# 2.8K steps each, ~7-8 minutes for the whole command. Top-K keeps 2048 of
# 2560-2784 candidates.
KERNELS = dict(b=4, n=32768, page=64)
ENGINE = dict(max_len=8192, page=64, prompts=(2560, 2624, 2688, 2752),
              max_new=32, stagger=1)
# Four shards of max_len 4352 (the smallest past min_n that splits into
# page-aligned spans) own 1088 positions each. Prompts of 3392 and 3328
# tokens reach all four shards and 2240 three, and all exceed K, so the
# cross-shard Top-K merge and the psum assembly of the K rows both carry
# rows from several shards. At one token per step that is ~3.4K steps
# per engine, two engines, on a host billed four times per second: the
# model is cut to 2 of its 16 layers (widths unchanged) to keep the call
# to a few minutes.
SHARDED = dict(max_len=4352, page=64, prompts=(3392, 2240, 1152, 3328),
               max_new=8, stagger=1, layers=2)


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"check {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip(),
              flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def log(msg: str) -> None:
    print(msg, flush=True)


def _sets_equal(a, b) -> bool:
    a, b = np.sort(np.asarray(a), -1), np.sort(np.asarray(b), -1)
    return bool(np.array_equal(a, b))


# ---- phase: kernels compiled on the chip -------------------------------

def phase_kernels(cfg, seed: int, check: Checks) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    b, n, page, k = KERNELS["b"], KERNELS["n"], KERNELS["page"], cfg.dsa.k
    mp, ih, idim = n // page, cfg.dsa.indexer_heads, cfg.dsa.indexer_dim
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    rng = np.random.default_rng(seed)
    log(f"kernels: B={b} K={k} N={n} page={page} indexer {ih}x{idim} "
        f"attention {h}/{kvh}x{hd} (interpret=False)")
    hp = jax.default_matmul_precision("highest")

    # gvr_topk: a score row and the previous step's Top-K of a nearby row
    x = rng.normal(size=(b, n)).astype(np.float32)
    prev_x = x + 0.1 * rng.normal(size=(b, n)).astype(np.float32)
    prev = np.argsort(-prev_x, axis=-1)[:, :k].astype(np.int32)
    t = time.perf_counter()
    v, i, stats = jax.block_until_ready(
        ops.gvr_topk(jnp.asarray(x), jnp.asarray(prev), k, interpret=False))
    log(f"kernel gvr_topk: {time.perf_counter() - t:.3f} s first call, "
        f"secant iters {np.asarray(stats)[:, 0].tolist()}")
    rv, ri = ref.topk_ref(jnp.asarray(x), k)
    check("gvr_topk_exact", _sets_equal(i, ri) and _sets_equal(v, rv),
          "(index and value sets == lax.top_k)")

    # paged_indexer_topk on integer-valued data: every score is an exact
    # f32 integer whatever the summation order, so sets must match exactly
    perm = rng.permutation(b * mp).astype(np.int32)
    table = perm.reshape(b, mp)
    table[1, mp // 2:] = -1                       # a half-mapped slot
    lengths = np.array([n, n // 2, n - 1000, 5000], np.int32)
    q = jnp.asarray(rng.integers(-2, 3, (b, ih, idim)), jnp.bfloat16)
    ikp = jnp.asarray(rng.integers(-2, 3, (b * mp + 1, page, idim)),
                      jnp.bfloat16)
    w = jnp.asarray(rng.integers(1, 9, (ih,)), jnp.float32)
    t = time.perf_counter()
    v, i, _ = jax.block_until_ready(ops.paged_indexer_topk(
        q, ikp, w, jnp.asarray(table), jnp.asarray(prev), k,
        lengths=jnp.asarray(lengths), interpret=False))
    log(f"kernel paged_indexer_topk: {time.perf_counter() - t:.3f} s "
        f"first call")
    with hp:
        view = ref.paged_gather_ref(ikp, jnp.asarray(table)).reshape(
            b, n, idim)
        s = ref.indexer_scores_ref(q, view, w, lengths=jnp.asarray(lengths))
        s = jnp.where(jnp.repeat(jnp.asarray(table) >= 0, page, axis=1), s,
                      jnp.float32(-3.4028235e38))
        rv, ri = ref.topk_ref(s, k)
    check("paged_indexer_topk_exact", _sets_equal(i, ri) and
          _sets_equal(v, rv), "(index and value sets == lax.top_k)")

    # paged_sparse_decode_attn: the Top-K rows gathered through the table
    qa = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(b * mp + 1, page, kvh, hd)),
                     jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(b * mp + 1, page, kvh, hd)),
                     jnp.bfloat16)
    idx = np.asarray(ri).copy()
    idx[3, k // 2:] = -1                          # padded entries
    t = time.perf_counter()
    out = jax.block_until_ready(ops.paged_sparse_decode_attn(
        qa, kp, vp, jnp.asarray(table), jnp.asarray(idx), interpret=False))
    log(f"kernel paged_sparse_decode_attn: {time.perf_counter() - t:.3f} s "
        f"first call")
    with hp:
        want = ref.paged_attn_ref(qa, kp, vp, jnp.asarray(table),
                                  jnp.asarray(idx))
    err = float(jnp.max(jnp.abs(out - want)))
    ok = bool(np.allclose(np.asarray(out), np.asarray(want),
                          atol=ATTN_ATOL, rtol=ATTN_RTOL))
    check("paged_sparse_decode_attn", ok and bool(np.isfinite(out).all()),
          f"(max |diff| {err:.3e}; atol {ATTN_ATOL}, rtol {ATTN_RTOL})")


# ---- phase: the served engine -------------------------------------------

def make_trace(cfg, seed: int, sizes):
    from repro.serve import Request
    rng = np.random.default_rng(seed + 1)
    return [Request(uid=u, prompt=rng.integers(0, cfg.vocab, (p,)),
                    max_new_tokens=sizes["max_new"],
                    arrival=u * sizes["stagger"])
            for u, p in enumerate(sizes["prompts"])]


def warm_compile(eng) -> float:
    """Compile the engine's one pool-wide step (decode and prefill share
    it) ahead of the run; returns the seconds it took."""
    import jax.numpy as jnp
    b = eng.num_slots
    t = time.perf_counter()
    eng._tick_fn.lower(eng.params, eng.state, jnp.zeros((b,), jnp.int32),
                       jnp.zeros((b,), bool),
                       jnp.zeros((b,), jnp.int32)).compile()
    return time.perf_counter() - t


def run_engine(model, params, cfg, trace, *, max_len: int, page: int,
               seq_shards: int = 1, label: str):
    from repro.serve import DecodeEngine
    eng = DecodeEngine(model, params, num_slots=4, max_len=max_len,
                       kv_layout="paged", paged_attn="fused", page_size=page,
                       prefill_chunk=16, seq_shards=seq_shards,
                       record_logits=True)
    log(f"{label}: compile seconds (pool-wide step) {warm_compile(eng):.2f}")
    step, steps = eng._tick_fn, [0]

    def counted(*a):
        steps[0] += 1
        return step(*a)
    eng._tick_fn = counted
    rep = eng.run(trace)
    log(f"{label}: wall {rep.wall_s:.2f} s, ticks {rep.ticks}, pool-wide "
        f"steps {steps[0]} ({1e3 * rep.wall_s / max(steps[0], 1):.1f} ms "
        f"each), prefill tokens {rep.prefill_tokens}, decoded tokens "
        f"{rep.decoded_tokens}, completed {rep.completed}/{len(trace)}")
    log(f"{label}: decode methods {rep.decode_method_counts}, prefill "
        f"methods {rep.prefill_method_counts}, gvr_hit_rate "
        f"{rep.gvr_hit_rate:.4f}")
    return eng, rep


def oracle_logits(model, params, trace, max_len: int):
    """Dense-layout serve_step loop, one jitted step per token like the
    oracle of tests/test_engine.py, with request r in batch row r (the
    engine's batch shape). Each row is fed its prompt and then the
    engine's own generated tokens (teacher-forced, so both runs see the
    same input at every position). Returns, per request, the logits of the
    positions the engine generated from, (len(generated), V)."""
    import jax
    import jax.numpy as jnp
    seqs = [np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)])
            for r in trace]
    firsts = [len(r.prompt) - 1 for r in trace]
    toks = np.zeros((max(map(len, seqs)), len(trace)), np.int32)
    for row, seq in enumerate(seqs):
        toks[:len(seq), row] = seq        # rows past their end are never read
    step = jax.jit(model.serve_step, donate_argnums=(1,))
    state = model.init_decode_state(batch=len(trace), max_len=max_len)
    rows = []
    for pos in range(len(toks)):
        logits, state = step(params, state, jnp.asarray(toks[pos]))
        if pos >= min(firsts):
            rows.append(logits)
    rows = np.asarray(jnp.stack(rows))                  # (steps, B, V)
    return [rows[f - min(firsts):f - min(firsts) + len(r.generated), row]
            for row, (f, r) in enumerate(zip(firsts, trace))]


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.abs(b).max(axis=-1,
                                                      keepdims=True)))


def phase_engine(model, params, cfg, seed: int, check: Checks) -> None:
    import jax
    max_len, page, max_new = ENGINE["max_len"], ENGINE["page"], \
        ENGINE["max_new"]
    trace = make_trace(cfg, seed, ENGINE)
    log(f"engine: paged/fused, 4 slots, max_len {max_len}, page {page}, "
        f"prompts {[len(r.prompt) for r in trace]}, max_new {max_new}, "
        f"arrivals {[r.arrival for r in trace]} ticks")
    eng, rep = run_engine(model, params, cfg, trace, max_len=max_len,
                          page=page, label="engine")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"engine: peak_bytes_in_use {stats.get('peak_bytes_in_use')}, "
        f"bytes_limit {stats.get('bytes_limit')}")
    check("engine_completed", rep.completed == len(trace)
          and all(len(r.generated) == max_new for r in trace))
    check("decode_gvr_hit_rate", rep.gvr_hit_rate > 0,
          f"({rep.gvr_hit_rate:.4f} > 0)")
    finite = all(np.isfinite(l).all() for r in trace for l in r.logits_log)
    check("logits_finite", finite)
    del eng                          # free the KV pools (the jit holds a
    gc.collect()                     # bound method: a reference cycle)

    t = time.perf_counter()
    want = oracle_logits(model, params, trace, max_len)
    got = [np.stack(r.logits_log) for r in trace]
    err = max(rel_err(g, w) for g, w in zip(got, want))
    agree = float(np.mean([np.mean(g.argmax(-1) == w.argmax(-1))
                           for g, w in zip(got, want)]))
    log(f"oracle: dense serve_step loop, {len(trace)} requests in the batch "
        f"rows, {max(len(r.prompt) + len(r.generated) - 1 for r in trace)} "
        f"steps in {time.perf_counter() - t:.2f} s (compile included); "
        f"argmax agreement {agree:.4f}")
    check("paged_vs_dense_logits", err <= LOGIT_RTOL
          and all(np.isfinite(w).all() for w in want),
          f"(max |diff| / row max |logit| = {err:.3e} <= {LOGIT_RTOL})")


# ---- phase: four chips ---------------------------------------------------

def phase_sharded(model, params, cfg, seed: int, check: Checks) -> None:
    max_len, page = SHARDED["max_len"], SHARDED["page"]
    log(f"sharded: seq_shards=4 vs single-device paged engine, "
        f"{cfg.n_layers} layers, {SHARDED}")
    single = make_trace(cfg, seed, SHARDED)
    eng, rep1 = run_engine(model, params, cfg, single, max_len=max_len,
                           page=page, label="single-device")
    del eng
    gc.collect()
    sharded = make_trace(cfg, seed, SHARDED)
    eng, rep4 = run_engine(model, params, cfg, sharded, max_len=max_len,
                           page=page, seq_shards=4, label="seq_shards=4")
    pools = {key: str(eng.state[key].sharding.spec)
             for key in ("k_pages", "v_pages", "idx_k_pages")}
    log(f"sharded: pool shardings {pools}")
    check("sharded_pools_on_mesh",
          all(len(eng.state[key].sharding.device_set) == 4 for key in pools))
    check("sharded_completed", rep4.completed == len(sharded))
    same = all(a.generated == b.generated for a, b in zip(single, sharded))
    check("sharded_tokens_equal", same)
    err = max(rel_err(np.stack(b.logits_log), np.stack(a.logits_log))
              for a, b in zip(single, sharded))
    check("sharded_logits", err <= LOGIT_RTOL,
          f"(max |diff| / row max |logit| = {err:.3e} <= {LOGIT_RTOL})")
    check("sharded_gvr_hit_rate", rep4.gvr_hit_rate > 0,
          f"({rep4.gvr_hit_rate:.4f})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import jax
        from repro.configs.registry import get_config
        from repro.models.api import build_model
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from "
              f"the repository root", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this check runs on a TPU only",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    log(f"device: {devices[0].device_kind} x {len(devices)}; jax "
        f"{jax.__version__}; compile cache {enable_compile_cache()}")

    cfg = get_config("llama3.2-1b")
    if args.chips == 4:
        cfg = dataclasses.replace(cfg, n_layers=SHARDED["layers"])
    log(f"config: {cfg.name} layers {cfg.n_layers} d_model {cfg.d_model} "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}x{cfg.hd} d_ff {cfg.d_ff} "
        f"vocab {cfg.vocab} dtype {cfg.dtype}; dsa k {cfg.dsa.k} indexer "
        f"{cfg.dsa.indexer_heads}x{cfg.dsa.indexer_dim} min_n "
        f"{cfg.dsa.min_n} selector {cfg.dsa.selector}; seed {args.seed}")
    check = Checks()
    t0 = time.perf_counter()
    model = build_model(cfg)
    params = jax.block_until_ready(
        jax.jit(model.init_params)(jax.random.PRNGKey(args.seed)))
    log(f"params: {sum(x.size for x in jax.tree.leaves(params))} "
        f"initialised in {time.perf_counter() - t0:.2f} s")
    phases = ([phase_sharded] if args.chips == 4
              else [phase_kernels, phase_engine])
    for phase in phases:
        t = time.perf_counter()
        try:
            if phase is phase_kernels:
                phase(cfg, args.seed, check)
            else:
                phase(model, params, cfg, args.seed, check)
        except Exception as e:                     # a phase that crashes fails
            import traceback
            traceback.print_exc()
            check(phase.__name__, False, f"({type(e).__name__}: {e})")
        log(f"{phase.__name__}: {time.perf_counter() - t:.2f} s")
    log(f"total: {time.perf_counter() - t0:.2f} s")
    if check.failed:
        print(f"chip_smoke: failed checks: {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
