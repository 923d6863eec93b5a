"""§Roofline: three-term roofline per (arch × shape) from the dry-run JSONs.

  compute    = FLOPs / (chips × 197 TF/s)
  memory     = HBM bytes per device / 819 GB/s
  collective = collective bytes per device / 50 GB/s link

Methodology note (EXPERIMENTS.md §Roofline): XLA's compiled.cost_analysis()
counts while-loop bodies ONCE (verified empirically — a 4-layer scan reports
1 layer of FLOPs), so the compute/memory terms here are ANALYTIC from the
architecture algebra below; the collective term comes from the partitioned
HLO with explicit loop-trip correction (launch/dryrun.parse_collectives);
HLO cost_analysis values are retained in the JSON as a body-once
cross-check, and compiled.memory_analysis() supplies the capacity column.

MODEL_FLOPS = 6·N_active·D (train) / 2·N_active per decoded token.
"""

from __future__ import annotations

import glob
import json
import os

PEAK = 197e12
HBM = 819e9
ICI = 50e9

SHAPES = {"train_4k": (256, 4096), "prefill_32k": (32, 32768),
          "decode_32k": (128, 32768), "long_500k": (1, 524288)}


def _cfg(arch):
    from repro.configs.registry import get_config
    return get_config(arch)


def analytic_terms(arch: str, shape: str, n_devices: int) -> dict:
    """FLOPs (global) and HBM bytes (per device) from architecture algebra."""
    cfg = _cfg(arch)
    b, s = SHAPES[shape]
    n_act = cfg.active_param_count()
    n_tot = cfg.param_count()
    model_ext = 16 if n_devices >= 256 else 1
    data_ext = n_devices // model_ext
    hd, h, kvh, l = cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.n_layers
    l_attn = (l // cfg.attn_every) if cfg.attn_every else \
        (0 if cfg.family == "ssm" else l)
    d_attn = h * hd

    if shape == "train_4k":
        tokens = b * s
        fl = 6.0 * n_act * tokens                       # matmul fwd+bwd
        fl += 3.5 * 2.0 * tokens * s * d_attn * 0.5 * l_attn  # causal attn
        fl *= 4.0 / 3.0                                 # full remat recompute
        # per-device HBM: params fwd+bwd+update, grads, adam moments,
        # activations at remat boundaries
        p_dev = n_tot * 2 / model_ext
        act = tokens / data_ext * cfg.d_model * 2 * 6 * l / max(l, 1)
        by = p_dev * 3 + n_tot * 4 / model_ext * 3 + \
            n_tot * 8 / n_devices * 2 + tokens / data_ext * cfg.d_model * 2 * 4 * l
        model_fl = 6.0 * n_act * tokens
    elif shape == "prefill_32k":
        tokens = b * s
        fl = 2.0 * n_act * tokens
        fl += 2.0 * tokens * s * d_attn * 0.5 * l_attn
        p_dev = n_tot * 2 / model_ext
        by = p_dev + tokens / data_ext * cfg.d_model * 2 * 4 * l
        model_fl = 2.0 * n_act * tokens
    else:  # decode (one token, cache length s)
        fl = 2.0 * n_act * b
        if cfg.dsa.enabled and l_attn:
            di, hi = cfg.dsa.indexer_dim, cfg.dsa.indexer_heads
            k = min(cfg.dsa.k, s)
            fl += b * l_attn * (2.0 * s * hi * di      # indexer MQA (Eq. 1)
                                + 3.0 * s              # GVR count passes
                                + 2.0 * 2.0 * k * d_attn)  # sparse MLA
        elif l_attn:
            fl += b * l_attn * 2.0 * 2.0 * s * d_attn
        # per-device bytes: full param shard each step + cache traffic
        b_loc = max(b // data_ext, 1)
        p_dev = n_tot * 2 / model_ext
        cache = 0.0
        if cfg.family == "ssm":
            di = cfg.d_model * cfg.mamba_expand
            cache = b_loc * l * (cfg.d_model // cfg.rwkv_head_dim) * \
                cfg.rwkv_head_dim ** 2 * 4 * 2
        else:
            seq_shard = data_ext if shape == "long_500k" else 1
            kvb = 2 * kvh * hd * 2
            idxb = (cfg.dsa.indexer_dim * 2 + (3 + 1) * 4) if cfg.dsa.enabled else 0
            cache = b_loc * l_attn * (s / seq_shard) * (
                (kvb if not cfg.dsa.enabled else 0) + idxb)
            # DSA: full KV not read — only K gathered rows + indexer cache
            if cfg.dsa.enabled:
                cache += b_loc * l_attn * min(cfg.dsa.k, s) * 2 * kvh * hd * 2
        by = p_dev + cache
        model_fl = 2.0 * n_act * b
    return dict(flops_global=fl, bytes_per_dev=by, model_flops=model_fl)


def analyze(path: str) -> dict:
    d = json.load(open(path))
    if d.get("status") != "ok":
        return d
    nd = d["n_devices"]
    a = analytic_terms(d["arch"], d["shape"], nd)
    cb = d.get("collectives", {}).get("total_bytes", 0)
    t_c = a["flops_global"] / (nd * PEAK)
    t_m = a["bytes_per_dev"] / HBM
    t_i = cb / ICI
    dom = max((t_c, "compute"), (t_m, "memory"), (t_i, "collective"))[1]
    step = max(t_c, t_m, t_i)
    return dict(
        arch=d["arch"], shape=d["shape"], multi_pod=d["multi_pod"],
        status="ok", n_devices=nd,
        compute_s=t_c, memory_s=t_m, collective_s=t_i, dominant=dom,
        step_s=step,
        model_flops=a["model_flops"],
        useful_ratio=a["model_flops"] / a["flops_global"],
        roofline_frac=t_c / step if step else 0.0,
        hlo_flops_bodyonce=d.get("flops_per_device", 0.0),
        mem_gb=d.get("memory", {}).get("per_device_total", 0) / 1e9,
        collective_detail={k: v for k, v in d.get("collectives", {}).items()
                           if isinstance(v, dict) and v.get("count")},
    )


def table(outdir="results/dryrun", multi_pod=False):
    rows = []
    for f in sorted(glob.glob(os.path.join(outdir, "*.json"))):
        if ("pod2" in f) != multi_pod:
            continue
        r = analyze(f)
        if r.get("status") == "ok":
            rows.append(r)
    return rows


def markdown(rows):
    out = ["| arch | shape | compute s | memory s | collective s | dominant | "
           "useful ratio | roofline frac | HBM GB/dev |",
           "|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.2e} | "
            f"{r['memory_s']:.2e} | {r['collective_s']:.2e} | "
            f"**{r['dominant']}** | {r['useful_ratio']:.2f} | "
            f"{r['roofline_frac']:.3f} | {r['mem_gb']:.1f} |")
    return "\n".join(out)


def bench_roofline():
    rows = table()
    if not rows:
        raise FileNotFoundError(
            "roofline: no results/dryrun/*pod1.json — run "
            "`python -m repro.launch.dryrun_all` first")
    out = []
    for r in rows:
        out.append((f"roofline/{r['arch']}/{r['shape']}", "",
                    f"compute={r['compute_s']:.2e}s;memory={r['memory_s']:.2e}s;"
                    f"collective={r['collective_s']:.2e}s;dom={r['dominant']};"
                    f"roofline_frac={r['roofline_frac']:.3f}"))
    return out


# --------------------------------------------------------------------------
# §Roofline, serving half: MEASURED serving kernels vs the memory-bound
# peak (EXPERIMENTS.md §Roofline). Three pins into BENCH_roofline.json:
#   1. per-kernel analytic HBM bytes vs 819 GB/s memory-bound peak, next to
#      the measured CPU-interpret wall (labeled cpu — a dispatch/algorithmic
#      reality check, NOT a TPU measurement),
#   2. mq vs scan speculative verify-tick wall on the real paged serving
#      step (asserted: mq <= scan at every spec_depth >= 2),
#   3. page- vs token-granular gather bytes from a REAL decode Top-K trace
#      (asserted: page bytes <= token bytes x page_size).
# --------------------------------------------------------------------------

BENCH_JSON = "BENCH_roofline.json"


def _kernel_rows():
    """Micro-roofline per serving Pallas kernel: analytic HBM bytes of one
    launch vs the TPU memory-bound floor, next to the measured CPU wall."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.sparse.dsa import page_gather_stats
    from .common import time_fn

    b, h, kvh, d, dv = 4, 8, 2, 32, 32
    page_size, mp, k, q_rows = 16, 32, 64, 3
    n = mp * page_size
    di, hi = 32, 4
    p_pages = b * mp

    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    qm = jnp.asarray(rng.standard_normal((b, q_rows, h, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((p_pages, page_size, kvh, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((p_pages, page_size, kvh, dv)),
                     jnp.float32)
    # fully mapped identity tables + clustered Top-K (page-locality is the
    # regime the pg kernel exists for; the stats row reports the real count)
    table = jnp.asarray(
        np.arange(b * mp, dtype=np.int32).reshape(b, mp))
    base = rng.integers(0, n - page_size, size=(b, 1))
    idx = jnp.asarray(np.sort(
        (base + rng.integers(0, 4 * page_size, size=(b, k))) % n,
        axis=-1).astype(np.int32))
    idx_mq = jnp.asarray(np.sort(
        (base[:, None] + rng.integers(0, 4 * page_size, size=(b, q_rows, k)))
        % n, axis=-1).astype(np.int32))
    lengths = jnp.full((b,), n, jnp.int32)
    lengths_mq = jnp.broadcast_to(lengths[:, None], (b, q_rows))
    qi = jnp.asarray(rng.standard_normal((b, hi, di)), jnp.float32)
    qi_mq = jnp.asarray(rng.standard_normal((b, q_rows, hi, di)), jnp.float32)
    ikp = jnp.asarray(rng.standard_normal((p_pages, page_size, di)),
                      jnp.float32)
    w = jnp.asarray(rng.random((hi,)), jnp.float32)
    prev = jnp.asarray(rng.permutation(n)[:k][None].repeat(b, 0)
                       .astype(np.int32))

    row_b = (kvh * d + kvh * dv) * 4                 # one gathered K+V row
    pages_touched = int(np.asarray(page_gather_stats(
        idx, page_size=page_size, num_logical_pages=mp)).sum())
    fixed = b * (h * d + h * dv) * 4                 # q in + out per launch

    kernels = [
        ("paged_sparse_decode_attn(token)",
         lambda: ops.paged_sparse_decode_attn(q, kp, vp, table, idx),
         fixed + b * k * row_b, b * k),
        ("paged_sparse_decode_attn_pg(page)",
         lambda: ops.paged_sparse_decode_attn_pg(q, kp, vp, table, idx),
         fixed + pages_touched * page_size * row_b, pages_touched),
        ("paged_sparse_decode_attn_mq",
         lambda: ops.paged_sparse_decode_attn_mq(qm, kp, vp, table, idx_mq),
         q_rows * (fixed + b * k * row_b), q_rows * b * k),
        ("paged_dense_decode_attn",
         lambda: ops.paged_dense_decode_attn(q, kp, vp, table, lengths),
         fixed + b * mp * page_size * row_b, b * mp),
        ("paged_indexer_topk",
         lambda: ops.paged_indexer_topk(qi, ikp, w, table, prev, k,
                                        lengths=lengths),
         b * (hi * di * 4 + n * di * 4 + k * 4 + k * 8), b * mp),
        ("paged_indexer_topk_mq",
         lambda: ops.paged_indexer_topk_mq(qi_mq, ikp, w, table, prev, k,
                                           lengths=lengths_mq),
         q_rows * b * (hi * di * 4 + n * di * 4 + k * 4 + k * 8),
         q_rows * b * mp),
    ]

    out = []
    for name, fn, hbm_bytes, descriptors in kernels:
        wall_us = time_fn(lambda f=fn: jax.block_until_ready(f()),
                          iters=3, warmup=1)
        peak_s = hbm_bytes / HBM
        out.append(dict(
            kernel=name, hbm_bytes=int(hbm_bytes), dma_descriptors=descriptors,
            tpu_memory_bound_peak_s=peak_s,
            cpu_wall_us=round(wall_us, 1),
            cpu_achieved_bytes_per_s=hbm_bytes / (wall_us * 1e-6),
            cpu_distance_from_tpu_peak=round(wall_us * 1e-6 / peak_s, 1),
        ))
    return out, dict(b=b, h=h, kvh=kvh, d=d, dv=dv, page_size=page_size,
                     mp=mp, k=k, q_rows=q_rows, indexer_dim=di,
                     indexer_heads=hi, pages_touched=pages_touched)


def _serving_setup():
    """Smoke model + warmed paged decode state with a real context."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_config
    from repro.models.api import build_model

    cfg = get_config("llama3.2-1b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch, max_len, page_size = 2, 64, 8
    mp = max_len // page_size
    state = model.init_paged_decode_state(batch, max_len,
                                          num_pages=batch * mp,
                                          page_size=page_size)
    state = dict(state)
    state["page_table"] = jnp.asarray(
        np.arange(batch * mp, dtype=np.int32).reshape(batch, mp))
    step = jax.jit(lambda p, s, t: model.serve_step_paged(p, s, t))
    rng = np.random.default_rng(3)
    toks = rng.integers(1, cfg.vocab, size=(20, batch)).astype(np.int32)
    for t in toks:                                   # real 20-token context
        _, state = step(params, state, jnp.asarray(t))
    return cfg, model, params, state, page_size


def _verify_tick_rows(cfg, model, params, state):
    """mq vs scan wall for ONE jitted speculative verify tick."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from .common import time_fn

    batch = int(state["length"].shape[0])
    rng = np.random.default_rng(9)
    rows = []
    for depth in (1, 2, 4):
        tokens = jnp.asarray(rng.integers(1, cfg.vocab,
                                          size=(batch, depth + 1)), jnp.int32)
        dl = jnp.full((batch,), depth, jnp.int32)
        ma = jnp.full((batch,), depth, jnp.int32)
        walls = {}
        for vk in ("scan", "mq"):
            fn = jax.jit(lambda p, s, t, d_, m_, _vk=vk:
                         model.serve_step_spec_paged(
                             p, s, t, draft_len=d_, max_accept=m_,
                             verify_kernel=_vk))
            walls[vk] = time_fn(fn, params, state, tokens, dl, ma)
        rows.append(dict(spec_depth=depth,
                         scan_wall_us=round(walls["scan"], 1),
                         mq_wall_us=round(walls["mq"], 1),
                         mq_speedup=round(walls["scan"] / walls["mq"], 2)))
        if depth >= 2:
            assert walls["mq"] <= walls["scan"], (
                f"mq verify tick slower than scan at depth {depth}: "
                f"{walls['mq']:.0f}us vs {walls['scan']:.0f}us")
    return rows


def _gather_bytes_row(cfg, state, page_size):
    """Page- vs token-granular gather traffic on the REAL Top-K trace left
    in the warmed decode state's prev_topk feedback."""
    import numpy as np
    from repro.sparse.dsa import page_gather_stats

    topk = state["prev_topk"]                        # (L, B, K)
    l, b, k = topk.shape
    mp = state["page_table"].shape[1]
    flat = topk.reshape(l * b, k)
    valid = int(np.asarray((flat >= 0).sum()))
    pages = int(np.asarray(page_gather_stats(
        flat, page_size=page_size, num_logical_pages=mp)).sum())
    row_b = (2 * cfg.n_kv_heads * cfg.hd) * state["k_pages"].dtype.itemsize
    token_bytes = valid * row_b
    page_bytes = pages * page_size * row_b
    assert page_bytes <= token_bytes * page_size, (page_bytes, token_bytes)
    return dict(layers=l, slots=b, k=k, page_size=page_size,
                selected_tokens=valid, distinct_pages=pages,
                token_granular_bytes=token_bytes,
                page_granular_bytes=page_bytes,
                page_over_token_ratio=round(page_bytes / token_bytes, 3),
                worst_case_ratio=page_size)


def bench_roofline_serving():
    kernel_rows, kernel_cfg = _kernel_rows()
    cfg, model, params, state, page_size = _serving_setup()
    tick_rows = _verify_tick_rows(cfg, model, params, state)
    gather = _gather_bytes_row(cfg, state, page_size)

    results = dict(
        peaks=dict(hbm_bytes_per_s=HBM, peak_flops=PEAK, ici_bytes_per_s=ICI),
        note=("cpu_* columns are CPU-interpret walls (dispatch/algorithmic "
              "reality check); tpu_memory_bound_peak_s is the analytic "
              "819 GB/s floor — see EXPERIMENTS.md §Roofline"),
        kernel_config=kernel_cfg,
        kernels=kernel_rows,
        verify_tick=dict(arch=cfg.name, rows=tick_rows,
                         asserted="mq_wall <= scan_wall at spec_depth >= 2"),
        gather_granularity=gather,
    )
    with open(BENCH_JSON, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")

    rows = []
    for r in kernel_rows:
        rows.append((f"roofline/{r['kernel']}/tpu_peak_s",
                     f"{r['tpu_memory_bound_peak_s']:.2e}",
                     f"hbm_bytes={r['hbm_bytes']};descr={r['dma_descriptors']}"))
        rows.append((f"roofline/{r['kernel']}/cpu_wall_us", r["cpu_wall_us"],
                     "cpu_interpret"))
    for r in tick_rows:
        rows.append((f"roofline/verify_d{r['spec_depth']}/mq_speedup",
                     r["mq_speedup"],
                     f"scan={r['scan_wall_us']}us;mq={r['mq_wall_us']}us"))
    rows.append(("roofline/gather/page_over_token_ratio",
                 gather["page_over_token_ratio"],
                 f"asserted_le_{page_size}x"))
    return rows


if __name__ == "__main__":
    import sys
    if "--dryrun" in sys.argv:
        print(markdown(table()))
    else:
        from .common import emit
        emit(bench_roofline_serving())
