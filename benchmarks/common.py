"""Benchmark helpers: wall-clock timing + the TPU roofline traffic model.

These benchmarks run the smoke preset on the CPU, so every one reports
BOTH (neither is a chip measurement; `chip_smoke.py` runs on the chip):
  * us_cpu      — measured CPU wall time (algorithmic reality check), and
  * us_tpu_model — modeled TPU v5e latency from *measured* pass/iteration
                   counts × the memory-bound traffic model (all Top-K stages
                   are memory-bound; paper §2.4): bytes / 819 GB/s + a fixed
                   per-pass latency overhead.

EXPERIMENTS.md labels which number is which everywhere.
"""

from __future__ import annotations

import time

import jax
import numpy as np

HBM_BW = 819e9            # bytes/s per chip (TPU v5e)
PEAK_FLOPS = 197e12       # bf16
ICI_BW = 50e9             # bytes/s per link
PASS_OVERHEAD_US = 1.0    # kernel-side fixed cost per full-row pass (launch,
                          # loop setup) — calibrated so radix@N=70K ≈ 44 us
                          # matches the paper's measured baseline (Table 9a)


def time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall-clock microseconds (jit-compiled, blocked)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def model_gvr_us(n: int, m: int, secant_iters: float, cand: float = 6144.0,
                 k: int = 2048) -> float:
    """GVR kernel TPU model: Phase1 scattered M reads + (I+1) full-row passes
    (I secant counts + 1 collect; the count-cache removes the count sub-pass)
    + candidate-buffer refine (VMEM-resident, ~free) + K outputs."""
    b_scatter = m * 4 * 2.0           # scattered reads: ~2x bandwidth penalty
    b_rows = (secant_iters + 1) * n * 4
    b_out = k * 8
    return ((b_scatter + b_rows + b_out) / HBM_BW * 1e6
            + (secant_iters + 1) * PASS_OVERHEAD_US)


def model_radix_us(n: int, passes: float, k: int = 2048,
                   survivors: float = 2048.0) -> float:
    """Radix-select TPU model: each digit pass = histogram scan + filter scan
    (2 full-row passes, paper §2.4) + survivor-sort tail."""
    b_rows = passes * 2 * n * 4
    b_tail = survivors * 8 * np.log2(max(survivors, 2)) / 8
    b_out = k * 8
    return ((b_rows + b_tail + b_out) / HBM_BW * 1e6
            + passes * 2 * PASS_OVERHEAD_US)


def model_sort_us(n: int) -> float:
    """Full-sort baseline: ~log2(N) passes (bitonic-ish)."""
    p = np.log2(max(n, 2))
    return p * n * 4 / HBM_BW * 1e6 + p * PASS_OVERHEAD_US


def emit(rows, header=("name", "us_per_call", "derived")):
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))
