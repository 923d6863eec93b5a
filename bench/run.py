"""Run one cell of the benchmark on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a `workloads` entry of BENCHMARK.json) names a configuration and
a traffic mix; every piece is found by name (bench/registry.py). One run:
weights and traffic drawn from the seed, the engine built and its traffic
set up (contexts prefilled, or the open loop warmed), a window of
`--seconds`, the reference comparison that decides `correct`, and one JSON
line on standard output. `--trace 0` reports the cell's end-to-end
metrics, `--trace 1` its per-layer ones, from a profiler trace of the
window's last seconds. It exits with 3 and prints no result where JAX
finds no TPU, or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                   # noqa: E402
import gc                                         # noqa: E402
import json                                       # noqa: E402
import os                                         # noqa: E402
import sys                                        # noqa: E402
import tempfile                                   # noqa: E402
from pathlib import Path                          # noqa: E402
from types import SimpleNamespace                 # noqa: E402
from typing import Optional                       # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# the traced part of a `--trace 1` window: enough steps of either cell for
# steady shares, few enough events to reduce in seconds
TRACE_SECONDS = 3.0


def enable_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout
    unless JAX_COMPILATION_CACHE_DIR names another."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def run_cell(reg, name: str, spec: dict, mix: dict, limits: dict, *,
             seed: int, seconds: float, trace: bool, peaks: dict,
             t_start: float, control: Optional[str] = None):
    """One run of a cell. Returns the result line as a dict, and what the
    metric readers read (stamps, method log, trace). With `control`
    (bench/calibrate.py only) it also reads the control's gap and judges
    it against the same limits: `control.correct` has to come out false."""
    import jax
    from bench import correct, program, serve, weights
    from bench import trace as trace_mod
    from bench.registry import read_metric

    specs = reg.generator(mix["generator"]).generate(
        mix, seed, vocab=spec["vocab"], seconds=seconds)
    flat = weights.make_flat(spec, seed, reg)
    eng = program.build_engine(spec, mix["engine"],
                               weights.program_params(flat, spec, reg))
    driver = serve.Driver(eng, specs)
    trace_dir = tempfile.TemporaryDirectory(prefix="bench_trace_")

    def on_trace(start: bool) -> None:
        if start:
            jax.profiler.start_trace(trace_dir.name,
                                     profiler_options=trace_mod.options())
        else:
            jax.profiler.stop_trace()

    st = driver.run(seconds=seconds, warm_s=mix.get("warm_s", 0.0),
                    trace_s=TRACE_SECONDS if trace else 0.0,
                    on_trace=on_trace if trace else None)
    setup_s = st.window[0] - t_start
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"window {st.window[1] - st.window[0]:.3f} s, "
        f"{st.window_ticks[1] - st.window_ticks[0]} ticks, "
        f"{st.compiles_in_window} compilations in it; set-up {setup_s:.3f} s;"
        f" peak bytes {mem}")
    late = [st.submitted[u] - st.due[u] for u in st.window_due
            if u in st.submitted]
    if late and any(s["due_s"] is not None for s in specs):
        log(f"generator lateness over {len(late)} window requests: mean "
            f"{1e3 * sum(late) / len(late):.3f} ms, max "
            f"{1e3 * max(late):.3f} ms")
    reduced = None
    if trace:
        t = time.perf_counter()
        reduced = trace_mod.reduce_dir(trace_dir.name, st.traced,
                                       program.step_hlo(eng))
        log(f"trace reduced in {time.perf_counter() - t:.1f} s")
    trace_dir.cleanup()

    reqs, method_log = driver.reqs, eng.method_log
    jax.tree.map(lambda a: a.delete(), eng.state)      # free the KV pools
    driver.eng = eng = None
    gc.collect()

    t = time.perf_counter()
    closed = all(s["due_s"] is None for s in specs)
    uids = correct.choose(reqs, st, closed, mix, seed)
    ref = reg.reference(spec["reference"])
    if uids:
        got, control_got, widest = correct.gaps(ref, spec, flat, reqs, uids,
                                                mix, control=control)
    else:
        got = control_got = {k: correct.NO_READING for k in limits}
        widest = None
    served = sum(len(reqs[u].generated) for u in uids)
    log(f"reference over {len(uids)} requests, {served} served tokens, in "
        f"{time.perf_counter() - t:.1f} s")

    def judge(values):
        checks = {k: {"value": values[k], "limit": lim["limit"]}
                  for k, lim in limits.items()}
        return checks, all(c["value"] <= c["limit"] for c in checks.values())
    checks, ok = judge(got)

    run = SimpleNamespace(stamps=st, method_log=method_log, cfg=spec, mix=mix,
                          trace=reduced, peaks=peaks, setup_s=setup_s)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in reg.metrics_for(name, kind):
        value = read_metric(reg, m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # attempted: the requests served in the window, and those due in it
    # that the engine refused (failed); queued: due in it, not refused,
    # and still without a token when it closed (above the knee, most)
    refused = set(driver.refused) & set(st.window_due)
    served_in = [u for u, ts in st.tokens.items()
                 if any(st.in_window(t) for t in ts)]
    queued = [u for u in st.window_due
              if u not in refused and not st.tokens.get(u)]
    log(f"{len(served_in)} requests served in the window, {len(refused)} "
        f"refused; {len(queued)} of the {len(st.window_due)} due in it had "
        f"no token when it closed")
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": 1,
              "memory_peak_bytes": mem}
    out = {"correct": ok, "attempted": len(served_in) + len(refused),
           "failed": len(refused), "queued": len(queued),
           "metrics": metrics, "device": device}
    if control is not None:
        _, control_ok = judge(control_got)
        out["control"] = {"correct": control_ok, "program": got,
                          "control": control_got, "widest": widest}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    out["checks"] = checks
    return out, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.registry import Registry
    reg = Registry()
    cell = reg.workload(args.workload)
    spec = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    limits = reg.limits(args.workload)
    try:
        import jax
        devices = jax.devices()
    except RuntimeError as e:
        log(f"JAX finds no device ({e})")
        return 3
    if devices[0].platform != "tpu":
        log(f"no TPU found (JAX platform {devices[0].platform!r}); the "
            f"benchmark runs on a TPU only")
        return 3
    if len(devices) < cell["chips"]:
        log(f"{args.workload} needs {cell['chips']} chips, JAX finds "
            f"{len(devices)}")
        return 3
    peaks = reg.peaks(devices[0].device_kind)
    log(f"{args.workload} seed {args.seed} on {devices[0].device_kind}; "
        f"compile cache {enable_cache()}")
    out, _ = run_cell(reg, args.workload, spec, mix, limits, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      peaks=peaks, t_start=T_START)
    print(json.dumps(out), flush=True)
    for key, c in out["checks"].items():
        log(f"check {key} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
