"""Record a short profiler trace of a cell's pool-wide step, on the chip.

    python bench/record_trace.py --workload <cell> [--layers N] [--ticks 3]
        --out DIR

Builds the cell's engine (the configuration's widths, optionally fewer
layers), fills every slot with a short greedy request, times a few warm
ticks untraced, then traces `--ticks` ticks and writes the `.xplane.pb`
under `--out`, with `summary.json` (planes, lines, event counts and the
stats of a few events of each line) and `step.hlo.txt`, the step's
compiled HLO, whose metadata names each op's scopes. bench/tests/data/ keeps one such
trace, recorded with `--layers 1`, for the tests of bench/trace.py.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import program, weights            # noqa: E402
from bench.registry import Registry           # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--ticks", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU found", file=sys.stderr)
        return 2
    from bench.run import enable_cache
    enable_cache()
    reg = Registry()
    cell = reg.workload(args.workload)
    spec = reg.config(cell["config"])
    if args.layers:
        spec = dict(spec, n_layers=args.layers,
                    changed_from_registry=sorted(
                        set(spec["changed_from_registry"]) | {"n_layers"}))
    mix = reg.traffic(cell["traffic"])
    t = time.perf_counter()
    params = weights.program_params(weights.make_flat(spec, 1))
    jax.block_until_ready(params)
    print(f"weights {time.perf_counter() - t:.2f} s", flush=True)
    eng = program.build_engine(spec, mix["engine"], params)
    rng = np.random.default_rng(1)
    slots = mix["engine"]["slots"]
    for u in range(slots):
        eng.submit(program.request(u, rng.integers(0, spec["vocab"], (8,)),
                                   64))
    t = time.perf_counter()
    while any(r is None or r.phase != "DECODE" for r in eng.slots):
        eng.tick()
    print(f"prefill ticks incl. compile {time.perf_counter() - t:.2f} s",
          flush=True)
    for _ in range(3):
        eng.tick()
    n = 10
    t = time.perf_counter()
    for _ in range(n):
        eng.tick()
    ms = 1e3 * (time.perf_counter() - t) / n
    print(f"decode tick {ms:.3f} ms (untraced, {slots} slots, "
          f"{spec['n_layers']} layers)", flush=True)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    from bench import trace
    with open(out / "step.hlo.txt", "w") as f:
        f.write(program.step_hlo(eng))
    jax.profiler.start_trace(str(out), profiler_options=trace.options())
    t = time.perf_counter()
    for _ in range(args.ticks):
        with jax.profiler.TraceAnnotation("bench.tick"):
            eng.tick()
    window = time.perf_counter() - t
    jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    path = sorted(glob.glob(str(out / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    summary = {"window_s": window, "decode_tick_ms": ms,
               "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
               "xplane": os.path.relpath(path, out),
               "bytes": os.path.getsize(path), "planes": []}
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        pl = {"name": plane.name, "lines": []}
        for line in plane.lines:
            evs = list(line.events)
            pl["lines"].append({
                "name": line.name, "events": len(evs),
                "sample": [{"name": e.name, "start_ns": e.start_ns,
                            "duration_ns": e.duration_ns,
                            "stats": {k: str(v) for k, v in e.stats}}
                           for e in evs[:: max(len(evs) // 12, 1)][:12]]})
        summary["planes"].append(pl)
    with open(out / "summary.json", "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({k: summary[k] for k in ("window_s", "decode_tick_ms",
                                              "peak_bytes_in_use", "bytes")}))
    for pl in summary["planes"]:
        print(pl["name"], [(l["name"], l["events"]) for l in pl["lines"]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
