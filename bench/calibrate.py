"""Readings that set the limits of `correct` (bench/limits/<cell>.json).

    python bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 ...

Runs the cell once per seed in one process, as bench/run.py does, and
reads for each seed every number `correct` can compare (bench/correct.py)
for the program and for the control: the float32 reference computed in
float8 (the next precision below the configuration's bfloat16), picking
its own tokens at the served positions. Both are judged against the
cell's limits (bench/limits/<cell>.json) as a run judges the program. One
JSON line per seed, with each side's verdict and widest gaps; the last
line gives each number's largest program reading (the lower reading) and
smallest control reading (the upper one). It exits with 1 where the
control came out correct on any seed. The benchmark's own runs never run
the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as run_mod                  # noqa: E402
from bench.registry import Registry               # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("calibrate: no TPU found", file=sys.stderr)
        return 3
    run_mod.enable_cache()
    reg = Registry()
    cell = reg.workload(args.workload)
    spec, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    limits, peaks = reg.limits(args.workload), reg.peaks(dev.device_kind)
    lows, highs, control_passed = {}, {}, []
    for seed in args.seeds:
        t = time.perf_counter()
        out, _ = run_mod.run_cell(reg, args.workload, spec, mix, limits,
                                  seed=seed, seconds=args.seconds,
                                  trace=False, peaks=peaks, t_start=t,
                                  control="fp8")
        for key, value in out["control"]["program"].items():
            lows.setdefault(key, []).append(value)
            highs.setdefault(key, []).append(out["control"]["control"][key])
        if out["control"]["correct"]:
            control_passed.append(seed)
        c = out["control"]
        print(json.dumps({"seed": seed, "program_correct": out["correct"],
                          "control_correct": c["correct"],
                          "program": c["program"], "control": c["control"],
                          "widest": c["widest"], "metrics": out["metrics"],
                          "seconds": time.perf_counter() - t}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "lower": {k: max(v) for k, v in lows.items()},
                      "upper": {k: min(v) for k, v in highs.items()},
                      "control_correct_on": control_passed}))
    return 1 if control_passed else 0


if __name__ == "__main__":
    sys.exit(main())
