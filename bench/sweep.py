"""Find the highest arrival rate an open-loop cell sustains: one sweep.

    python bench/sweep.py --workload <cell> --seconds <s> --seed <n>
        --rates 1.0 1.5 2.0 ...

Runs the cell once per rate in one process (the mix's `rate_per_s`
replaced, nothing else) and prints, per rate, the end-to-end metrics and
whether the queue grew through the window: the mean wait for a slot of
the last fifth of the window's requests against the first fifth's. The
cell's rate is then fixed as a number in its mix file: about 0.8 of the
highest rate whose queue did not grow for a cell judged by its tails, or
a rate above it for one judged by the tokens per second it completes.
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
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 3
    run_mod.enable_cache()
    reg = Registry()
    cell = reg.workload(args.workload)
    spec, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    limits, peaks = reg.limits(args.workload), reg.peaks(dev.device_kind)
    for rate in args.rates:
        out, run = run_mod.run_cell(reg, args.workload, spec,
                                    dict(mix, rate_per_s=rate), limits,
                                    seed=args.seed, seconds=args.seconds,
                                    trace=False, peaks=peaks,
                                    t_start=time.perf_counter())
        st = run.stamps
        due = sorted(st.window_due, key=lambda u: st.due[u])
        fifth = max(len(due) // 5, 1)
        end = st.ticks[-1][1]

        def wait(uids):
            return 1e3 * sum(st.admitted.get(u, end) - st.due[u]
                             for u in uids) / len(uids)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(due),
            "queue_wait_first_fifth_ms": wait(due[:fifth]),
            "queue_wait_last_fifth_ms": wait(due[-fifth:]),
            "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
