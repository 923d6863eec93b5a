"""Where a cell's tick goes: the engine's phases and the step's scopes, from
the trace of one `--trace 1` run of the harness.

    python bench/phases.py --workload <cell> --seed <n> [--seconds 12]

Makes one traced run of the cell through bench/run.py's `run_cell` (its
window `--seconds` long, the last 3 s traced, the reference and `correct`
as in any run) and prints two JSON lines: the run's own result line, and
what the harness's result line cannot hold yet, read from the same trace:

    ticks                 traced ticks (the harness's `bench.tick` spans)
    idle_ms_per_tick      the device's idle gaps, each split over the
                          innermost engine span it overlaps (`engine.admit`
                          ... `engine.retire`; `engine.tick` itself is
                          engine code outside every phase; `outside`,
                          time outside every tick), ms per traced tick;
                          they sum to the trace's idle time
    step_split            each step scope's share of the step and the
                          unscoped rest, % (bench/scopes.py), with the
                          unscoped ops that took most device time
    host_events_per_tick  host spans inside the ticks by name, per tick
                          (the transfers among them: `DevicePut...`,
                          `np.asarray...`)
    tok_s                 tokens/s in the window before the trace and
                          while it ran: what tracing costs
    counters              the engine's GVR counters over the traced ticks
                          (`DecodeEngine.counters()`, read at the trace's
                          edges) and `gvr_secant_iters`, their iterations
                          per GVR row-layer

The harness keeps no trace file and reads no counter: this wrapper takes
both from inside the run (the harness's `bench.trace.reduce_dir` and
`bench.serve.Driver`, wrapped for the run's length). A program without
the engine's spans or counters gives what it has: its gaps fall under
`bench.tick`, and `counters` is empty.
"""

from __future__ import annotations

import argparse
import bisect
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import scopes, serve, trace                      # noqa: E402

TICK = "bench.tick"
OUTSIDE = "outside"


def phase_split(spans: List[Tuple[int, int, str]],
                gaps: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """Nanoseconds of the gaps (start, duration, ...) under each innermost
    tick or engine span of `spans` (start, end, name; one thread's, so
    they nest); what lies under none goes to `outside`. The values sum to
    the gaps' total."""
    phases = sorted((s for s in spans
                     if s[2] == TICK or s[2].startswith("engine.")),
                    key=lambda s: (s[0], -s[1]))
    bounds = sorted({t for s, e, _ in phases for t in (s, e)})
    names = trace.name_gaps(phases, [(a + b) // 2
                                     for a, b in zip(bounds, bounds[1:])])
    out: Dict[str, int] = {}
    for start, dur, _ in gaps:
        end = start + dur
        inside = 0
        i = max(bisect.bisect_right(bounds, start) - 1, 0)
        while i < len(names) and bounds[i] < end:
            a, b = max(start, bounds[i]), min(end, bounds[i + 1])
            if b > a:
                name = OUTSIDE if names[i] == "no host span" else names[i]
                out[name] = out.get(name, 0) + b - a
                inside += b - a
            i += 1
        if dur - inside:
            out[OUTSIDE] = out.get(OUTSIDE, 0) + dur - inside
    return out


def host_events(spans: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """How often each span name occurs inside a tick (the ticks too)."""
    ticks = [(s, e) for s, e, n in spans if n == TICK]
    starts = [s for s, _ in ticks]
    out: Dict[str, int] = {}
    for s, e, n in spans:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= ticks[i][1]:
            out[n] = out.get(n, 0) + 1
    return out


def unscoped_ops(tr, top: int = 8) -> List[list]:
    """The ops outside every step scope that took most device time, s."""
    by: Dict[str, int] = {}
    for name, _, d, scope in tr.ops:
        if "/step." not in scope:
            key = f"{name} {scope.split('/')[-1]}".strip()
            by[key] = by.get(key, 0) + d
    worst = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return [[k, 1e-9 * v] for k, v in worst]


def reduce(tr, path: str) -> dict:
    """The phase split, the step split and the host events of one trace:
    `tr`, the harness's reduction of the trace file at `path`."""
    import jax
    # the tick thread's spans, as the harness names its gaps by them
    spans = trace._host_spans(jax.profiler.ProfileData.from_file(path))
    ticks = sum(1 for _, _, n in spans if n == TICK)
    split = phase_split(spans, tr.gaps)
    per = max(ticks, 1)
    return {
        "ticks": ticks,
        "idle_ms": 1e-6 * sum(d for _, d, _ in tr.gaps),
        "idle_ms_per_tick": {k: 1e-6 * v / per for k, v in
                             sorted(split.items(), key=lambda kv: -kv[1])},
        "step_ms": 1e3 * tr.step_s / max(tr.step_count, 1),
        "step_split": dict(scopes.split(tr), unscoped_ops=unscoped_ops(tr)),
        "host_events_per_tick": {k: v / per for k, v in sorted(
            host_events(spans).items(), key=lambda kv: -kv[1])[:30]},
    }


def measure(reg, name: str, spec: dict, mix: dict, limits: dict, *,
            seed: int, seconds: float, peaks: dict, t_start: float):
    """One traced run of the cell (`run_cell`). Returns its result line
    and the phase record of its trace."""
    from bench import run as run_mod
    record: Dict[str, object] = {}
    counts: Dict[str, int] = {}
    reduce_dir = trace.reduce_dir

    def reduce_and_split(directory, traced=None, hlo_text=""):
        tr = reduce_dir(directory, traced, hlo_text)
        record.update(reduce(tr, trace.find_xplane(directory)))
        return tr

    class Driver(serve.Driver):
        def run(self, *, on_trace=None, **kw):
            read = getattr(self.eng, "counters", None)

            def edges(start: bool) -> None:
                # the counters' reads stay outside the trace
                if start:
                    read()
                on_trace(start)
                if not start:
                    counts.update(read())
            return super().run(on_trace=edges if read and on_trace
                               else on_trace, **kw)

    with mock.patch.object(trace, "reduce_dir", reduce_and_split), \
            mock.patch.object(serve, "Driver", Driver):
        out, run = run_mod.run_cell(reg, name, spec, mix, limits, seed=seed,
                                    seconds=seconds, trace=True, peaks=peaks,
                                    t_start=t_start)
    st = run.stamps

    def tok_s(a: float, b: float) -> float:
        n = sum(1 for ts in st.tokens.values() for t in ts if a < t <= b)
        return n / (b - a) if b > a else 0.0
    record["tok_s"] = {"untraced": tok_s(st.window[0], st.traced[0]),
                       "traced": tok_s(st.traced[0], st.window[1])}
    if counts.get("gvr_row_layers"):
        counts["gvr_secant_iters"] = (counts["gvr_secant_iters"]
                                      / counts["gvr_row_layers"])
    record["counters"] = counts
    return out, record


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("phases: no TPU found", file=sys.stderr)
        return 2
    from bench.registry import Registry
    from bench.run import enable_cache
    enable_cache()
    reg = Registry()
    cell = reg.workload(args.workload)
    out, record = measure(
        reg, args.workload, reg.config(cell["config"]),
        reg.traffic(cell["traffic"]), reg.limits(args.workload),
        seed=args.seed, seconds=args.seconds,
        peaks=reg.peaks(jax.devices()[0].device_kind), t_start=t_start)
    print(json.dumps(out), flush=True)
    print(json.dumps(dict(workload=args.workload, seed=args.seed, **record)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
