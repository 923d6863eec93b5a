"""Device-idle time inside the engine's ticks, per traced tick, in ms.

The trace names each idle gap by the innermost host span around its
middle (bench/trace.py). The harness wraps each `DecodeEngine.tick()` in
`bench.tick` and its own bookkeeping after it in `bench.stamp`; the
engine spans its tick (`engine.tick`) and the tick's phases inside it
(`engine.admit` ... `engine.emit`). A gap under any span but the
harness's lies inside the engine's tick: the sum of those, divided by the
ticks that started inside the traced window. This is `device_idle_share`
in ms a tick, less the harness's own time; the split by phase waits for a
field of the trace's reduction (bench/phases.py reads it meanwhile).
Nothing to read where no gap lies under an engine span (a program
without them)."""

HARNESS = ("bench.tick", "bench.stamp", "no host span")


def read(run):
    tr, st = run.trace, run.stamps
    if tr is None or st.traced is None:
        return None
    if not any(host.startswith("engine.") for _, _, host in tr.gaps):
        return None
    ticks = sum(1 for t0, _ in st.ticks if st.traced[0] <= t0 < st.traced[1])
    if not ticks:
        return None
    idle_ns = sum(d for _, d, host in tr.gaps if host not in HARNESS)
    return 1e-6 * idle_ns / ticks
