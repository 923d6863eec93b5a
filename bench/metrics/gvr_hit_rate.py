"""Share of the window's decode slot-ticks whose Top-K the GVR path served,
from the engine's own per-row selector log (the count EngineReport's
gvr_hit_rate is made of), in %. Nothing to read where no decode
slot-tick went through the selector (the DSA gate closed)."""


def read(run):
    first, last = run.stamps.window_ticks
    counts = {}
    for entries in run.method_log.values():
        for tick, phase, method in entries:
            if first <= tick < last and phase == "DECODE":
                counts[method] = counts.get(method, 0) + 1
    total = sum(v for k, v in counts.items() if k != "dense")
    return 100.0 * counts.get("gvr", 0) / total if total else None
