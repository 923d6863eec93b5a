"""The held experts' share of their roofline, in %: the bytes a step must
read under `step.experts` (every held expert's weights and the router's,
in each expert layer: `experts_bytes` of the configuration's layout) over
the scope's device time per step, over the chip's HBM bandwidth. At
decode batch sizes the experts are bound by their weights' bytes, not by
their FLOPs. Nothing to read without the scope in the trace, or where the
layout counts no such bytes."""

from bench import scopes, weights


def read(run):
    tr = run.trace
    if tr is None or not tr.step_count:
        return None
    seconds = scopes.time_in(tr, "step.experts")
    count = getattr(weights.layout(run.cfg), "experts_bytes", None)
    if not seconds or count is None:
        return None
    per_step = seconds / tr.step_count
    return 100.0 * count(run.cfg) / per_step / run.peaks["hbm_bytes_per_s"]
