"""Device time of the ops under the selector's name stacks (`jit(gvr_
threshold)`, `jit(radix_select_topk)`) over the step's device time, in %,
from the trace."""

SCOPES = ("jit(gvr_threshold)", "jit(radix_select_topk)")


def read(run):
    tr = run.trace
    if tr is None or not tr.step_s:
        return None
    under = tr.time_under(SCOPES)
    return 100.0 * under / tr.step_s if under else None
