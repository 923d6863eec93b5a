"""Device time of the pool-wide step's executions (the XLA module of the
engine's jitted `_tick_impl`) over their count, in ms, from the trace."""


def read(run):
    tr = run.trace
    if tr is None or not tr.step_count:
        return None
    return 1e3 * tr.step_s / tr.step_count
