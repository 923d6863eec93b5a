"""Device time of the ops under the step's `step.attention` scope (sparse
attention, or dense attention where the DSA gate is closed, and the
output projection) over the step's device time, in %, from the trace
(bench/scopes.py)."""

from bench import scopes


def read(run):
    return scopes.share(run.trace, "step.attention")
