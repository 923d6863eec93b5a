"""Device time of the ops under the step's `step.mlp` scope (the second
norm and the dense MLP or the MoE block) over the step's device time, in
%, from the trace (bench/scopes.py)."""

from bench import scopes


def read(run):
    return scopes.share(run.trace, "step.mlp")
