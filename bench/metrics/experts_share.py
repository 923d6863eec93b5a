"""Device time of the ops under the step's `step.experts` scope (the
routing over every routed expert, the experts held here and their
combine; inside `step.mlp`, without the shared expert) over the step's
device time, in %, from the trace (bench/scopes.py). Nothing to read in
a program without the scope."""

from bench import scopes


def read(run):
    return scopes.share(run.trace, "step.experts")
