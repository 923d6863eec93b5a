"""Device time of the ops under the step's `step.indexer` scope (indexer
keys, the logical indexer view, the indexer scores) over the step's
device time, in %, from the trace (bench/scopes.py)."""

from bench import scopes


def read(run):
    return scopes.share(run.trace, "step.indexer")
