"""Device time of the ops under the step's `step.topk` scope (all of the
Top-K selection: GVR's threshold, the extraction, radix, the masking and
the per-row choice) over the step's device time, in %, from the trace
(bench/scopes.py). A superset of `selector_share`."""

from bench import scopes


def read(run):
    return scopes.share(run.trace, "step.topk")
