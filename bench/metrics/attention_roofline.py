"""Sparse latent attention's share of its roofline, in %: the bytes a
step must read under `step.attention` (in every layer each slot's K
gathered latent rows and the weights W_uv and W_o: `attention_bytes` of
the configuration's layout, at the mix's slots and max_len) over the
scope's device time per step, over the chip's HBM bandwidth. Nothing to
read without the scope in the trace, or where the layout counts no such
bytes."""

from bench import scopes, weights


def read(run):
    tr = run.trace
    if tr is None or not tr.step_count:
        return None
    seconds = scopes.time_in(tr, "step.attention")
    count = getattr(weights.layout(run.cfg), "attention_bytes", None)
    if not seconds or count is None:
        return None
    eng = run.mix["engine"]
    per_step = seconds / tr.step_count
    return (100.0 * count(run.cfg, eng["slots"], eng["max_len"]) / per_step
            / run.peaks["hbm_bytes_per_s"])
