"""1 − (union of the device's op intervals ÷ the traced window), in %.
Nothing to read where the trace holds no op of the device."""


def read(run):
    tr = run.trace
    if tr is None or not tr.window_s or not tr.busy_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
