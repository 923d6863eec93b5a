"""Model FLOPs of the tokens processed in the window (bench/flops.py, from
the configuration's shapes) over the window's seconds and the chip's bf16
peak, in %."""

from bench import flops


def read(run):
    st = run.stamps
    sparse = flops.sparse_cell(run.cfg, run.mix["engine"]["max_len"])
    total = 0.0
    for uid, ts in st.tokens.items():
        for j, t in enumerate(ts):
            if st.in_window(t):
                total += flops.per_token(run.cfg, st.prompt_len[uid] + j,
                                         sparse)
    if not total:
        return None
    seconds = st.window[1] - st.window[0]
    return 100.0 * total / seconds / run.peaks["bf16_flops_per_s"]
