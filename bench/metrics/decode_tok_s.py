"""Output tokens emitted in the window over the window's seconds."""


def read(run):
    st = run.stamps
    n = sum(1 for ts in st.tokens.values() for t in ts if st.in_window(t))
    return n / (st.window[1] - st.window[0]) if n else None
