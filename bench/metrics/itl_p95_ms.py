"""95th percentile of every gap between consecutive output tokens of one
request that ends in the window, in ms."""

import numpy as np


def read(run):
    st = run.stamps
    gaps = [b - a for ts in st.tokens.values() for a, b in zip(ts, ts[1:])
            if st.in_window(b)]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
