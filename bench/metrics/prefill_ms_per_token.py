"""Mean, over the requests whose first token came in the window, of
(first token − admission) divided by the prompt's tokens, in ms. Above
the knee no request due in the window is admitted in it; those that
finish their prefill in it are read instead."""


def read(run):
    st = run.stamps
    per = [(ts[0] - st.admitted[u]) / st.prompt_len[u]
           for u, ts in st.tokens.items()
           if ts and st.in_window(ts[0]) and u in st.admitted]
    return 1e3 * sum(per) / len(per) if per else None
