"""Closed-loop sessions: a fixed set of long prompts, prefilled in set-up.

Every seed gets the same prompt lengths (evenly spaced over the mix's
range) in another order, and its own token ids; so every seed does the
same work. No request is due in the window: the sessions decode through
it, each sending its next token as soon as the last one came back.
"""

from __future__ import annotations

import numpy as np


def generate(mix: dict, seed: int, *, vocab: int, seconds: float) -> list:
    rng = np.random.default_rng(seed)
    n = mix["sessions"]
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    lengths = np.linspace(lo, hi, n).round().astype(int)
    return [dict(uid=u, prompt=rng.integers(0, vocab, (int(p),), np.int32),
                 max_new_tokens=mix["max_new_tokens"], due_s=None)
            for u, p in enumerate(rng.permutation(lengths))]
