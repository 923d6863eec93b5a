"""Whether what the timed path served is right: the served tokens against
the plain float32 reference.

After the window a sample of served requests is drawn from the seed: in a
closed loop every session; in an open loop the requests that finished in
the window (their last token stamped in it, so served on the full pool
the window times), the one that served the most tokens first, then the
others in a seeded order until the sample holds the mix's
`sample_served_tokens`. The reference runs once over each sampled prompt
followed by the tokens the engine served (teacher-forced), and at each
served position reads how far the served token's reference logit lies
below the reference's best:

    logit_gap      = max over served tokens of (max_v ref[v] - ref[served])
    logit_gap_mean = the same gap averaged over the served tokens
    logit_gap_top8 = the mean of the 8 widest gaps: a few wrong tokens
                     move it, a lone token near a tie hardly does

A cell's limits file names the numbers it compares.
Greedy serving of the reference's own model reads 0 but for rounding; a
served token that the reference would not have picked reads its loss. The
control (bench/calibrate.py) reads the same gap for the tokens a lower
precision would pick, and is judged against the same limits.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from bench import flops

# the reading where there is nothing to compare, or the gap is not finite
NO_READING = float(np.finfo(np.float32).max)
# how many of the widest gaps bench/calibrate.py reports, for each side
TOP = 32


def choose(reqs: Dict[int, object], stamps, closed: bool, mix: dict,
           seed: int) -> List[int]:
    """The sampled request ids."""
    if closed:
        return sorted(reqs)
    done = [u for u, r in reqs.items() if r.phase == "DONE" and
            stamps.tokens[u] and stamps.in_window(stamps.tokens[u][-1])]
    if not done:
        return []
    longest = max(done, key=lambda u: (len(reqs[u].generated), -u))
    rng = np.random.default_rng(seed)
    rest = [u for u in sorted(done) if u != longest]
    order = [longest] + [rest[i] for i in rng.permutation(len(rest))]
    out, served = [], 0
    for u in order:
        out.append(u)
        served += len(reqs[u].generated)
        if served >= mix["sample_served_tokens"]:
            break
    return out


def teacher_forced(reqs, uids):
    """Sequences (prompt + served tokens but the last), the positions the
    served tokens were produced at, and the served tokens."""
    seqs, rows, served = [], [], []
    for u in uids:
        r = reqs[u]
        gen = np.asarray(r.generated, np.int32)
        seqs.append(np.concatenate([r.prompt, gen[:-1]]))
        rows.append(np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + len(gen)))
        served.append(gen)
    return seqs, rows, np.concatenate(served)


def gaps(ref, spec, flat, reqs, uids, mix, *, control: Optional[str] = None):
    """The numbers compared, for the served tokens and (with `control`) for
    the tokens the control would pick: `logit_gap`, the widest gap,
    `logit_gap_mean`, the gap averaged over every served position, and
    `logit_gap_top8`, the mean of the 8 widest. With `control`, also the
    `TOP` widest gaps of each side, largest first."""
    seqs, rows, served = teacher_forced(reqs, uids)
    sparse = flops.sparse_cell(spec, mix["engine"]["max_len"])
    ref_logits = ref.logits(spec, flat, seqs, rows, sparse=sparse)
    best = jnp.max(ref_logits, axis=-1)
    idx = jnp.arange(len(served))

    def read(tokens):
        gap = best - ref_logits[idx, tokens]
        top = np.sort(np.asarray(gap))[::-1][:TOP]
        out = {"logit_gap": float(top[0]),
               "logit_gap_mean": float(jnp.mean(gap)),
               "logit_gap_top8": float(np.mean(top[:8]))}
        return {k: v if np.isfinite(v) else NO_READING
                for k, v in out.items()}, [float(g) for g in top]
    mine, mine_top = read(jnp.asarray(served))
    if control is None:
        return mine, None, None
    low = ref.logits(spec, flat, seqs, rows, sparse=sparse, quant=control)
    theirs, theirs_top = read(jnp.argmax(low, axis=-1))
    return mine, theirs, {"program": mine_top, "control": theirs_top}
