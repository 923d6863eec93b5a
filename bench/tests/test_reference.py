"""The float32 references against the program, at small widths on the CPU.

Both run float32 here, so they must agree to rounding: the program's
logits for every served token (prefill's last position, then each decode
step through the paged cache) against the reference's full forward over
prompt and served tokens. Sparse: max_len 128 passes min_n 64, contexts of
40-60 tokens exceed K=16, so the indexer and the exact Top-K select.
Dense: max_len 64 keeps the gate closed.
"""

from __future__ import annotations

import numpy as np
import pytest

from bench import flops, program, weights
from bench.registry import Registry
from bench.tests.small import small_config, small_engine

CASES = [("chatglm3-6b.stage7", 128), ("chatglm3-6b.stage7", 64),
         ("granite-3.0-1b-a400m", 64), ("granite-3.0-1b-a400m", 128)]


def serve(spec, eng_spec, seed, prompts, new):
    flat = weights.make_flat(spec, seed)
    eng = program.build_engine(spec, eng_spec, weights.program_params(flat))
    eng.record_logits = True
    reqs = [program.request(u, p, new) for u, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    while not eng.idle():
        eng.tick()
    return flat, reqs


@pytest.mark.parametrize("name,max_len", CASES)
def test_reference_matches_program(name, max_len):
    spec = small_config(name)
    eng_spec = small_engine(max_len=max_len)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, spec["vocab"], (n,)) for n in (40, 47, 33, 52)]
    flat, reqs = serve(spec, eng_spec, 2**31 + 3, prompts, 8)
    ref = Registry().reference(spec["reference"])
    seqs = [np.concatenate([r.prompt, r.generated[:-1]]) for r in reqs]
    rows = [np.arange(len(r.prompt) - 1, len(s)) for r, s in zip(reqs, seqs)]
    got = np.concatenate([np.stack(r.logits_log) for r in reqs])
    want = np.asarray(ref.logits(
        spec, flat, seqs, rows, sparse=flops.sparse_cell(spec, max_len),
        pad_to=64))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err
