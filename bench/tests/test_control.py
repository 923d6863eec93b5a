"""The control fails where the program passes: at small widths on the CPU.

The program serves in bfloat16, as the configurations state; the control
is the float32 reference computed in float8, the next precision below:
every matmul's operands and the residual stream rounded to it. The
harness judges both against the limits on the numbers each cell compares
(the widest logit gap in the decode cell; the mean gap and the mean of
the 8 widest in the chat cell): on each seed the program comes out
correct and the control does not. The limits here are set between the
two sides' readings at these widths on these seeds (program at most
0.058, 0.0005 and 0.0041, control at least 0.246, 0.013 and 0.097); on
the chip, at the cells' own sizes, bench/calibrate.py takes the same
readings and verdicts against bench/limits/<cell>.json.
"""

from __future__ import annotations

import time

import pytest

from bench import run as run_mod
from bench.registry import Registry
from bench.tests.small import small_config, small_engine
from bench.tests.test_run import MIXES, PEAKS

SMALL_LIMITS = {"decode": {"logit_gap": {"limit": 0.12}},
                "chat": {"logit_gap_mean": {"limit": 0.004},
                         "logit_gap_top8": {"limit": 0.03}}}


def sizes(kind):
    """The decode kind where Top-K keeps 512 of 1200-1600 rows: with a
    small K a row swapped in at the K-th score weighs much of the
    attention, and the program's bfloat16 swaps then read like the
    control's (K=16: 1/16 a row; the cell's K=2048: 1/2048)."""
    config, mix = MIXES[kind]
    spec = small_config(config, "bfloat16")
    if kind == "decode":
        spec.update(d_model=512, n_heads=8, head_dim=64, d_ff=1024,
                    vocab=2000)
        spec["dsa"] = dict(spec["dsa"], indexer_heads=16, indexer_dim=64,
                           k=512)
        mix = dict(mix, engine=small_engine(max_len=2048), max_new_tokens=100,
                   prompt_tokens={"min": 1200, "max": 1500})
    return spec, mix


@pytest.mark.parametrize("kind", sorted(MIXES))
def test_control_reads_twice_the_program(kind):
    spec, mix = sizes(kind)
    lows, highs = {}, {}
    for seed in (5, 2**31 + 9, 77):
        out, _ = run_mod.run_cell(
            Registry(), f"small.{kind}", spec, mix, SMALL_LIMITS[kind],
            seed=seed, seconds=3.0, trace=False, peaks=PEAKS,
            t_start=time.perf_counter(), control="fp8")
        assert out["correct"], out["checks"]
        assert not out["control"]["correct"], out["control"]
        for key in SMALL_LIMITS[kind]:
            lows.setdefault(key, []).append(out["control"]["program"][key])
            highs.setdefault(key, []).append(out["control"]["control"][key])
    for key in SMALL_LIMITS[kind]:
        assert min(highs[key]) > 2 * max(lows[key]), (key, lows, highs)
