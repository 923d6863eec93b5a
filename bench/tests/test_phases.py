"""bench/phases.py: the idle split over the engine's tick phases, on
made-up spans, and a whole measurement at small widths on the CPU."""

from __future__ import annotations

import time
from unittest import mock

from bench import phases
from bench import run as run_mod
from bench.registry import Registry
from bench.tests.small import small_config, small_engine


def test_gaps_split_over_the_innermost_phase():
    spans = [(0, 100, "bench.tick"), (2, 98, "engine.tick"),
             (10, 40, "engine.dispatch"), (12, 20, "PjitFunction(x)"),
             (50, 90, "engine.emit"), (70, 80, "engine.retire"),
             (120, 130, "bench.stamp")]
    gaps = [(0, 12, ""), (30, 30, ""), (75, 50, "")]
    got = phases.phase_split(spans, gaps)
    assert got == {"bench.tick": 2 + 2, "engine.tick": 8 + 10 + 8,
                   "engine.dispatch": 2 + 10, "engine.emit": 10 + 10,
                   "engine.retire": 5, "outside": 25}
    assert sum(got.values()) == sum(d for _, d, _ in gaps)


def test_host_events_count_inside_ticks_only():
    spans = [(0, 100, "bench.tick"), (10, 20, "DevicePut"),
             (30, 40, "DevicePut"), (120, 130, "bench.stamp"),
             (200, 300, "bench.tick"), (210, 220, "DevicePut")]
    assert phases.host_events(spans) == {"bench.tick": 2, "DevicePut": 3}


def test_small_measurement():
    """The decode kind at small widths, through the harness's own run:
    its result line, the counters over the traced ticks, tokens/s on both
    sides of the trace's start (the CPU trace holds no device plane, so
    there is no idle time to split)."""
    spec = small_config("chatglm3-6b.stage7")
    mix = dict(generator="sessions", engine=small_engine(max_len=512),
               sessions=4, prompt_tokens={"min": 70, "max": 90},
               max_new_tokens=400)
    # a window with an untraced part to compare the traced one with
    with mock.patch.object(run_mod, "TRACE_SECONDS", 0.5):
        out, rec = phases.measure(
            Registry(), "small.decode", spec, mix,
            {"logit_gap": {"limit": 1e-3}}, seed=2**31 + 11, seconds=1.5,
            peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    assert rec["ticks"] > 0
    assert rec["tok_s"]["untraced"] > 0 and rec["tok_s"]["traced"] > 0
    c = rec["counters"]
    assert c["gvr_row_layers"] > 0 and c["gvr_fallbacks"] == 0
    assert 0 <= c["gvr_secant_iters"] <= 12
    assert rec["host_events_per_tick"]["engine.tick"] == 1.0
