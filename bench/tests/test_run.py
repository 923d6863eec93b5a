"""A whole run of each cell's kind, at small widths on the CPU: the
harness past its look for a chip (weights, traffic, engine, window,
reference, metrics), with `correct` true on the sound program and false
under each fault the cell can have, planted underneath the timed path:

  stale     the pool-wide step returns its state unchanged
  half      half of the slots' rows left out of the step's result
  token     a served token altered where the engine produces it

(One chip per cell: there is no exchange between chips to leave out.)
"""

from __future__ import annotations

import pytest

from bench import run as run_mod
from bench.registry import Registry
from bench.tests.small import small_config, small_engine

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LIMITS = {"logit_gap": {"limit": 1e-3}}
# float32 at small widths: a sound run reads 0 but for rounding; each kind
# compares the number its cell's limits file names
KIND_LIMITS = {"decode": LIMITS, "chat": {"logit_gap_mean": {"limit": 1e-4},
                                         "logit_gap_top8": {"limit": 1e-4}}}

MIXES = {
    "decode": ("chatglm3-6b.stage7", dict(
        generator="sessions", engine=small_engine(max_len=128), sessions=4,
        prompt_tokens={"min": 70, "max": 90}, max_new_tokens=30)),
    "chat": ("granite-3.0-1b-a400m", dict(
        generator="poisson", engine=small_engine(max_len=64), rate_per_s=8.0,
        warm_s=0.5,
        prompt_tokens={"mean": 12, "sigma": 0.8, "min": 4, "max": 24},
        output_tokens={"mean": 10, "sigma": 0.6, "min": 4, "max": 24},
        sample_served_tokens=60)),
}


def run(kind, seed=2**31 + 7, trace=False):
    config, mix = MIXES[kind]
    import time
    out, _ = run_mod.run_cell(Registry(), f"small.{kind}",
                              small_config(config), mix, KIND_LIMITS[kind],
                              seed=seed,
                              seconds=1.5, trace=trace, peaks=PEAKS,
                              t_start=time.perf_counter())
    return out


@pytest.mark.parametrize("kind", sorted(MIXES))
def test_sound_run_is_correct(kind):
    out = run(kind)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert all(c["value"] < 1e-4 for c in out["checks"].values())


def _stale(orig):
    def step(self, params, state, tokens, active, mwp):
        _, tok, logits, gvr = orig(self, params, state, tokens, active, mwp)
        return state, tok, logits, gvr
    return step


def _half(orig):
    def step(self, params, state, tokens, active, mwp):
        import jax.numpy as jnp
        keep = jnp.arange(active.shape[0]) < active.shape[0] // 2
        return orig(self, params, state, tokens, active & keep, mwp)
    return step


def _token(orig):
    def next_token(self, req, argmax_tok, logits_row):
        tok = orig(self, req, argmax_tok, logits_row)
        return (tok + 1) % self.cfg.vocab if len(req.generated) == 3 else tok
    return next_token


FAULTS = {"stale": ("_tick_impl", _stale), "half": ("_tick_impl", _half),
          "token": ("_next_token", _token)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("kind", sorted(MIXES))
def test_fault_is_not_correct(kind, fault, monkeypatch):
    from repro.serve import DecodeEngine
    attr, wrap = FAULTS[fault]
    monkeypatch.setattr(DecodeEngine, attr, wrap(getattr(DecodeEngine, attr)))
    out = run(kind)
    assert not out["correct"], out["checks"]
