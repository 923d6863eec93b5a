"""The DeepSeek-V3.2 configuration, its layout and its reference, at
small widths on the CPU: the committed file resolves and agrees with the
program, the layout's FLOP and byte counts match a hand count at the
published widths, the reference matches the program, and the float8
control fails where the bfloat16 program passes; the new metric
readers against a hand count, and silent where there is nothing to
read."""

from __future__ import annotations

import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from bench import flips, flops, program, trace, weights
from bench import run as run_mod
from bench.registry import Registry
from bench.tests.small_mla import NAME, small_engine, small_mla
from bench.tests.test_run import PEAKS

SEED = 2**31 + 3
DATA = Path(__file__).parent / "data"


def test_file_states_every_published_number_as_run_or_reduced():
    """Each published key is at the top level with its published value,
    unless `reduced` names it; the held experts are the reduced count."""
    spec = Registry().config(NAME)
    for key, value in spec["published"].items():
        if key in spec["reduced"]:
            assert spec[key] != value, key
        else:
            assert spec[key] == value, key
    assert spec["n_routed_experts"] == spec["moe"]["num_experts"] == 8
    assert spec["moe"]["total_experts"] == spec["published"][
        "n_routed_experts"]


def test_small_configuration_resolves_draws_and_agrees_with_the_program():
    reg = Registry()
    spec = small_mla()
    cfg = program.model_config(spec)
    assert cfg.is_mla and cfg.first_k_dense == 1
    assert (cfg.moe.num_experts, cfg.moe.routed_experts,
            cfg.moe.first_expert) == (4, 16, 8)
    flat = weights.make_flat(spec, SEED, reg)
    params = weights.program_params(flat, spec, reg)
    assert params["moe_layers"]["w_gate"] is flat["moe_w_gate"]
    assert flat["moe_w_gate"].shape == (2, 4, 32, 12)
    assert flat["moe_router"].shape == (2, 32, 16)
    assert flat["dense_wkv_b"].shape == (1, 16, 2 * (8 + 8))
    # a size changed in the file without being declared stops the run
    bad = dict(spec, kv_lora_rank=8)
    bad["changed_from_registry"] = [k for k in spec["changed_from_registry"]
                                    if k != "kv_lora_rank"]
    with pytest.raises(ValueError, match="kv_lora_rank"):
        program.model_config(bad)


def test_flops_and_bytes_by_hand():
    """At the published widths: one token over 5000 positions, sparse."""
    spec = Registry().config(NAME)
    layout = Registry().layout(spec)
    d, h, ql, kvl = 7168, 128, 1536, 512
    attn = (2 * d * ql + 2 * ql * h * 192 + 2 * d * 576
            + 2 * h * 128 * 512 + 2 * h * 512 * 128 + 2 * h * 128 * d)
    attn += 2 * ql * 64 * 128 + 2 * d * 128 + 2 * d * 64      # indexer
    attn += 2 * 5000 * 64 * 128 + 2 * 64 * 5000                 # its scores
    attn += 2 * h * 576 * 2048 + 2 * h * 512 * 2048              # K rows
    dense = attn + 6 * d * 18432
    expert = (attn + 2 * d * 256 + 6 * d * 2048
              + 8 * 8 / 256 * 6 * d * 2048)
    assert layout.flops_per_layer(spec, 5000, True) == [dense] + [expert] * 4
    assert flops.per_token(spec, 5000, True) == \
        dense + 4 * expert + 2 * d * 129280
    assert layout.experts_bytes(spec) == 4 * (
        8 * 3 * 7168 * 2048 * 2 + 7168 * 256 * 4 + 256 * 4)
    assert layout.attention_bytes(spec, 4, 8192) == 5 * (
        4 * 2048 * 1152 + 512 * 128 * 128 * 2 + 16384 * 7168 * 2)


def test_row_logits_match_the_reference_and_follow_what_is_forced():
    """One position recomputed alone (absorbed attention over one query)
    equals the reference's full forward at that position; setting its
    Top-K selection and experts to the reference's own changes nothing,
    and a routing flip or another selection changes its logits."""
    ref = Registry().reference("mla")
    spec = small_mla(min_n=16)
    flat = weights.make_flat(spec, SEED)
    rng = np.random.default_rng(2)
    seqs = [rng.integers(0, spec["vocab"], (n,)) for n in (40, 57)]
    rows = [np.arange(30, len(q)) for q in seqs]
    want = ref.logits(spec, flat, seqs, rows, sparse=True, pad_to=64)
    with jax.default_matmul_precision("highest"):
        xs = list(ref.stream(spec, flat, seqs[1], 64, sparse=True))
        for p in (30, 45, 56):
            got, sigs, own = ref.row_logits(spec, flat, xs, p, sparse=True)
            w = want[len(rows[0]) + p - 30]
            assert np.abs(got - w).max() < 1e-5 * np.abs(w).max()
        ds = flips.decisions(spec, flat, sigs)
        assert ds and {d["kind"] for d in ds} <= {"group", "expert"}
        nd = spec["first_k_dense"]
        own_route = {layer: flips.chosen(
            sigs[layer], np.asarray(flat["moe_router_bias"][layer - nd]),
            spec["moe"]) for layer in range(nd, spec["n_layers"])}
        same, _, _ = ref.row_logits(spec, flat, xs, 56, sparse=True,
                                    topk=dict(enumerate(own)),
                                    route=own_route)
        assert np.abs(same - got).max() < 1e-5 * np.abs(got).max()
        for d in ds:
            moved, _, _ = ref.row_logits(spec, flat, xs, 56, sparse=True,
                                         start=d["layer"],
                                         route={d["layer"]: d["route"]})
            assert np.abs(moved - got).max() > 1e-3
        # the first position selected in layer 0 swapped for one left out
        out = np.setdiff1d(np.arange(57), own[0])[0]
        swapped = np.where(own[0] == own[0].min(), out, own[0])
        moved, _, _ = ref.row_logits(spec, flat, xs, 56, sparse=True,
                                     topk={0: swapped})
        assert np.abs(moved - got).max() > 1e-4


def _bf16_cell():
    """A bfloat16 configuration at widths where gaps show, K=256 of 600-800
    positions, and its mix."""
    spec = small_mla("bfloat16", min_n=256)
    spec.update(d_model=256, n_heads=4, q_lora_rank=96, kv_lora_rank=64,
                qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                d_ff=512, vocab=2000)
    spec["moe"] = dict(spec["moe"], expert_d_ff=128, shared_d_ff=128)
    spec["dsa"] = dict(spec["dsa"], k=256, indexer_heads=8, indexer_dim=32)
    mix = dict(generator="sessions", engine=small_engine(max_len=1024),
               sessions=4, prompt_tokens={"min": 600, "max": 800},
               max_new_tokens=60)
    return spec, mix


def test_flips_explain_each_widest_gap():
    """bfloat16 program: each of the 4 widest gaps vanishes on the
    program's recorded Top-K selection or under one routing flip, and the
    row recomputed alone reads the gap the full forward reads."""
    spec, mix = _bf16_cell()
    out = flips.flips(Registry(), spec, mix, seed=5, seconds=3.0, top=4,
                      typical=16)
    assert len(out["tokens"]) == 4 and out["typical"] == 16
    for tok in out["tokens"]:
        assert abs(tok["gap_row"] - tok["gap"]) < 1e-4
        assert tok["overlap"] and max(tok["overlap"]) <= 256
        assert tok["gap"] > flips.NEAR
        assert tok["explained"], tok
    assert out["explained"] == 4


# the mean gap and the mean of the 8 widest, set between the two sides'
# readings at these widths on these seeds (program at most 0.022 and
# 0.45, control at least 0.107 and 0.95)
SMALL_LIMITS = {"logit_gap_mean": {"limit": 0.05},
                "logit_gap_top8": {"limit": 0.7}}


def test_control_fails_where_the_program_passes():
    """bfloat16 program, float8 control, K=256 of 600-800 positions: on
    each seed the program is correct and the control is not, by at least
    twice the program's reading."""
    spec, mix = _bf16_cell()
    lows, highs = {}, {}
    for seed in (5, 2**31 + 9, 77):
        out, _ = run_mod.run_cell(
            Registry(), "small.mla", spec, mix, SMALL_LIMITS, seed=seed,
            seconds=3.0, trace=False, peaks=PEAKS,
            t_start=time.perf_counter(), control="fp8")
        assert out["correct"], out["checks"]
        assert not out["control"]["correct"], out["control"]
        for key in SMALL_LIMITS:
            lows.setdefault(key, []).append(out["control"]["program"][key])
            highs.setdefault(key, []).append(out["control"]["control"][key])
    for key in SMALL_LIMITS:
        assert min(highs[key]) > 2 * max(lows[key]), (key, lows, highs)


def _roofline_run(spec, trace):
    return SimpleNamespace(trace=trace, cfg=spec, peaks=PEAKS,
                           mix=Registry().traffic("decode_sessions"))


def test_roofline_readers_against_a_hand_count():
    """A step whose `step.experts` and `step.attention` take twice the
    time their bytes need at the HBM peak reads 50 % on each roofline;
    `experts_share` is the scope's time over the step's."""
    reg = Registry()
    spec = reg.config(NAME)
    layout = reg.layout(spec)
    steps, hbm = 10, PEAKS["hbm_bytes_per_s"]
    seconds = {"step.experts": steps * 2 * layout.experts_bytes(spec) / hbm,
               "step.attention": steps * 2 * layout.attention_bytes(
                   spec, 4, 8192) / hbm}
    step_s = 4 * seconds["step.experts"]
    tr = SimpleNamespace(step_count=steps, step_s=step_s,
                         time_under=lambda p: seconds.get(p[0].strip("/"), 0))
    run = _roofline_run(spec, tr)
    assert reg.metric_reader("experts_roofline").read(run) == \
        pytest.approx(50.0)
    assert reg.metric_reader("attention_roofline").read(run) == \
        pytest.approx(50.0)
    assert reg.metric_reader("experts_share").read(run) == \
        pytest.approx(25.0)


def test_new_readers_read_nothing_without_their_scope_or_bytes():
    """On a recorded decode step of a program without `step.experts`, and
    for a layout that counts no such bytes, the three new readers return
    None, as on a parent commit."""
    reg = Registry()
    tr = trace.reduce_file(str(DATA / "step_trace_scoped.xplane.pb"),
                           hlo_text=(DATA / "step_trace_scoped.hlo.txt")
                           .read_text())
    for spec in (reg.config("chatglm3-6b.stage7"), reg.config(NAME)):
        run = _roofline_run(spec, tr)
        assert reg.metric_reader("experts_share").read(run) is None
        assert reg.metric_reader("experts_roofline").read(run) is None
    run = _roofline_run(reg.config("chatglm3-6b.stage7"), tr)
    assert reg.metric_reader("attention_roofline").read(run) is None
