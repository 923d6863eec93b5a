"""DeepSeek-V3.2's latent attention, V3.2 indexer and held-expert layer
through the paged engine, on the CPU at small widths in float32.

The engine serves the committed configuration (bench/configs/
deepseek-v3.2.ep32.json) cut to small widths, with weights drawn by the
benchmark, and its logits are compared with the benchmark's plain float32
reference (bench/reference/mla.py): both sides compute in float32, so
only the order of summation differs (the absorbed form against the plain
one, blocked softmaxes), and 1e-4 of the largest logit leaves that room
many times over while a wrong mask, scale or rotation reads ~1e-1.
"""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import flops, program, weights                    # noqa: E402
from bench.registry import Registry                          # noqa: E402
from bench.tests.small_mla import small_engine, small_mla    # noqa: E402
from repro.configs.registry import get_config                # noqa: E402
from repro.models import transformer                         # noqa: E402
from repro.models.api import build_model                     # noqa: E402
from repro.models.layers import (held_experts_mlp,           # noqa: E402
                                 route_grouped_sigmoid, swiglu_mlp)
from repro.serve import DecodeEngine, Request                # noqa: E402

SEED = 2**31 + 3
RNG = np.random.default_rng(5)
STEP_SCOPES = ("step.qkv", "step.kv_write", "step.indexer", "step.topk",
               "step.attention", "step.mlp", "step.head")


def _serve(spec, max_len, prompts, new):
    flat = weights.make_flat(spec, SEED)
    eng = program.build_engine(spec, small_engine(max_len=max_len),
                               weights.program_params(flat, spec))
    eng.record_logits = True
    reqs = [program.request(u, p, new) for u, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    while not eng.idle():
        eng.tick()
    return flat, reqs, eng


@pytest.mark.parametrize("gate", ["sparse", "closed"])
def test_engine_matches_reference(gate):
    """Prefill, then decode through the latent page pool, against the
    reference's full forward over prompt and served tokens. Sparse:
    max_len 128 passes min_n 16 and contexts of 33-60 exceed K=16, so the
    V3.2 indexer and the exact Top-K choose the rows; closed: min_n 128
    keeps the gate shut and attention reads every position."""
    spec = small_mla(min_n=16 if gate == "sparse" else 128)
    prompts = [RNG.integers(0, spec["vocab"], (n,)) for n in (40, 47, 33, 52)]
    flat, reqs, eng = _serve(spec, 128, prompts, 8)
    assert all(len(r.generated) == 8 for r in reqs)
    seqs = [np.concatenate([r.prompt, r.generated[:-1]]) for r in reqs]
    rows = [np.arange(len(r.prompt) - 1, len(s)) for r, s in zip(reqs, seqs)]
    got = np.concatenate([np.stack(r.logits_log) for r in reqs])
    want = np.asarray(Registry().reference("mla").logits(
        spec, flat, seqs, rows, sparse=flops.sparse_cell(spec, 128),
        pad_to=64))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err
    methods = {m for entries in eng.method_log.values() for *_, m in entries}
    assert methods <= ({"gvr", "radix"} if gate == "sparse" else {"dense"})


def _layer_and_rows(held, first):
    """The first expert layer of the small configuration with `held`
    experts from `first`, drawn from one seed, and rows to route."""
    spec = small_mla(num_experts=held, first_expert=first)
    flat = weights.make_flat(spec, SEED)
    p = {k[len("moe_"):]: v[0] for k, v in flat.items()
         if k.startswith("moe_")}
    cfg = program.model_config(spec)
    h = jnp.asarray(RNG.standard_normal((24, spec["d_model"])), jnp.float32)
    return cfg, p, h


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of 4 experts each (EP4 over 16): the routed parts each
    computes, plus the shared expert once, add up to the layer that holds
    every expert. Float32 on both sides, sums in another order: 1e-5."""
    cfg, whole, h = _layer_and_rows(16, 0)
    with jax.default_matmul_precision("highest"):
        want, held_all = transformer._mla_moe(whole, h, cfg)
        total = swiglu_mlp(h, whole["shared_gate"], whole["shared_up"],
                           whole["shared_down"])
        held = 0
        for first in (0, 4, 8, 12):
            cfg_c, p, _ = _layer_and_rows(4, first)
            y, rows = transformer._mla_moe(p, h, cfg_c)
            total = total + y - swiglu_mlp(h, p["shared_gate"],
                                           p["shared_up"], p["shared_down"])
            held = held + rows
    err = np.abs(np.asarray(total - want)).max() / np.abs(
        np.asarray(want)).max()
    assert err < 1e-5, err
    np.testing.assert_array_equal(np.asarray(held), np.asarray(held_all))
    assert (np.asarray(held_all) == cfg.moe.top_k).all()


def _noaux_tc(logits, bias, top_k, n_group, topk_group, scaling):
    """DeepSeek-V3's noaux_tc, row by row in plain numpy (float64)."""
    out_idx, out_g = [], []
    for row in logits.astype(np.float64):
        s = 1.0 / (1.0 + np.exp(-row))
        choice = s + bias
        groups = choice.reshape(n_group, -1)
        gscore = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        keep = np.argsort(-gscore, kind="stable")[:topk_group]
        masked = np.full_like(groups, -np.inf)
        masked[keep] = groups[keep]
        idx = np.argsort(-masked.reshape(-1), kind="stable")[:top_k]
        g = s[idx] / s[idx].sum() * scaling
        out_idx.append(idx)
        out_g.append(g)
    return np.array(out_idx), np.array(out_g)


def test_grouped_sigmoid_routing_matches_numpy():
    """Biased random logits: the same experts in the same order, the same
    gates (float32 against float64)."""
    t, d, e = 64, 32, 256
    x = RNG.standard_normal((t, d)).astype(np.float32)
    w = (RNG.standard_normal((d, e)) * d ** -0.5).astype(np.float32)
    bias = (0.1 * RNG.standard_normal(e)).astype(np.float32)
    idx, gates = route_grouped_sigmoid(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias), top_k=8,
        n_group=8, topk_group=4, routed_scaling=2.5)
    want_idx, want_g = _noaux_tc(x.astype(np.float64) @ w, bias, 8, 8, 4,
                                 2.5)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(gates), want_g, rtol=1e-5)


def test_held_rows_count_the_assignments_to_held_experts():
    """Each row's count is its routed experts that fall in the held range,
    and the held experts' output weighs each by its gate (zero elsewhere)."""
    t, d, f, held, first = 16, 8, 4, 4, 8
    idx = jnp.asarray(RNG.integers(0, 16, (t, 4)), jnp.int32)
    gates = jnp.asarray(RNG.random((t, 4)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((t, d)), jnp.float32)
    wg, wu = (jnp.asarray(RNG.standard_normal((held, d, f)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(RNG.standard_normal((held, f, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, rows = held_experts_mlp(x, idx, gates, wg, wu, wd,
                                   first_expert=first)
        want = np.zeros((t, d), np.float32)
        for i in range(t):
            for j in range(4):
                e = int(idx[i, j]) - first
                if 0 <= e < held:
                    want[i] += float(gates[i, j]) * np.asarray(
                        swiglu_mlp(x[i], wg[e], wu[e], wd[e]))
    local = np.asarray(idx) - first
    np.testing.assert_array_equal(
        np.asarray(rows), ((local >= 0) & (local < held)).sum(axis=1))
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)


def test_compiled_step_carries_every_scope_and_the_expert_scope():
    """The latent step names every stage, and the routed experts sit in
    `step.experts` inside `step.mlp`."""
    spec = small_mla()
    flat = weights.make_flat(spec, SEED)
    eng = program.build_engine(spec, small_engine(),
                               weights.program_params(flat, spec))
    hlo = program.step_hlo(eng)
    assert {s for s in STEP_SCOPES if f"/{s}/" in hlo} == set(STEP_SCOPES)
    assert "/step.mlp/step.experts/" in hlo
    stacks = re.findall(r'op_name="([^"]*step\.experts/[^"]*)"', hlo)
    assert stacks and all("step.mlp/step.experts/" in s for s in stacks)


def test_held_expert_rows_count_every_routed_assignment():
    """Holding every expert, each step's active row adds top_k per expert
    layer: over a run, top_k × expert layers × the tokens the steps took
    (each prompt token, then each generated token but the last). The
    selector's counters come alongside."""
    cfg = get_config("deepseek-v3.2", smoke=True)
    model = build_model(cfg)
    eng = DecodeEngine(model, model.init_params(jax.random.PRNGKey(0)),
                       num_slots=2, max_len=64, kv_layout="paged",
                       page_size=16, prefill_chunk=4)
    reqs = [Request(uid=i, prompt=RNG.integers(0, cfg.vocab, (p,)),
                    max_new_tokens=6) for i, p in enumerate((7, 12))]
    for r in reqs:
        eng.submit(r)
    eng.counters()
    while not eng.idle():
        eng.tick()
    got = eng.counters()
    steps = sum(len(r.prompt) + len(r.generated) - 1 for r in reqs)
    n_moe = cfg.n_layers - cfg.first_k_dense
    assert got["held_expert_rows"] == cfg.moe.top_k * n_moe * steps
    assert got["gvr_row_layers"] + got["radix_row_layers"] >= (
        cfg.n_layers * steps)


@pytest.mark.parametrize("body", ["serve_step", "serve_step_sp_paged",
                                  "serve_step_spec_paged/scan",
                                  "serve_step_spec_paged/mq",
                                  "serve_step_sp_spec_paged"])
def test_other_decode_bodies_refuse_mla(body):
    """The bodies without a latent form say which they are."""
    cfg = get_config("deepseek-v3.2", smoke=True)
    name, _, kernel = body.partition("/")
    fn = getattr(transformer, name)
    kw = {"verify_kernel": kernel} if kernel else {}
    if "spec" in name:
        kw.update(draft_len=0, max_accept=0)
    if "sp_" in name:
        kw["mesh"] = None
    with pytest.raises(ValueError, match=name):
        fn({}, {}, jnp.zeros((1,), jnp.int32), cfg, **kw)


@pytest.mark.parametrize("field", [
    dict(moe=dict(total_experts=64)), dict(moe=dict(first_expert=8)),
    dict(moe=dict(shared_d_ff=512)), dict(moe=dict(n_group=4)),
    dict(moe=dict(topk_group=2)), dict(moe=dict(routed_scaling=2.5)),
    dict(rope_kind="yarn")], ids=lambda f: str(f).translate(
        str.maketrans("", "", "{}' :")))
def test_mla_expert_and_yarn_fields_refused_elsewhere(field):
    """Only the MLA model reads the expert share, the shared expert, the
    grouped routing and YaRN: an expert layer that routes by softmax would
    leave them unread, so its configuration refuses them."""
    import dataclasses
    cfg = get_config("granite-moe-1b-a400m")
    change = dict(field)
    if "moe" in change:
        change["moe"] = dataclasses.replace(cfg.moe, **change["moe"])
    with pytest.raises(ValueError, match="multi-head latent attention"):
        dataclasses.replace(cfg, **change)
