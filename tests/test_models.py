"""Per-arch smoke tests (reduced configs): train step + decode steps on CPU.

Asserts output shapes, finiteness, loss decrease over a few steps, and the
DSA feedback loop (prev-Top-K carried across decode steps)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import all_archs, get_config
from repro.models.api import build_model, supported_shapes
from repro.optim import adamw

RNG = np.random.default_rng(3)


def _batch(cfg, b=2, s=32):
    tok = np.stack([np.roll(np.arange(s) % min(cfg.vocab, 97), r)
                    for r in range(b)]).astype(np.int32)
    batch = {"tokens": jnp.asarray(tok),
             "targets": jnp.asarray(np.roll(tok, -1, axis=1))}
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            RNG.standard_normal((b, cfg.encoder_frames, cfg.d_model)), jnp.float32)
    if cfg.num_patches:
        batch["patch_embeds"] = jnp.asarray(
            RNG.standard_normal((b, cfg.num_patches, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", all_archs())
def test_arch_smoke_train_and_decode(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss = model.loss_fn(params, batch)
    assert loss.shape == () and bool(jnp.isfinite(loss)), arch

    # a few decode steps with the cache exercised past the DSA gate; an
    # MLA config decodes through its served body, the paged step
    b, max_len = 2, 64
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (20, b)), jnp.int32)
    if cfg.is_mla:
        page = 16
        state = model.init_paged_decode_state(
            b, max_len, num_pages=b * max_len // page, page_size=page)
        state["page_table"] = jnp.arange(
            b * max_len // page, dtype=jnp.int32).reshape(b, -1)

        def step(state, t):
            logits, state = model.serve_step_paged(params, state, t)
            return state, logits
    else:
        state = model.init_decode_state(batch=b, max_len=max_len)

        def step(state, t):
            logits, state = model.serve_step(params, state, t)
            return state, logits

    state, logits = jax.lax.scan(step, state, toks)
    assert logits.shape == (20, b, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), arch
    np.testing.assert_array_equal(np.asarray(state["length"]), [20, 20])
    if cfg.dsa.enabled:
        pt = np.asarray(state["prev_topk"])
        assert pt.min() >= 0 and pt.max() < max_len


@pytest.mark.parametrize("arch", ["llama3.2-1b", "granite-moe-1b-a400m",
                                  "rwkv6-3b", "jamba-1.5-large-398b"])
def test_arch_loss_decreases(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    opt = adamw.init(params)
    ocfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=1, total_steps=100)
    batch = _batch(cfg, b=4, s=32)

    @jax.jit
    def step(params, opt):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss_fn(p, batch))(params)
        params, opt, _ = adamw.update(grads, opt, params, ocfg)
        return params, opt, loss

    losses = []
    for _ in range(8):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_dsa_feedback_improves_overlap():
    """After enough decode steps, consecutive Top-K sets overlap far above
    the random baseline (paper Fig. 3 behavior, toy scale).

    The decode is greedy and self-feeding — the paper's temporal-correlation
    claim is about autoregressive decode traffic, where consecutive queries
    are correlated. (Teacher-forcing i.i.d. random tokens destroys exactly
    the signal under test: each step then queries with an unrelated
    embedding and the overlap collapses to — or below — chance.)"""
    from repro.core.temporal import hit_ratio
    cfg = get_config("llama3.2-1b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(2))
    b, max_len = 2, 96
    state = model.init_decode_state(batch=b, max_len=max_len)
    step = jax.jit(lambda p, s, tk: model.serve_step(p, s, tk))

    tok = jnp.asarray(RNG.integers(0, cfg.vocab, (b,)), jnp.int32)
    prevs = []
    for t in range(40):
        logits, state = step(params, state, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)   # greedy self-feed
        prevs.append(np.asarray(state["prev_topk"][0]))   # layer 0
    k = prevs[-1].shape[-1]
    hr = float(np.mean(np.asarray(hit_ratio(
        jnp.asarray(prevs[-1]), jnp.asarray(prevs[-2]), max_len))))
    # with a 40-token cache and k=16 the random baseline is k/len = 0.4;
    # temporal correlation must clear it (toy scale: margin is modest)
    assert hr > (k / 40) + 0.05, hr


def test_supported_shapes_policy():
    for arch in all_archs():
        cfg = get_config(arch)
        shapes = supported_shapes(cfg)
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in shapes
        else:
            assert "long_500k" not in shapes


def test_param_counts_match_published_scale():
    """Sanity: full configs land near their published parameter counts."""
    expect = {"llama3.2-1b": (1.0e9, 2.1e9), "granite-34b": (30e9, 55e9),
              "chatglm3-6b": (5e9, 9e9), "jamba-1.5-large-398b": (350e9, 450e9),
              "rwkv6-3b": (2.5e9, 4e9), "moonshot-v1-16b-a3b": (14e9, 30e9),
              "deepseek-v3.2": (650e9, 700e9)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)
