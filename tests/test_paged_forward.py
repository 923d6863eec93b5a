"""The paged forward over Q query rows, at Q = 1.

`serve_step_paged` (a decode step) and the multi-query verify tick
(`serve_step_spec_paged(verify_kernel="mq")`) are calls of one paged
forward over Q rows a slot. A verify tick with no draft is its Q = 1 call,
so it must leave exactly what one decode step leaves: logits, page pools,
feedback, telemetry and counts, bit for bit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.api import build_model

SLOTS, MAX_LEN, PAGE = 2, 64, 8


def _config(gate):
    if gate == "open":           # indexer, Top-K and sparse attention
        return get_config("llama3.2-1b", smoke=True)
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    # dense fallback and the expert block
    return dataclasses.replace(cfg, dsa=dataclasses.replace(cfg.dsa,
                                                            min_n=4096))


def _warm_state(model, params, rng):
    """Both slots' pages mapped, 12 tokens decoded; slot 1's feedback then
    marked cold, so that the compared step mixes a warm and a cold row."""
    mp = MAX_LEN // PAGE
    state = model.init_paged_decode_state(SLOTS, MAX_LEN,
                                          num_pages=SLOTS * mp,
                                          page_size=PAGE)
    state["page_table"] = jnp.arange(SLOTS * mp, dtype=jnp.int32).reshape(
        SLOTS, mp)
    step = jax.jit(model.serve_step_paged)
    for _ in range(12):
        tokens = jnp.asarray(rng.integers(1, model.cfg.vocab, SLOTS),
                             jnp.int32)
        _, state = step(params, state, tokens)
    if "topk_valid" in state:
        state["topk_valid"] = state["topk_valid"].at[:, 1].set(False)
    return state


@pytest.mark.parametrize("gate", ["open", "closed"])
def test_mq_verify_of_one_row_is_the_decode_step(gate):
    cfg = _config(gate)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    state = _warm_state(model, params, rng)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, SLOTS), jnp.int32)
    mwp = jnp.zeros((SLOTS,), jnp.int32)
    none = jnp.zeros((SLOTS,), jnp.int32)

    logits, step_state, counts = jax.jit(
        lambda p, s, t: model.serve_step_paged(
            p, s, t, min_write_pos=mwp, with_counts=True))(
                params, state, tokens)
    out_tokens, accept, logits_all, _, v_counts, v_state = jax.jit(
        lambda p, s, t: model.serve_step_spec_paged(
            p, s, t, draft_len=none, max_accept=none, min_write_pos=mwp,
            verify_kernel="mq"))(params, state, tokens[:, None])

    np.testing.assert_array_equal(np.asarray(logits_all[:, 0]),
                                  np.asarray(logits))
    np.testing.assert_array_equal(np.asarray(out_tokens[:, 0]),
                                  np.asarray(jnp.argmax(logits, axis=-1)))
    np.testing.assert_array_equal(np.asarray(accept), 0)
    np.testing.assert_array_equal(np.asarray(v_counts), np.asarray(counts))
    assert set(v_state) == set(step_state)
    for key in step_state:
        np.testing.assert_array_equal(np.asarray(v_state[key]),
                                      np.asarray(step_state[key]),
                                      err_msg=key)
    if gate == "open":
        # the compared step ran the sparse path on a warm and a cold row
        assert np.asarray(step_state["sel_gvr"]).any()
        assert np.asarray(counts)[:, 3].any()
