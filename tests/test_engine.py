"""Continuous-batching engine: scheduler invariants, cold→warm dispatch,
bit-exactness vs single-request decode, feedback recycle hygiene."""

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.api import build_model
from repro.serve import (DECODE, DONE, DecodeEngine, FIFOScheduler,
                         LongestContextFirstScheduler, Request,
                         make_scheduler)

MAX_LEN = 64
RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("llama3.2-1b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("prefill_chunk", 4)
    return DecodeEngine(model, params, **kw)


def _reqs(cfg, specs):
    """specs: list of (prompt_len, max_new, arrival)."""
    return [Request(uid=i, prompt=RNG.integers(0, cfg.vocab, (p,)),
                    max_new_tokens=m, arrival=a)
            for i, (p, m, a) in enumerate(specs)]


# ---------------- scheduler policies (host-side, no model) ----------------

class _R:
    def __init__(self, uid, plen, arrival=0):
        self.uid, self.prompt, self.arrival = uid, np.zeros(plen), arrival


def test_fifo_policy_order():
    s = FIFOScheduler()
    for r in [_R(0, 5), _R(1, 50), _R(2, 1)]:
        s.submit(r)
    assert [s.pick().uid for _ in range(3)] == [0, 1, 2]
    assert s.pick() is None


def test_longest_context_first_policy():
    s = LongestContextFirstScheduler()
    for r in [_R(0, 5), _R(1, 50), _R(2, 30)]:
        s.submit(r)
    assert [s.pick().uid for _ in range(3)] == [1, 2, 0]


def test_arrival_gating():
    s = FIFOScheduler()
    s.submit(_R(0, 5, arrival=10))
    assert s.pick(now=3) is None
    assert s.pick(now=10).uid == 0


def test_unknown_policy_raises():
    with pytest.raises(ValueError):
        make_scheduler("banana")


# ---------------- engine lifecycle invariants -----------------------------

def test_no_slot_leak_and_completion(model_and_params):
    cfg, model, params = model_and_params
    eng = _engine(model, params, num_slots=2)
    reqs = _reqs(cfg, [(5, 4, 0), (9, 3, 0), (3, 5, 2), (7, 2, 4)])
    rep = eng.run(reqs, max_ticks=500)
    assert rep.completed == len(reqs)
    assert all(r.phase == DONE for r in reqs)
    assert all(s is None for s in eng.slots)            # no slot leak
    assert eng.pool.admissions == len(reqs)
    assert eng.pool.evictions == len(reqs)
    assert all(len(r.generated) == r.max_new_tokens for r in reqs)


def test_fifo_fairness_in_engine(model_and_params):
    """With one slot and simultaneous arrivals, FIFO must admit (and hence
    finish) strictly in submission order."""
    cfg, model, params = model_and_params
    eng = _engine(model, params, num_slots=1, scheduler="fifo")
    reqs = _reqs(cfg, [(6, 2, 0), (4, 2, 0), (8, 2, 0)])
    eng.run(reqs, max_ticks=500)
    admits = [r.admitted_at for r in reqs]
    assert admits == sorted(admits)
    assert [r.uid for r in sorted(reqs, key=lambda r: r.admitted_at)] == [0, 1, 2]


def test_finished_slots_never_decoded(model_and_params):
    """After a request retires with nothing queued, its slot's state must be
    frozen: further ticks never advance the freed slot's length."""
    cfg, model, params = model_and_params
    eng = _engine(model, params, num_slots=2)
    reqs = _reqs(cfg, [(5, 3, 0), (5, 12, 0)])          # req0 retires early
    for r in reqs:
        eng.submit(r)
    while reqs[0].phase != DONE:
        eng.tick()
    slot0 = reqs[0].slot
    frozen = int(np.asarray(eng.state["length"])[slot0])
    for _ in range(4):                                   # req1 keeps decoding
        eng.tick()
        assert int(np.asarray(eng.state["length"])[slot0]) == frozen
    eng.run(max_ticks=500)
    assert reqs[1].phase == DONE
    assert len(reqs[0].generated) == 3                   # never grew post-DONE


# ---------------- cold→warm selector dispatch -----------------------------

def test_cold_admission_falls_back_then_flips_to_gvr(model_and_params):
    """A freshly admitted slot has no prediction history: its first tick
    must be served by a non-GVR path, and by GVR within 2 ticks."""
    cfg, model, params = model_and_params
    eng = _engine(model, params, num_slots=2)
    reqs = _reqs(cfg, [(6, 4, 0), (10, 4, 0), (6, 4, 3)])  # uid2 mid-stream
    eng.run(reqs, max_ticks=500)
    for r in reqs:
        methods = [m for _, _, m in eng.method_log[r.uid]]
        assert methods[0] != "gvr", (r.uid, methods)     # cold first tick
        assert methods[0] in ("radix", "exact")
        assert "gvr" in methods[:2], (r.uid, methods)    # warm within 2 ticks
        assert all(m == "gvr" for m in methods[1:]), (r.uid, methods)
    # uid2 was admitted mid-stream, while uid0/uid1 were already decoding
    assert reqs[2].admitted_at > 0


# ---------------- bit-exactness vs single-request decode ------------------

def test_engine_bit_identical_to_solo_decode(model_and_params):
    """Ragged pool with staggered admissions vs each request decoded alone:
    tokens must match exactly and full logits to float32 rounding.

    Decode is row-parallel, but XLA picks a different float32 reduction
    order for a batch of 3 than for a batch of 1, so logits may differ in
    the last bits (observed: 2.3e-5 absolute on logits of magnitude ~20).
    The bound is 1e-5 of the row's largest logit plus 1e-5 relative —
    about 10x the observed drift, far below any gap argmax could flip on."""
    cfg, model, params = model_and_params
    prompts = [RNG.integers(0, cfg.vocab, (p,)) for p in (5, 9, 12)]

    eng = _engine(model, params, num_slots=3, record_logits=True)
    multi = [Request(uid=i, prompt=p, max_new_tokens=6, arrival=3 * i)
             for i, p in enumerate(prompts)]
    eng.run(multi, max_ticks=500)

    for i, p in enumerate(prompts):
        solo_eng = _engine(model, params, num_slots=1, record_logits=True)
        solo = Request(uid=0, prompt=p, max_new_tokens=6)
        solo_eng.run([solo], max_ticks=500)
        assert multi[i].generated == solo.generated, i
        assert len(multi[i].logits_log) == len(solo.logits_log)
        for lm, ls in zip(multi[i].logits_log, solo.logits_log):
            np.testing.assert_allclose(lm, ls, rtol=1e-5,
                                       atol=1e-5 * np.abs(ls).max())


def test_engine_matches_raw_serve_step_loop(model_and_params):
    """Independent reference: feed the prompt token-by-token through a raw
    batch-1 serve_step loop and greedy-decode — the engine (with other
    requests in flight) must reproduce it exactly."""
    import jax.numpy as jnp
    cfg, model, params = model_and_params
    prompt = RNG.integers(0, cfg.vocab, (7,))

    state = model.init_decode_state(batch=1, max_len=MAX_LEN)
    step = jax.jit(lambda p, s, t: model.serve_step(p, s, t))
    logits = None
    for t in prompt:
        logits, state = step(params, state, jnp.asarray([t], jnp.int32))
    ref = [int(jnp.argmax(logits[0]))]
    for _ in range(5):
        logits, state = step(params, state,
                             jnp.asarray([ref[-1]], jnp.int32))
        ref.append(int(jnp.argmax(logits[0])))

    eng = _engine(model, params, num_slots=2)
    reqs = [Request(uid=0, prompt=prompt, max_new_tokens=6),
            Request(uid=1, prompt=RNG.integers(0, cfg.vocab, (11,)),
                    max_new_tokens=6)]
    eng.run(reqs, max_ticks=500)
    assert reqs[0].generated == ref


# ---------------- feedback recycle regression -----------------------------

def test_recycled_slot_never_references_evicted_indices(model_and_params):
    """Evict a long request, admit a short one into the same slot: at no
    point may the new request's prediction rows contain indices that only
    existed in the evicted request's context (>= the slot's live extent)."""
    cfg, model, params = model_and_params
    k_sel = min(cfg.dsa.k, MAX_LEN)
    eng = _engine(model, params, num_slots=1, prefill_chunk=8)

    long_req = Request(uid=0, prompt=RNG.integers(0, cfg.vocab, (40,)),
                       max_new_tokens=3)
    eng.submit(long_req)
    while long_req.phase != DONE:
        eng.tick()
    # eviction poisons the slot's prediction rows outright
    assert np.all(np.asarray(eng.state["prev_topk"][:, 0]) == -1)
    assert not np.any(np.asarray(eng.state["topk_valid"][:, 0]))

    short_req = Request(uid=1, prompt=RNG.integers(0, cfg.vocab, (6,)),
                        max_new_tokens=4)
    eng.submit(short_req)
    while short_req.phase != DONE:
        eng.tick()
        if short_req.slot is None:
            continue
        pt = np.asarray(eng.state["prev_topk"][:, 0])
        length = int(np.asarray(eng.state["length"])[0])
        # live extent: real feedback < length; sentinel-tie filler < k_sel;
        # the even-spacing seed < prompt_len. The evicted request's context
        # reached index 42 — any index >= this bound is a leak.
        bound = max(length, k_sel, len(short_req.prompt))
        assert pt.max() < bound, (pt.max(), bound)
    # the long request really did have feedback beyond that bound
    assert 40 + 3 > max(len(short_req.prompt) + 4, k_sel)
