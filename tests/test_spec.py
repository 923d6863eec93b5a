"""Speculative decoding subsystem (serve.spec): drafters, verify-tick
acceptance invariance, exact rollback, multi-query kernels.

The load-bearing claim (DESIGN.md §spec-decode): for greedy decoding, spec
mode emits BIT-IDENTICAL tokens / method log / GVR hit rate / logits to
non-speculative decode for every draft trace — perfect, partial, or fully
rejected — and the page rollback leaves block tables and ref-counts
exactly where non-speculative decode would hold them. Pinned here at
engine level (single-device fused; sharded meshes in the subprocess test)
and as a property over page sizes × spec depths × corruption patterns ×
warm/cold rows.
"""

import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs.registry import get_config
from repro.models.api import build_model
from repro.serve import (DECODE, DecodeEngine, NgramDrafter, PagedKVManager,
                         ReplayDrafter, Request, ScriptedDrafter,
                         ShardedPagedKVManager)

MAX_LEN = 64
RNG = np.random.default_rng(11)


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
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("page_size", 8)
    return DecodeEngine(model, params, **kw)


def _methods(eng, reqs):
    """Per-request (phase, method) sequence — tick numbers compress under
    spec mode (several accepted positions share one engine tick), so the
    invariance claim is over the SEQUENCE of selector decisions."""
    return {r.uid: [(ph, m) for _, ph, m in eng.method_log[r.uid]]
            for r in reqs}


# ---------------- drafter units (host-side, no model) ----------------------


class _Req:
    def __init__(self, uid, prompt, generated=()):
        self.uid = uid
        self.prompt = np.asarray(prompt, np.int32)
        self.generated = list(generated)


def test_ngram_drafter_matches_most_recent_occurrence():
    d = NgramDrafter(max_ngram=2)
    # context ... [7 8] 9 1 ... [7 8] -> the trailing bigram's most recent
    # earlier occurrence is followed by 9 1
    req = _Req(0, [5, 7, 8, 9, 1, 2, 7, 8])
    assert d.draft(req, 2) == [9, 1]
    assert d.draft(req, 4) == [9, 1, 2, 7]     # continuation keeps flowing
    # no repeated suffix anywhere -> no draft
    assert NgramDrafter(max_ngram=3, min_ngram=2).draft(
        _Req(1, [1, 2, 3, 4, 5]), 4) == []


def test_ngram_drafter_prefers_longer_ngrams():
    # bigram [3 4] recurs with continuation 9; unigram [4] also recurs
    # earlier with continuation 7 — the longer match must win
    d = NgramDrafter(max_ngram=2)
    req = _Req(0, [4, 7, 3, 4, 9, 3, 4])
    assert d.draft(req, 1) == [9]


def test_replay_and_scripted_drafters():
    r = ReplayDrafter({0: [10, 11, 12, 13]})
    req = _Req(0, [1, 2], generated=[10, 11])
    assert r.draft(req, 3) == [12, 13]          # indexed by generated count
    assert r.draft(_Req(9, [1]), 3) == []       # unknown uid: no draft
    s = ScriptedDrafter(lambda rq, d: [1] * 10)
    assert s.draft(req, 3) == [1, 1, 1]         # clamped to depth


def test_request_spec_depth_validation():
    with pytest.raises(ValueError, match="spec_depth"):
        Request(uid=0, prompt=np.ones(3, np.int32), spec_depth=-1)


def test_spec_requires_paged_layout(model_and_params):
    cfg, model, params = model_and_params
    with pytest.raises(ValueError, match="paged"):
        DecodeEngine(model, params, num_slots=2, max_len=MAX_LEN,
                     kv_layout="dense", spec_depth=2)


# ---------------- manager rollback + unified admission core ----------------


def test_admission_core_is_shared():
    """The ROADMAP open item: the probe→match→map admission core must be
    ONE implementation, owner-routed — not two drifting copies (the
    doomed-admission fix had to land twice). Pin the unification itself."""
    assert PagedKVManager.admit is ShardedPagedKVManager.admit
    assert PagedKVManager.rewind_slot is ShardedPagedKVManager.rewind_slot


def test_rewind_slot_frees_pages_beyond_keep_len():
    kv = PagedKVManager(num_slots=1, max_len=64, page_size=8, num_pages=8)
    assert kv.admit(0, np.arange(10, dtype=np.int32)) is not None  # 2 pages
    for pos in (16, 24, 32):                     # map 3 more (spec window)
        kv.ensure_mapped(0, pos)
    assert kv.pages_in_use == 5
    kv.dirty = False
    # accepted prefix = 18 tokens -> keep pages 0..2, free pages 3..4
    assert kv.rewind_slot(0, 18) == 2
    assert kv.pages_in_use == 3
    assert kv.dirty
    assert kv.tables[0].mapped() == kv.tables[0].row[:3].tolist()
    kv.pool.assert_consistent()
    # idempotent: nothing left beyond the keep point
    assert kv.rewind_slot(0, 18) == 0


def test_rewind_slot_routes_to_owner_shards():
    kv = ShardedPagedKVManager(num_slots=1, max_len=64, page_size=8,
                               num_pages_per_shard=4, seq_shards=2)
    assert kv.admit(0, np.arange(20, dtype=np.int32)) is not None  # 3 pages
    kv.ensure_mapped(0, 24)                      # shard 0's last page
    kv.ensure_mapped(0, 32)                      # first shard-1 page
    assert [p.pages_in_use for p in kv.pools] == [4, 1]
    assert kv.rewind_slot(0, 21) == 2            # keep pages 0..2
    assert [p.pages_in_use for p in kv.pools] == [3, 0]
    kv.assert_consistent()


def test_pages_in_shard_counts_owner_pages():
    kv = ShardedPagedKVManager(num_slots=2, max_len=64, page_size=8,
                               num_pages_per_shard=4, seq_shards=2)
    assert kv.admit(0, np.arange(40, dtype=np.int32)) is not None  # 5 pages
    assert kv.pages_in_shard(0, 0) == 4
    assert kv.pages_in_shard(0, 1) == 1
    assert kv.pages_in_shard(0, None) == 5
    assert kv.pages_in_shard(1, 0) == 0


# ---------------- engine-level acceptance invariance -----------------------


def _trace(cfg, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(uid=0, prompt=rng.integers(0, cfg.vocab, (9,)),
                    max_new_tokens=8),
            Request(uid=1, prompt=rng.integers(0, cfg.vocab, (14,)),
                    max_new_tokens=6, arrival=2),
            Request(uid=2, prompt=rng.integers(0, cfg.vocab, (5,)),
                    max_new_tokens=7, arrival=5)]


@pytest.fixture(scope="module")
def nonspec_reference(model_and_params):
    cfg, model, params = model_and_params
    eng = _engine(model, params, record_logits=True)
    reqs = _trace(cfg)
    rep = eng.run(reqs, max_ticks=500)
    assert rep.completed == len(reqs)
    return {
        "tokens": [list(r.generated) for r in reqs],
        "logits": [[np.asarray(l) for l in r.logits_log] for r in reqs],
        "methods": _methods(eng, reqs),
        "gvr": rep.gvr_hit_rate,
        "decode_counts": rep.decode_method_counts,
        "pages_in_use": eng.kv.pages_in_use,
    }


def _spec_run(model, params, cfg, drafter, depth, *, check_tables=False):
    eng = _engine(model, params, record_logits=True, spec_depth=depth,
                  drafter=drafter)
    reqs = _trace(cfg)
    for r in reqs:
        eng.submit(r)
    t0 = eng.tick_count
    while not eng.idle() and eng.tick_count - t0 < 500:
        eng.tick()
        if check_tables:
            _assert_nonspec_page_shape(eng)
    # driving tick() directly (for the per-tick table checks) bypasses
    # run()'s report; reconstruct the decode split from the method log
    decode_counts = {}
    for entries in eng.method_log.values():
        for _, ph, m in entries:
            if ph == DECODE:
                decode_counts[m] = decode_counts.get(m, 0) + 1
    total = sum(decode_counts.values())
    gvr = decode_counts.get("gvr", 0) / total if total else 0.0
    return eng, reqs, decode_counts, gvr


def _assert_nonspec_page_shape(eng):
    """After any engine tick, a DECODE slot's mapped logical pages must be
    exactly the contiguous range covering [0, length): the state a
    NON-speculative engine maintains tick by tick. A leaked speculative
    page (rewind bug) or a lost one breaks this immediately."""
    lengths = np.asarray(eng.state["length"])
    for s, req in enumerate(eng.slots):
        if req is None or req.phase != DECODE:
            continue
        length = int(lengths[s])
        want = list(range((length - 1) // eng.kv.page_size + 1))
        got = [lp for lp in range(eng.kv.pages_per_slot)
               if eng.kv.tables[s].get(lp) >= 0]
        assert got == want, (s, length, got, want)
    if hasattr(eng.kv, "pool"):
        eng.kv.pool.assert_consistent()
    else:
        eng.kv.assert_consistent()


@pytest.mark.slow
@pytest.mark.parametrize("depth", [1, 4])
def test_spec_replay_bit_identical_and_fewer_ticks(model_and_params,
                                                   nonspec_reference, depth):
    """Oracle replay drafts (100% acceptance): tokens, per-position method
    sequence, GVR hit rate and every logit must match the non-speculative
    run bit-for-bit, while the engine spends strictly fewer ticks."""
    cfg, model, params = model_and_params
    ref = nonspec_reference
    drafter = ReplayDrafter({i: t for i, t in enumerate(ref["tokens"])})
    eng, reqs, decode_counts, gvr = _spec_run(model, params, cfg, drafter,
                                              depth, check_tables=True)
    assert [list(r.generated) for r in reqs] == ref["tokens"]
    assert _methods(eng, reqs) == ref["methods"]
    assert decode_counts == ref["decode_counts"]
    assert gvr == ref["gvr"]
    for r, logits in zip(reqs, ref["logits"]):
        assert len(r.logits_log) == len(logits)
        for la, lb in zip(r.logits_log, logits):
            np.testing.assert_array_equal(la, lb)
    assert eng.spec_accepted == eng.spec_drafted > 0
    # drained engines hold the same residual pages (prefix cache only)
    assert eng.kv.pages_in_use == ref["pages_in_use"]


@pytest.mark.slow
def test_spec_rejection_bit_identical(model_and_params, nonspec_reference):
    """Fully-wrong and partially-wrong drafts: every rejection pattern
    must roll back to the exact non-speculative trajectory."""
    cfg, model, params = model_and_params
    ref = nonspec_reference
    cont = {i: t for i, t in enumerate(ref["tokens"])}

    wrong = ScriptedDrafter(
        lambda req, d: [(req.generated[-1] + 1) % cfg.vocab] * d)
    eng, reqs, decode_counts, gvr = _spec_run(model, params, cfg, wrong, 3,
                                              check_tables=True)
    assert [list(r.generated) for r in reqs] == ref["tokens"]
    assert _methods(eng, reqs) == ref["methods"]
    assert gvr == ref["gvr"]
    assert eng.spec_accepted == 0 and eng.spec_drafted > 0

    def partial(req, d):
        draft = list(cont[req.uid][len(req.generated):
                                   len(req.generated) + d])
        if len(draft) >= 2:            # corrupt the second position
            draft[1] = (draft[1] + 1) % cfg.vocab
        return draft
    eng, reqs, decode_counts, gvr = _spec_run(
        model, params, cfg, ScriptedDrafter(partial), 4, check_tables=True)
    assert [list(r.generated) for r in reqs] == ref["tokens"]
    assert _methods(eng, reqs) == ref["methods"]
    assert gvr == ref["gvr"]
    assert 0 < eng.spec_accepted < eng.spec_drafted


def test_spec_sampled_requests_decode_unspeculated(model_and_params):
    """Sampled requests verify with depth 0 (greedy-only speculation):
    their tokens must equal the non-speculative sampled run's, and no
    draft may ever be proposed for them."""
    cfg, model, params = model_and_params

    def mk():
        rng = np.random.default_rng(17)
        return [Request(uid=0, prompt=rng.integers(0, cfg.vocab, (7,)),
                        max_new_tokens=5, temperature=0.8, top_p=0.9),
                Request(uid=1, prompt=rng.integers(0, cfg.vocab, (9,)),
                        max_new_tokens=5)]

    base = _engine(model, params)
    rb = mk()
    base.run(rb, max_ticks=300)

    calls = []

    class Spy(ReplayDrafter):
        def draft(self, req, depth):
            calls.append(req.uid)
            return super().draft(req, depth)

    eng = _engine(model, params, spec_depth=3,
                  drafter=Spy({1: list(rb[1].generated)}))
    rs = mk()
    eng.run(rs, max_ticks=300)
    assert [r.generated for r in rs] == [r.generated for r in rb]
    assert 0 not in calls          # the sampled request never drafted
    assert 1 in calls


def test_spec_eos_truncates_acceptance(model_and_params):
    """A verify tick whose emission hits eos must stop AT the eos token —
    exactly where the non-speculative engine retires the request."""
    cfg, model, params = model_and_params
    prompt = RNG.integers(0, cfg.vocab, (6,))
    base = _engine(model, params, num_slots=1)
    rb = Request(uid=0, prompt=prompt, max_new_tokens=10)
    base.run([rb], max_ticks=300)
    assert len(rb.generated) >= 3
    # truncation point: the first position whose token's FIRST occurrence
    # it is (greedy traces from the random smoke model are repetitive, so
    # this is usually position 0 — the verify tick then has to cut a
    # full-accept draft of depth 6 down to a single emitted token)
    cut = next(i for i in range(len(rb.generated))
               if rb.generated[i] not in rb.generated[:i])
    eos = rb.generated[cut]
    for spec_eng in (
            _engine(model, params, num_slots=1, eos_id=eos),
            _engine(model, params, num_slots=1, eos_id=eos, spec_depth=6,
                    drafter=ReplayDrafter({0: list(rb.generated)}))):
        r = Request(uid=0, prompt=prompt, max_new_tokens=10)
        spec_eng.run([r], max_ticks=300)
        assert r.generated == rb.generated[:cut + 1], r.generated
        assert r.phase == "DONE"


@pytest.mark.slow
def test_model_drafter_self_speculation(model_and_params):
    """ModelDrafter wrapping the TARGET model itself drafts the exact
    greedy continuation — classic self-speculation: every draft accepts,
    and the engine still matches the non-speculative run bit for bit."""
    from repro.serve import ModelDrafter
    cfg, model, params = model_and_params

    def mk():
        rng = np.random.default_rng(23)
        return [Request(uid=0, prompt=rng.integers(0, cfg.vocab, (8,)),
                        max_new_tokens=6),
                Request(uid=1, prompt=rng.integers(0, cfg.vocab, (11,)),
                        max_new_tokens=5, arrival=3)]

    base = _engine(model, params)
    rb = mk()
    base.run(rb, max_ticks=300)

    drafter = ModelDrafter(model, params, max_len=MAX_LEN)
    eng = _engine(model, params, spec_depth=3, drafter=drafter)
    rs = mk()
    rep = eng.run(rs, max_ticks=300)
    assert [r.generated for r in rs] == [r.generated for r in rb]
    assert rep.spec_acceptance_rate == 1.0
    assert not drafter._ctx          # release() ran for every retirement


# ---------------- property: any accept/reject trace rolls back exactly ----


_PROP = {"uid": 5000, "spec": {}}


@pytest.fixture(scope="module", autouse=True)
def _prop_ctx(model_and_params):
    cfg, model, params = model_and_params
    _PROP.update(cfg=cfg, model=model, params=params, base={})
    yield


@pytest.mark.slow
@settings(max_examples=5, deadline=None)
@given(data=st.data())
def test_property_spec_replays_nonspec_exactly(data):
    """Randomized page sizes, spec depths, corruption patterns (position
    AND probability), ragged staggered arrivals (warm/cold rows), engine
    reuse across examples: every accept/reject trace must replay the
    non-speculative run bit-identically — tokens, method sequence, GVR
    hit rate — while each tick leaves the block tables / ref-counts in
    the exact non-speculative shape (checked tick by tick)."""
    cfg, model, params = _PROP["cfg"], _PROP["model"], _PROP["params"]
    page_size = data.draw(st.sampled_from([4, 8]), label="page_size")
    depth = data.draw(st.integers(1, 4), label="spec_depth")
    corrupt_at = data.draw(st.integers(0, 4), label="corrupt_at")
    corrupt_p = data.draw(st.floats(0.0, 1.0), label="corrupt_p")
    seed = data.draw(st.integers(0, 10_000), label="seed")
    rng = np.random.default_rng(seed)

    specs = []
    for _ in range(data.draw(st.integers(2, 3), label="n_req")):
        specs.append((rng.integers(0, cfg.vocab,
                                   (int(rng.integers(3, 20)),)),
                      int(rng.integers(2, 8)), int(rng.integers(0, 5))))

    def mk(uid0):
        return [Request(uid=uid0 + i, prompt=p, max_new_tokens=m, arrival=a)
                for i, (p, m, a) in enumerate(specs)]

    # one non-speculative twin per speculative engine: both see the same
    # request history, so their prefix caches (whose hits depend on the
    # page size and on which prompts came before) stay in step and the
    # method sequences are comparable
    key = (page_size, depth)
    if key not in _PROP["base"]:
        _PROP["base"][key] = _engine(model, params, page_size=page_size)
        _PROP["spec"][key] = _engine(model, params, page_size=page_size,
                                     spec_depth=depth)
    base = _PROP["base"][key]
    rb = mk(_PROP["uid"])
    base.run(rb, max_ticks=800)
    cont = {r.uid - _PROP["uid"]: list(r.generated) for r in rb}

    def draft_fn(req, d):
        c = cont[req.uid - _PROP["uid"] - 1000]
        draft = list(c[len(req.generated):len(req.generated) + d])
        # seeded per-call corruption: stable across engine reuse because
        # it depends only on the request's visible progress
        call_rng = np.random.default_rng(
            (seed, req.uid, len(req.generated)))
        if draft and call_rng.random() < corrupt_p:
            at = min(corrupt_at, len(draft) - 1)
            draft[at] = (draft[at] + 1) % cfg.vocab
        return draft

    eng = _PROP["spec"][key]
    eng.drafter = ScriptedDrafter(draft_fn)
    rs = mk(_PROP["uid"] + 1000)
    for r in rs:
        eng.submit(r)
    t0 = eng.tick_count
    while not eng.idle() and eng.tick_count - t0 < 800:
        eng.tick()
        _assert_nonspec_page_shape(eng)

    assert [r.generated for r in rs] == [r.generated for r in rb], \
        (page_size, depth, corrupt_at, corrupt_p)
    ms = {r.uid - _PROP["uid"] - 1000: [(p, m) for _, p, m
                                        in eng.method_log[r.uid]]
          for r in rs}
    mb = {r.uid - _PROP["uid"]: [(p, m) for _, p, m
                                 in base.method_log[r.uid]]
          for r in rb}
    assert ms == mb
    _PROP["uid"] += 2000


# ---------------- sharded verify (forced multi-device mesh) ----------------


_SP_SCRIPT = r"""
import jax, numpy as np, json
from repro.configs.registry import get_config
from repro.models.api import build_model
from repro.serve import DecodeEngine, Request, ReplayDrafter, ScriptedDrafter

cfg = get_config("llama3.2-1b", smoke=True)
model = build_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))

def mk(seed=3):
    rng = np.random.default_rng(seed)
    return [Request(uid=0, prompt=rng.integers(0, cfg.vocab, (20,)),
                    max_new_tokens=8),
            Request(uid=1, prompt=rng.integers(0, cfg.vocab, (12,)),
                    max_new_tokens=6, arrival=2)]

def run(drafter=None, depth=0, **kw):
    eng = DecodeEngine(model, params, num_slots=2, max_len=64,
                       prefill_chunk=4, kv_layout="paged", page_size=8,
                       spec_depth=depth, drafter=drafter, **kw)
    reqs = mk()
    rep = eng.run(reqs, max_ticks=500)
    if hasattr(eng.kv, "assert_consistent"):
        eng.kv.assert_consistent()
    return {
        "tokens": [r.generated for r in reqs],
        "methods": {str(r.uid): [(ph, m) for _, ph, m in
                                 eng.method_log[r.uid]] for r in reqs},
        "hit": rep.gvr_hit_rate,
        "accept": rep.spec_acceptance_rate,
        "ticks": rep.ticks,
    }

base = run(paged_attn="fused")
cont = {i: list(t) for i, t in enumerate(base["tokens"])}

def partial(req, d):
    c = cont[req.uid]
    draft = list(c[len(req.generated):len(req.generated) + d])
    if len(draft) >= 3:
        draft[2] = (draft[2] + 1) % cfg.vocab
    return draft

out = {"base": base,
       "replay_sp2": run(ReplayDrafter(cont), depth=3, seq_shards=2),
       "partial_sp2": run(ScriptedDrafter(partial), depth=3, seq_shards=2),
       "replay_single": run(ReplayDrafter(cont), depth=3)}
print("RESULT:" + json.dumps(out))
"""


from _mesh_compat import REPO_ROOT, forced_mesh_env, probe_forced_mesh


@pytest.fixture(scope="module")
def sp_spec_results():
    if not probe_forced_mesh(2):
        pytest.skip("runner cannot force a 2-device CPU mesh")
    r = subprocess.run([sys.executable, "-c", _SP_SCRIPT],
                       capture_output=True, text=True,
                       env=forced_mesh_env(2), timeout=900, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


@pytest.mark.mesh
@pytest.mark.slow
@pytest.mark.parametrize("leg", ["replay_sp2", "partial_sp2",
                                 "replay_single"])
def test_sp_spec_bit_identical_to_nonspec(sp_spec_results, leg):
    """Sequence-sharded verify ticks (and the single-device spec run, as
    the control) must reproduce the non-speculative single-device fused
    engine verbatim — tokens, method sequence, GVR hit rate — for both
    full-accept and mid-tick-rejection draft traces."""
    base, spec = sp_spec_results["base"], sp_spec_results[leg]
    assert spec["tokens"] == base["tokens"]
    assert spec["methods"] == base["methods"]
    assert spec["hit"] == base["hit"]
    if leg.startswith("replay"):
        assert spec["accept"] == 1.0
        assert spec["ticks"] < base["ticks"]
    else:
        assert 0.0 < spec["accept"] < 1.0


# ---------------- multi-query-row kernels ----------------------------------


def test_paged_attn_mq_matches_single_and_ref():
    from repro.kernels import (paged_sparse_decode_attn,
                               paged_sparse_decode_attn_mq)
    from repro.kernels.ref import paged_attn_mq_ref
    rng = np.random.default_rng(0)
    B, Q, H, KVH, D = 2, 3, 4, 2, 8
    P, PS, MP, K = 9, 8, 4, 8
    kp = rng.normal(size=(P, PS, KVH, D)).astype(np.float32)
    vp = rng.normal(size=(P, PS, KVH, D)).astype(np.float32)
    table = np.full((B, MP), -1, np.int32)
    table[0, :3] = [2, 0, 5]
    table[1, :4] = [1, 3, 4, 6]
    q = rng.normal(size=(B, Q, H, D)).astype(np.float32)
    idx = rng.integers(0, 24, size=(B, Q, K)).astype(np.int32)
    idx[0, 1, -2:] = -1
    out = np.asarray(paged_sparse_decode_attn_mq(q, kp, vp, table, idx))
    for qq in range(Q):
        single = paged_sparse_decode_attn(q[:, qq], kp, vp, table,
                                          idx[:, qq])
        np.testing.assert_allclose(out[:, qq], np.asarray(single),
                                   rtol=1e-6, atol=1e-6)
    import jax.numpy as jnp
    ref = paged_attn_mq_ref(jnp.asarray(q), jnp.asarray(kp),
                            jnp.asarray(vp), jnp.asarray(table),
                            jnp.asarray(idx))
    np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_paged_indexer_topk_mq_threads_feedback_across_rows():
    """The mq kernel's row q must equal the single-row kernel called
    sequentially with prev = row q-1's OUTPUT — the in-kernel form of the
    verify tick's causally-extended feedback."""
    from repro.kernels import paged_indexer_topk, paged_indexer_topk_mq
    rng = np.random.default_rng(1)
    B, Q, H, DI = 2, 3, 4, 8
    P, PS, MP, K = 9, 8, 4, 8
    ikp = rng.normal(size=(P, PS, DI)).astype(np.float32)
    w = np.abs(rng.normal(size=(H,))).astype(np.float32)
    table = np.full((B, MP), -1, np.int32)
    table[0, :3] = [2, 0, 5]
    table[1, :4] = [1, 3, 4, 6]
    q = rng.normal(size=(B, Q, H, DI)).astype(np.float32)
    prev = rng.integers(0, 20, size=(B, K)).astype(np.int32)
    lens = np.stack([np.arange(Q) + 15, np.arange(Q) + 20]).astype(np.int32)
    v_mq, i_mq, s_mq = paged_indexer_topk_mq(q, ikp, w, table, prev, K,
                                             lengths=lens)
    pv = prev
    for qq in range(Q):
        v1, i1, _ = paged_indexer_topk(q[:, qq], ikp, w, table, pv, K,
                                       lengths=lens[:, qq])
        np.testing.assert_array_equal(np.asarray(i_mq[:, qq]),
                                      np.asarray(i1), err_msg=f"q={qq}")
        np.testing.assert_array_equal(np.asarray(v_mq[:, qq]),
                                      np.asarray(v1))
        pv = np.asarray(i1)
    assert s_mq.shape == (B, Q, 8)


def test_dsa_paged_mq_form_matches_single_rows():
    from repro.sparse.dsa import (dsa_sparse_attention_paged,
                                  dsa_sparse_attention_paged_mq)
    rng = np.random.default_rng(2)
    B, Q, H, KVH, D = 2, 3, 4, 2, 8
    P, PS, MP, K = 9, 8, 4, 8
    kp = rng.normal(size=(P, PS, KVH, D)).astype(np.float32)
    vp = rng.normal(size=(P, PS, KVH, D)).astype(np.float32)
    table = np.full((B, MP), -1, np.int32)
    table[0, :3] = [2, 0, 5]
    table[1, :4] = [1, 3, 4, 6]
    q = rng.normal(size=(B, Q, H, D)).astype(np.float32)
    idx = rng.integers(0, 24, size=(B, Q, K)).astype(np.int32)
    lens = rng.integers(10, 24, size=(B, Q)).astype(np.int32)
    import jax.numpy as jnp
    mq = dsa_sparse_attention_paged_mq(jnp.asarray(q), jnp.asarray(kp),
                                       jnp.asarray(vp), jnp.asarray(table),
                                       jnp.asarray(idx), jnp.asarray(lens),
                                       scale=0.35)
    for qq in range(Q):
        single = dsa_sparse_attention_paged(
            jnp.asarray(q[:, qq]), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(idx[:, qq]),
            jnp.asarray(lens[:, qq]), scale=0.35)
        np.testing.assert_array_equal(np.asarray(mq[:, qq]),
                                      np.asarray(single))
