"""Property-based selector dispatch tests (paper Fig. 8 / §5.5).

PROPERTY: for any finite scores, any lengths, any k and any prediction
state — warm, cold, or a per-row mix — every dispatch path returns the
exact Top-K set of `lax.top_k` under the lowest-index tie policy.

Runs under real `hypothesis` when installed, else the deterministic
seeded-examples shim (tests/_hypothesis_compat.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.sparse.selector import select_topk

NEG = np.float32(-3.4028235e38)


def _expected_topk_idx(x_masked: np.ndarray, k: int) -> np.ndarray:
    """Exact Top-K indices, lowest-index-first on ties: stable argsort on
    descending value keeps the smaller index ahead of an equal value."""
    order = np.argsort(-x_masked, axis=-1, kind="stable")
    return np.sort(order[:, :k], axis=-1)


def _scores(rng, b, n, dist):
    if dist == "normal":
        x = rng.normal(size=(b, n)) * 10 ** rng.uniform(-6, 6)
    elif dist == "heavy":
        x = rng.standard_cauchy(size=(b, n)).clip(-1e37, 1e37)
    elif dist == "ties":
        x = rng.integers(-4, 4, size=(b, n)).astype(float)
    else:  # const — everything ties
        x = np.full((b, n), float(rng.normal()))
    return x.astype(np.float32)


@pytest.mark.slow
@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(32, 512),
    k_frac=st.floats(0.02, 0.98),
    dist=st.sampled_from(["normal", "heavy", "ties", "const"]),
    method=st.sampled_from(["gvr", "radix", "exact", "auto"]),
    ragged=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_all_paths_exact_topk(n, k_frac, dist, method, ragged, seed):
    rng = np.random.default_rng(seed)
    b = 3
    k = max(1, int(n * k_frac))
    x = _scores(rng, b, n, dist)
    lengths = (rng.integers(1, n + 1, (b,)).astype(np.int32)
               if ragged else None)
    m = max(k, 8)
    prev = rng.integers(0, n, (b, m)).astype(np.int32)

    out = select_topk(jnp.asarray(x), k,
                      prev_idx=jnp.asarray(prev),
                      method=method,
                      lengths=(None if lengths is None
                               else jnp.asarray(lengths)),
                      min_n_for_selection=64)

    xm = x.copy()
    if lengths is not None:
        xm[np.arange(n)[None, :] >= lengths[:, None]] = NEG
    want_idx = _expected_topk_idx(xm, k)
    got_idx = np.sort(np.asarray(out.indices), axis=-1)
    np.testing.assert_array_equal(got_idx, want_idx, err_msg=out.method)
    # values must be the gathered scores at those indices
    np.testing.assert_array_equal(
        np.sort(np.asarray(out.values), -1),
        np.sort(np.take_along_axis(xm, want_idx, -1), -1))


@pytest.mark.slow
@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(128, 512),
    k_frac=st.floats(0.02, 0.5),
    dist=st.sampled_from(["normal", "ties", "const"]),
    ragged=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_mixed_warm_cold_rows(n, k_frac, dist, ragged, seed):
    """Per-row dispatch: a batch mixing warm and cold slots must (a) stay
    exact on every row, (b) report exactly the warm rows as GVR-served."""
    rng = np.random.default_rng(seed)
    b = 4
    k = max(1, int(n * k_frac))
    x = _scores(rng, b, n, dist)
    lengths = (rng.integers(k, n + 1, (b,)).astype(np.int32)
               if ragged else None)
    prev = rng.integers(0, n, (b, max(k, 8))).astype(np.int32)
    valid = rng.integers(0, 2, (b,)).astype(bool)

    out = select_topk(jnp.asarray(x), k,
                      prev_idx=jnp.asarray(prev),
                      prev_valid=jnp.asarray(valid),
                      method="auto",
                      lengths=(None if lengths is None
                               else jnp.asarray(lengths)),
                      min_n_for_selection=64, gate_max_n=10**6)

    assert out.method == "mixed"
    np.testing.assert_array_equal(np.asarray(out.gvr_rows), valid)
    xm = x.copy()
    if lengths is not None:
        xm[np.arange(n)[None, :] >= lengths[:, None]] = NEG
    want_idx = _expected_topk_idx(xm, k)
    np.testing.assert_array_equal(np.sort(np.asarray(out.indices), -1),
                                  want_idx)


def test_mixed_requires_auto_gate():
    """Explicit methods ignore prev_valid (forced path), and the auto gate
    still resolves all-or-nothing when no validity signal is given."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 256)).astype(np.float32))
    prev = jnp.asarray(rng.integers(0, 256, (2, 16)).astype(np.int32))
    valid = jnp.asarray(np.array([True, False]))
    out = select_topk(x, 8, prev_idx=prev, prev_valid=valid, method="gvr")
    assert out.method == "gvr" and bool(np.asarray(out.gvr_rows).all())
    out = select_topk(x, 8, prev_idx=prev, method="auto",
                      min_n_for_selection=64)
    assert out.method == "gvr"
    out = select_topk(x, 8, prev_idx=prev, prev_valid=valid, method="auto",
                      min_n_for_selection=64)
    assert out.method == "mixed"


def _compute_both(x, prev, valid, k, lengths):
    """The mixed dispatch as it was before the batch-level switch: both
    paths on every row, picked per row."""
    from repro.core.gvr import extract_topk, gvr_threshold
    from repro.core.topk_baselines import radix_select_topk
    stats = gvr_threshold(x, prev, k, lengths=lengths)
    g_vals, g_idx = extract_topk(x, stats.threshold, k, lengths=lengths)
    xm = jnp.where(jnp.arange(x.shape[-1])[None, :] < lengths[:, None], x,
                   NEG)
    r_vals, r_idx, r_st = radix_select_topk(xm, k)
    return dict(indices=jnp.where(valid[:, None], g_idx, r_idx),
                values=jnp.where(valid[:, None], g_vals, r_vals),
                secant_iters=jnp.where(valid, stats.secant_iters,
                                       r_st.passes),
                gvr_rows=valid, fallback=stats.fallback & valid)


VALIDITY = {"all_warm": [True] * 4, "all_cold": [False] * 4,
            "mixed": [True, False, False, True]}


@pytest.mark.parametrize("mode", ["eager", "jit", "scan", "mesh"])
@pytest.mark.parametrize("validity", sorted(VALIDITY))
def test_mixed_dispatch_bit_equal_to_compute_both(validity, mode):
    """Whichever branch the batch takes (every row warm: GVR alone; none:
    radix alone; a mix: both), every output is bit-equal to computing
    both paths and picking per row, eagerly, under jit, inside a lax.scan
    (the decode step's layer scan) and inside the shard_map body a mesh
    routes selection through (a one-device mesh); radix is reported
    computed on every row unless every row is warm."""
    rng = np.random.default_rng(7)
    b, n, k, layers = 4, 384, 24, 2
    valid = jnp.asarray(np.array(VALIDITY[validity]))
    xs = jnp.asarray(np.stack([_scores(rng, b, n, d)
                               for d in ("ties", "normal")]))
    prevs = jnp.asarray(rng.integers(0, n, (layers, b, k)).astype(np.int32))
    lens = jnp.asarray(rng.integers(k, n + 1, (layers, b)).astype(np.int32))
    keys = ("indices", "values", "secant_iters", "gvr_rows", "fallback",
            "radix_rows")

    mesh = (jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
            if mode == "mesh" else None)

    def sel(x, prev, lengths):
        out = select_topk(x, k, prev_idx=prev, prev_valid=valid,
                          lengths=lengths, min_n_for_selection=64,
                          mesh=mesh)
        assert out.method == "mixed"
        return tuple(getattr(out, key) for key in keys)

    if mode == "scan":
        got = jax.lax.scan(lambda c, inp: (c, sel(*inp)), 0,
                           (xs, prevs, lens))[1]
    else:
        f = sel if mode == "eager" else jax.jit(sel)
        got = [jnp.stack(o) for o in zip(*(f(xs[i], prevs[i], lens[i])
                                           for i in range(layers)))]
    for i in range(layers):
        want = _compute_both(xs[i], prevs[i], valid, k, lens[i])
        want["radix_rows"] = np.full((b,), validity != "all_warm")
        for key, g in zip(keys, got):
            np.testing.assert_array_equal(np.asarray(g[i]),
                                          np.asarray(want[key]),
                                          err_msg=f"{key}, layer {i}")
            assert np.asarray(g[i]).dtype == np.asarray(want[key]).dtype


def _jits_in(jaxpr, name):
    """How many `jit(name)` calls a jaxpr holds, through nested jaxprs."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.params.get("name") == name:
            count += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _jits_in(sub, name)
    return count


def test_all_warm_branch_runs_no_radix():
    """The mixed dispatch is one switch: radix-select lies only in its
    all-cold and mixed branches, and the all-warm branch holds GVR alone."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 256)).astype(np.float32))
    prev = jnp.asarray(rng.integers(0, 256, (4, 16)).astype(np.int32))
    closed = jax.make_jaxpr(lambda x, p, v: select_topk(
        x, 16, prev_idx=p, prev_valid=v, min_n_for_selection=64).indices)(
            x, prev, jnp.ones((4,), bool))
    conds = [e for e in closed.jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    warm, cold, mixed = (br.jaxpr for br in conds[0].params["branches"])
    assert _jits_in(closed.jaxpr, "radix_select_topk") == 2
    assert _jits_in(warm, "radix_select_topk") == 0
    assert _jits_in(warm, "gvr_threshold") == 1
    assert _jits_in(cold, "radix_select_topk") == 1
    assert _jits_in(cold, "gvr_threshold") == 0
    assert _jits_in(mixed, "radix_select_topk") == 1
    assert _jits_in(mixed, "gvr_threshold") == 1
