"""bench/weights.py: the committed configurations draw what they drew
before layouts were split out, stream ids never collide, and a chip's
share of the experts is exactly that slice of the uncut layer."""

from __future__ import annotations

import hashlib

import jax
import numpy as np
import pytest

from bench import weights
from bench.registry import Registry
from bench.tests.small import MLA, mla_root, small_config

SEED = 2**31 + 3

# sha256 (first 16 hex digits) of each leaf's bytes, drawn at SEED by the
# harness that drew every leaf in one jitted call (before bench/layouts/)
PARENT = {
    "chatglm3-6b.stage7/float32": {
        "embed": "7c246d33a1659871", "final_norm": "538f22743658cbf1",
        "idx_w": "829959122871ad61", "idx_wk": "2ccd8c1bf052ea56",
        "idx_wq": "081a880ef24386dd", "lm_head": "ea6c390d3d9e01dd",
        "ln1": "004edfe6696ef61a", "ln2": "aca62899c6e91f29",
        "w_down": "602ab2d254ef94a2", "w_gate": "ec1f4519b2ddad98",
        "w_up": "c38ae7aecb625c90", "wk": "49dd0a63289e87c3",
        "wo": "f04c5cd1f0bb829f", "wq": "716a45f7cb4d1b89",
        "wv": "8ffe3d13768c93c3"},
    "chatglm3-6b.stage7/bfloat16": {
        "embed": "17c47c0878ff8f9a", "final_norm": "538f22743658cbf1",
        "idx_w": "829959122871ad61", "idx_wk": "0130db03adf5d9df",
        "idx_wq": "911a41851c604468", "lm_head": "00220efd502c4171",
        "ln1": "004edfe6696ef61a", "ln2": "aca62899c6e91f29",
        "w_down": "dc1befa877f3ec5e", "w_gate": "6c26a757d1210f83",
        "w_up": "f2223763ad309d2e", "wk": "3ac061c86caef46a",
        "wo": "f397cdd2637c7b86", "wq": "de101f794a3c0e28",
        "wv": "d4b940f473f8c7bf"},
    "granite-3.0-1b-a400m/float32": {
        "embed": "49804be2abdcd7a4", "final_norm": "538f22743658cbf1",
        "idx_w": "829959122871ad61", "idx_wk": "2ccd8c1bf052ea56",
        "idx_wq": "081a880ef24386dd", "ln1": "004edfe6696ef61a",
        "ln2": "aca62899c6e91f29", "router": "9248276d81fbc1d3",
        "w_down": "bcfd3c0f236f437b", "w_gate": "b91f49953edf1e33",
        "w_up": "c7dfffec9ffb2a2c", "wk": "49dd0a63289e87c3",
        "wo": "f04c5cd1f0bb829f", "wq": "716a45f7cb4d1b89",
        "wv": "8ffe3d13768c93c3"},
    "granite-3.0-1b-a400m/bfloat16": {
        "embed": "804acf852fdd7863", "final_norm": "538f22743658cbf1",
        "idx_w": "829959122871ad61", "idx_wk": "0130db03adf5d9df",
        "idx_wq": "911a41851c604468", "ln1": "004edfe6696ef61a",
        "ln2": "aca62899c6e91f29", "router": "9248276d81fbc1d3",
        "w_down": "ceb2a60c7c4ab46c", "w_gate": "832f63a60ef58214",
        "w_up": "5ca4439e54332ee2", "wk": "3ac061c86caef46a",
        "wo": "f397cdd2637c7b86", "wq": "de101f794a3c0e28",
        "wv": "d4b940f473f8c7bf"},
}


def digest(x) -> str:
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("case", sorted(PARENT))
def test_committed_configurations_draw_as_before(case):
    name, dtype = case.split("/")
    flat = weights.make_flat(small_config(name, dtype), SEED)
    assert {k: digest(v) for k, v in flat.items()} == PARENT[case]


@pytest.mark.parametrize("case", ["chatglm3-6b.stage7",
                                  "granite-3.0-1b-a400m", "mla"])
def test_stream_ids_are_distinct(case, tmp_path):
    if case == "mla":
        spec, reg = MLA, mla_root(tmp_path)
    else:
        spec, reg = Registry().config(case), Registry()
    names = list(reg.layout(spec).shapes(spec))
    ids = [weights.stream(n) for n in names]
    assert len(set(ids)) == len(ids), sorted(zip(ids, names))


def test_expert_share_is_a_slice_of_the_uncut_layer(tmp_path):
    reg = mla_root(tmp_path)
    share = weights.make_flat(MLA, SEED, reg)               # 4 held from 8
    whole = weights.make_flat(
        dict(MLA, moe=dict(MLA["moe"], num_experts=16, first_expert=0)),
        SEED, reg)
    assert set(share) == set(whole)
    for name, x in share.items():
        want = whole[name]
        if name in ("moe_w_gate", "moe_w_up", "moe_w_down"):
            assert x.shape[1] == 4 and want.shape[1] == 16
            want = want[:, 8:12]
        np.testing.assert_array_equal(np.asarray(x), np.asarray(want),
                                      err_msg=name)
    # the router and its bias keep the published width
    assert share["moe_router"].shape[-1] == 16
    assert share["moe_router_bias"].shape[-1] == 16
    # expert e of layer l: fold_in(fold_in(<leaf key>, l), e)
    key = jax.random.fold_in(weights.seed_key(SEED), weights.stream("moe_w_up"))
    one = weights.draw(jax.random.fold_in(jax.random.fold_in(key, 1), 10),
                       (32, 12), "float32", 32 ** -0.5)
    np.testing.assert_array_equal(np.asarray(share["moe_w_up"][1, 2]),
                                  np.asarray(one))


def test_a_layer_drawn_alone_does_not_depend_on_depth(tmp_path):
    reg = mla_root(tmp_path)
    deep = weights.make_flat(MLA, SEED, reg)                # 2 expert layers
    shallow = weights.make_flat(dict(MLA, n_layers=2), SEED, reg)
    for name, x in shallow.items():
        want = deep[name][:1] if name.startswith("moe_") else deep[name]
        np.testing.assert_array_equal(np.asarray(x), np.asarray(want),
                                      err_msg=name)
