"""Compile the served kernels and the paged decode step for TPU v5e.

Nothing runs: each program is compiled for a v5e chip that is described,
not attached (the TPU compiler ships with jaxlib), at the widths of
llama3.2-1b — K=2048 over a 32K context in 64-token pages, 64 indexer
heads × 128, 32/8 attention heads × 64 (and the block-table kernels
again at 128K context). Mosaic refuses here exactly what
it would refuse on the chip (tiling, unsupported ops, VMEM/SMEM budgets),
and the whole-step compile reports the device memory the step needs.
At small widths, the paged step and the speculative verify tick, on one
chip and sequence-sharded over four, are compiled once more to read how
their page pools move through the layer scan.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.registry import get_config
from repro.kernels import ops
from repro.models.api import build_model

B, K, PAGE, N = 4, 2048, 64, 32768
H, KVH, HD = 32, 8, 64
IH, ID = 64, 128
HBM_BYTES = 16 * 2 ** 30                  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a described chip's executables cannot be read back from the
    # persistent cache: keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _kernel_case(name, n=N):
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    mp = n // PAGE                        # block-table width, in SMEM
    n_pages = B * mp + 1
    pages = ((n_pages, PAGE, KVH, HD), bf16)
    cases = {
        "gvr_topk": (
            lambda s, p: ops.gvr_topk(s, p, K, interpret=False),
            [((B, n), f32), ((B, K), i32)]),
        "indexer_topk": (
            lambda q, kc, w, p: ops.indexer_topk(q, kc, w, p, K,
                                                 interpret=False),
            [((B, IH, ID), bf16), ((B, n, ID), bf16), ((B, IH), f32),
             ((B, K), i32)]),
        "paged_indexer_topk": (
            lambda q, kp, w, t, p: ops.paged_indexer_topk(
                q, kp, w, t, p, K, interpret=False),
            [((B, IH, ID), bf16), ((n_pages, PAGE, ID), bf16), ((B, IH), f32),
             ((B, mp), i32), ((B, K), i32)]),
        "paged_sparse_decode_attn": (
            lambda q, kp, vp, t, i: ops.paged_sparse_decode_attn(
                q, kp, vp, t, i, interpret=False),
            [((B, H, HD), bf16), pages, pages, ((B, mp), i32),
             ((B, K), i32)]),
        "paged_sparse_decode_attn_pg": (
            lambda q, kp, vp, t, i: ops.paged_sparse_decode_attn_pg(
                q, kp, vp, t, i, interpret=False),
            [((B, H, HD), bf16), pages, pages, ((B, mp), i32),
             ((B, K), i32)]),
        "sparse_decode_attn": (
            lambda q, kc, vc, i: ops.sparse_decode_attn(
                q, kc, vc, i, interpret=False),
            [((B, H, HD), bf16), ((B, n, KVH, HD), bf16),
             ((B, n, KVH, HD), bf16), ((B, K), i32)]),
        "paged_dense_decode_attn": (
            lambda q, kp, vp, t, n: ops.paged_dense_decode_attn(
                q, kp, vp, t, n, interpret=False),
            [((B, H, HD), bf16), pages, pages, ((B, mp), i32), ((B,), i32)]),
        "paged_gather": (
            lambda kp, t: ops.paged_gather(kp, t, interpret=False),
            [pages, ((B, mp), i32)]),
        "paged_indexer_topk_mq": (
            lambda q, kp, w, t, p, n: ops.paged_indexer_topk_mq(
                q, kp, w, t, p, K, lengths=n, interpret=False),
            [((B, 3, IH, ID), bf16), ((n_pages, PAGE, ID), bf16), ((B, IH), f32),
             ((B, mp), i32), ((B, K), i32), ((B, 3), i32)]),
        "paged_sparse_decode_attn_mq": (
            lambda q, kp, vp, t, i: ops.paged_sparse_decode_attn_mq(
                q, kp, vp, t, i, interpret=False),
            [((B, 3, H, HD), bf16), pages, pages, ((B, mp), i32),
             ((B, 3, K), i32)]),
    }
    return cases[name]


@pytest.mark.parametrize("name", ["gvr_topk", "indexer_topk",
                                  "paged_indexer_topk",
                                  "paged_sparse_decode_attn",
                                  "paged_sparse_decode_attn_mq",
                                  "paged_sparse_decode_attn_pg",
                                  "sparse_decode_attn",
                                  "paged_dense_decode_attn", "paged_gather",
                                  "paged_indexer_topk_mq"])
def test_kernel_compiles_for_v5e(one_chip, name):
    _compile_kernel(one_chip, *_kernel_case(name))


@pytest.mark.parametrize("name", ["paged_indexer_topk",
                                  "paged_sparse_decode_attn"])
def test_block_table_fits_smem_at_128k(one_chip, name):
    """At 128K context the scalar-prefetched (B, MP) block table is
    B × 2048 int32 in SMEM."""
    _compile_kernel(one_chip, *_kernel_case(name, n=131072))


def _compile_kernel(one_chip, fn, specs):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()      # Mosaic, not XLA


def test_paged_decode_step_fits_one_v5e(one_chip):
    """The served step at full width — 4 slots × 16K context of paged KV,
    bf16 weights — compiles for one chip and, with the state donated as
    the engine does, needs less than its 16 GiB."""
    model = build_model(get_config("llama3.2-1b"))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init_params,
                                    jax.random.PRNGKey(0)))
    state = on_chip(jax.eval_shape(lambda: model.init_paged_decode_state(
        B, 16384, num_pages=B * 16384 // PAGE, page_size=PAGE)))
    tokens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, s, t, m: model.serve_step_paged(
        p, s, t, min_write_pos=m), donate_argnums=(1,))
    mem = step.lower(params, state, tokens, tokens).compile() \
        .memory_analysis()
    assert mem.alias_size_in_bytes > 0                  # the pools in place
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def _pool_guard_config(case):
    """Small configurations of the three served step bodies, each with
    page rows of whole 128-lane rows, as in every served configuration:
    heads of 128 as in chatglm3, of 64 as in granite, and DeepSeek's
    latent rows."""
    def smoke(name, dsa=None, **kw):
        cfg = get_config(name, smoke=True)
        dsa = dataclasses.replace(cfg.dsa, indexer_dim=128, **(dsa or {}))
        return dataclasses.replace(cfg, dtype="bfloat16", dsa=dsa, **kw)
    if case == "gqa_dsa":            # gate open: indexer, Top-K, sparse
        return smoke("chatglm3-6b", head_dim=128)
    if case == "gqa_closed":         # granite-like: dense fallback, experts
        return smoke("granite-moe-1b-a400m", dsa={"min_n": 4096},
                     head_dim=64)
    return smoke("deepseek-v3.2", kv_lora_rank=124)     # [c_kv | k_r] = 128


@pytest.mark.parametrize("case", ["gqa_dsa", "gqa_closed", "mla",
                                  "gqa_dsa.verify", "gqa_closed.verify"])
def test_paged_step_moves_no_pool(one_chip, case):
    """The layer-stacked page pools stay whole and in place through the
    step's layer scan: with the state donated, the step's temporaries are
    smaller than one layer's pools, and no copy, pad, concatenation or
    (dynamic) slice in the optimized program has the shape of a whole
    stacked pool or of one layer's pool. The pools hold four times the
    pages the slots map (room a prefix cache would use), so that one
    layer's pools outweigh the logical views the step builds of them.
    A `.verify` case compiles the speculative verify tick of depth 2 in
    its multi-query form (`verify_kernel="mq"`), the same forward over
    three query rows a slot."""
    body, _, tick = case.partition(".")
    cfg = _pool_guard_config(body)
    model = build_model(cfg)
    slots, max_len = 4, 1024

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init_params,
                                    jax.random.PRNGKey(0)))
    state = on_chip(jax.eval_shape(lambda: model.init_paged_decode_state(
        slots, max_len, num_pages=4 * slots * max_len // PAGE,
        page_size=PAGE)))
    pools = {k: v for k, v in state.items() if k.endswith("_pages")}
    layer_bytes = sum(v.size * v.dtype.itemsize // v.shape[0]
                      for v in pools.values())
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    if tick == "verify":
        tokens = jax.ShapeDtypeStruct((slots, 3), jnp.int32,
                                      sharding=one_chip)
        step = jax.jit(lambda p, s, t, m: model.serve_step_spec_paged(
            p, s, t, draft_len=m, max_accept=m, min_write_pos=m,
            verify_kernel="mq"), donate_argnums=(1,))
    else:
        tokens = rows
        step = jax.jit(lambda p, s, t, m: model.serve_step_paged(
            p, s, t, min_write_pos=m, with_counts=True), donate_argnums=(1,))
    compiled = step.lower(params, state, tokens, rows).compile()

    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_bytes, (temp, layer_bytes)
    shapes = set()
    for v in pools.values():
        shapes |= {v.shape, v.shape[1:], (1,) + v.shape[1:]}
    found = _pool_moves(compiled.as_text(), shapes)
    assert not found, found


def _pool_moves(hlo, shapes):
    """The copies, pads, concatenations and (dynamic) slices in optimized
    HLO text whose result has one of `shapes`."""
    moved = re.compile(r"copy|pad|concatenate|slice")
    found = []
    for m in re.finditer(r"%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
                         hlo):
        name, dims, op = m.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        if shape in shapes and (moved.search(op) or moved.search(name)):
            found.append(f"{op} {name} {shape}")
    return found


@pytest.mark.parametrize("tick", ["step", "verify"])
def test_sharded_step_moves_no_pool(one_chip, topo, tick):
    """The sequence-sharded decode step and mq verify tick (depth 2), on a
    four-chip "seq" mesh, keep each shard's stacked pools whole and in
    place too: temporaries below one layer of a shard's pools, and no
    copy, pad, concatenation or slice shaped like a shard's stacks or one
    layer of them (`test_paged_step_moves_no_pool`'s test, per shard)."""
    mesh = Mesh(np.array(topo.devices), ("seq",))
    model = build_model(_pool_guard_config("gqa_dsa"))
    slots, max_len, shards = 4, 1024, 4

    def placed(tree, spec=lambda key: P()):
        return {k: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec(k))), v)
            for k, v in tree.items()}

    params = placed(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: model.init_sp_paged_decode_state(
        slots, max_len, num_pages_per_shard=slots * max_len // PAGE,
        page_size=PAGE, seq_shards=shards))
    pools = {k: v for k, v in state.items() if k.endswith("_pages")}
    state = placed(state, lambda k: P(None, "seq") if k in pools else P())
    layer_bytes = sum(v.size * v.dtype.itemsize // (v.shape[0] * shards)
                      for v in pools.values())
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    if tick == "verify":
        tokens = jax.ShapeDtypeStruct((slots, 3), jnp.int32,
                                      sharding=NamedSharding(mesh, P()))
        step = jax.jit(lambda p, s, t, m: model.serve_step_sp_spec_paged(
            p, s, t, mesh=mesh, draft_len=m, max_accept=m, min_write_pos=m,
            verify_kernel="mq"), donate_argnums=(1,))
    else:
        tokens = rows
        step = jax.jit(lambda p, s, t, m: model.serve_step_sp_paged(
            p, s, t, mesh=mesh, min_write_pos=m, with_counts=True),
            donate_argnums=(1,))
    compiled = step.lower(params, state, tokens, rows).compile()

    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_bytes, (temp, layer_bytes)
    shapes = set()
    for v in pools.values():
        shard = (v.shape[0], 1) + v.shape[2:]            # one device's
        layer = v.shape[2:]
        shapes |= {shard, shard[:1] + shard[2:], layer, (1,) + layer,
                   (1, 1) + layer}
    found = _pool_moves(compiled.as_text(), shapes)
    assert not found, found
