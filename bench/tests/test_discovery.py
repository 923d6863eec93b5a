"""A new mix, generator, metric and layout are found by name, with no edit.

The test writes a dummy traffic mix, its generator and a dummy metric
reader into a fresh checkout root, adds a cell and the metric to its
BENCHMARK.json, and has the registry resolve them; a test-only layout
with its own leaves, stacks and expert share is resolved and drawn the
same way.
"""

import json
from types import SimpleNamespace

from bench import flops, weights
from bench.registry import ROOT, Registry, read_metric
from bench.tests.small import MLA, mla_root


def test_new_files_resolve_by_name(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "dummy.cell", "config": "dummy_cfg",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "dummy_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "test", "moves": "decode_tok_s",
                               "workloads": ["dummy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub in ("traffic", "metrics", "configs"):
        (tmp_path / "bench" / sub).mkdir(parents=True)
    (tmp_path / "bench/configs/dummy_cfg.json").write_text('{"vocab": 7}')
    (tmp_path / "bench/traffic/dummy_mix.json").write_text(
        json.dumps({"generator": "dummy", "n": 3}))
    (tmp_path / "bench/traffic/gen_dummy.py").write_text(
        "def generate(mix, seed, *, vocab, seconds):\n"
        "    return [dict(uid=i, prompt=[seed % vocab], max_new_tokens=1,\n"
        "                 due_s=None) for i in range(mix['n'])]\n")
    (tmp_path / "bench/metrics/dummy_metric.py").write_text(
        "def read(run):\n    return run.answer\n")

    reg = Registry(tmp_path)
    cell = reg.workload("dummy.cell")
    mix = reg.traffic(cell["traffic"])
    reqs = reg.generator(mix["generator"]).generate(
        mix, 9, vocab=reg.config(cell["config"])["vocab"], seconds=1.0)
    assert [r["prompt"] for r in reqs] == [[2]] * 3
    names = [m["name"] for m in reg.metrics_for("dummy.cell", "per_layer")]
    assert "dummy_metric" in names
    assert "selector_share" not in names
    assert read_metric(reg, "dummy_metric",
                       SimpleNamespace(answer=4)) == 4.0
    # a variant of a quantity (another cell's bound or `moves`) has no
    # reader of its own: it reads the quantity's
    assert read_metric(reg, "dummy_metric.other_cell",
                       SimpleNamespace(answer=5)) == 5.0


def test_new_layout_resolves_by_name(tmp_path):
    reg = mla_root(tmp_path)
    layout = reg.layout(MLA)
    assert layout.__file__ == str(tmp_path / "bench/layouts/mla.py")
    assert Registry().layout({}).__file__ == str(ROOT / "bench/layouts/gqa.py")
    flat = weights.make_flat(MLA, 5, reg)
    assert flat["dense_wkv_b"].shape == (1, 16, 2 * (8 + 8))
    assert flat["moe_wq_a"].shape == (2, 32, 24)
    assert flat["moe_router"].shape == (2, 32, 16)
    assert flat["moe_router_bias"].shape == (2, 16)
    assert flat["moe_shared_down"].shape == (2, 20, 32)
    assert flat["moe_w_gate"].shape == (2, 4, 32, 12)
    params = weights.program_params(flat, MLA, reg)
    assert params["moe_layers"]["w_down"] is flat["moe_w_down"]
    assert params["dense_layers"]["idx_weights_proj"] is \
        flat["dense_idx_weights_proj"]
    per_layer = layout.flops_per_layer(MLA, 100, True)
    assert len(per_layer) == 3 and per_layer[1] == per_layer[2] > 0
    assert flops.per_token(MLA, 100, True, reg) == \
        sum(per_layer) + 2 * 32 * 50
