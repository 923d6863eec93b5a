"""A new mix, generator and metric are found by name, with no edit.

The test writes a dummy traffic mix, its generator and a dummy metric
reader into a fresh checkout root, adds a cell and the metric to its
BENCHMARK.json, and has the registry resolve them.
"""

import json
from types import SimpleNamespace

from bench.registry import ROOT, Registry, read_metric


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
