"""Find every piece of a cell by name, under one checkout root.

Nothing here names a configuration, a traffic mix or a metric: a cell in
`BENCHMARK.json` names them, and each resolves to a file of its own.

    bench/configs/<config>.json        sizes as run, source, cuts, departures
    bench/traffic/<mix>.json           mix parameters and the engine's shape
    bench/traffic/gen_<generator>.py   the generator a mix names
    bench/metrics/<metric>.py          one reader per quantity: read(run);
                                       <metric>.<variant> (the same quantity
                                       in cells that move another metric or
                                       need another bound) has no file of its
                                       own and reads <metric>.py
    bench/layouts/<layout>.py          a configuration's leaves, parameter
                                       tree and FLOPs (bench/weights.py)
    bench/reference/<family>.py        the plain float32 reference
    bench/limits/<workload>.json       the limits `correct` is held to
    bench/peaks.json                   peaks keyed by device kind
"""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]


def _load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# a layout is read for every token a FLOP count covers: load it once
_load_module_once = functools.lru_cache(maxsize=None)(_load_module)


def _load_json(path: Path) -> Any:
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


class Registry:
    """Name → file resolution below `root` (the checkout's root)."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"

    def benchmark(self) -> Dict[str, Any]:
        return _load_json(self.root / "BENCHMARK.json")

    def workload(self, name: str) -> Dict[str, Any]:
        for cell in self.benchmark()["workloads"]:
            if cell["name"] == name:
                return cell
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def metrics_for(self, workload: str, kind: str) -> List[Dict[str, Any]]:
        """The cell's `end_to_end` or `per_layer` metric entries."""
        return [m for m in self.benchmark()[kind]
                if "workloads" not in m or workload in m["workloads"]]

    def config(self, name: str) -> Dict[str, Any]:
        return _load_json(self.bench / "configs" / f"{name}.json")

    def traffic(self, name: str) -> Dict[str, Any]:
        return _load_json(self.bench / "traffic" / f"{name}.json")

    def generator(self, name: str) -> ModuleType:
        return _load_module(self.bench / "traffic" / f"gen_{name}.py",
                            f"bench_gen_{name}")

    def metric_reader(self, name: str) -> ModuleType:
        """`metrics/<name>.py`, else the reader of the name less its last
        `.<variant>`."""
        base = name
        while not (self.bench / "metrics" / f"{base}.py").is_file() \
                and "." in base:
            base = base.rsplit(".", 1)[0]
        return _load_module(self.bench / "metrics" / f"{base}.py",
                            f"bench_metric_{base.replace('.', '_')}")

    def layout(self, spec: Dict[str, Any]) -> ModuleType:
        """`layouts/<layout>.py` of a configuration: the one its `layout`
        key names, `gqa` where it names none."""
        name = spec.get("layout", "gqa")
        return _load_module_once(self.bench / "layouts" / f"{name}.py",
                                 f"bench_layout_{name}")

    def reference(self, family: str) -> ModuleType:
        return _load_module(self.bench / "reference" / f"{family}.py",
                            f"bench_reference_{family}")

    def limits(self, workload: str) -> Dict[str, Any]:
        return _load_json(self.bench / "limits" / f"{workload}.json")

    def peaks(self, device_kind: str) -> Dict[str, Any]:
        table = _load_json(self.bench / "peaks.json")
        if device_kind not in table["devices"]:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"bench/peaks.json")
        return table["devices"][device_kind]


def read_metric(reg: Registry, name: str, run) -> Optional[float]:
    """The reader's value, or None where it found nothing to read."""
    value = reg.metric_reader(name).read(run)
    return None if value is None else float(value)
