"""The system under test, built from a configuration file and a mix.

This is the only module of the benchmark that imports the program: its
registry configuration, its model API and its serving engine. The
configuration file holds the sizes as run; the program's configuration is
the registry entry with the keys the file says it changed, and the two
must then agree on every size, or the run stops.
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path
from typing import Any, Dict

SRC = Path(__file__).resolve().parents[1] / "src"


def _import_program():
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.configs.registry import get_config
    from repro.models.api import build_model
    from repro.serve import DecodeEngine, Request
    return get_config, build_model, DecodeEngine, Request


def model_config(spec: Dict[str, Any]):
    """The program's ModelConfig for a configuration file."""
    get_config, *_ = _import_program()
    base = get_config(spec["registry"])
    changes = {}
    for key in spec["changed_from_registry"]:
        value = spec[key]
        old = getattr(base, key)
        if dataclasses.is_dataclass(old):
            value = dataclasses.replace(old, **value)
        changes[key] = value
    cfg = dataclasses.replace(base, **changes)
    for field in dataclasses.fields(cfg):
        if field.name not in spec or field.name == "name":
            continue
        have, want = getattr(cfg, field.name), spec[field.name]
        if dataclasses.is_dataclass(have):
            have = {k: getattr(have, k) for k in want}
        if have != want:
            raise ValueError(
                f"config {spec['name']}: {field.name} is {have!r} in the "
                f"program but {want!r} in the file, and not listed in "
                f"changed_from_registry")
    return cfg


def build_engine(spec: Dict[str, Any], engine: Dict[str, Any], params):
    """The paged, fused DecodeEngine of the mix's serving shape."""
    _, build_model, DecodeEngine, _ = _import_program()
    model = build_model(model_config(spec))
    return DecodeEngine(model, params, num_slots=engine["slots"],
                        max_len=engine["max_len"], kv_layout="paged",
                        paged_attn="fused", page_size=engine["page_size"],
                        prefill_chunk=engine["prefill_chunk"])


def request(uid: int, prompt, max_new_tokens: int):
    """A greedy request (temperature 0), admissible at once."""
    *_, Request = _import_program()
    return Request(uid=uid, prompt=prompt, max_new_tokens=max_new_tokens)


def step_hlo(eng) -> str:
    """The compiled HLO text of the engine's pool-wide step (the persistent
    compilation cache holds it): the name stack of every instruction the
    trace shows."""
    import jax.numpy as jnp
    b = eng.num_slots
    return eng._tick_fn.lower(eng.params, eng.state, jnp.zeros((b,), jnp.int32),
                              jnp.zeros((b,), bool),
                              jnp.zeros((b,), jnp.int32)).compile().as_text()
