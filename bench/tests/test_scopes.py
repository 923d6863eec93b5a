"""The readers of the step's named scopes and of the engine's tick
phases, on a recorded chip trace.

`data/step_trace_scoped.xplane.pb` and `data/step_trace_scoped.hlo.txt`
were recorded on one TPU v5e with `python bench/record_trace.py
--workload chatglm3-6b.decode5k --layers 1 --ticks 2`, from a program
whose decode step names its stages (`step.*` scopes) and whose engine
spans its tick phases (`engine.*`): two decode ticks of the cell's engine,
one layer at full width, and the step's compiled HLO.
"""

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import phases, scopes, trace
from bench.registry import Registry
from bench.serve import Stamps

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def recorded():
    hlo = (DATA / "step_trace_scoped.hlo.txt").read_text()
    path = str(DATA / "step_trace_scoped.xplane.pb")
    return trace.reduce_file(path, hlo_text=hlo), path, hlo


def _run(tr, ticks: int):
    st = Stamps(ticks=[(10.0 + i, 10.5 + i) for i in range(ticks)],
                traced=(9.0, 10.0 + ticks))
    return SimpleNamespace(trace=tr, stamps=st)


@pytest.mark.parametrize("scope", scopes.STEP_SCOPES)
def test_every_scope_has_device_time(recorded, scope):
    tr, _, _ = recorded
    assert scopes.share(tr, scope) > 0


def test_no_op_counts_under_two_scopes(recorded):
    """The scopes partition the step's ops: no op's name stack holds two
    of them, so the shares never count an op twice and what they leave of
    the step, the unscoped rest, is not negative."""
    tr, _, _ = recorded
    scoped = 0
    for _, _, _, stack in tr.ops:
        held = [s for s in scopes.STEP_SCOPES if f"/{s}/" in stack]
        assert len(held) <= 1, (stack, held)
        scoped += bool(held)
    assert scoped > 0
    assert scopes.split(tr)["unscoped"] >= 0


READERS = {"indexer_share": "step.indexer", "topk_share": "step.topk",
           "attention_share": "step.attention", "mlp_share": "step.mlp",
           "attention_share.chat": "step.attention",
           "mlp_share.chat": "step.mlp"}


@pytest.mark.parametrize("name", sorted(READERS))
def test_share_readers(recorded, name):
    tr, _, _ = recorded
    value = Registry().metric_reader(name).read(_run(tr, 2))
    assert 0 < value < 100
    assert value == scopes.share(tr, READERS[name])


def test_topk_share_holds_selector_share(recorded):
    tr, _, _ = recorded
    reg = Registry()
    run = _run(tr, 2)
    topk = reg.metric_reader("topk_share").read(run)
    selector = reg.metric_reader("selector_share").read(run)
    assert 0 < selector <= topk


def test_a_trace_without_scopes_reads_nothing():
    """The older recording (a program with no `step.*` scopes and no
    `engine.*` spans) leaves the new readers with nothing to read, as a
    parent commit would."""
    tr = trace.reduce_file(str(DATA / "step_trace.xplane.pb"),
                           hlo_text=(DATA / "step_trace.hlo.txt").read_text())
    for name in ("indexer_share", "topk_share", "attention_share",
                 "mlp_share", "engine_idle_ms"):
        assert Registry().metric_reader(name).read(_run(tr, 2)) is None


@pytest.mark.parametrize("name", ["engine_idle_ms", "engine_idle_ms.chat"])
def test_engine_idle_within_the_trace_idle(recorded, name):
    tr, _, _ = recorded
    ms = Registry().metric_reader(name).read(_run(tr, 2))
    total_ms = 1e-6 * sum(d for _, d, _ in tr.gaps)
    assert 0 < ms * 2 <= total_ms


def test_every_gap_goes_to_a_phase(recorded):
    """Idle time under the engine's phases and outside them adds up to the
    trace's idle time; the readback and the per-slot emit are phases of
    their own."""
    tr, path, _ = recorded
    out = phases.reduce(tr, path)
    assert out["ticks"] == 2
    per_tick = out["idle_ms_per_tick"]
    assert sum(per_tick.values()) * 2 == pytest.approx(out["idle_ms"])
    assert out["idle_ms"] == pytest.approx(
        1e-6 * sum(d for _, d, _ in tr.gaps))
    assert {"engine.dispatch", "engine.readback",
            "engine.emit"} <= set(per_tick)
    assert out["host_events_per_tick"]["engine.tick"] == 1.0
