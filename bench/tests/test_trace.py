"""bench/trace.py on a recorded chip trace and on made-up intervals.

`data/step_trace.xplane.pb` and `data/step_trace.hlo.txt` were recorded
on one TPU v5e with `python bench/record_trace.py --workload
chatglm3-6b.decode5k --layers 1 --ticks 2`: two decode ticks of the cell's
engine (4 slots, max_len 8192, so the DSA path and the selector run), one
layer at full width, and the step's compiled HLO.
"""

from pathlib import Path

from bench import trace

DATA = Path(__file__).parent / "data"


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9), (10, 11)]) == [
        (0, 3), (5, 9), (10, 11)]


def test_gap_takes_the_innermost_host_span():
    spans = [(0, 100, "bench.tick"), (10, 40, "PjitFunction(x)"),
             (12, 20, "DevicePut"), (50, 60, "bench.stamp"),
             (120, 130, "bench.tick")]
    assert trace.name_gaps(spans, [200, 45, 15, 25, 55, 125, 110]) == [
        "no host span", "bench.tick", "DevicePut", "PjitFunction(x)",
        "bench.stamp", "bench.tick", "no host span"]


HLO = """\
%fused (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  ROOT %add.1 = s32[8]{0} add(%p, %p), metadata={op_name="jit(_tick_impl)/jit(gvr_threshold)/add"}
}

%fused.2 (q: s32[8]) -> s32[8] {
  %q = s32[8]{0} parameter(0)
  ROOT %scatter.1 = s32[8]{0} scatter(%q, %q, %q), to_apply=%r
}

ENTRY %main (a: s32[8]) -> s32[8] {
  %a = s32[8]{0} parameter(0)
  %fusion.4 = s32[8]{0} fusion(%a), kind=kLoop, calls=%fused
  %fusion.5 = s32[8]{0} fusion(%fusion.4), kind=kCustom, calls=%fused.2
  ROOT %reshape.6 = s32[8]{0} reshape(%fusion.5), metadata={op_name="jit(_tick_impl)/jit(radix_select_topk)/scatter-add"}
}
"""


def test_instruction_and_name_stack():
    names = trace.op_names(HLO)
    # a fusion without metadata takes the root of what it calls
    assert names["fusion.4"] == "jit(_tick_impl)/jit(gvr_threshold)/add"
    # and where nothing inside has any, the stack of its consumer
    assert names["fusion.5"] == (
        "jit(_tick_impl)/jit(radix_select_topk)/scatter-add")
    assert trace.instruction("%fusion.4 = s32[8]{0} fusion(%a)") == "fusion.4"


def test_recorded_trace():
    r = trace.reduce_file(str(DATA / "step_trace.xplane.pb"),
                          hlo_text=(DATA / "step_trace.hlo.txt").read_text())
    assert r.step_count == 2                      # two traced ticks
    assert 0 < r.busy_s <= r.window_s
    assert 0 < r.step_s <= r.busy_s
    selector = r.time_under(("jit(gvr_threshold)", "jit(radix_select_topk)"))
    assert 0.2 * r.step_s < selector < r.step_s   # 44 % when recorded
    assert r.time_under(("no such scope",)) == 0
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in b["device_ops"])
