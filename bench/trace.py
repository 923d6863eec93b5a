"""Reduce a profiler trace of the window to the numbers the metrics read.

The JAX profiler writes an `.xplane.pb`; `jax.profiler.ProfileData` reads
it. On a TPU the chip is the plane `/device:TPU:0`; its line `XLA Ops`
holds one event per operation run on the chip, and `XLA Modules` one per
execution of a compiled program. The host plane `/host:CPU` holds the
threads' spans: the benchmark's own (`bench.tick` around each engine tick,
`bench.stamp` around its bookkeeping) and JAX's dispatches.

    busy       union of the chip's op intervals
    steps      executions of the module whose name holds STEP_MODULE (the
               engine's jitted pool-wide step, `jit(_tick_impl)`)
    ops        the chip's ops less the loops that hold others (`while`,
               `conditional`, `call`: their body's ops are events of their
               own), each with its name stack: the event names the HLO
               instruction (`%fusion.465 = ...`), and the step's compiled
               HLO text gives that instruction's metadata op_name, e.g.
               `jit(_tick_impl)/.../jit(gvr_threshold)/...`; `time_under`
               sums the ops whose stack holds any of the given scopes
    idle gaps  the spaces between busy intervals, each named by the
               innermost host span on the thread that holds the
               benchmark's `bench.tick` spans, around the gap's middle
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

STEP_MODULE = "_tick_impl"
DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
CONTAINERS = ("while", "conditional", "call")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    step_count: int
    step_s: float
    ops: List[Tuple[str, int, int, str]]          # name, start, dur, scope
    gaps: List[Tuple[int, int, str]]              # start, dur, host span

    def time_under(self, scopes: Sequence[str]) -> float:
        return 1e-9 * sum(d for _, _, d, sc in self.ops
                          if any(s in sc for s in scopes))

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The ops that took most device time (instruction and the tail of
        its name stack) and the longest idle gaps by host span, seconds."""
        by_op: Dict[str, int] = {}
        for name, _, d, scope in self.ops:
            parts = scope.split("/")
            jits = [p for p in parts[1:] if p.startswith("jit(")]
            where = "/".join(jits[-1:] + parts[-1:]) if scope else ""
            key = f"{name} {where}".strip()
            by_op[key] = by_op.get(key, 0) + d
        by_host: Dict[str, int] = {}
        for _, d, host in self.gaps:
            by_host[host] = by_host.get(host, 0) + d
        order = lambda m: sorted(m.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, 1e-9 * v] for k, v in order(by_op)],
                "idle_gaps": [[k, 1e-9 * v] for k, v in order(by_host)]}


def options():
    """Profiler options for a traced window: JAX's and the benchmark's own
    spans on the host, but not every Python call (the Python tracer's cost
    would swell the host's share of the window it measures)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def op_names(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its name stack (metadata op_name). A fusion
    often carries no metadata of its own: it takes the stack of the root
    of the computation it calls, or failing that of the first instruction
    there that has one, down through nested fusions; where none has one
    (XLA drops it from some scatters), the stack of the first instruction
    that consumes it."""
    comps: Dict[str, List[Tuple[str, bool]]] = {}
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    users: Dict[str, List[str]] = {}
    current = None
    for line in (hlo_text or "").splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = comps.setdefault(head.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if not m or current is None:
            continue
        name = m.group(2)
        current.append((name, bool(m.group(1))))
        op = _OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        for operand in _OPERAND.findall(line[m.end():].split("metadata=")[0]):
            users.setdefault(operand, []).append(name)

    memo: Dict[str, str] = {}

    def resolve(name: str, depth: int = 0) -> str:
        if name in memo:
            return memo[name]
        found = own.get(name, "")
        body = comps.get(calls.get(name, ""), [])
        if not found and depth < 16:
            roots = [n for n, root in body if root]
            for n in roots + [n for n, root in body if not root]:
                found = resolve(n, depth + 1)
                if found:
                    break
        memo[name] = found
        return found

    out = {}
    for body in comps.values():
        for name, _ in body:
            out[name] = resolve(name) or next(
                (own[u] for u in users.get(name, []) if u in own), "")
    return out


def instruction(event_name: str) -> str:
    """`%fusion.465 = s32[...] fusion(...)` -> `fusion.465`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged (start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _host_spans(pd) -> List[Tuple[int, int, str]]:
    """(start, end, name) of the spans on the host thread that holds the
    benchmark's `bench.tick` spans (they nest: one thread's stack)."""
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            evs = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                   for e in line.events]
            if any(n == "bench.tick" for _, _, n in evs):
                return sorted(evs, key=lambda t: (t[0], -t[1]))
    return []


def name_gaps(spans, mids: List[int]) -> List[str]:
    """For each time in `mids` the innermost span holding it, by one sweep
    over the nested spans (sorted by start, outer first)."""
    order = sorted(range(len(mids)), key=lambda i: mids[i])
    names = ["no host span"] * len(mids)
    stack: List[Tuple[int, str]] = []
    j = 0
    for i in order:
        t = mids[i]
        while j < len(spans) and spans[j][0] <= t:
            s, e, n = spans[j]
            while stack and stack[-1][0] <= s:
                stack.pop()
            stack.append((e, n))
            j += 1
        while stack and stack[-1][0] <= t:
            stack.pop()
        if stack:
            names[i] = stack[-1][1]
    return names


def reduce_file(path: str, window_s: Optional[float] = None,
                hlo_text: str = "") -> Reduced:
    """`hlo_text`: the step's compiled HLO (`compiled.as_text()`), for the
    ops' name stacks; without it every op's stack is empty."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    scopes = op_names(hlo_text)
    ops, intervals, steps = [], [], []
    for plane in pd.planes:
        if plane.name != DEVICE_PLANE:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                for e in line.events:
                    start, dur = int(e.start_ns), int(e.duration_ns)
                    intervals.append((start, start + dur))
                    name = instruction(e.name)
                    if not name.startswith(CONTAINERS):
                        ops.append((name, start, dur, scopes.get(name, "")))
            elif line.name == MODULES_LINE:
                steps.extend(int(e.duration_ns) for e in line.events
                             if STEP_MODULE in e.name)
    busy = union(intervals)
    busy_ns = sum(e - s for s, e in busy)
    if window_s is None:
        window_s = 1e-9 * (busy[-1][1] - busy[0][0]) if busy else 0.0
    between = [(a_end, b_start) for (_, a_end), (b_start, _)
               in zip(busy, busy[1:])]
    names = name_gaps(_host_spans(pd), [(a + b) // 2 for a, b in between])
    gaps = [(a, b - a, n) for (a, b), n in zip(between, names)]
    return Reduced(window_s=window_s, busy_s=1e-9 * busy_ns,
                   step_count=len(steps), step_s=1e-9 * sum(steps), ops=ops,
                   gaps=gaps)


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def reduce_dir(directory: str, traced=None, hlo_text: str = "") -> Reduced:
    window = traced[1] - traced[0] if traced else None
    return reduce_file(find_xplane(directory), window, hlo_text)
