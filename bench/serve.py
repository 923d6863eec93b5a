"""Drive the engine through set-up and the measured window.

The harness calls `DecodeEngine.tick()` itself and stamps the host clock
around each tick, so that ticks map to times (the engine counts in ticks
only). A token is stamped with the end of the tick that produced it: that
is when its request could have seen it.

Closed loop (no request has a due time): every request is submitted in
set-up and prefilled there; the window starts once each has its first
token. Open loop: requests are submitted as their due times pass, from the
start of set-up's warm-up on; the window follows the warm-up (`warm_s`,
and until a first request has finished). The run ends with the window.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax

from bench import program


@dataclasses.dataclass
class Stamps:
    """Host-clock record of one run (perf_counter seconds)."""
    due: Dict[int, float] = dataclasses.field(default_factory=dict)
    submitted: Dict[int, float] = dataclasses.field(default_factory=dict)
    admitted: Dict[int, float] = dataclasses.field(default_factory=dict)
    tokens: Dict[int, List[float]] = dataclasses.field(default_factory=dict)
    prompt_len: Dict[int, int] = dataclasses.field(default_factory=dict)
    ticks: List[Tuple[float, float]] = dataclasses.field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)
    window_ticks: Tuple[int, int] = (0, 0)         # engine tick indices
    window_due: List[int] = dataclasses.field(default_factory=list)
    traced: Optional[Tuple[float, float]] = None
    compiles_in_window: int = 0

    def in_window(self, t: float) -> bool:
        return self.window[0] < t <= self.window[1]


class _CompileCounter:
    """Counts XLA compilations in this process (JAX's monitoring events);
    one listener, registered on first use, serves every run."""
    count = 0
    _registered = False

    @classmethod
    def read(cls) -> int:
        if not cls._registered:
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._registered = True
        return cls.count

    @classmethod
    def _on(cls, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            cls.count += 1


class Driver:
    def __init__(self, eng, specs: List[dict]):
        self.eng = eng
        self.st = Stamps()
        self.reqs = {}
        self.seen: Dict[int, int] = {}
        self.live = {}
        self.closed = [s for s in specs if s["due_s"] is None]
        self.open = sorted((s for s in specs if s["due_s"] is not None),
                           key=lambda s: s["due_s"])
        self.next_open = 0
        self.t_open = 0.0
        self.refused: List[int] = []

    def _submit(self, spec: dict, due: float) -> None:
        uid = spec["uid"]
        req = program.request(uid, spec["prompt"], spec["max_new_tokens"])
        try:
            self.eng.submit(req)
        except ValueError:                 # the engine refuses it outright
            self.refused.append(uid)
            return
        self.reqs[uid] = req
        self.seen[uid] = 0
        self.live[uid] = req
        self.st.due[uid] = due
        self.st.submitted[uid] = time.perf_counter()
        self.st.tokens[uid] = []
        self.st.prompt_len[uid] = len(spec["prompt"])

    def _submit_due(self) -> None:
        now = time.perf_counter()
        while (self.next_open < len(self.open) and
               self.t_open + self.open[self.next_open]["due_s"] <= now):
            spec = self.open[self.next_open]
            self._submit(spec, self.t_open + spec["due_s"])
            self.next_open += 1

    def _next_due(self) -> float:
        if self.next_open < len(self.open):
            return self.t_open + self.open[self.next_open]["due_s"]
        return float("inf")

    def tick(self, deadline: float) -> None:
        """Submit what is due, then one engine tick and its stamps; when the
        engine has nothing to do, wait for the next arrival instead."""
        self._submit_due()
        if self.eng.idle():
            wait = min(self._next_due(), deadline) - time.perf_counter()
            if wait > 0:
                time.sleep(min(wait, 0.05))
            return
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.tick"):
            self.eng.tick()
        t1 = time.perf_counter()
        self.st.ticks.append((t0, t1))
        with jax.profiler.TraceAnnotation("bench.stamp"):
            self._stamp(t1)

    def _stamp(self, t1: float) -> None:
        done = []
        for uid, req in self.live.items():
            if uid not in self.st.admitted and req.admitted_at is not None:
                # the driver alone ticks the engine: its tick count indexes
                # our list of ticks, and admission happens at a tick's start
                self.st.admitted[uid] = self.st.ticks[req.admitted_at][0]
            n, seen = len(req.generated), self.seen[uid]
            if n > seen:
                self.st.tokens[uid].extend([t1] * (n - seen))
            elif n < seen:                         # preempted: replayed
                del self.st.tokens[uid][n:]
            self.seen[uid] = n
            if req.phase == "DONE":
                done.append(uid)
        for uid in done:
            del self.live[uid]

    def run(self, *, seconds: float, warm_s: float, trace_s: float = 0.0,
            on_trace: Optional[Callable[[bool], None]] = None) -> Stamps:
        """Set-up traffic, then the window. `on_trace(True)` is
        called once `trace_s` of the window is left, `on_trace(False)` at
        its end."""
        _CompileCounter.read()
        now = time.perf_counter()
        for spec in self.closed:
            self._submit(spec, now)
        self.t_open = time.perf_counter()
        if self.closed:
            while any(not self.st.tokens.get(s["uid"]) for s in self.closed
                      if s["uid"] not in self.refused):
                self.tick(float("inf"))
        else:
            # warm until `warm_s` has passed and a request has finished:
            # retiring a slot runs programs of its own, which would
            # otherwise compile inside the window
            end = self.t_open + warm_s
            while time.perf_counter() < end or not self.eng.completed:
                self.tick(max(end, time.perf_counter()))

        compiles = _CompileCounter.read()
        t0 = time.perf_counter()
        first_tick = len(self.st.ticks)
        end = t0 + seconds
        tracing = False
        while time.perf_counter() < end:
            if on_trace and not tracing and end - time.perf_counter() <= trace_s:
                on_trace(True)
                tracing = True
                traced_from = time.perf_counter()
            self.tick(end)
        t1 = time.perf_counter()
        if tracing:
            self.st.traced = (traced_from, t1)
            on_trace(False)
        self.st.window = (t0, t1)
        self.st.window_ticks = (first_tick, len(self.st.ticks))
        self.st.compiles_in_window = _CompileCounter.read() - compiles

        if not self.open:
            self.st.window_due = [s["uid"] for s in self.closed]
            return self.st
        for s in self.open:
            if t0 <= self.t_open + s["due_s"] < t1:
                self.st.window_due.append(s["uid"])
                self.st.due[s["uid"]] = self.t_open + s["due_s"]
        return self.st
