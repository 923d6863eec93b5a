"""Continuous-batching GVR decode engine (serving layer).

## The slot/tick model

The engine owns a fixed pool of **B slots** — the batch dimension of every
decode-state array (`models.api.Model.state_batch_axes` names the slot axis
of each leaf). Requests flow through a per-slot lifecycle

    QUEUED → PREFILL → DECODE → DONE

managed by a `Scheduler` (FIFO or longest-context-first admission,
`serve.scheduler`). One **tick** = one jitted `serve_step` over the whole
ragged pool: every slot carries its own `length`, finished/idle slots are
masked out by the engine's merge (their rows still flow through the jitted
step — shapes stay static, so the step **never recompiles** — but their
state is discarded; score rows beyond a slot's `length` are already dead
via the `NEG_SENTINEL` masking convention in `core.gvr`/`sparse.dsa`).
Freed slots are refilled mid-stream by **chunked prefill**: each tick
streams one chunk of every admitted request's prompt, one token per slot
per pool-wide step (the same jitted step decode uses, with only the
prefilling rows active), while the other slots keep decoding — no global
pause.

## Mapping to the paper's per-step Top-K feedback buffer

The paper's `heuristic_prev_topk` HBM buffer (L × B × K int32, Appendix C)
is the pool's `prev_topk` state: slot b's rows hold request b's previous
step Top-K per layer, and every DSA step overwrites them with fresh
feedback — GVR's temporal warm start (§3.1), amortized across whatever mix
of requests occupies the pool. Continuous batching makes the buffer's
*lifecycle* explicit (`serve.feedback_pool` over `core.temporal`):

* **admission** re-seeds the slot's rows with the even-spacing prior over
  the request's own prefix and drops `topk_valid` — a fresh request still
  warm-starts Phase 1 (paper Table 9 row b), but its first selection
  dispatches through the non-GVR fallback (row-level `canUseHeuristic`
  false, Fig. 8) until genuine feedback lands, one tick later;
* **eviction** poisons the rows (-1) so a recycled slot can never leak the
  evicted request's indices into its successor.

`DecodeEngine.method_log` records which selector path (`gvr` / `radix` /
`exact` / `dense`) served each slot on each tick, straight from the
selector's own per-row report (`SelectorOutput.gvr_rows`);
`EngineReport` splits the counts into prefill-tick and decode-tick
buckets, and `gvr_hit_rate` is defined over decode ticks only.

## Paged KV layout

`DecodeEngine(kv_layout="paged", page_size=..., num_pages=...)` swaps the
dense per-slot caches for the pool-of-pages layout in `serve.paged`:
block tables translate logical token positions to physical pages, shared
prompt prefixes are admitted by ref-count through a hash-chain prefix
cache, admission fails over to queueing under page pressure, and DECODE
slots preempt the lowest-priority PREFILL slot rather than deadlock.
Decode stays bit-identical to the dense layout (the Top-K/feedback state
is logical-space; see `serve.paged`'s module docstring).

The sparse-attention stage inside the paged step is block-table-native
by default (`paged_attn="fused"`): attention gathers its Top-K rows
straight from the page pools through the logical→physical translation,
so the contiguous logical K/V views are never materialized and per-tick
gathered KV traffic is O(K) rather than O(N). `paged_attn="gather"`
keeps the materialize-then-attend oracle; both modes are pinned
bit-identical (DESIGN.md §paged, tests/test_paged_attn.py).

`seq_shards=S` (paged layout only) additionally shards the page pools —
and the whole serving step — over a 1-D sequence mesh for contexts no
single device can hold: per-device KV residency is max_len/S, selection
runs SP-GVR's O(1)-collective schedule, and decode stays bit-identical
to the single-device fused engine (DESIGN.md §sp-serving,
tests/test_sp_engine.py).

## Speculative decoding

`spec_depth=d` (+ a `serve.spec` drafter) turns the decode tick into a
d+1-position verify tick over the paged step — draft, verify, and roll
back exactly on rejection, with the GVR feedback causally extended
across the draft positions inside the tick. The decode step and the
verify tick are one paged forward over Q query rows a slot (Q = 1 and
Q = d+1; `verify_kernel="mq"`), one for each placement, single-device
and sequence-sharded; `verify_kernel="scan"` runs d+1 one-row steps
instead, the reference the mq form is pinned against. Greedy decode
stays bit-identical to the non-speculative engine for any draft trace
(DESIGN.md §spec-decode, tests/test_spec.py).
"""

from .engine import DecodeEngine, EngineReport, Request
from .feedback_pool import FeedbackPool
from .paged import (AdmitPlan, BlockPool, BlockTable, PagedKVManager,
                    PoolExhausted, PrefixCache, ShardedPagedKVManager)
from .sampling import sample_token
from .scheduler import (DECODE, DONE, PREFILL, QUEUED, FIFOScheduler,
                        LongestContextFirstScheduler, Scheduler,
                        make_scheduler)
from .spec import (Drafter, ModelDrafter, NgramDrafter, ReplayDrafter,
                   ScriptedDrafter)

__all__ = [
    "DecodeEngine", "EngineReport", "Request",
    "FeedbackPool",
    "AdmitPlan", "BlockPool", "BlockTable", "PagedKVManager",
    "PoolExhausted", "PrefixCache", "ShardedPagedKVManager", "sample_token",
    "Scheduler", "FIFOScheduler", "LongestContextFirstScheduler",
    "make_scheduler", "QUEUED", "PREFILL", "DECODE", "DONE",
]
