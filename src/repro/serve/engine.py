"""Continuous-batching decode engine over the model API.

One `DecodeEngine` owns a fixed pool of B slots (the batch axis of the
decode state). Per tick it:

  1. admits queued requests into freed slots (scheduler policy), resetting
     the slot's GVR feedback through the `FeedbackPool`;
  2. streams one `prefill_chunk` of each PREFILL slot's prompt into the
     pool: one pool-wide step per chunk position in which every PREFILL
     slot takes its next prompt token (other slots are untouched — they
     keep decoding the same tick);
  3. runs ONE jitted `serve_step` over the whole pool for the DECODE slots,
     samples their next tokens (greedy by default; per-request temperature/
     top-p with a seeded PRNG key otherwise), and merges the new state back
     only for active rows — finished/idle/prefilling slots keep their state
     bit-for-bit, and the step never recompiles (static shapes, masking
     instead of shape changes, per the NEG_SENTINEL convention);
  4. retires finished slots (eos or max_new_tokens), recycling their
     feedback rows so no prediction survives into the next admission.

Every served slot-tick is logged with the selector path that actually
produced its Top-K (`gvr`/`radix`/`exact`, or `dense` before the DSA gate
opens) — taken from the selector's own per-row report, not inferred.
`EngineReport` splits the counts by phase: prefill chunks are admission-
adjacent (their first tick can never be warm), so `gvr_hit_rate` is
defined over decode ticks only.

Slot lifecycle (one request, see also serve.scheduler):

    QUEUED → [admit: slot reset, feedback re-seeded cold] → PREFILL
           → [first tick after admission is always cold — the selector's
              per-row canUseHeuristic is false until genuine feedback
              lands one tick later] → DECODE (warm steady state)
           → [evict on eos/max_new_tokens: pages released, feedback row
              poisoned so no prediction leaks to the slot's successor]
           → DONE

Preemption order (paged layout, under page pressure): reclaim cold
prefix-cache pages first; then preempt the PREFILL slot with the most
remaining prompt tokens (least sunk cost, ties toward the latest
admission); only if every other slot is decoding, preempt the DECODE slot
with the fewest generated tokens. The victim returns to the FRONT of the
queue and replays deterministically.

KV layouts (`kv_layout`):

* "dense" — per-slot `(num_slots, max_len)` caches (PR 1 behavior).
* "paged" — pool-of-pages caches behind `serve.paged.PagedKVManager`:
  per-slot block tables translate logical positions to physical pages,
  shared prompt prefixes are admitted by ref-count through the prefix
  cache (the engine then skips streaming the shared tokens, replaying at
  least the last prompt token), admission fails over to queueing when
  pages are exhausted, and a DECODE slot that needs a page under a full
  pool preempts the lowest-priority PREFILL slot (pages released, feedback
  poisoned, request re-queued at the front) instead of deadlocking. Decode
  is bit-identical to the dense layout for the same trace — Top-K and the
  GVR feedback buffer live in logical token space (see serve.paged).
  `paged_attn` picks the sparse-attention form inside the step: "fused"
  (default) is block-table-native — attention gathers its Top-K rows
  straight from the page pools, O(K) traffic per tick — while "gather"
  materializes the contiguous logical view first (the PR-2 oracle both
  modes are pinned bit-identical against; see DESIGN.md §paged).
* "paged" + `seq_shards=S` — sequence-sharded serving (DESIGN.md
  §sp-serving): the page pools partition over a 1-D sequence mesh
  (device s owns the pages of logical span s; `num_pages` is PER SHARD —
  the per-device KV budget), the step runs inside a shard_map routing
  selection through SP-GVR's O(1)-collective schedule and attention
  through the O(K)-psum paged assembly (`sparse/sp_dsa.py`), and the
  host-side paging (`serve.paged.ShardedPagedKVManager`) resolves
  admission/COW/preemption pressure against each page's OWNER shard.
  Decode is bit-identical to the single-device fused engine — tokens,
  method log, GVR hit rate, preemption schedule (tests/test_sp_engine.py)
  — while per-device KV residency drops to max_len/S and per-tick
  collective traffic is independent of context length.

Speculative decoding (`spec_depth=d`, paged layouts only — serve.spec,
DESIGN.md §spec-decode): a host-side drafter proposes up to d next tokens
per DECODE slot, the decode tick becomes ONE jitted verify tick scoring
all d+1 positions through the paged step (GVR feedback causally extended
inside the tick), and acceptance/rollback restore the state — length,
feedback buffers, block tables, ref-counts — to exactly the
non-speculative trajectory. Greedy spec decode is bit-identical to
non-spec decode for every accept/reject trace (tests/test_spec.py);
sampled requests verify at depth 0 (greedy-only speculation).

Bit-exactness: every per-slot computation in `serve_step` is row-parallel
(attention, norms, projections act per batch row), so a request decoded in
a busy pool produces bit-identical tokens to the same request decoded
alone. Row-coupled families (MoE with shared expert capacity) void that
guarantee; the engine targets the row-parallel decode families.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.models.transformer import PAGED_NEVER_WRITE
from repro.models.transformer import GVR_COUNTERS  # noqa: F401  (re-export)

from . import sampling
from .feedback_pool import FeedbackPool
from .paged import PagedKVManager, PoolExhausted, ShardedPagedKVManager
from .scheduler import DECODE, DONE, PREFILL, QUEUED, Scheduler, make_scheduler


def _add_counts(total, counts, active):
    """The pool-global counters plus the active rows' GVR counts (B, 4)
    of one step (the model's `sel_counts`)."""
    return total + jnp.sum(jnp.where(active[:, None], counts, 0), axis=0)


@dataclasses.dataclass(eq=False)       # identity equality: the scheduler
class Request:                         # queue must never compare ndarray fields
    uid: int
    prompt: np.ndarray                 # (P,) int32 prompt tokens
    max_new_tokens: int = 16
    arrival: int = 0                   # tick at which the request may admit
    # sampling policy: temperature == 0 → greedy (the bit-exact default)
    temperature: float = 0.0
    top_p: float = 1.0
    seed: Optional[int] = None         # PRNG seed (default: uid)
    # speculative decoding: per-request draft-depth cap, clamped to the
    # engine's (static) spec_depth; None = use the engine's. Sampled
    # requests (temperature > 0) always verify with depth 0 — greedy-only
    # speculation (serve.spec package doc).
    spec_depth: Optional[int] = None
    # lifecycle bookkeeping (engine-owned)
    phase: str = QUEUED
    slot: Optional[int] = None
    prefill_pos: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    admitted_at: Optional[int] = None
    finished_at: Optional[int] = None
    logits_log: List[np.ndarray] = dataclasses.field(default_factory=list)
    preemptions: int = 0
    # paged-layout internals
    _materialized: int = 0             # prompt positions backed by shared pages
    _skip: int = 0                     # prefill_pos at admission (cache skip)
    _key: Optional[jnp.ndarray] = None

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if len(self.prompt) == 0:
            raise ValueError(f"request {self.uid}: empty prompt")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError(f"request {self.uid}: top_p must be in (0, 1], "
                             f"got {self.top_p}")
        if self.spec_depth is not None and self.spec_depth < 0:
            raise ValueError(f"request {self.uid}: spec_depth must be >= 0, "
                             f"got {self.spec_depth}")


@dataclasses.dataclass
class EngineReport:
    """One `run()` window's telemetry (the engine may be reused; every
    field is a delta over that window, not a lifetime total).

    * `ticks` / `wall_s` — engine ticks driven and wall-clock seconds.
    * `decoded_tokens` / `prefill_tokens` — DELIVERED work only: a
      preempted pass's tokens are rolled back when the request re-queues
      (its method_log entries stay — those selector invocations really
      ran, so per-tick cost telemetry keeps them).
    * `completed` — requests that reached DONE inside the window.
    * `method_counts` — selector path (`gvr`/`radix`/`exact`/`dense`) per
      served slot-tick, both phases combined; `prefill_method_counts` /
      `decode_method_counts` split it by phase and partition it exactly.
    * `gvr_hit_rate` (property) — GVR coverage of DECODE ticks ONLY. The
      first chunk after an admission can never be warm, so folding prefill
      in would dilute the steady-state serving metric; prefill coverage is
      `prefill_gvr_hit_rate`.
    * `preemptions` — slots evicted back to the queue under page pressure.
    * `prefix_hit_tokens` — prompt tokens served from the prefix cache
      instead of being streamed (paged layout only).
    * `spec_ticks` / `spec_drafted` / `spec_accepted` — speculative-mode
      telemetry (spec_depth > 0 only): per-SLOT verify passes that
      carried at least one draft token (one engine tick verifying two
      drafting slots counts 2 — the unit the drafted/accepted totals
      amortize over), draft tokens proposed, draft tokens accepted.
      `spec_acceptance_rate` (property) = accepted / drafted. Method-log
      entries (and hence `gvr_hit_rate`) count ACCEPTED positions only —
      the positions that correspond one-to-one to non-speculative ticks —
      which is what keeps the report bit-comparable to a non-spec run;
      the wasted (rejected) verify positions are visible as
      `spec_drafted - spec_accepted`.
    * `gvr_hit_rate_by_draft_pos` — per verify-tick position j (0 = the
      non-speculative input token, j >= 1 = draft depth j), the fraction
      of EXECUTED positions the GVR path served. Position j warms from
      position j-1's selection inside the tick, so this list is the
      paper's "how does the prev-Top-K hit rate degrade with draft depth"
      measurement (BENCH_spec.json records it per depth).
    * `gvr_secant_iters_mean` / `gvr_fallbacks` — from the on-device GVR
      counters (`DecodeEngine.counters()`): the secant iterations (the
      paper's I, which it expects to be 1–2) per row-layer of an active
      slot that the GVR path served in the window — prefill and decode
      steps alike, and every executed verify position under speculation
      — and how many of those row-layers fell back to GVR's safety net.
      The operator's view of whether the temporal prediction still holds.
    * `peak_page_utilization` — max utilization of the MOST-PRESSURED
      pool over the window's ticks (the single pool, or the hottest
      shard's pool under `seq_shards` — an aggregate ratio could read
      half-empty while one shard saturates and preempts), re-baselined to
      the live state at `run()` entry (paged layout only; 0.0 for dense).
    """
    ticks: int
    wall_s: float
    decoded_tokens: int
    prefill_tokens: int
    completed: int
    method_counts: Dict[str, int]                  # combined (both phases)
    prefill_method_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    decode_method_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    preemptions: int = 0
    prefix_hit_tokens: int = 0                     # prompt tokens not streamed
    peak_page_utilization: float = 0.0             # paged layout only
    spec_ticks: int = 0                            # slot verify passes w/ drafts
    spec_drafted: int = 0                          # draft tokens proposed
    spec_accepted: int = 0                         # draft tokens accepted
    gvr_hit_rate_by_draft_pos: List[float] = dataclasses.field(
        default_factory=list)
    gvr_secant_iters_mean: float = 0.0             # per GVR row-layer
    gvr_fallbacks: int = 0                         # GVR row-layers

    @property
    def tokens_per_s(self) -> float:
        return self.decoded_tokens / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def spec_acceptance_rate(self) -> float:
        return (self.spec_accepted / self.spec_drafted
                if self.spec_drafted else 0.0)

    @property
    def gvr_hit_rate(self) -> float:
        """GVR coverage of DECODE ticks. Prefill chunks are excluded: the
        first chunk after an admission can never be warm, so folding
        prefill in dilutes the steady-state serving metric the paper's
        claim is about (prefill coverage is reported separately)."""
        total = sum(self.decode_method_counts.values())
        return (self.decode_method_counts.get("gvr", 0) / total
                if total else 0.0)

    @property
    def prefill_gvr_hit_rate(self) -> float:
        total = sum(self.prefill_method_counts.values())
        return (self.prefill_method_counts.get("gvr", 0) / total
                if total else 0.0)


class DecodeEngine:
    """Fixed-slot continuous-batching decode engine (see module docstring)."""

    def __init__(self, model, params, *, num_slots: int, max_len: int,
                 prefill_chunk: int = 8, scheduler="fifo",
                 eos_id: Optional[int] = None, record_logits: bool = False,
                 kv_layout: str = "dense", page_size: int = 16,
                 num_pages: Optional[int] = None, prefix_caching: bool = True,
                 paged_attn: str = "fused", gather_granularity: str = "token",
                 seq_shards: int = 1, mesh=None,
                 spec_depth: int = 0, drafter=None,
                 verify_kernel: str = "scan"):
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if paged_attn not in ("fused", "gather"):
            raise ValueError(f"unknown paged_attn {paged_attn!r} "
                             f"(expected 'fused' or 'gather')")
        if gather_granularity not in ("token", "page"):
            raise ValueError(f"unknown gather_granularity "
                             f"{gather_granularity!r} "
                             f"(expected 'token' or 'page')")
        if gather_granularity == "page" and kv_layout != "paged":
            raise ValueError(
                "gather_granularity='page' requires kv_layout='paged' "
                "(page-granular DMA addresses the page pools)")
        if gather_granularity == "page" and seq_shards > 1:
            raise ValueError(
                "gather_granularity='page' is not supported under "
                "seq_shards > 1: the sharded attention assembles selected "
                "rows via the O(K) psum, not the paged gather")
        if verify_kernel not in ("scan", "mq"):
            raise ValueError(f"unknown verify_kernel {verify_kernel!r} "
                             f"(expected 'scan' or 'mq')")
        if spec_depth < 0:
            raise ValueError(f"spec_depth must be >= 0, got {spec_depth}")
        if spec_depth > 0 and kv_layout != "paged":
            raise ValueError(
                "spec_depth > 0 requires kv_layout='paged': the verify "
                "tick runs through the paged step and its rollback is the "
                "page-cursor rewind (serve.spec)")
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.prefill_chunk = int(prefill_chunk)
        self.eos_id = eos_id
        self.record_logits = record_logits
        self.kv_layout = kv_layout
        self.paged_attn = paged_attn
        self.gather_granularity = gather_granularity
        self.verify_kernel = verify_kernel
        self.seq_shards = int(seq_shards)
        self.mesh = mesh
        self.scheduler: Scheduler = (scheduler if isinstance(scheduler, Scheduler)
                                     else make_scheduler(scheduler))
        self.pool = FeedbackPool(model, self.num_slots)

        if self.seq_shards > 1:
            # sequence-sharded serving (DESIGN.md §sp-serving): the paged
            # pool partitions over a 1-D sequence mesh and serve_step runs
            # the SP-GVR path inside a shard_map
            if kv_layout != "paged":
                raise ValueError("seq_shards > 1 requires kv_layout='paged' "
                                 "(the dense layout has no sharded pool)")
            if paged_attn != "fused":
                raise ValueError(
                    "seq_shards > 1 requires paged_attn='fused': the "
                    "sharded step is block-table-native per shard and "
                    "never materializes a logical view to 'gather' from")
            cfg = model.cfg
            if not (cfg.dsa.enabled and self.max_len > cfg.dsa.min_n):
                raise ValueError(
                    "seq_shards > 1 requires the DSA gate open "
                    f"(dsa.enabled and max_len > dsa.min_n="
                    f"{cfg.dsa.min_n}): the sequence-sharded step has no "
                    "dense fallback attention")
            if self.max_len % (int(page_size) * self.seq_shards) != 0:
                raise ValueError(
                    f"max_len ({self.max_len}) must be a multiple of "
                    f"page_size × seq_shards ({page_size}×{self.seq_shards})"
                    f" — shard token spans must be page-aligned")
            if self.mesh is None:
                from repro.launch.mesh import make_seq_mesh
                self.mesh = make_seq_mesh(self.seq_shards)
            if ("seq" not in self.mesh.axis_names
                    or self.mesh.shape["seq"] != self.seq_shards):
                raise ValueError(
                    f"mesh must carry a 'seq' axis of extent "
                    f"{self.seq_shards}, got {dict(self.mesh.shape)}")
            axes = model.sp_paged_state_batch_axes()
            if axes is None:
                raise ValueError(f"model family {model.cfg.family!r} does "
                                 f"not expose a sequence-sharded paged "
                                 f"decode state")
            self._axes = axes
            span_pages = self.max_len // int(page_size) // self.seq_shards
            # `num_pages` is PER SHARD here: it is the per-device KV budget
            # the sharded deployment actually provisions
            per_shard = (int(num_pages) if num_pages is not None
                         else self.num_slots * span_pages)
            self.num_pages = per_shard * self.seq_shards
            # duck-typed manager surface shared by both paged layouts —
            # engine code must stay on the manager-level accessors
            # (never `.pool`, which the sharded manager does not have)
            self.kv: Optional[Union[PagedKVManager, ShardedPagedKVManager]] \
                = ShardedPagedKVManager(
                num_slots=self.num_slots, max_len=self.max_len,
                page_size=int(page_size), num_pages_per_shard=per_shard,
                seq_shards=self.seq_shards, prefix_caching=prefix_caching)
            # pools are created split over the mesh (no device ever holds
            # the whole pool); weights are replicated once here, not moved
            # to every device on every step
            self.state = model.init_sp_paged_decode_state(
                self.num_slots, self.max_len, num_pages_per_shard=per_shard,
                page_size=int(page_size), seq_shards=self.seq_shards,
                mesh=self.mesh)
            self.params = jax.device_put(
                params, NamedSharding(self.mesh, PartitionSpec()))
        elif kv_layout == "paged":
            axes = model.paged_state_batch_axes()
            if axes is None:
                raise ValueError(f"model family {model.cfg.family!r} does "
                                 f"not expose a paged decode state")
            self._axes = axes
            pages_per_slot = -(-self.max_len // int(page_size))
            if self.max_len % int(page_size) != 0:
                raise ValueError(
                    f"max_len ({self.max_len}) must be a multiple of "
                    f"page_size ({page_size}) — the gathered logical view "
                    f"must match the dense cache shape exactly")
            self.num_pages = (int(num_pages) if num_pages is not None
                              else self.num_slots * pages_per_slot)
            self.kv = PagedKVManager(
                num_slots=self.num_slots, max_len=self.max_len,
                page_size=int(page_size), num_pages=self.num_pages,
                prefix_caching=prefix_caching)
            self.state = model.init_paged_decode_state(
                self.num_slots, self.max_len, num_pages=self.num_pages,
                page_size=int(page_size))
        else:
            axes = model.state_batch_axes()
            if axes is None:
                raise ValueError(f"model family {model.cfg.family!r} does not "
                                 f"expose slot-wise decode state")
            self._axes = axes
            self.kv = None
            self.state = model.init_decode_state(self.num_slots, self.max_len)
        # pool-global (no slot axis, so the row merge passes it whole)
        # the names of its entries: the columns of the step's counts
        self.counter_names = model.step_counters()
        self.state["gvr_counters"] = self._zero_counters()

        # speculative decoding (serve.spec): the drafter proposes up to
        # spec_depth tokens per DECODE slot per tick; the verify tick
        # scores them all in one jitted scan. Default drafter: self-
        # drafting n-gram lookup (no second model).
        self.spec_depth = int(spec_depth)
        if drafter is None and self.spec_depth > 0:
            from .spec import NgramDrafter
            drafter = NgramDrafter()
        self.drafter = drafter
        self.spec_ticks = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self._spec_pos_hits = np.zeros((self.spec_depth + 1,), np.int64)
        self._spec_pos_total = np.zeros((self.spec_depth + 1,), np.int64)

        self.slots: List[Optional[Request]] = [None] * self.num_slots
        self.tick_count = 0
        self.decoded_tokens = 0
        self.prefill_tokens = 0
        self.preemptions = 0
        self.peak_occupancy = 0
        self.peak_pages_in_use = 0
        self.peak_pool_util = 0.0
        self.completed: List[Request] = []
        # per-request: [(tick, phase, method), ...] — which selector path
        # served the request on each tick it was live
        self.method_log: Dict[int, List[Tuple[int, str, str]]] = {}

        cfg = self.cfg
        self._use_dsa = bool(cfg.dsa.enabled) and self.max_len > cfg.dsa.min_n
        # Static fallback method for cold rows, mirroring the selector's
        # trace-time auto gate over n = max_len (selector.select_topk).
        if not self._use_dsa:
            self._cold_method = "dense"
        elif cfg.dsa.selector != "auto":
            self._cold_method = cfg.dsa.selector
        else:
            # auto + use_dsa implies max_len > min_n, so the selector's
            # cold-row fallback is always radix (never the tiny-n exact path)
            self._cold_method = "radix"

        # one compiled pool-wide step serves decode and prefill. The pool
        # state is donated: each call updates the KV pools in place instead
        # of holding a second copy (host code only reads the state a call
        # returns)
        self._tick_fn = jax.jit(self._tick_impl, donate_argnums=(1,))
        self._spec_fn = (jax.jit(self._tick_spec_impl, donate_argnums=(1,))
                         if self.spec_depth > 0 else None)

    # ---- jitted kernels -------------------------------------------------

    def _serve_step(self, params, state, tokens, min_write_pos=None,
                    with_counts=False):
        """Layout dispatch: one model step over the given (sub-)pool.
        `with_counts` adds the rows' GVR counts (B, 4) as a third result."""
        if self.seq_shards > 1:
            return self.model.serve_step_sp_paged(
                params, state, tokens, min_write_pos=min_write_pos,
                mesh=self.mesh, with_counts=with_counts)
        if self.kv is not None:
            return self.model.serve_step_paged(
                params, state, tokens, min_write_pos=min_write_pos,
                paged_attn=self.paged_attn,
                gather_granularity=self.gather_granularity,
                with_counts=with_counts)
        return self.model.serve_step(params, state, tokens,
                                     with_counts=with_counts)

    def _merge_active(self, new_state, state, active):
        """Keep `new_state` only for active rows; pool-global leaves (the
        paged page arrays — absent from the axes map) pass through whole,
        their inactive-row writes having been redirected to the sink page
        inside the step."""
        merged = {}
        for key, arr in new_state.items():
            ax = self._axes.get(key)
            if ax is None:
                merged[key] = arr
                continue
            shape = [1] * arr.ndim
            shape[ax] = self.num_slots
            merged[key] = jnp.where(active.reshape(shape), arr, state[key])
        return merged

    def _zero_counters(self):
        zeros = jnp.zeros((len(self.counter_names),), jnp.int32)
        if self.seq_shards > 1:
            # replicated over the mesh like the rest of the small state: a
            # leaf on one device would be another step signature
            zeros = jax.device_put(zeros,
                                   NamedSharding(self.mesh, PartitionSpec()))
        return zeros

    def _tick_impl(self, params, state, tokens, active, min_write_pos):
        """One pool-wide step, for decode and prefill alike: active rows
        take one token each, inactive rows keep their old state. Paged
        layout: inactive rows additionally redirect their cache write to
        the sink page (pool-global page leaves can't be row-merged), and
        active rows skip it below `min_write_pos` — the shared-prefix
        replay must not touch pages it shares. The active rows' GVR
        row-layers, secant iterations and fallbacks add to the state's
        `gvr_counters`. Returns the merged state, the argmax tokens, the
        logits and which active rows the GVR path served."""
        mwp = (jnp.where(active, min_write_pos, jnp.int32(PAGED_NEVER_WRITE))
               if self.kv is not None else None)
        if "sel_gvr" not in state:
            logits, new_state = self._serve_step(params, state, tokens, mwp)
            merged = self._merge_active(new_state, state, active)
            gvr = jnp.zeros_like(active)
        else:
            logits, new_state, counts = self._serve_step(
                params, state, tokens, mwp, with_counts=True)
            merged = self._merge_active(new_state, state, active)
            merged["gvr_counters"] = _add_counts(state["gvr_counters"],
                                                 counts, active)
            gvr = new_state["sel_gvr"][0] & active
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return merged, next_tok, logits, gvr

    def _tick_spec_impl(self, params, state, tokens, active, draft_len,
                        max_accept):
        """One speculative verify tick over the pool: all d+1 draft
        positions of every active DECODE row scored in one scan of the
        paged step, with in-graph greedy acceptance and exact rollback of
        length/feedback to the accepted position (serve.spec; the model
        side is transformer.serve_step_spec_paged). Inactive rows keep
        their state bit-for-bit, exactly as in `_tick_impl`."""
        mwp = jnp.where(active, jnp.int32(0), jnp.int32(PAGED_NEVER_WRITE))
        eos = self.eos_id if self.eos_id is not None else -1
        if self.seq_shards > 1:
            out = self.model.serve_step_sp_spec_paged(
                params, state, tokens, mesh=self.mesh, draft_len=draft_len,
                max_accept=max_accept, eos_id=eos, min_write_pos=mwp,
                verify_kernel=self.verify_kernel)
        else:
            out = self.model.serve_step_spec_paged(
                params, state, tokens, draft_len=draft_len,
                max_accept=max_accept, eos_id=eos, min_write_pos=mwp,
                paged_attn=self.paged_attn, verify_kernel=self.verify_kernel,
                gather_granularity=self.gather_granularity)
        out_tokens, accept_len, logits_all, sel_pos, counts, new_state = out
        merged = self._merge_active(new_state, state, active)
        merged["gvr_counters"] = _add_counts(state["gvr_counters"], counts,
                                             active)
        return merged, out_tokens, accept_len, logits_all, sel_pos

    # ---- host-side lifecycle --------------------------------------------

    def submit(self, request: Request) -> None:
        if len(request.prompt) + request.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {request.uid}: prompt ({len(request.prompt)}) + "
                f"max_new ({request.max_new_tokens}) exceeds max_len "
                f"({self.max_len})")
        if self.kv is not None:
            total = len(request.prompt) + request.max_new_tokens
            # manager-level check: the sharded layout must bound each
            # SHARD's span demand by that shard's own pool, not the
            # aggregate (a global-pool check would admit requests that can
            # never map their pages — see ShardedPagedKVManager)
            if not self.kv.can_ever_hold(total):
                raise ValueError(
                    f"request {request.uid}: "
                    f"{self.kv.sizing_error(total)} — it could never admit")
        self.method_log.setdefault(request.uid, [])
        self.scheduler.submit(request)

    def counters(self) -> Dict[str, int]:
        """The on-device GVR counters since the last read, as Python ints,
        which are then reset (keys: `counter_names`, the model's
        `step_counters()`: GVR_COUNTERS, then any its expert layers add).
        `gvr_row_layers` counts the row-layers of active slots whose Top-K
        the GVR path served, `gvr_secant_iters` their secant iterations,
        `gvr_fallbacks` those that fell back to GVR's safety net, and
        `radix_row_layers` the row-layers of active slots whose radix path
        was computed (every row of a layer whose batch held a cold row,
        none where all rows were warm: 1 − `radix_row_layers` ÷ the active
        row-layers with DSA on is the share of radix the selector skipped).
        `held_expert_rows` counts the active rows' routed (token, expert)
        assignments that land on the experts this chip holds, over the
        expert layers: top_k × expert layers × tokens × held/routed on
        average. Every step adds to them on the device; a read is their
        only transfer, so read them at the edges of a measured window, not
        per tick (`run()` reads them at its start and end).

        The leaf is int32. A step adds at most 12 iterations
        (DEFAULT_MAX_SECANT) per row-layer, so at the chat cell's shape and
        rate (24 layers, 64 slots, ~10 pool-wide steps a second) it holds
        at least 3.4 hours between reads, and at the decode cell's (7
        layers, 4 slots, ~33 steps a second) at least 53 hours."""
        values = np.asarray(self.state["gvr_counters"])
        self.state["gvr_counters"] = self._zero_counters()
        return {key: int(v) for key, v in zip(self.counter_names, values)}

    def _log(self, req: Request, method: str) -> None:
        self.method_log[req.uid].append((self.tick_count, req.phase, method))

    def _method_name(self, gvr_row: bool) -> str:
        return "gvr" if gvr_row else self._cold_method

    def _next_token(self, req: Request, argmax_tok: int, logits_row) -> int:
        """Greedy by default; temperature/top-p sampling with the request's
        own PRNG key otherwise (key advances one split per sampled token)."""
        if req.temperature <= 0.0:
            return int(argmax_tok)
        req._key, sub = jax.random.split(req._key)
        return sampling.sample_token(logits_row, sub,
                                     temperature=req.temperature,
                                     top_p=req.top_p)

    # ---- paged-layout page bookkeeping ----------------------------------

    def _push_page_table(self) -> None:
        if self.kv is not None and self.kv.dirty:
            table = jnp.asarray(self.kv.table_array())
            if self.seq_shards > 1:
                # keep it replicated over the mesh, as created: a table on
                # one device is another signature, and the step would
                # compile again
                table = jax.device_put(table,
                                       NamedSharding(self.mesh, PartitionSpec()))
            self.state["page_table"] = table
            self.kv.dirty = False

    def _copy_page(self, cow) -> None:
        """Device-side page copy backing a copy-on-write remap. The
        descriptor is `(src, dst)` for the single-pool layout and
        `(shard, src, dst)` for the sequence-sharded one (page ids are
        shard-local there — copying across the global page axis would hit
        the wrong shard's pool)."""
        for key in ("k_pages", "v_pages", "kv_pages", "idx_k_pages"):
            if key in self.state:
                arr = self.state[key]
                if self.seq_shards > 1:
                    shard, src, dst = cow
                    self.state[key] = arr.at[:, shard, dst].set(
                        arr[:, shard, src])
                else:
                    src, dst = cow
                    self.state[key] = arr.at[:, dst].set(arr[:, src])

    def _preempt_victim(self, exclude: Optional[int] = None,
                        shard: Optional[int] = None) -> Optional[int]:
        """Lowest-priority victim under page pressure. PREFILL slots first
        (most remaining prompt tokens = least sunk cost, ties toward the
        latest admission); if every other slot is already decoding, fall
        back to the DECODE slot with the fewest generated tokens — losing a
        nearly-done request to save a barely-started one would waste the
        most work.

        Shard-aware (sequence-sharded layout): when the exhaustion names a
        pressured shard, only slots actually HOLDING pages in that shard
        are candidates — evicting a slot whose pages all live in other
        shards can never free a page where the allocation failed, so the
        old shard-blind order could burn a victim's work for nothing
        (regression-pinned in tests/test_sp_engine.py). With no holder
        left, the caller's give-up path reports the per-shard squeeze."""
        def holds(s):
            return shard is None or self.kv.pages_in_shard(s, shard) > 0
        best, best_key = None, None
        for s, req in enumerate(self.slots):
            if req is None or req.phase != PREFILL or s == exclude:
                continue
            if not holds(s):
                continue
            key = (len(req.prompt) - req.prefill_pos, req.admitted_at)
            if best_key is None or key > best_key:
                best, best_key = s, key
        if best is not None:
            return best
        for s, req in enumerate(self.slots):
            if req is None or req.phase != DECODE or s == exclude:
                continue
            if not holds(s):
                continue
            key = (-len(req.generated), req.admitted_at)
            if best_key is None or key > best_key:
                best, best_key = s, key
        return best

    def _preempt(self, victim: int) -> None:
        """Evict a slot back to the queue: pages released, feedback row
        poisoned, request re-queued at the front. Its streamed prefix (and,
        for a DECODE victim, its generated tokens) is discarded — the replay
        regenerates it deterministically (greedy is a pure function of the
        prompt; sampling re-derives the same per-request key). The token
        counters are rolled back with it, so the report's decoded/prefill
        totals stay delivered-work only; method_log keeps the discarded
        pass's entries — those selector invocations really ran (cost
        telemetry, per-tick)."""
        req = self.slots[victim]
        self.kv.release_slot(victim)
        self.state = self.pool.evict(self.state, victim)
        self.decoded_tokens -= len(req.generated)
        self.prefill_tokens -= max(req.prefill_pos - req._skip, 0)
        req.phase, req.slot = QUEUED, None
        req.prefill_pos = 0
        req._materialized = 0
        req._skip = 0
        req.generated.clear()
        req.logits_log.clear()
        req.preemptions += 1
        self.slots[victim] = None
        self.preemptions += 1
        if self.drafter is not None:
            # stateful drafters resync from scratch on the replay — the
            # same drafts re-derive deterministically
            self.drafter.release(req.uid)
        self.scheduler.requeue(req)

    def _ensure_decode_page(self, slot: int, pos: int) -> None:
        """Map (and COW-protect) the page a DECODE slot is about to write.
        Pool pressure resolves in order: reclaim cold prefix-cache pages →
        preempt the lowest-priority slot (PREFILL first) → give up (the
        requester alone exceeds the pool — a sizing error, caught at
        submit)."""
        while True:
            try:
                self.kv.ensure_mapped(slot, pos)
                cow = self.kv.ensure_writable(slot, pos)
                if cow is not None:
                    self._copy_page(cow)
                return
            except PoolExhausted as exc:
                victim = self._preempt_victim(exclude=slot,
                                              shard=getattr(exc, "shard",
                                                            None))
                if victim is None:
                    # the original message names the binding pool (the
                    # sharded manager's says WHICH shard) — the aggregate
                    # page count would misstate a per-shard squeeze. Under
                    # the shard-aware victim filter "nothing left" means
                    # no other slot holds pages in THAT shard, so slot
                    # `slot`'s own span demand is what exceeds it.
                    raise RuntimeError(
                        f"page pool exhausted ({exc}) with nothing left "
                        f"to preempt: slot {slot} alone needs more pages "
                        f"than the binding pool holds — increase "
                        f"num_pages") from None
                self._preempt(victim)

    def _admit(self) -> None:
        for slot in range(self.num_slots):
            if self.slots[slot] is not None:
                continue
            req = self.scheduler.peek(self.tick_count)
            if req is None:
                return
            if self.kv is not None:
                plan = self.kv.admit(slot, req.prompt)
                if plan is None:
                    # pool exhausted: fail over to queueing (the request —
                    # and FIFO order — stay intact; retried next tick)
                    return
                self.scheduler.take(req)
                self.state = self.pool.admit(self.state, slot,
                                             seq_len_hint=len(req.prompt))
                req._materialized = plan.materialized
                req._skip = plan.skip_len
                req.prefill_pos = plan.skip_len
                if plan.skip_len:
                    self.state["length"] = \
                        self.state["length"].at[slot].set(plan.skip_len)
            else:
                self.scheduler.take(req)
                self.state = self.pool.admit(self.state, slot,
                                             seq_len_hint=len(req.prompt))
                req.prefill_pos = 0
                req._materialized = 0
                req._skip = 0
            if req.temperature > 0.0:
                # re-derived per admission: a preempted request replays the
                # same draws on its second pass (deterministic traces)
                req._key = sampling.request_key(
                    req.seed if req.seed is not None else req.uid)
            req.slot, req.phase = slot, PREFILL
            req.admitted_at = self.tick_count
            self.slots[slot] = req

    def _prefill_tick(self) -> None:
        """Stream one `prefill_chunk` of every PREFILL slot's prompt: one
        pool-wide step per chunk position, each PREFILL slot taking its
        next prompt token (DECODE slots sit these steps out)."""
        chunks = {s: r.prompt[r.prefill_pos:r.prefill_pos + self.prefill_chunk]
                  for s, r in enumerate(self.slots)
                  if r is not None and r.phase == PREFILL}
        if not chunks:
            return
        self._push_page_table()
        mwp = np.zeros((self.num_slots,), np.int32)
        for s in chunks:
            mwp[s] = self.slots[s]._materialized
        mwp = jnp.asarray(mwp)
        last = {}
        for i in range(max(len(c) for c in chunks.values())):
            tokens = np.zeros((self.num_slots,), np.int32)
            active = np.zeros((self.num_slots,), bool)
            for s, c in chunks.items():
                if i < len(c):
                    tokens[s], active[s] = c[i], True
            self.state, next_tok, logits, gvr = self._tick_fn(
                self.params, self.state, jnp.asarray(tokens),
                jnp.asarray(active), mwp)
            if i == 0:
                # the tick's dispatch decision is made at tick entry — log
                # the path that served the chunk's first token
                first_gvr = gvr
            for s, c in chunks.items():
                if i == len(c) - 1:
                    last[s] = (next_tok, logits)
        first_gvr = np.asarray(first_gvr)
        for s, c in chunks.items():
            req = self.slots[s]
            self._log(req, self._method_name(bool(first_gvr[s])))
            req.prefill_pos += len(c)
            self.prefill_tokens += len(c)
            if req.prefill_pos >= len(req.prompt):
                if self.kv is not None:
                    self.kv.commit_prefix(s, req.prompt)
                # the last prompt token's logits yield the first generation
                next_tok, logits = last[s]
                req.phase = DECODE
                req.generated.append(self._next_token(
                    req, int(next_tok[s]), logits[s]))
                if self.record_logits:
                    req.logits_log.append(np.asarray(logits[s]))
                self.decoded_tokens += 1
                self._maybe_finish(s)

    # ---- speculative decode tick (serve.spec) ---------------------------

    def _draft_depth(self, req: Request) -> int:
        """Draft depth for one DECODE slot, clamped to the engine's static
        depth, the request's own cap, its remaining max_new budget, and
        greedy-only speculation (sampled requests verify depth 0)."""
        depth = (self.spec_depth if req.spec_depth is None
                 else min(req.spec_depth, self.spec_depth))
        if req.temperature > 0.0:
            depth = 0
        return min(depth, req.max_new_tokens - len(req.generated) - 1)

    def _request_draft(self, req: Request) -> List[int]:
        """Host-side draft for one DECODE slot (see `_draft_depth`)."""
        depth = self._draft_depth(req)
        if depth <= 0:
            return []
        draft = self.drafter.draft(req, depth)
        return [int(t) for t in draft][:depth]

    def _collect_drafts(self, wanting: List[Tuple[int, Request]]
                        ) -> Dict[int, List[int]]:
        """Drafts for every drafting DECODE slot. Drafters exposing
        `draft_batch` (ModelDrafter) get ONE call covering all slots —
        their per-slot catch-up/rollout steps fold into batched model
        steps; the tokens are pinned identical to per-slot `draft` calls
        (serve.spec.drafter). Everything else drafts per slot."""
        batch_fn = getattr(self.drafter, "draft_batch", None)
        if batch_fn is not None:
            pairs = [(req, self._draft_depth(req)) for _, req in wanting]
            by_uid = batch_fn(pairs)
            return {s: [int(t) for t in by_uid.get(req.uid, [])][:depth]
                    for (s, req), (_, depth) in zip(wanting, pairs)}
        return {s: self._request_draft(req) for s, req in wanting}

    def _decode_tick_spec(self) -> None:
        """Speculative variant of `_decode_tick`: draft per slot, map the
        verify window's pages (up to d+1 write positions ahead — pool
        pressure may preempt here, exactly as in the non-spec tick, just
        earlier), run ONE verify tick, append the accepted tokens, and
        rewind each slot's page cursor to the accepted prefix so block
        tables and ref-counts end bit-identical to non-speculative decode
        (DESIGN.md §spec-decode)."""
        d1 = self.spec_depth + 1
        wanting = [(s, req) for s, req in enumerate(self.slots)
                   if req is not None and req.phase == DECODE]
        drafts: Dict[int, List[int]] = self._collect_drafts(wanting)
        with jax.profiler.TraceAnnotation("engine.pages"):
            for s in list(drafts):
                req = self.slots[s]
                if req is None or req.phase != DECODE:
                    drafts.pop(s)      # preempted while mapping another slot
                    continue
                pos0 = len(req.prompt) + len(req.generated) - 1
                for pos in range(pos0, pos0 + len(drafts[s]) + 1):
                    self._ensure_decode_page(s, pos)
            self._push_page_table()
        with jax.profiler.TraceAnnotation("engine.dispatch"):
            active = np.array([r is not None and r.phase == DECODE
                               for r in self.slots])
            if not active.any():
                return
            tokens = np.zeros((self.num_slots, d1), np.int32)
            draft_len = np.zeros((self.num_slots,), np.int32)
            max_accept = np.zeros((self.num_slots,), np.int32)
            for s, req in enumerate(self.slots):
                if not active[s]:
                    continue
                draft = drafts.get(s, [])
                tokens[s, 0] = req.generated[-1]
                tokens[s, 1:1 + len(draft)] = draft
                draft_len[s] = len(draft)
                max_accept[s] = req.max_new_tokens - len(req.generated) - 1
            self.state, out_tokens, accept_len, logits_all, sel_pos = \
                self._spec_fn(self.params, self.state, jnp.asarray(tokens),
                              jnp.asarray(active), jnp.asarray(draft_len),
                              jnp.asarray(max_accept))
        with jax.profiler.TraceAnnotation("engine.readback"):
            out_tokens = np.asarray(out_tokens)
            accept_len = np.asarray(accept_len)
            sel_pos = np.asarray(sel_pos)
            logits_np = (np.asarray(logits_all) if self.record_logits
                         else None)
        with jax.profiler.TraceAnnotation("engine.emit"):
            for s, req in enumerate(self.slots):
                if not active[s]:
                    continue
                a = int(accept_len[s])
                dlen = int(draft_len[s])
                for p in range(a + 1):
                    # accepted positions map one-to-one to non-spec ticks:
                    # log the selector path that really served each
                    self._log(req, self._method_name(bool(sel_pos[s, p])))
                    if p == 0:
                        # position 0 is the ordinary next-token step;
                        # sampled requests (always depth 0) draw from its
                        # logits
                        tok = self._next_token(req, int(out_tokens[s, 0]),
                                               logits_all[s, 0])
                    else:
                        tok = int(out_tokens[s, p])
                    req.generated.append(tok)
                    if self.record_logits:
                        # copy: a view would pin the whole per-tick
                        # (num_slots, d+1, vocab) block for the log's
                        # lifetime
                        req.logits_log.append(logits_np[s, p].copy())
                    self.decoded_tokens += 1
                # telemetry: every EXECUTED position (accepted or wasted)
                if dlen > 0:
                    self.spec_ticks += 1
                    self.spec_drafted += dlen
                    self.spec_accepted += a
                for j in range(dlen + 1):
                    self._spec_pos_total[j] += 1
                    self._spec_pos_hits[j] += bool(sel_pos[s, j])
                # page-cursor rewind: drop pages mapped past the accepted
                # prefix — rollback exactness vs non-speculative decode
                self.kv.rewind_slot(s, int(len(req.prompt)
                                           + len(req.generated) - 1))
                self._maybe_finish(s)

    def _decode_tick(self) -> None:
        if self.spec_depth > 0:
            return self._decode_tick_spec()
        if self.kv is not None:
            # map (and COW-protect) each DECODE slot's write page up front;
            # pool pressure may preempt PREFILL slots here
            with jax.profiler.TraceAnnotation("engine.pages"):
                for s, req in enumerate(self.slots):
                    if req is None or req.phase != DECODE:
                        continue
                    pos = len(req.prompt) + len(req.generated) - 1
                    self._ensure_decode_page(s, pos)
                self._push_page_table()
        with jax.profiler.TraceAnnotation("engine.dispatch"):
            active = np.array([r is not None and r.phase == DECODE
                               for r in self.slots])
            if not active.any():
                return
            tokens = np.zeros((self.num_slots,), np.int32)
            for s, req in enumerate(self.slots):
                if active[s]:
                    tokens[s] = req.generated[-1]
            self.state, next_tok, _logits, sel_gvr = self._tick_fn(
                self.params, self.state, jnp.asarray(tokens),
                jnp.asarray(active), jnp.zeros((self.num_slots,), jnp.int32))
        with jax.profiler.TraceAnnotation("engine.readback"):
            next_tok = np.asarray(next_tok)
            sel_gvr = np.asarray(sel_gvr)
        with jax.profiler.TraceAnnotation("engine.emit"):
            for s, req in enumerate(self.slots):
                if not active[s]:
                    continue
                self._log(req, self._method_name(bool(sel_gvr[s])))
                req.generated.append(self._next_token(req, int(next_tok[s]),
                                                      _logits[s]))
                if self.record_logits:
                    req.logits_log.append(np.asarray(_logits[s]))
                self.decoded_tokens += 1
                self._maybe_finish(s)

    def _maybe_finish(self, slot: int) -> None:
        req = self.slots[slot]
        if (len(req.generated) >= req.max_new_tokens
                or (self.eos_id is not None
                    and req.generated[-1] == self.eos_id)):
            # a span per retirement: once in a request's life, not per tick
            with jax.profiler.TraceAnnotation("engine.retire"):
                req.phase = DONE
                req.finished_at = self.tick_count
                if self.kv is not None:
                    self.kv.release_slot(slot)
                self.state = self.pool.evict(self.state, slot)
                self.slots[slot] = None
                if self.drafter is not None:
                    self.drafter.release(req.uid)
                self.completed.append(req)

    def tick(self) -> None:
        """One engine tick: admit → chunked prefill → pool decode → retire.

        The tick is a profiler step span (`engine.tick`), and its phases
        spans inside it: `engine.admit`, `engine.prefill`, `engine.pages`
        (mapping the decode pages, pushing the block table),
        `engine.dispatch` (the step's inputs and its call),
        `engine.readback` (tokens and GVR telemetry to the host) and
        `engine.emit` (per-slot bookkeeping), with `engine.retire` around
        each retirement inside it. A trace puts them on the host's clock
        beside the device's ops, so each stretch of idle device time can
        be laid to a phase; with no profiler running a span costs about a
        microsecond."""
        with jax.profiler.StepTraceAnnotation("engine.tick",
                                              step_num=self.tick_count):
            with jax.profiler.TraceAnnotation("engine.admit"):
                self._admit()
            # occupancy of the serving work this tick: measured
            # post-admission, pre-retirement (a slot admitted and one
            # retiring this same tick are both genuinely served by it)
            self.peak_occupancy = max(self.peak_occupancy,
                                      sum(r is not None for r in self.slots))
            with jax.profiler.TraceAnnotation("engine.prefill"):
                self._prefill_tick()
            self._decode_tick()
            if self.kv is not None:
                self.peak_pages_in_use = max(self.peak_pages_in_use,
                                             self.kv.pages_in_use)
                self.peak_pool_util = max(self.peak_pool_util,
                                          self.kv.hot_pool_utilization)
            self.tick_count += 1

    def idle(self) -> bool:
        return (all(r is None for r in self.slots)
                and self.scheduler.pending() == 0)

    def run(self, requests=None, max_ticks: int = 10_000) -> EngineReport:
        """Drive until drained (or `max_ticks`). Returns throughput +
        selector-path telemetry; per-request outputs live on the requests."""
        for r in (requests or []):
            self.submit(r)
        t0 = time.perf_counter()
        # peak counters are per-run-window, like every other report field:
        # re-baseline them to the engine's current live state (an engine
        # reused across runs would otherwise report the old window's peak)
        self.peak_occupancy = sum(r is not None for r in self.slots)
        self.peak_pages_in_use = (self.kv.pages_in_use
                                  if self.kv is not None else 0)
        self.peak_pool_util = (self.kv.hot_pool_utilization
                               if self.kv is not None else 0.0)
        start_tick = self.tick_count
        start_decoded = self.decoded_tokens
        start_prefill = self.prefill_tokens
        start_completed = len(self.completed)
        start_preempt = self.preemptions
        start_skipped = self.kv.skipped_tokens if self.kv is not None else 0
        start_spec = (self.spec_ticks, self.spec_drafted, self.spec_accepted)
        start_pos_hits = self._spec_pos_hits.copy()
        start_pos_total = self._spec_pos_total.copy()
        self.counters()                    # drop what came before the run
        while not self.idle() and self.tick_count - start_tick < max_ticks:
            self.tick()
        wall = time.perf_counter() - t0
        gvr = self.counters()
        # report THIS run's window only — the engine may be reused
        combined: Dict[str, int] = {}
        by_phase: Dict[str, Dict[str, int]] = {PREFILL: {}, DECODE: {}}
        for entries in self.method_log.values():
            for tick, phase, method in entries:
                if tick >= start_tick:
                    combined[method] = combined.get(method, 0) + 1
                    bucket = by_phase.setdefault(phase, {})
                    bucket[method] = bucket.get(method, 0) + 1
        pos_hits = self._spec_pos_hits - start_pos_hits
        pos_total = self._spec_pos_total - start_pos_total
        return EngineReport(
            ticks=self.tick_count - start_tick, wall_s=wall,
            decoded_tokens=self.decoded_tokens - start_decoded,
            prefill_tokens=self.prefill_tokens - start_prefill,
            completed=len(self.completed) - start_completed,
            method_counts=combined,
            prefill_method_counts=by_phase[PREFILL],
            decode_method_counts=by_phase[DECODE],
            preemptions=self.preemptions - start_preempt,
            prefix_hit_tokens=(self.kv.skipped_tokens - start_skipped
                               if self.kv is not None else 0),
            peak_page_utilization=(self.peak_pool_util
                                   if self.kv is not None else 0.0),
            spec_ticks=self.spec_ticks - start_spec[0],
            spec_drafted=self.spec_drafted - start_spec[1],
            spec_accepted=self.spec_accepted - start_spec[2],
            gvr_hit_rate_by_draft_pos=[
                float(h) / float(t) if t else 0.0
                for h, t in zip(pos_hits, pos_total)],
            gvr_secant_iters_mean=(
                gvr["gvr_secant_iters"] / gvr["gvr_row_layers"]
                if gvr["gvr_row_layers"] else 0.0),
            gvr_fallbacks=gvr["gvr_fallbacks"])
