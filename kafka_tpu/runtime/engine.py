"""The TPU inference engine: continuous batching over a paged KV pool.

This is the component that replaces the reference's remote LLM hop
(src/llm/portkey.py — an HTTPS proxy to provider GPUs) with local TPU
compute.  Architecture:

* **Jitted device programs** (runtime/step_programs.py; this module
  schedules and calls them).  `prefill` (per chunk-length bucket) writes
  prompt KV into a sequence's pages and samples the first token; `decode`
  advances *every* active batch slot one token (k tokens fused, or K+1
  speculated).  All donate the KV pool arrays, so the pool is updated in
  place — no per-step copies of cache memory.
* **Static shapes everywhere.** Prompt chunks are bucketed; the decode batch
  is a fixed max_batch wide with inactive slots masked (they write to the
  trash page and their samples are discarded).  Nothing recompiles as
  requests come and go — the continuous-batching invariant that keeps XLA
  happy.
* **Device-resident decode state.** The control arrays the decode step
  consumes (page table, last tokens, sequence lengths, sampling params) live
  on the device between steps.  The step function returns the next step's
  `last_tokens` and `seq_lens`, so in steady state the host uploads
  *nothing* — it re-uploads control arrays only when scheduling changes them
  (admit/retire/page-growth), and `last_tokens` is never round-tripped.
* **Pipelined async token fetch.** A blocking device→host read stalls the
  single scheduler thread for the device's backlog plus the copy.  Each
  step's sampled-token vector instead starts an async copy and joins a
  FIFO, hiding the copy behind dispatched steps; an entry is popped once
  its transfer has landed, so the host does not block on a read.  How far
  the host runs ahead of the device is bounded by `step()` itself: decode
  is dispatched only while at most one program's worth of steps
  (`multi_step`) is queued that the device has not been seen to finish, so
  one program runs and one waits behind it, and a new turn's prefill
  chunk, which is never held, waits for those two and no more.
  `fetch_lag` is the memory backstop behind that bound (a force-pop the
  run-ahead no longer reaches).
  Token events are therefore emitted a few steps late; the scheduler
  reconciles (stop tokens found in flight truncate the output and retire
  the slot, which at worst wasted the run-ahead's speculative decode
  steps).
* **Host-side scheduler** (`step()`): admit waiting requests when a batch
  slot + pages are free (prefill), dispatch one decode for everyone, drain
  matured token fetches, retire finished sequences.  Preemption: if page
  allocation fails mid-decode, in-flight fetches are drained and the
  youngest request is rolled back to the waiting queue with its pages freed
  (it will re-prefill later — the conversation itself is durable in the
  thread store, which is the recovery model the reference uses for
  sandboxes, SURVEY §5.4).

Determinism note: with f32 compute ("highest" matmul precision) resumed
requests reproduce their solo trajectories exactly (tested).  At serving
precision (bf16 on the MXU), rounding is matmul-shape-dependent, so a
re-prefill after preemption can flip greedy choices on near-tied logits —
the same property bf16 GPU serving stacks have; per-request seeds still make
*sampling* reproducible given identical logits.

The engine is synchronous; the async serving layer (llm/tpu_provider.py)
runs it on a dispatch thread and streams tokens out per-request.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import WINDOWED, ModelConfig, UnsupportedConfigError
from ..models.ffn import experts_int8
from ..models.mixers.state import tail_step_note
from .failpoints import failpoint
from .flight_recorder import (
    FlightRecorder,
    KIND_DECODE,
    KIND_MULTI,
    KIND_VERIFY,
    ring_default,
)
from .kv_cache import (
    OutOfPagesError,
    PagePool,
    SequencePages,
    StatePool,
    TRASH_PAGE,
    default_state_slots,
    make_kv_pool_arrays,
    page_table_array,
)
from .metrics import EngineMetrics
from .phase_clock import SchedClock
from .planner import (
    first_fit_bucket,
    first_fit_launches,
    grammar_table_cap_bytes,
    prefill_launches,
)
from .prefix_cache import PrefixCache
from .speculative import LaneSpeculator
from .step_programs import Fsm, Lanes, StepPrograms
from ..tracing import (
    add_event,
    annotate,
    profiler_annotations_enabled,
    record_span,
)

logger = logging.getLogger("kafka_tpu.engine")


class AdmissionError(RuntimeError):
    """submit() rejected a request because the waiting queue is at its
    configured bound (EngineConfig.max_waiting).  Carries the engine's
    Retry-After estimate so the serving layer can surface HTTP 429
    without another cross-thread round trip."""

    def __init__(self, depth: int, limit: int, retry_after_s: float):
        self.depth = depth
        self.limit = limit
        self.retry_after_s = retry_after_s
        super().__init__(
            f"waiting queue full ({depth}/{limit}); retry in "
            f"~{retry_after_s:.0f}s"
        )

class WindowedAttentionUnsupported(ValueError):
    """A model with sliding-window layers was given an engine option whose
    attention path has no windowed form.  Raised when the engine is built,
    naming the path (`.path`): no path may serve such a model and ignore
    its window."""

    def __init__(self, path: str, why: str):
        self.path = path
        super().__init__(
            f"{path} does not honour a sliding window and this model has "
            f"sliding-window layers: {why}")


class LatentAttentionUnsupported(ValueError):
    """A latent-attention (MLA) model was given an engine option that has
    no latent form: its pool row is one compressed vector a token shared by
    all heads, which these paths cannot read.  Raised when the engine is
    built, naming the option (`.path`)."""

    def __init__(self, path: str, why: str):
        self.path = path
        super().__init__(
            f"{path} has no latent-attention form and this model caches "
            f"latent rows: {why}")


class RoutedTreeUnsupported(ValueError):
    """A grouped-query model whose tree leads with dense layers ahead of
    routed ones (shared experts, perhaps a HELD share of the experts) was
    given an engine option that tree has no form for.  Raised when the
    engine is built, naming the option (`.path`).  A held share is one
    chip's part of an expert-parallel layer: the other chips and their
    exchange are absent, and nothing here stands in for them."""

    def __init__(self, path: str, why: str):
        self.path = path
        super().__init__(
            f"{path} has no form for a dense lead ahead of routed layers "
            f"on grouped-query attention: {why}")


WAITING, PREFILLING, PARKED, ACTIVE, DRAINING, FINISHED = (
    "waiting", "prefilling", "parked", "active", "draining", "finished"
)

# The run-ahead bound (_hold_decode) where no fused program sets it
# (multi_step <= 1): single steps then queue this deep, enough that a
# scheduler thread woken a few milliseconds late finds the device busy.
_HOLD_FLOOR_STEPS = 4
# What the engine's own loops (run_to_completion, generate) sleep between
# two held iterations; a serving loop waits on its inbox instead.
_HOLD_NAP_S = 0.0005

# Agent-native scheduling (ISSUE 20, README "Agent-native scheduling").
AGENT_DEMOTE_ENV = "KAFKA_TPU_AGENT_DEMOTE"
AGENT_LINGER_ENV = "KAFKA_TPU_AGENT_LINGER_MS"


class RecurrentStateUnsupported(ValueError):
    """An engine option (or a request) that cannot carry a model's
    recurrent state: the per-thread state of its state-space or short
    convolution layers lives in a state slot beside the pages
    (models/hybrid.py), and this path
    moves, shards, rolls back or stores pages alone.  Raised at engine
    construction or at admission, naming the path: never a wrong token."""

    def __init__(self, path: str, why: str):
        self.path = path
        super().__init__(
            f"{path} cannot carry a recurrent state: {why}"
        )


def agent_demote_default() -> str:
    """KAFKA_TPU_AGENT_DEMOTE -> "" (off) | "host" | "object".  "1"/"on"
    mean host — the tier ladder's first rung; "object" additionally
    archives the gap-demoted chain + sleep manifest so the return hint's
    wake prefetch works cross-replica.  Nonsense = off."""
    raw = (os.environ.get(AGENT_DEMOTE_ENV) or "").strip().lower()
    if raw in ("1", "on", "true", "host"):
        return "host"
    if raw == "object":
        return "object"
    return ""


def agent_linger_default() -> float:
    """KAFKA_TPU_AGENT_LINGER_MS -> seconds (default 250ms): how long a
    tool-call gap lingers before the thread's KV demotes.  Sub-linger
    tools (the common quick calls) never pay the round trip."""
    raw = os.environ.get(AGENT_LINGER_ENV)
    try:
        ms = float(raw) if raw not in (None, "") else 250.0
    except ValueError:
        ms = 250.0
    return max(0.0, ms) / 1e3


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    page_size: int = 16
    num_pages: int = 256
    max_pages_per_seq: int = 16  # attention window = this * page_size
    prefill_buckets: Tuple[int, ...] = (16, 32, 64, 128, 256, 512)
    max_new_tokens_default: int = 512
    # In-flight DEVICE STEPS tolerated in the fetch pipeline before the
    # host force-pops the oldest entry (a fused k-step dispatch counts k).
    # Sized so fetch_lag * step_time exceeds the device->host copy time —
    # then every forced read finds its transfer already complete.  The
    # age/landed bounds pop entries long before this depth when copies
    # land quickly, so the depth is a backstop, not the cadence; and it
    # is NOT what bounds the host's run-ahead: step() withholds decode
    # while more than max(multi_step, _HOLD_FLOOR_STEPS) unfinished steps
    # are queued (_hold_decode), far under this.  (Value chosen on an
    # earlier machine: ROADMAP Queue 1 re-decides it.)
    fetch_lag: int = 96
    # Also pop a fetch once it has been in flight this long (seconds) —
    # bounds token latency when the pipeline fills slower than fetch_lag
    # steps.  With <=2 active streams the engine tightens this bound to
    # ~1.25x the measured device->host RTT (see _emit_wait) so a lone
    # interactive stream gets smooth per-token cadence, not 150ms bursts.
    # (Value chosen on an earlier machine: ROADMAP Queue 1 re-decides it.)
    fetch_wait_s: float = 0.15
    # Decode attention backend: "auto" resolves to the Pallas paged kernel
    # on single-device TPU (when shapes meet its lane-alignment contract)
    # and to the XLA gather path otherwise; "xla"/"pallas" force.
    attention_backend: str = "auto"
    # KV-cache quantization: "" (model dtype) or "int8" — per-slot
    # symmetric scales (runtime/kv_cache.py), halving KV window traffic
    # and doubling pool capacity.  Resolves attention to the XLA gather
    # path (the Pallas kernel's DMA contract is dense rows).
    kv_quantize: str = ""
    # Radix prefix cache (runtime/prefix_cache.py): cross-thread KV reuse
    # over the refcounted pool.  prefix_cache_entries is the legacy on/off
    # knob (0 disables; any positive value enables — the tree is no longer
    # entry-counted).  prefix_cache_pages bounds the pages the cache may
    # retain (None = bounded only by pool pressure via reclaim).
    prefix_cache_entries: int = 64
    prefix_cache_pages: Optional[int] = None
    # Tiered KV cache (runtime/kv_tier.py, README "KV tiering"): a
    # host-RAM page tier under the pool.  When > 0, prefix-cache eviction
    # DEMOTES page runs into a pinned host pool of this many MiB (async
    # D2H) instead of dropping them, and a lookup hit against a demoted
    # run PROMOTES it back (async H2D overlapped with the suffix prefill)
    # — a returning thread re-materializes its conversation KV instead of
    # re-prefilling it.  0 (default) disables the tier entirely: no
    # manager is built and every dispatch/eviction path is byte-identical
    # to before.  KAFKA_TPU_KV_HOST_TIER_MB via the serving config.
    kv_host_tier_mb: int = 0
    # Spill directory below the host tier (KAFKA_TPU_KV_DISK_TIER_DIR):
    # host-budget overflow spills page runs to disk (second-chance LRU)
    # instead of dropping them; the tracing span ring persists alongside.
    # None/"" = drop on host-tier overflow.
    kv_disk_tier_dir: Optional[str] = None
    # Object-store KV tier (KAFKA_TPU_KV_OBJECT_DIR, README "Object-store
    # KV tier", ISSUE 14): a SHARED store below host+disk that makes
    # thread state portable across hosts — runs the local ladder would
    # drop archive there content-addressed (identical prefixes dedupe
    # across hosts), per-thread sleep manifests let a dormant thread wake
    # on ANY replica (cache_source="object_tier" instead of re-prefill),
    # and POST /admin/drain/{replica} flushes a replica's warm state
    # before the autoscaler shrinks it away.  None/"" (default) =
    # disabled; every dispatch/eviction path is byte-identical to before.
    kv_object_dir: Optional[str] = None
    # Byte budget (MiB) on the object-store references THIS replica
    # holds (second-chance LRU; dropping the last reference deletes the
    # object).  0 = unbounded.  KAFKA_TPU_KV_OBJECT_MB.
    kv_object_mb: int = 0
    # Context-parallel strategy for sp>1 chunked prefill: "ring" (KV shards
    # rotate over ICI — bandwidth-optimal, any head count) or "ulysses"
    # (all_to_all to head-sharded layout — needs heads/tp % sp == 0).
    cp_strategy: str = "ring"
    # Decode steps fused into one device dispatch (lax.scan) when the batch
    # is busy and stable — amortizes per-dispatch host cost.
    # Engages with >=3 active streams, no HOST-masked constrained lanes
    # (device-FSM grammar lanes fuse fine), and no lane
    # mid-prefill; a waiting queue with every slot busy keeps fusion ON
    # (admission waits at most k-1 steps — see _pick_multi_step).
    # Depth pays only where per-dispatch host cost is a visible share of
    # a step; 16 was chosen on an earlier machine and is not measured on
    # the current one (ROADMAP Queue 1 re-decides it).  1 disables.
    multi_step: int = 16
    # Off-slot admission: when every decode slot is busy, waiting requests
    # may still prefill and emit their FIRST token ("parked"), then join
    # the decode batch as slots free.  Under oversubscription this bounds
    # TTFT by prefill latency instead of queue wait (BASELINE's <200ms p50
    # north star held at p90 too — round-3's measured phase stacked 640ms
    # of queueing at 4x load).  Parked sequences pin their KV pages until
    # seated, so parking is page-gated (park_reserve_pages stay free) and
    # always reclaimable: under page pressure parked lanes roll back to
    # the waiting queue BEFORE any active lane is preempted.  0 disables.
    max_parked: int = 64
    # Pool pages kept free of parked pinning (headroom for active lanes'
    # decode growth).  None -> 2 * max_batch.
    park_reserve_pages: Optional[int] = None
    # Request lifecycle bounds (None/0 = disabled).  max_ttft_s times out a
    # request still waiting for its FIRST token; max_total_s bounds total
    # wall time from submit.  Both finish with finish_reason="timeout" and
    # free slot + pages exactly like a cancel.
    max_ttft_s: Optional[float] = None
    max_total_s: Optional[float] = None
    # Admission backpressure: submit() raises AdmissionError once the
    # waiting queue holds this many requests (0 = unbounded).  The serving
    # layer surfaces it as HTTP 429 + Retry-After.
    max_waiting: int = 0
    # Draft-free speculative decoding (runtime/speculative.py): up to K
    # n-gram prompt-lookup candidates per lane are verified in ONE
    # [B, K+1]-query device dispatch — each accepted run amortizes one
    # weight-stream over several tokens (decode is HBM-bound).  0 (the
    # default) disables it completely: no verify program is built and the
    # dispatch paths are byte-for-byte the non-speculative ones.  Greedy
    # output is bit-identical to plain decode and sampled output follows
    # the target distribution at any temperature (exact-match acceptance
    # with the sequential path's own per-(seed, position) keys).  Does not
    # compose with sp/pp meshes yet (validated at construction).
    speculative_k: int = 0
    # Scheduler flight recorder (runtime/flight_recorder.py, README
    # "Flight recorder"): a fixed ring of this many per-iteration records
    # (decision log + measured dispatch timing + anomaly detectors +
    # postmortem capture).  0 disables it entirely: no recorder is built
    # and every dispatch/eviction path is byte-identical to before (each
    # hook is one `if flight is not None` branch).  Default honors
    # KAFKA_TPU_FLIGHT_RING at construction time.
    flight_ring: int = dataclasses.field(default_factory=ring_default)
    # Agent-native scheduling (ISSUE 20): a lane that finishes into a
    # tool-call gap (the provider signals note_tool_gap on
    # finish_reason=tool_calls) has its thread's KV proactively demoted
    # down the tier ladder after agent_linger_s without a return — dead
    # HBM freed mid-gap instead of waiting for eviction pressure.
    # "" (default) disables: note_tool_gap/note_tool_return are no-ops
    # and every scheduler path is byte-identical to before.  "host"
    # demotes into the host/disk tier; "object" additionally archives
    # the chain + sleep manifest (cross-replica return prefetch).
    # Requires the prefix cache + KV tier; inert without them.
    agent_demote: str = dataclasses.field(
        default_factory=agent_demote_default
    )
    agent_linger_s: float = dataclasses.field(
        default_factory=agent_linger_default
    )

    @property
    def max_window(self) -> int:
        return self.max_pages_per_seq * self.page_size


@dataclasses.dataclass
class GenRequest:
    """One generation request moving through the scheduler."""

    request_id: str
    prompt_ids: List[int]
    # None -> EngineConfig.max_new_tokens_default is applied at submit()
    max_new_tokens: Optional[int] = None
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    stop_token_ids: Tuple[int, ...] = ()
    # Per-request deadline overrides (seconds from submit); None defers to
    # EngineConfig.max_ttft_s / max_total_s.  Enforced by _check_deadlines.
    deadline_ttft_s: Optional[float] = None
    deadline_s: Optional[float] = None
    # engine bookkeeping
    state: str = WAITING
    slot: int = -1
    seq: Optional[SequencePages] = None
    output_ids: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    # TTFT decomposition stamps (VERDICT r4 #5): queue wait ends when the
    # first prefill chunk dispatches; prefill ends when the first token is
    # sampled on device; the remainder to first_token_time is fetch/drain
    # (transfer landing + emission runway) — the device->host copy's part.
    t_prefill_start: Optional[float] = None
    t_first_dispatch: Optional[float] = None
    # Genuine constrained choice points that awaited a device->host round
    # trip (forced-singleton tokens chain without one) — the count that
    # prices the host-mask path: round trips x device->host latency.
    constrained_roundtrips: int = 0
    # tokens sampled on device / processed on host (emission lags dispatch
    # by up to fetch_lag steps)
    dispatched: int = 0
    drained: int = 0
    # True while re-entering after preemption: the prefill's sampled token
    # was already emitted before preemption and must not be re-emitted.
    resumed: bool = False
    # Token ids the next prefill must materialize. Equals prompt_ids at
    # submit; recomputed from (prompt_ids, output_ids) on preemption —
    # always derived from the immutable prompt, so repeated preemptions
    # cannot duplicate context.
    prefill_ids: List[int] = dataclasses.field(default_factory=list)
    # constrained decoding: fn(output_ids) -> allowed token id list or None
    logits_mask_fn: Optional[Callable[[List[int]], Optional[List[int]]]] = None
    # On-device grammar FSM (llm/constrained.CompiledGrammar): when set,
    # the lane carries a device-side automaton state advanced INSIDE the
    # jitted decode step — constrained sampling with zero host round
    # trips, riding the same batched dispatch as free lanes (and the
    # speculative verify step).  logits_mask_fn stays attached as the
    # fallback: a lane whose grammar cannot register (table-set cap) or
    # whose host replay stops validating degrades to the awaited
    # micro-batch path.  None = host mask path (the pre-ISSUE-7 behavior).
    grammar: Optional[Any] = None
    # over-tight mask rows log once per request (the counter counts all)
    overtight_logged: bool = False
    # Singleton-mask chaining: tokens already dispatched whose value is
    # grammar-FORCED (mask of exactly one id — masked sampling must return
    # it), not yet drained.  Masks for later positions build on
    # output_ids + predicted, so forced runs of tool-call JSON dispatch at
    # scheduler cadence instead of one token per device->host round trip.
    predicted: List[int] = dataclasses.field(default_factory=list)
    # (position, _next_constraint result) memo, where the result is one of
    # ("forced", token_id) / ("ids", np array) / ("free", None): a lane
    # blocked behind an in-flight awaited fetch must not re-run its mask
    # fn (full automaton walk) every scheduler iteration
    mask_cache: Optional[Tuple[int, Tuple[str, Any]]] = None
    # device-resident constrained mask for the in-progress prefill (built
    # once at prefill start; the mask depends only on output_ids, constant
    # across chunks)
    prefill_allowed: Optional[Any] = None
    # KV prefix reuse: requests sharing a key (thread id) share cached
    # prompt-prefix pages and re-prefill only the suffix (BASELINE config 2)
    prefix_key: Optional[str] = None
    # Radix-cache hit accounting (set by _attach_prefix): tokens served
    # from cached pages and whether the match came from this thread's own
    # prior turn or another thread's shared prefix.  Rides out on the
    # engine.prefill span and usage.prompt_tokens_details.cached_tokens.
    cached_tokens: int = 0
    # "own" | "cross" | "host_tier" | "object_tier" | "shipped"
    cache_source: Optional[str] = None
    # Tokens of the hit re-materialized from the host/disk KV tier
    # (runtime/kv_tier.py) rather than found in HBM — rides out on the
    # engine.prefill span so a resume-without-re-prefill is provable.
    promoted_tokens: int = 0
    # Tokens of the hit woken from the shared OBJECT store (runtime/
    # object_tier.py): the cross-host resume-without-re-prefill proof.
    object_tokens: int = 0
    # The FIRST admission's radix share, frozen at the first prefill
    # start (usage.prompt_tokens_details.cached_tokens reads this).
    # cached_tokens above tracks the LATEST attach — a preemption or
    # disaggregated-hand-off resume re-attaches the whole materialized
    # prefix, which is scheduler bookkeeping, not compute the client
    # saved: without the split, every shipped thread would bill ~its
    # entire prompt as "cached" on a cold first turn.
    usage_cached_tokens: Optional[int] = None
    # Disaggregated prefill/decode (ISSUE 12): a prefill-and-hand-off
    # request terminates at its FIRST token with its pages kept — the
    # engine parks (request, token) on `engine.handoffs` instead of
    # emitting a terminal event, and the DP router ships the page run to
    # a decode-pool replica and requeues the request there (preemption-
    # style resume: the re-prefill's sampled token is the deterministic
    # duplicate of the already-emitted first token and is dropped).
    # Only the router sets this, and only for prefix-keyed requests —
    # the radix cache is what names the shipped run at the destination.
    handoff: bool = False
    # Off-slot (parked) admission: the prefill's sampled token as a device
    # scalar, held until a decode slot frees and seeds _d_last at seating.
    # None for resumed parked lanes — their pending token is host-known
    # (output_ids[-1]).
    pending_tok: Optional[Any] = None
    # Request tracing (kafka_tpu/tracing.py): the trace context this request
    # carries — None = untraced, and every engine span site is then ONE
    # branch.  trace_last_t stamps the previous decode dispatch so
    # engine.decode spans tile the request's timeline at burst granularity.
    trace: Optional[Any] = None
    trace_last_t: Optional[float] = None
    # Vision soft-prompt (models/vision.py): projected image-patch rows
    # replacing the prompt's image_token_id placeholders at prefill.
    # override_pos are ABSOLUTE prompt positions, so chunked prefill,
    # prefix-hit resume, and preemption re-prefill all recompute the same
    # per-chunk slices.  None = text-only request.
    override_pos: Optional[Any] = None   # np [K] int32
    override_rows: Optional[Any] = None  # np [K, H] float
    # Speculative decoding (EngineConfig.speculative_k > 0): the lane's
    # n-gram proposer + acceptance EWMA (runtime/speculative.py), created
    # at submit.  spec_ahead > 0 while a verify dispatch for this lane is
    # in flight — the lane's host seq.length/dispatched are then
    # confirmed-only (the actual advance, 1..K+1 tokens, reconciles at
    # drain) and the lane is masked out of every dispatch until it drains.
    spec: Optional[LaneSpeculator] = None
    spec_ahead: int = 0
    # Background priority class (ISSUE 20): tool-result prefill and
    # in-engine context-compaction summarization.  Background requests
    # queue on engine.waiting_bg, admit only when no interactive request
    # is waiting (and never into the page reserve), yield their prefill
    # chunks to any interactive prefill, and are the FIRST preemption
    # victims under page pressure.  They are exempt from the max_waiting
    # admission bound (engine-internal work must not 429 the client that
    # triggered it).  Nothing sets this by default — the False paths are
    # byte-identical to before the class existed.
    background: bool = False
    # SLO verdict (ISSUE 10): set at finalize by engine._finalize_slo —
    # True = met every configured target, False = missed, None = excluded
    # (client cancel) or not yet finalized.  The serving layer reads it
    # for span attrs / logs; /metrics aggregates the counters.
    slo_met: Optional[bool] = None
    # A model with a recurrent state (engine.state_pool): the snapshot slot
    # the prefix hit found (one reference held until the restore is
    # enqueued), how far the PAGES matched past it (the first prefill chunk
    # is cut there, so that boundary's snapshot is stored for the next
    # request), and that match in tokens (for the counters).
    state_snapshot: Optional[int] = None
    # the snapshot slot the last admission restored into the lane's slot
    # (None: a cold start); the engine.prefill span carries it
    state_restored: Optional[int] = None
    state_cut: int = 0
    state_matched: int = 0
    # the chunk plan cuts the remainder now being prefilled into more
    # launches than the first bucket that holds it would make, and did so to
    # some remainder of this prefill (_count_prefill_plan)
    split_now: bool = False
    plan_split: bool = False

    @property
    def cached_len(self) -> int:
        return self.seq.length if self.seq else 0


@dataclasses.dataclass
class TokenEvent:
    """One emitted token (or terminal event) for a request."""

    request_id: str
    token_id: Optional[int]
    finished: bool = False
    finish_reason: Optional[str] = None


@dataclasses.dataclass
class _SpecMeta:
    """Per-lane candidate widths of one speculative verify dispatch.

    cand_lens[i] == 0 marks a RIDER lane: it rode the verify program
    masked down to ordinary 1-token decode and keeps the plain path's
    at-dispatch accounting.  cand_lens[i] > 0 marks a PROPOSER: its
    actual advance (accepted+1 tokens) is only known at drain, so its
    host accounting reconciles there (engine._finish_verify_entry)."""

    cand_lens: List[int]
    width: int  # K + 1 sample columns per lane in the fetched array


@dataclasses.dataclass(eq=False)  # identity semantics (list.remove / `is`)
class _Fetch:
    """One in-flight sampled-token transfer awaiting host processing.

    For decode steps `arr` is the [B] token vector ([steps, B] for a fused
    multi-step dispatch) and `items[i]` records which request slot i's lane
    belonged to at dispatch (None for idle lanes); for prefill `arr` is a
    scalar and `items` has one entry.  `final` is per step then per lane:
    `final[j][i]` marks the request's last dispatched token (it hit a
    length/window limit at dispatch time) with its finish reason.

    Speculative verify dispatches set `spec`: `arr` is then [B, K+2]
    (K+1 samples + the accepted count per lane), `steps` counts the
    dispatch's candidate-token width in the fetch_lag FIFO, and `final`
    holds one row covering only the rider lanes.
    """

    arr: jnp.ndarray
    items: List[Optional[GenRequest]]
    final: List[List[Optional[str]]]  # [steps][lanes] finish reasons
    t0: float = 0.0  # dispatch time (fetch_wait_s aging)
    steps: int = 1
    # first time device compute was observed complete (is_ready); the
    # async host copy starts at compute completion and lands ~RTT later —
    # t_ready + rtt_est is when popping becomes non-blocking
    t_ready: Optional[float] = None
    # the first-fetch ledger's other two marks (metrics.ttft_fetch_stages):
    # when the device began this dispatch, max(t0, the completion of the
    # dispatch enqueued before it), kept by _note_ready; and when the
    # entry left the FIFO (_process_entry)
    t_start: Optional[float] = None
    t_pop: Optional[float] = None
    # how many step programs the engine had dispatched when this entry was
    # queued, its own included (_push_entry): the entry whose number is
    # still the engine's is the last thing the device was given
    seq: int = 0
    spec: Optional[_SpecMeta] = None
    # Flight-recorder attribution (ISSUE 11): which utilization kind this
    # dispatch bills to and its modeled roofline seconds.  When the
    # completion is observed (t_ready stamped), the measured device time
    # derived from fetch-maturation order feeds the modeled-vs-measured
    # skew gauge.  modeled_s None = no cost model / recorder off: the
    # entry is timed for the ring but never billed to the skew gauge.
    kind: str = "decode"
    modeled_s: Optional[float] = None
    # each pass of this dispatch's experts read and picks ([3] or [steps, 3]
    # i32: `forward`'s tally), where its program counts them
    # (StepPrograms.tallies)
    reads: Optional[jnp.ndarray] = None


class _DispatchScope:
    """Around one dispatch call of a step program: the profiler annotation
    where there is one (engine._dispatch_scope), and the starvation
    account.  If a stamp has seen the device's queue empty since the last
    dispatch (engine._starve), this call ends the gap: from its start
    (lower bound) and its return (upper bound) the thread's clock books
    how long the device had nothing to run and what the thread did
    meanwhile (SchedClock.book_starved)."""

    __slots__ = ("engine", "ann", "t_call")

    def __init__(self, engine: "InferenceEngine", ann):
        self.engine, self.ann = engine, ann

    def __enter__(self):
        self.t_call = time.monotonic()
        if self.ann is not None:
            self.ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        engine, t_return = self.engine, time.monotonic()
        engine._dispatch_seq += 1
        since, engine._starve = engine._starve, None
        if since is not None and exc_type is None:
            engine.sched.book_starved(since, self.t_call, t_return)
        engine._seen_running = engine.sched.seen_running(t_return)
        return False


class _GrammarTables:
    """Device residency for registered CompiledGrammar artifacts.

    All live grammars share ONE padded table set so a mixed batch needs a
    single compiled decode program: per-grammar transition blocks are
    concatenated along the state axis (entries offset at registration, so
    a lane's absolute int32 state addresses the combined [S, C] array) and
    token-class rows stack into [G, V].  Registration is append-only —
    offsets never move, so in-flight lanes' device states stay valid
    across registrations.  The grammar axis is allocated at MAX_LIVE rows
    up front (G x V int32: 4 MB at a 128k vocab), so registering the
    grammar of a new tool_choice never reshapes the fsm programs on that
    axis; the state axis grows geometrically, so the decode program
    retraces O(log S) times, not per grammar.  A full registry (MAX_LIVE)
    returns None and the request degrades to the host mask path.
    """

    MAX_LIVE = 8
    MIN_STATE_PAD = 256

    def __init__(self, engine: "InferenceEngine"):
        self._engine = engine
        self.grammars: List[Any] = []
        self.offsets: List[int] = []
        self._total_states = 0
        # device arrays (padded); None until the first registration
        self.token_class = None   # [G_pad, V] int32
        self.trans = None         # [S_pad, C_pad] int32
        self.dist = None          # [S_pad] int32
        self.slack = None         # [] int32 (wrap-up window)

    @property
    def active(self) -> bool:
        return bool(self.grammars)

    def register(self, grammar) -> Optional[int]:
        """Index of `grammar` in the table set (registering if new);
        None when the registry is full, the vocab doesn't match, or the
        COMBINED padded tables would exceed the KAFKA_TPU_GRAMMAR_TABLE_MB
        budget (the same figure the memory planner charges — the cap is a
        total device budget, not per-artifact)."""
        for i, g in enumerate(self.grammars):
            if g is grammar:
                return i
        if len(self.grammars) >= self.MAX_LIVE:
            return None
        if grammar.vocab_size != self._engine.cfg.vocab_size:
            return None
        if self._padded_bytes(
            self._total_states + grammar.num_states,
            max([grammar.num_classes] + [g.num_classes
                                         for g in self.grammars]),
        ) > grammar_table_cap_bytes():
            return None
        self.grammars.append(grammar)
        self.offsets.append(self._total_states)
        self._total_states += grammar.num_states
        self._rebuild()
        return len(self.grammars) - 1

    def _padded_bytes(self, total_states: int, max_classes: int) -> int:
        """Device bytes of the padded table set for a prospective shape."""
        V = self._engine.cfg.vocab_size
        S_pad = self._pad(total_states, self.MIN_STATE_PAD)
        C_pad = self._pad(max_classes, 32)
        return 4 * (self.MAX_LIVE * V + S_pad * C_pad + S_pad)

    def _pad(self, n: int, lo: int) -> int:
        p = lo
        while p < n:
            p *= 2
        return p

    def _rebuild(self) -> None:
        V = self._engine.cfg.vocab_size
        S_pad = self._pad(self._total_states, self.MIN_STATE_PAD)
        C_pad = self._pad(max(g.num_classes for g in self.grammars), 32)
        G_pad = self.MAX_LIVE
        tc = np.zeros((G_pad, V), np.int32)
        trans = np.full((S_pad, C_pad), -1, np.int32)
        # padded/unreachable states read as "far from done" so wrap-up
        # never engages on them
        dist = np.full(S_pad, 1 << 20, np.int32)
        for gi, (g, off) in enumerate(zip(self.grammars, self.offsets)):
            tc[gi] = g.token_class
            block = g.trans.copy()
            block[block >= 0] += off
            trans[off:off + g.num_states, : g.num_classes] = block
            dist[off:off + g.num_states] = g.dist
        dev = self._engine._dev
        self.token_class = dev(tc)
        self.trans = dev(trans)
        self.dist = dev(dist)
        # conservative across grammars: extra slack engages wrap earlier
        # but never breaks closure
        self.slack = dev(np.int32(
            max(g.wrap_slack for g in self.grammars)
        ))

    def args(self) -> Tuple:
        """The table argument tuple the fsm decode/verify programs take."""
        return (self.token_class, self.trans, self.dist, self.slack)


@jax.jit
def _fsm_advance(token_class, trans, last_tokens, g_idx, state, slot):
    """trans[state, class of the lane's last token]: _set_fsm_lane's lazy
    device-side advance as ONE program (warmup_grammar compiles it), not
    a chain of eager gathers that each compile on first use."""
    return trans[state, token_class[g_idx, last_tokens[slot]]]


class InferenceEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        engine_cfg: Optional[EngineConfig] = None,
        kv_dtype=None,
        mesh=None,
    ):
        """mesh: optional jax.sharding.Mesh (parallel/mesh.py). When given,
        params are placed per the TP rules, the KV pool is head-sharded, and
        the jitted step programs run SPMD with XLA inserting the collectives
        (all-reduce after row-parallel einsums, logit gather)."""
        self.ecfg = engine_cfg or EngineConfig()
        self.mesh = mesh
        sp = mesh.shape.get("sp", 1) if mesh is not None else 1
        self._sp = sp
        self._pp = mesh.shape.get("pp", 1) if mesh is not None else 1
        self._ep = mesh.shape.get("ep", 1) if mesh is not None else 1
        # grouped-GQA kv replica factor (parallel/mesh.py factor_tp_for_kv):
        # q heads/MLP shard over tp*tq, kv params + pool over tp alone
        self._tq = mesh.shape.get("tq", 1) if mesh is not None else 1
        if cfg.has_state:
            # The recurrent state lives in state slots beside the pages;
            # whatever moves, shards, rolls back or stores pages alone is
            # refused here, by name (models/hybrid.HybridPathError is the
            # backstop for direct callers of forward).  Each reason is the
            # one that is true of THIS model's kind of state: a Mamba
            # decoder's scan and differential kernels, a conv layer's tail.
            sharded = mesh is not None and mesh.size > 1
            mamba = cfg.hybrid_decoder
            refused = (
                ("speculative verify (paged_verify_attention)",
                 self.ecfg.speculative_k > 0,
                 "a rejected candidate's state update cannot be rolled "
                 "back; set speculative_k=0"),
                ("kv_quantize int8 pool", bool(self.ecfg.kv_quantize),
                 ("the int8 kernels have no differential form and the state "
                  "stays float32" if mamba else
                  "the float32 state slots ride in the v pool's pytree "
                  "beside dense rows, and int8 rows beside them are not "
                  "built") + "; serve with a dense pool"),
                ("prefill_ring", sp > 1,
                 ("a chunk sharded over the sp axis has no sequential scan"
                  if mamba else
                  "a chunk sharded over the sp axis would need its "
                  "neighbour's last conv rows, and no such exchange is "
                  "built") + "; use sp=1"),
                ("a pp / tp / ep mesh", sharded and sp == 1,
                 ("the state slots and the scan kernel live" if mamba else
                  "the state slots live") + " on one device; serve each "
                 "replica on one device (dp)"),
                ("a KV tier (kv_host_tier_mb / kv_object_dir) and its "
                 "sleep manifests",
                 bool(self.ecfg.kv_host_tier_mb or self.ecfg.kv_object_dir),
                 "the page shipper moves pages and a demoted run would "
                 "come back without its snapshot; serve without a tier"),
            )
            for path, hit, why in refused:
                if hit:
                    raise RecurrentStateUnsupported(path, why)
        if cfg.nextn_predict_layers and self.ecfg.speculative_k > 0:
            raise UnsupportedConfigError(
                f"num_nextn_predict_layers = {cfg.nextn_predict_layers} with "
                f"speculative_k = {self.ecfg.speculative_k}: the model's "
                "multi-token-prediction module is recorded and not built, so "
                "no draft would come from it; set speculative_k=0")
        if cfg.hc_mult > 1 and mesh is not None and mesh.size > 1:
            raise UnsupportedConfigError(
                f"hc_mult = {cfg.hc_mult} on a mesh of {mesh.size} devices "
                "(tp / ep / pp / sp): the widened residual stream and its "
                "per-token mappings are built on one device; serve each "
                "replica on one device (dp)")
        if cfg.lead_tree and not cfg.is_latent:
            # models/init_params._init_lead_tree_params' tree on grouped-query
            # attention: parallel/sharding.param_specs has no rule for it
            sharded = mesh is not None and mesh.size > 1
            refused = (
                ("a held share of the experts (num_experts_routed) on a "
                 "tp / ep mesh", sharded and bool(cfg.num_experts_routed),
                 "this chip's experts are one share of an expert-parallel "
                 "layer already; serve it on one device"),
                ("a tp / ep / pp / sp mesh", sharded,
                 "the sharding rules place a homogeneous stack of layers; "
                 "serve each replica on one device (dp)"),
            )
            for path, hit, why in refused:
                if hit:
                    raise RoutedTreeUnsupported(path, why)
        if cfg.is_windowed:
            # Every attention path honours the window (models/llama.py) or
            # is refused here, by name.
            refused = (
                ("speculative verify (paged_verify_attention)",
                 self.ecfg.speculative_k > 0,
                 "the K+1-query verify kernel masks causally only; set "
                 "speculative_k=0"),
                ("prefill_ring", sp > 1,
                 "ring / ulysses prefill over the sp axis masks causally "
                 "only; use a mesh with sp=1"),
                ("kv_quantize int8 kernel", bool(self.ecfg.kv_quantize),
                 "the int8 paged-decode kernel walks every chunk from "
                 "position 0; serve with a dense pool"),
                ("pp > 1 (parallel/pipeline.py)", self._pp > 1,
                 "the stage splitter scans one homogeneous layer body; "
                 "use tp / dp"),
            )
            for path, hit, why in refused:
                if hit:
                    raise WindowedAttentionUnsupported(path, why)
        if cfg.is_latent:
            # Every option below reads k/v rows of Hkv*D lanes a head; the
            # latent pool has none (mixers/index.py LatentPathError is the
            # backstop for direct callers of forward).
            sharded = mesh is not None and mesh.size > 1
            refused = (
                ("speculative verify (paged_verify_attention)",
                 self.ecfg.speculative_k > 0,
                 "the K+1-query verify kernel reads per-head K and V rows; "
                 "set speculative_k=0"),
                ("kv_quantize int8 pool", bool(self.ecfg.kv_quantize),
                 "the int8 paged-decode kernel dequantises per-head K and V "
                 "rows; serve with a dense pool"),
                ("prefill_ring", sp > 1,
                 "ring / ulysses prefill shards per-head K and V over the "
                 "sp axis; use sp=1"),
                ("pp > 1 (parallel/pipeline.py)", self._pp > 1,
                 "the stage splitter scans one homogeneous layer body and "
                 "this model leads with dense layers; use dp"),
                ("a tp / ep mesh", sharded and self._pp == 1 and sp == 1,
                 "the latent row is shared by all heads and cannot be split "
                 "by head; serve each replica on one device (dp)"),
                ("a KV tier (kv_host_tier_mb / kv_object_dir)",
                 cfg.by_kind and bool(
                     self.ecfg.kv_host_tier_mb or self.ecfg.kv_object_dir),
                 "the page shipper moves pages of one row width and this "
                 "model's kinds of layer store rows of different widths; "
                 "serve without a tier"),
            )
            for path, hit, why in refused:
                if hit:
                    raise LatentAttentionUnsupported(path, why)
        if self._tq > 1:
            if self._pp > 1:
                raise ValueError(
                    "grouped GQA sharding (tq>1) does not compose with pp "
                    "stage sharding: pipeline specs assume the plain tp "
                    "head split — pick a tensor degree dividing "
                    f"num_kv_heads ({cfg.num_kv_heads}) for pp meshes"
                )
            if (self.ecfg.cp_strategy == "ulysses") and sp > 1:
                raise ValueError(
                    "grouped GQA sharding (tq>1) composes with "
                    "cp_strategy='ring' only: the ulysses all_to_all "
                    "head scatter assumes the plain tp head split"
                )
        if self._ep > 1:
            if not cfg.is_moe:
                raise ValueError(
                    f"mesh has ep={self._ep} but {cfg.name!r} is dense: "
                    "the ep axis shards MoE expert weights"
                )
            if cfg.num_experts % self._ep:
                raise ValueError(
                    f"num_experts={cfg.num_experts} not divisible by "
                    f"ep={self._ep}"
                )
        if self._pp > 1:
            if cfg.is_moe:
                raise ValueError(
                    "pp stage sharding does not support MoE models yet: "
                    "use ep x tp meshes for Mixtral-class serving"
                )
            if cfg.vision is not None:
                raise ValueError(
                    "pp stage sharding does not support vision models "
                    "yet: the stage-0 embed has no override lane"
                )
            from ..models.quant import QTensor

            if any(isinstance(x, QTensor) for x in jax.tree.leaves(
                params, is_leaf=lambda v: isinstance(v, QTensor)
            )):
                raise ValueError(
                    "pp stage sharding does not support int8 QTensor "
                    "params yet: quantization targets single-chip/tp "
                    "serving"
                )
            if sp > 1:
                raise ValueError(
                    "pp does not compose with sp ring prefill yet: use "
                    "pp x tp (stage-sharded serving) or sp x tp (ring "
                    "long-context) meshes"
                )
            from ..parallel.pipeline import _check_pp_divisibility

            _check_pp_divisibility(cfg, self._pp, mesh.shape.get("tp", 1))
        if sp > 1:
            bad = [b for b in self.ecfg.prefill_buckets if b % sp]
            if bad:
                raise ValueError(
                    f"prefill buckets {bad} not divisible by sp={sp}: the "
                    "ring shards each chunk across the sp axis"
                )
            if self.ecfg.cp_strategy not in ("ring", "ulysses"):
                raise ValueError(
                    f"unknown cp_strategy {self.ecfg.cp_strategy!r}: "
                    "expected 'ring' or 'ulysses'"
                )
            if self.ecfg.cp_strategy == "ulysses":
                # mirror ulysses_prefill_sharded's head_ax rule: heads are
                # tp-sharded only when tp divides BOTH head counts, else
                # each shard holds all heads
                tp = mesh.shape.get("tp", 1)
                tp_sharded = (
                    tp > 1
                    and cfg.num_heads % tp == 0
                    and cfg.num_kv_heads % tp == 0
                )
                per_shard_heads = (
                    cfg.num_heads // tp if tp_sharded else cfg.num_heads
                )
                if per_shard_heads % sp:
                    raise ValueError(
                        f"ulysses needs the per-shard head count "
                        f"({per_shard_heads}) divisible by sp={sp}; use "
                        "cp_strategy='ring'"
                    )
        if self.ecfg.speculative_k < 0:
            raise ValueError("speculative_k must be >= 0 (0 disables)")
        if self.ecfg.speculative_k > 0:
            if sp > 1 or self._pp > 1:
                raise ValueError(
                    "speculative decoding (speculative_k>0) does not "
                    "compose with sp/pp meshes yet: the verify step's "
                    "K+1-query attention takes the single-chunk paged "
                    "path (tp/tq/dp compose)"
                )
            if self.ecfg.speculative_k + 2 > self.ecfg.max_window:
                raise ValueError(
                    f"speculative_k={self.ecfg.speculative_k} does not fit "
                    f"the attention window ({self.ecfg.max_window})"
                )
        if (
            self.ecfg.attention_backend == "pallas"
            and mesh is not None
            and mesh.size > 1
        ):
            from ..ops.pallas import pallas_mesh_ok

            if not pallas_mesh_ok(mesh, cfg.num_heads, cfg.num_kv_heads):
                raise ValueError(
                    "attention_backend='pallas' needs a pure tp(/tq) mesh "
                    "whose head split lines up per-shard (tp | kv heads; "
                    "grouped meshes need one kv head per shard) — this "
                    f"mesh is {dict(mesh.shape)} over Hq={cfg.num_heads}/"
                    f"Hkv={cfg.num_kv_heads}: use 'auto' or 'xla'"
                )
            # Mosaic lane/sublane alignment, validated at construction on
            # real TPUs — the 'auto' rule checks these before resolving to
            # pallas, but a FORCED pallas backend used to skip them and
            # fail much later with an opaque Mosaic compile error.  Off-TPU
            # the kernel runs in interpret mode with no such contract, and
            # CPU-mesh tests deliberately use tiny unaligned shapes.
            if jax.default_backend() == "tpu":
                tp = mesh.shape.get("tp", 1)
                merged_kv = cfg.kv_row_widths()[0]
                if (merged_kv // tp) % 128 != 0:
                    raise ValueError(
                        "attention_backend='pallas' needs the per-shard "
                        f"merged KV row (Hkv*D/tp = {merged_kv // tp}) to "
                        "be a multiple of 128 lanes — use 'auto' or 'xla'"
                    )
                if self.ecfg.page_size % 16 != 0:
                    raise ValueError(
                        "attention_backend='pallas' needs page_size "
                        f"({self.ecfg.page_size}) to be a multiple of the "
                        "16-row bf16 sublane tile — use 'auto' or 'xla'"
                    )
        self.cfg = cfg.replace(
            attention_backend=self._resolve_backend(cfg, self.ecfg, mesh),
            prefill_ring=sp > 1,
            cp_strategy=self.ecfg.cp_strategy,
        )
        if self.cfg.attention_backend == "pallas" and (
            mesh is None or mesh.size == 1
        ) and not self.ecfg.kv_quantize:
            # flash prefill tiles a chunk into q blocks of a power of two
            # of rows that must divide it (ops/pallas/flash_prefill.
            # prefill_plan: the geometry's byte-sized block, halved until it
            # does); a multiple of 64 is divided by every block it can reach,
            # so that stays the rule.  Catch the misconfiguration at
            # construction rather than as a trace-time error.  Mesh engines and
            # int8-KV engines keep prefill on the XLA path (llama.py), so
            # the constraint is single-device dense-pool only.
            bad = [
                b for b in self.ecfg.prefill_buckets
                if b > 64 and b % 64
            ]
            if bad:
                raise ValueError(
                    f"prefill buckets {bad} incompatible with the pallas "
                    "flash-prefill kernel: a bucket over 64 must be a "
                    "multiple of 64, so that the kernel's power-of-two q "
                    "block divides it"
                )
        if self.ecfg.kv_quantize and self._pp > 1:
            raise ValueError(
                "kv_quantize does not compose with pp stage sharding yet: "
                "the stage splitter slices dense pool arrays"
            )
        ps = self.ecfg.page_size
        self.pool = PagePool(self.ecfg.num_pages, ps)
        # State slots of a model with a recurrent state (None otherwise):
        # lane i's slot is slot i, then the trash slot, then snapshots.
        self.state_pool: Optional[StatePool] = None
        if cfg.has_state:
            self.state_pool = StatePool(
                default_state_slots(self.ecfg.max_batch),
                self.ecfg.max_batch)
        k_pool, v_pool = make_kv_pool_arrays(
            cfg, self.ecfg.num_pages, ps, kv_dtype,
            quantize=self.ecfg.kv_quantize,
            state_slots=self.state_pool.n_slots if self.state_pool else 0,
        )
        if mesh is not None:
            # placement happens for ANY mesh, including a 1-device one —
            # that is how DP replicas pin themselves to their own device
            # slice (runtime/dp_router.py)
            if self._pp > 1:
                # stage-sharded: each device holds 1/(pp*tp) of weights AND
                # its stage's shard of the KV pool (parallel/pipeline.py)
                from ..parallel.pipeline import kv_pool_spec_pp, shard_params_pp

                self.params = shard_params_pp(params, cfg, mesh)
                pool_sh = jax.sharding.NamedSharding(
                    mesh, kv_pool_spec_pp(cfg, mesh)
                )
                self.k_pool = jax.device_put(k_pool, pool_sh)
                self.v_pool = jax.device_put(v_pool, pool_sh)
            else:
                from ..parallel.sharding import shard_kv_pool, shard_params

                self.params = shard_params(params, cfg, mesh)
                self.k_pool, self.v_pool = shard_kv_pool(
                    k_pool, v_pool, cfg, mesh
                )
            self._replicated = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()
            )
        else:
            self.params = params
            self.k_pool, self.v_pool = k_pool, v_pool
            self._replicated = None
        # bytes one cached token holds over all layers, as allocated (both
        # pools, every leaf: int8 scales and lane padding included)
        self.kv_bytes_per_token = sum(
            a.nbytes for a in jax.tree.leaves(
                (self.k_pool, self.v_pool["v"] if cfg.has_state
                 else self.v_pool))
        ) // (self.ecfg.num_pages * ps)
        # Monotonic (a model with a recurrent state; 0 otherwise): snapshot
        # restores enqueued, and over the admissions that started a prefill
        # the tokens their PAGES matched against the tokens a snapshot let
        # them skip (skipped / matched is what the snapshots make of the
        # page cache).
        self.state_restores = 0
        self.state_tokens_matched = 0
        self.state_tokens_skipped = 0
        if self.ecfg.num_pages - 1 < self.ecfg.max_pages_per_seq:
            raise ValueError(
                "num_pages must exceed max_pages_per_seq: a lone sequence "
                "must always be able to reach the full attention window"
            )
        B = self.ecfg.max_batch
        self.slots: List[Optional[GenRequest]] = [None] * B
        self.waiting: List[GenRequest] = []
        # Background priority class (ISSUE 20): its own FIFO so interactive
        # admission never has to scan past deferred background work.
        self.waiting_bg: List[GenRequest] = []
        # off-slot lanes (state PREFILLING with slot -1, or PARKED), FIFO
        self.parked: List[GenRequest] = []
        # Agent tool-call gaps (ISSUE 20): prefix_key -> monotonic due
        # time (submit order == due order: the linger is constant).  A
        # key past due demotes via prefix_cache.demote_thread; a return
        # (note_tool_return) or a fresh submit of the thread cancels it.
        self._agent_gaps: Dict[str, float] = {}
        # prefix_key -> pages demoted mid-gap, awaiting the tool return
        # (the "demoted-awaiting" gauge; cleared on return/resubmit)
        self._awaiting_demoted: Dict[str, int] = {}
        # AGENT_METRIC_KEYS counters (runtime/metrics.py registry)
        self.agent_gaps = 0
        self.agent_gap_demotions = 0
        self.agent_gap_pages_demoted = 0
        self.agent_gap_bytes_demoted = 0
        self.agent_gap_cancelled = 0
        self.agent_hint_hits = 0
        self.agent_hint_misses = 0
        self.bg_admitted = 0
        self.bg_chunks = 0
        self.bg_yields = 0
        # scheduler iterations left before off-slot admission may resume
        # after a page-pressure rollback (see _ensure_pages)
        self._park_cooldown = 0
        self._requests: Dict[str, GenRequest] = {}
        self._step_count = 0
        # device-resident all-zero override buffers (vision engines,
        # text-only chunks) — see _zero_override
        self._zero_ov_cache: Dict[Tuple, Tuple[Any, Any]] = {}
        # The jitted device programs (runtime/step_programs.py).  All are
        # built lazily; the speculative verify program on the FIRST
        # proposal (speculative_k=0 engines never compile it — hard
        # acceptance criterion for the default-off path).
        self._programs = StepPrograms(
            self.cfg, mesh, self.ecfg.page_size, B,
            self.ecfg.max_pages_per_seq,
            self.cfg.is_moe and experts_int8(params["layers"]),
            bool(self.ecfg.kv_quantize))
        self._counter = itertools.count()
        # device-resident decode control state (see module docstring)
        self._d_last = self._dev(np.zeros(B, np.int32))
        self._d_seq_lens = self._dev(np.zeros(B, np.int32))
        # On-device grammar FSM lanes (ISSUE 7): per-lane automaton state
        # (-1 = unconstrained), grammar index into the shared table set,
        # and the remaining token budget driving device-side wrap-up.
        # Maintained like _d_last: seeded at activation, advanced by the
        # fsm decode/verify programs, never rebuilt from host mid-flight.
        self._grammars = _GrammarTables(self)
        self._all_allowed = self._dev(np.ones((1, cfg.vocab_size), bool))
        self._d_fsm = self._dev(np.full(B, -1, np.int32))
        self._d_fsm_g = self._dev(np.zeros(B, np.int32))
        self._d_budget = self._dev(np.zeros(B, np.int32))
        self._d_table = None
        self._d_active = None
        self._d_temps = self._d_top_ks = self._d_top_ps = self._d_seeds = None
        self._ctl_dirty = True
        self._pending: List[_Fetch] = []
        # device steps represented by _pending (fused entries count k):
        # the fetch_lag depth bound is in STEPS, so multi-step dispatch
        # doesn't multiply the emission runway by k
        self._pending_steps = 0
        # In-flight constrained micro-batch fetch (at most one): constrained
        # lanes redispatch only after it matures, so their masks always see
        # complete output_ids while unconstrained lanes stay pipelined.
        self._constrained_fetch: Optional[_Fetch] = None
        self._out_events: List[TokenEvent] = []
        # Prefill-and-hand-off completions (disaggregated serving):
        # (request, first_token) pairs whose prefill finished with their
        # pages retained, awaiting the DP router's ship + requeue.  The
        # router drains this every step; a single engine never populates
        # it (GenRequest.handoff is router-set only).
        self.handoffs: List[Tuple[GenRequest, int]] = []
        if (
            self.ecfg.prefix_cache_pages is not None
            and self.ecfg.prefix_cache_pages < 0
        ):
            raise ValueError(
                "prefix_cache_pages must be >= 0 (0 disables; None = "
                "bounded only by pool pressure)"
            )
        if self.ecfg.kv_host_tier_mb < 0:
            raise ValueError(
                "kv_host_tier_mb must be >= 0 (0 disables the host tier)"
            )
        if self.ecfg.kv_object_mb < 0:
            raise ValueError(
                "kv_object_mb must be >= 0 (0 = unbounded references)"
            )
        if self.ecfg.agent_demote not in ("", "host", "object"):
            raise ValueError(
                "agent_demote must be '' (off), 'host', or 'object'"
            )
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.pool, max_pages=self.ecfg.prefix_cache_pages,
                        state_pool=self.state_pool)
            if self.ecfg.prefix_cache_entries > 0
            and self.ecfg.prefix_cache_pages != 0
            else None
        )
        # Tiered KV cache (ISSUE 9): host-RAM (+ optional disk) page tier
        # under the pool.  Built only when enabled AND the prefix cache
        # exists (the radix tree is what names demotable runs); with the
        # knob unset every eviction/dispatch path is byte-identical.
        self.kv_tier = None
        if self.prefix_cache is not None and (
            self.ecfg.kv_host_tier_mb > 0 or self.ecfg.kv_object_dir
        ):
            from .kv_tier import KVTierManager, LocalPageShipper

            self.kv_tier = KVTierManager(
                LocalPageShipper(self, ps),
                host_budget_bytes=self.ecfg.kv_host_tier_mb * 1024 * 1024,
                disk_dir=self.ecfg.kv_disk_tier_dir or None,
                page_size=ps,
            )
            self.prefix_cache.tier = self.kv_tier
            if self.ecfg.kv_object_dir:
                # Object-store tier (ISSUE 14): mounted under the tier
                # manager (which may run host-budget-0 as a pure mount
                # point when only the object knob is set — the full
                # ladder wants both).  The content-address fingerprint
                # covers the pool geometry + model name, so incompatible
                # pools can never exchange KV through a shared store.
                # build_object_store picks the backend by scheme
                # (http(s):// = S3-shaped HTTPObjectStore, else a shared
                # directory) and wraps it in a StoreGuard — deadline,
                # retry, circuit breaker — configured from the
                # KAFKA_TPU_KV_OBJECT_* env knobs, so a dead store
                # degrades warm resumes instead of stalling dispatch.
                from .object_tier import ObjectTier, build_object_store

                obj_tier = ObjectTier(
                    build_object_store(self.ecfg.kv_object_dir),
                    budget_bytes=self.ecfg.kv_object_mb * 1024 * 1024,
                    fingerprint=self._object_fingerprint(),
                    page_size=ps,
                )
                # opt-in in-process janitor (default off: one offline
                # objstore_fsck.py per store beats N replicas scrubbing).
                # Malformed knobs fall back to the defaults, same as the
                # KAFKA_TPU_KV_OBJECT_* guard knobs (StoreGuard.from_env).
                def _env_f(name: str, default: float) -> float:
                    try:
                        return float(os.environ.get(name, default) or default)
                    except (TypeError, ValueError):
                        return default

                obj_tier.start_janitor(
                    _env_f("KAFKA_TPU_KV_OBJECT_SCRUB_S", 0.0),
                    grace_s=_env_f("KAFKA_TPU_KV_OBJECT_SCRUB_GRACE_S",
                                   3600.0),
                )
                # Wake prefetch (ISSUE 19): opt-in via
                # KAFKA_TPU_WAKE_PREFETCH_MB — the DP router's manifest
                # probe starts object GETs at submit time so store RTT
                # overlaps queue wait.  None when unset: the wake path
                # stays the synchronous fetch, bit-identical.
                from .object_tier import WakePrefetcher

                obj_tier.prefetcher = WakePrefetcher.from_env(obj_tier)
                self.kv_tier.attach_object(obj_tier)
        if self.ecfg.flight_ring < 0:
            raise ValueError(
                "flight_ring must be >= 0 (0 disables the flight recorder)"
            )
        # Scheduler flight recorder (ISSUE 11): one record per scheduler
        # iteration + anomaly detectors + postmortem capture.  None when
        # disabled — every hook site below is one branch, so the
        # flight_ring=0 dispatch paths are byte-identical to a
        # recorder-less build (tested).
        self.flight: Optional[FlightRecorder] = (
            FlightRecorder(self.ecfg.flight_ring)
            if self.ecfg.flight_ring > 0 else None
        )
        # Autoscaler degradation ladder (runtime/autoscaler.py rung 2):
        # None = unthrottled (the default — proposals honor
        # ecfg.speculative_k exactly, byte-identical paths); an integer
        # clamps per-lane speculative proposals (0 pauses speculation;
        # in-flight verify entries still drain).  Written cross-thread by
        # the controller as one GIL-atomic attribute store.
        self.spec_k_cap: Optional[int] = None
        # completion time of the previously-observed fetch: the baseline
        # the measured-dispatch-latency derivation subtracts from (in-
        # order device execution — a dispatch starts when its predecessor
        # finishes or when it was enqueued, whichever is later)
        self._last_ready_t: Optional[float] = None
        # The clock of the thread that drives this engine: every instant
        # of step() is charged to one phase (tracing.SCHED_PHASES).  An
        # engine driven by a worker is handed the worker's
        # (llm/worker.EngineWorker); driven without one it keeps its own.
        self.sched = SchedClock()
        # The device's starvation as the host knows it: set by the stamp
        # that saw the last queued program done (SchedClock.emptied: the
        # stamp, the last look that saw the program running, the clock's
        # vector then), booked by the next dispatch (_DispatchScope),
        # dropped when no lane is active (time without work is not
        # starvation).
        self._starve: Optional[Tuple] = None
        # the last instant the program dispatched last was known to be
        # unfinished, with the clock's vector then: its dispatch call's
        # return, or a later poll that found it running (_stamp_ready)
        self._seen_running: Tuple[float, List[float]] = (0.0, [])
        # step programs dispatched (a prefill chunk that is not a prompt's
        # last queues no fetch entry, and still keeps the device busy)
        self._dispatch_seq = 0
        # Modeled roofline seconds accumulated over prefill chunk
        # dispatches whose completions are UNOBSERVED (intermediate
        # chunks create no fetch entry).  The final chunk's entry
        # carries the whole accumulated sum: its measured span covers
        # the device backlog of every unobserved chunk before it, so
        # pairing it with only the last chunk's modeled cost would
        # inflate the prefill skew gauge by ~the chunk count on long
        # prompts — exactly the workload the gauge calibrates.
        self._prefill_modeled_acc: Optional[float] = None
        self.metrics = EngineMetrics()
        # Device-utilization estimator (ISSUE 10): the planner's
        # per-dispatch flop/byte cost model plus this chip's datasheet
        # roofline.  Every dispatch site reports its modeled cost to
        # metrics.record_dispatch_cost; wall time is attributed there.
        # Off-TPU the estimator is best-effort (an exotic tree/mesh that
        # defeats the arithmetic disables it, never a CPU test); on a TPU
        # a failure here — an unknown device_kind above all — raises: a
        # served chip without a roofline would report utilization it
        # cannot have.
        dev = (mesh.devices.flat[0] if mesh is not None
               else jax.devices()[0])
        n_dev = int(mesh.devices.size) if mesh is not None else 1
        on_tpu = dev.platform == "tpu"
        self._cost_model = None
        self._roofline: Optional[Tuple] = None
        self._have_roofline = False
        # The chunk plan (planner.prefill_launches): what one prefill launch
        # of (rows, tokens held, start) is modeled to cost on this chip, and
        # the first bucket of each plan made so far.  No roofline (the CPU,
        # an unlisted chip): no price, and a remainder goes out in the first
        # bucket that holds it.
        self._launch_price: Optional[Callable[[int, int, int], float]] = None
        self._first_buckets: Dict[Tuple[int, int], Tuple[int, bool]] = {}
        try:
            from ..models.quant import param_bytes as _param_bytes
            from .planner import device_peaks, dispatch_cost_model

            # (a pool per kind of layer is a dict of arrays of one dtype)
            pool = (jax.tree.leaves(self.k_pool)[0] if cfg.by_kind
                    else self.k_pool)
            kv_b = int(getattr(pool.dtype, "itemsize", 2))
            # (self.cfg: the price follows the backend the engine RESOLVED,
            # which the constructor's argument does not name)
            self._cost_model = dispatch_cost_model(
                self.cfg,
                n_devices=n_dev,
                weight_bytes_total=_param_bytes(params),
                kv_dtype_bytes=kv_b,
                kv_replication=self._tq,
                int8_experts=(cfg.is_moe
                              and experts_int8(params["layers"])),
            )
            self._roofline = device_peaks(dev)
            self.metrics.set_roofline(*self._roofline)
            # a known roofline must survive metrics RESETS (warmup and
            # bench swap in fresh EngineMetrics objects): the cost
            # helpers re-apply it on the first dispatch they record
            self._have_roofline = self._roofline[2] != "unknown"
            if self._have_roofline and all(self._roofline[:2]):
                self._launch_price = self._cost_model.launch_price(
                    *self._roofline[:2])
        except Exception as e:
            if on_tpu:
                raise
            logger.debug("dispatch cost model unavailable: %s", e)
        # Live HBM accounting (ISSUE 18): per-device memory_stats polled
        # at step cadence (throttled inside the monitor), reconciled
        # against the MemoryPlan the serving layer attaches after
        # planning (engine.memory_monitor.plan = plan).  Read-only
        # device introspection — no dispatch path depends on it.
        from .planner import MemoryMonitor

        self.memory_monitor: Optional[MemoryMonitor] = MemoryMonitor(
            list(mesh.devices.flat) if mesh is not None
            else jax.devices()[:1]
        )
        # What this engine actually runs on, resolved ONCE here and
        # served verbatim by /health: models/llama.py picks Pallas
        # interpret mode from the same default-backend test at trace
        # time, so "interpret" below is what the kernels will do.
        self.device_info: Dict[str, Any] = {
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "count": n_dev,
            "attention_backend": self.cfg.attention_backend,
            "interpret": (self.cfg.attention_backend == "pallas"
                          and jax.default_backend() != "tpu"),
            # the kinds of one period of the layer pattern and the window
            # of its sliding layers (one global kind, no window: a model
            # without a pattern)
            "layer_pattern": list(self.cfg.layer_period),
            "sliding_window": (self.cfg.sliding_window
                               if self.cfg.is_windowed else None),
        }
        note = tail_step_note(self.cfg)
        if note:
            logger.info("attention backend %s; %s",
                        self.cfg.attention_backend, note)
        # DP replica index (set by runtime/dp_router.py): traced requests'
        # engine spans carry it so a timeline names the replica it ran on
        self.replica: Optional[int] = None
        # Monotonic: rows of every prefill program dispatched (lanes x
        # bucket) and the rows of them that held a token (sum of
        # chunk_len).  1 - filled / dispatched is the padding the buckets
        # cost: the rows the flash-prefill kernel skips and an XLA prefill
        # (static shapes) computes.
        self.prefill_rows_dispatched = 0
        self.prefill_rows_filled = 0
        # requests prefilled, and those of them the chunk plan split
        # (_count_prefill_plan)
        self.prefill_plans = 0
        self.prefill_plans_split = 0
        # Monotonic, and 0 wherever decode does not run the XLA walk
        # (StepPrograms.decode_keys): the keys every decode step gathered
        # a layer (lanes x chunks x chunk keys, per step of a fused
        # dispatch) and the keys of the static windows it no longer
        # gathers (lanes x max_pages_per_seq x page_size); `shared` are the
        # walked keys of the trips whose pages every active lane of the
        # dispatch held in the same leading columns of its page table, read
        # once for all of them (StepPrograms.decode_keys_shared).
        self.decode_keys_walked = 0
        self.decode_keys_window = 0
        self.decode_keys_shared = 0
        # Monotonic, and 0 where no global layer's decode walks in the
        # Pallas kernel (StepPrograms.decode_steps): the whole softmax steps
        # that walk fetched over the dispatched decode steps, a lane and a
        # pass, one layer's worth, and those of them whose pages were one
        # ascending run of physical pages and were fetched as one copy a
        # pool (counted from SequencePages.run_steps, kept as pages are
        # appended: no page list is scanned a dispatch); `all` are that
        # walk's softmax steps, each lane's last included, and `ahead` those
        # of them whose copies the kernel had started before their lane's
        # program began (the call's lanes are one pipeline: every lane but
        # the first finds its first RING - 1 steps under way).
        self.decode_steps_walked = 0
        self.decode_steps_run = 0
        self.decode_steps_all = 0
        self.decode_steps_ahead = 0
        # Monotonic, and 0 for a model without an indexer
        # (StepPrograms.index_keys): the keys a layer's indexer scored over
        # every dispatched decode step (each lane's context, its own row
        # included) and the keys attention then read (at most index_topk a
        # lane).  kept / scored is what the selection keeps; `shared` are
        # the scored keys that sat in pages every lane of the dispatch held
        # in the same leading columns of its page table and were scored for
        # all of them in one product (StepPrograms.index_keys_shared).
        self.index_keys_scored = 0
        self.index_keys_kept = 0
        self.index_keys_shared = 0
        # Monotonic, and 0 where prefill does not walk the keys in chunks
        # (StepPrograms.prefill_walk_trips): the trips latent prefill's key
        # walk looped over every dispatched launch and layer, and those of
        # them whose fold ran as the Pallas kernel (equal on the Pallas
        # backend, 0 on XLA).
        self.prefill_walk_trips = 0
        self.prefill_walk_kernel_trips = 0
        # Monotonic, and both 0 for a model without linear-attention layers:
        # the chunks the gated-delta prefill kernel looped over every
        # dispatched launch, layer and active lane
        # (StepPrograms.delta_chunk_trips; 0 where the XLA scan runs), and
        # the bytes of delta state the decode passes read and wrote (busy
        # lanes x layers x 2 x a layer's slot: StepPrograms.delta_state_bytes)
        self.delta_chunk_trips = 0
        self.delta_state_bytes = 0
        # The same two of a model with an SSD mixer (both 0 without one):
        # StepPrograms.ssd_chunk_trips / ssd_state_bytes
        self.ssd_chunk_trips = 0
        self.ssd_state_bytes = 0
        # Monotonic, all 0 for a model without a state: passes x state
        # layers of the dispatched programs by the FORM each op of a state
        # layer took, the recurrence and the convolution's tail as a Pallas
        # kernel or in XLA (StepPrograms.state_forms: decided by shapes when
        # a program is traced, and seen in a device capture only before)
        self.state_launches = dict.fromkeys(
            (f"{op}_{form}" for op in ("recurrence", "tail")
             for form in ("kernel", "xla")), 0)
        # ... and the rows x SSD layers of the dispatched launches, padding
        # included, counted where prefill_rows_dispatched is
        self.ssd_rows_dispatched = 0
        # Rows x mapping sites of a widened residual stream (two a layer)
        # the dispatched launches ran, padding included; 0 with one row
        self.hc_site_rows = 0
        self._hc_sites = 2 * cfg.num_layers if cfg.hc_mult > 1 else 0
        # what every traced pass's span says of the model (`_pass_attrs`; the
        # default samples every request, so this is read a request a
        # dispatch: computed once)
        self._layer_attrs = dict(
            state_layers=cfg.state_layers, row_layers=cfg.kv_layers,
            routed_layers=cfg.routed_layers,
            **({"residual_streams": cfg.hc_mult} if cfg.hc_mult > 1 else {}))
        # Monotonic, and all 0 for a model with no routed block
        # (StepPrograms.moe_dispatch): step programs dispatched by the form
        # their routed blocks take, and the rows those blocks were handed
        # (rows a pass x the passes of a fused dispatch).
        self.moe_dispatch = dict.fromkeys(
            ("token_launches", "token_rows", "dense_launches", "dense_rows"),
            0)
        # Monotonic, 0 for a model with no routed block: over the decode
        # passes (single and fused steps), the held experts whose weights
        # the routed layers read, and those they hold (held experts x routed
        # layers a pass).  Equal where the blocks read every held expert
        # (dense: counted at dispatch); where they dispatch by token the
        # program counts the experts that had rows on the device and both
        # move when the step's tokens are fetched (_Fetch.reads).
        self.moe_experts_read = 0
        self.moe_experts_held = 0
        # Monotonic, 0 for a model with no routed block: over the same decode
        # passes, the picks (active rows x top-k x routed layers) that fell
        # on an expert THIS chip holds, and all of them.  Equal where the
        # experts are held whole; a held share (`cfg.num_experts_routed`)
        # sees the part of its deployment's load that its experts draw,
        # counted by the program beside the experts read (_Fetch.reads).
        self.moe_picks_held = 0
        self.moe_picks_routed = 0
        # Monotonic: the host's run-ahead, sampled at every decode / fused
        # / verify dispatch (_backlog_steps: steps in the FIFO the device
        # has not been seen to finish, the number _hold_decode bounds);
        # sum / samples is its mean over any window.
        self.fetch_depth_steps_sum = 0
        self.fetch_depth_samples = 0
        # The run-ahead's bound (_hold_decode).  decode_held: did the last
        # step() withhold decode (the driving loop then waits a moment
        # instead of stepping again at once).  Monotonic: iterations that
        # withheld it, and the seconds from each held iteration to the
        # next look at the backlog.
        self.decode_held = False
        self.decode_holds = 0
        self.decode_hold_s = 0.0
        self._hold_t = 0.0
        # Monotonic: seconds the scheduler thread sat in a read whose
        # transfer had not landed (_process_entry), and entries popped by
        # what released them: the age-and-landed rule, the fetch_lag depth
        # bound, a blocking drain, an out-of-order pop for a constrained
        # lane (_pop_entry_now / _pop_through).
        self.fetch_blocked_s = 0.0
        self.fetch_pops = {"aged": 0, "depth": 0, "blocking": 0, "now": 0}
        self._rtt_est = self._measure_rtt()

    def kv_window_dead_share(self) -> float:
        """Of the KV rows live lanes hold (tokens x layers), the share no
        later query can attend: a sliding-window layer's rows older than
        the window (the next query, at position `length`, reads positions
        > length - window).  The pool is uniform, every layer keeps every
        page: this is the memory a window-aware allocator would give
        back.  0.0 for a model without windowed layers."""
        cfg = self.cfg
        if not cfg.is_windowed:
            return 0.0
        windowed = cfg.layer_types.count(WINDOWED)
        held = dead = 0
        for req in list(self.slots):
            seq = getattr(req, "seq", None)
            if seq is None:
                continue
            held += seq.length * cfg.kv_layers
            dead += max(seq.length - cfg.sliding_window + 1, 0) * windowed
        return dead / held if held else 0.0

    def _measure_rtt(self) -> float:
        """Time a device→host fetch to seed the adaptive emit cadence.

        Fresh device_put arrays are probed (jax caches a materialized host
        value, so re-fetching the same array would measure nothing).  The
        estimate is kept honest by an EWMA over real blocking fetches in
        _process_entry.
        """
        samples = []
        for _ in range(2):
            probe = np.zeros(self.ecfg.max_batch, np.int32)
            arr = (
                jax.device_put(probe, self._replicated)
                if self._replicated is not None
                else jax.device_put(probe)
            )
            t0 = time.monotonic()
            np.asarray(arr)
            samples.append(time.monotonic() - t0)
        # ground-truth-ish copy latency: no compute in the probe, so traffic
        # EWMA updates are clamped around it (see _process_entry)
        self._rtt_probe = min(samples)
        return self._rtt_probe

    @staticmethod
    def _resolve_backend(cfg: ModelConfig, ecfg: EngineConfig, mesh) -> str:
        """Pick the decode attention backend (EngineConfig "auto" rule).

        The Pallas kernel needs: a real TPU (it runs in slow interpret mode
        anywhere else), a mesh whose head split the per-shard kernel can
        express (single device, or a pure tp/tq mesh passing
        pallas_mesh_ok — shard_map runs the custom call GSPMD cannot
        partition), a merged KV row that is lane-tile aligned
        (Hkv*D % 128, per shard on meshes), page rows aligned
        to the bf16 sublane tile (page_size % 16), and the 7 MB rule: the
        flash-prefill kernel once stacked a [Hq*D, Hkv*D]-shaped bf16
        working set (block-diagonal q rows over the whole merged row),
        which at Llama-3-8B geometry (4096 x 1024) measured 19.5 MB against
        the 16 MB v5e limit — past ~7 MB for that product, resolve to the
        XLA formulation (3B at 3072 x 1024 = 6.3 MB compiled and ran).

        That working set is gone since PR 44: the kernel multiplies one
        128-lane group of KV heads at a time, a q block's rows are one lane
        tile wide whatever Hkv is, and its q block is sized from the VMEM
        bytes it holds (ops/pallas/flash_prefill.q_block_rows).  The rule
        stays "auto"'s all the same, because a configuration that "auto"
        sends to XLA is checked and measured there (Mixtral's cell, 4096 x
        1024, pins "xla" in its check): lifting it is a benchmark change.
        A configuration may PIN "pallas" (EngineConfig /
        ServingConfig.attention_backend) once its geometry is shown to
        compile and run on the chip; the pin is returned as given.  Pinned
        today: K-EXAONE's 64 / 8 x 128 (8192 x 1024 = 16.8 MB by that
        product; its q block is 64 positions, 8 before PR 44).  Resolved by
        "auto" to the kernels: Yi and Mellum2 (32 / 4 x 128),
        Phi-4-mini-flash (40 / 20
        x 64), and every latent model.
        """
        choice = ecfg.attention_backend
        if ecfg.kv_quantize:
            # int8 KV: decode runs the int8 kernel (int8 page DMAs — half
            # the bf16 kernel's HBM traffic — with the per-slot dequant
            # fused into scores/probabilities, paged_attention.py);
            # prefill keeps the XLA dequantizing gather (llama.py gates
            # the flash kernel off QTensor pools).
            if choice != "auto":
                return choice
            merged_kv = cfg.kv_row_widths()[0]
            if mesh is not None and mesh.size > 1:
                from ..ops.pallas import pallas_mesh_ok

                tp = mesh.shape.get("tp", 1)
                ok = (
                    jax.default_backend() == "tpu"
                    and pallas_mesh_ok(
                        mesh, cfg.num_heads, cfg.num_kv_heads
                    )
                    and (merged_kv // tp) % 128 == 0
                    and ecfg.page_size % 16 == 0
                )
                return "pallas" if ok else "xla"
            ok = (
                jax.default_backend() == "tpu"
                and merged_kv % 128 == 0
                and ecfg.page_size % 16 == 0
            )
            return "pallas" if ok else "xla"
        if choice != "auto":
            return choice
        if cfg.is_latent:
            # the latent decode kernel DMAs pages of both pools' rows; there
            # is no flash prefill over latent rows, so no VMEM rule
            return "pallas" if (
                jax.default_backend() == "tpu"
                and all(w % 128 == 0 for kind in cfg.kinds
                        for w in cfg.kv_row_widths(kind))
                and ecfg.page_size % 16 == 0
            ) else "xla"
        merged_q = cfg.num_heads * cfg.head_dim
        merged_kv = cfg.kv_row_widths()[0]
        if mesh is not None and mesh.size > 1:
            # mesh path: the decode kernel runs per-shard via shard_map
            # (paged_decode_attention_sharded); prefill keeps the XLA
            # formulation (models/llama.py gates the flash kernel to
            # single-device), so only the decode kernel's per-shard
            # geometry matters: the pool's LOCAL merged row must stay
            # lane-tile aligned.  VMEM is no constraint — decode scratch
            # is a few chunk buffers, not flash-prefill's [Hq*D, Hkv*D]
            # working set.
            from ..ops.pallas import pallas_mesh_ok

            tp = mesh.shape.get("tp", 1)
            ok = (
                jax.default_backend() == "tpu"
                and pallas_mesh_ok(mesh, cfg.num_heads, cfg.num_kv_heads)
                and (merged_kv // tp) % 128 == 0
                and ecfg.page_size % 16 == 0
            )
            return "pallas" if ok else "xla"
        ok = (
            jax.default_backend() == "tpu"
            and merged_kv % 128 == 0
            and ecfg.page_size % 16 == 0
            and merged_q * merged_kv * 2 <= 7 * 1024 * 1024
        )
        return "pallas" if ok else "xla"

    def _tattrs(self, **kw) -> Dict[str, Any]:
        """Span attrs for this engine's traced requests (replica-stamped
        on DP replicas).  Called only for traced requests — cold path."""
        if self.replica is not None:
            kw["replica"] = self.replica
        return kw

    def _pass_attrs(self, **kw) -> Dict[str, Any]:
        """Attrs of a span over forward passes (engine.prefill,
        engine.decode): the layers the pass ran by what they hold and by
        their feed-forward (the `engine.state_layers` / `row_layers` /
        `routed_layers` gauges' values); a model with a widened residual
        stream says how many rows a token."""
        kw.update(self._layer_attrs)
        return self._tattrs(**kw)

    def _prefill_attrs(self, req: "GenRequest", **kw) -> Dict[str, Any]:
        """engine.prefill span attrs: prompt size plus the radix-cache
        share (cached_tokens / cache_source: own-thread vs cross-thread)
        when the prefill resumed past cached pages.  Traced requests
        only — cold path."""
        kw["tokens"] = len(req.prefill_ids)
        if req.cached_tokens:
            kw["cached_tokens"] = req.cached_tokens
            kw["cache_source"] = req.cache_source
            if req.promoted_tokens:
                kw["promoted_tokens"] = req.promoted_tokens
            if req.object_tokens:
                kw["object_tokens"] = req.object_tokens
        if req.state_restored is not None:
            # a model with a recurrent state: the snapshot slot that was
            # copied into the lane's slot ahead of this prefill
            kw["state_snapshot"] = req.state_restored
        return self._pass_attrs(**kw)

    def _dispatch_scope(self, kind: str,
                        members: Sequence[Optional["GenRequest"]],
                        fused: bool = False):
        """Host annotation `kafka.<kind>[<trace ids>]` around one dispatch
        (kind: prefill, decode, verify), so a /debug/profile xplane capture
        correlates device slices with server-side spans and labels idle
        gaps by what the scheduler was dispatching.  One module-global
        bool read when disabled (KAFKA_TPU_PROFILING unset).  The scope
        also books the device's starvation (_DispatchScope): every step
        program is dispatched inside one, which is also where the
        iteration learns what it did (tracing.SCHED_ITER_CLASSES)."""
        self.sched.did("prefill" if kind == "prefill"
                       else "multi" if fused else "decode")
        if not profiler_annotations_enabled():
            return _DispatchScope(self, None)
        ids = sorted({
            m.trace.trace_id[:8] for m in members
            if m is not None and m.trace is not None
        })
        return _DispatchScope(self, jax.profiler.TraceAnnotation(
            f"kafka.{kind}[" + ",".join(ids) + "]"
        ))

    def _dev(self, x) -> jnp.ndarray:
        """Host -> device, replicated across the mesh when one is active.
        For device-RESIDENT state (control arrays reused across steps)."""
        arr = jnp.asarray(x)
        if self._replicated is not None:
            arr = jax.device_put(arr, self._replicated)
        return arr

    def _arg(self, x):
        """Prepare a host value used once as a jit argument.

        Single device: pass the numpy value through — jit transfers it as
        part of the call instead of a standalone device_put dispatch per
        argument (a prefill chunk passes seven).  Mesh engines still place
        explicitly so every argument is replicated across devices.
        """
        return self._dev(x) if self._replicated is not None else x

    def _lanes(self, active) -> Lanes:
        """The device-resident lane arrays of a decode-side dispatch, with
        `active` [B] choosing the lanes it advances."""
        return Lanes(self._d_table, self._d_last, self._d_seq_lens, active,
                     self._d_temps, self._d_top_ks, self._d_top_ps,
                     self._d_seeds)

    def _fsm(self, on: bool) -> Optional[Fsm]:
        """The on-device grammar automaton of a decode-side dispatch, lane
        state and tables, or None for the plain program."""
        if not on:
            return None
        return Fsm(self._d_fsm, self._d_fsm_g, self._d_budget,
                   *self._grammars.args())

    def _keep_fsm(self, fsm_out) -> None:
        """The (state, budget) an fsm program's result ends with is the
        lanes' new device truth; a plain program's ends with nothing."""
        if fsm_out:
            self._d_fsm, self._d_budget = fsm_out

    @property
    def _verify_fn(self) -> Optional[Callable]:
        """The plain verify program if this engine ever asked for it."""
        return self._programs.built.get(("verify", None))

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def submit(self, req: GenRequest) -> None:
        if len(req.prompt_ids) == 0:
            raise ValueError("empty prompt")
        if req.handoff and self.state_pool is not None:
            raise RecurrentStateUnsupported(
                "dp hand-off of a prefilled run",
                "the page shipper would move the run's pages and leave its "
                "state behind; serve colocated (no dp_roles)")
        if (
            not req.background
            and self.ecfg.max_waiting > 0
            and len(self.waiting) >= self.ecfg.max_waiting
        ):
            self.metrics.record_rejected()
            if self.flight is not None:
                self.flight.note_cause("reject")
            raise AdmissionError(
                len(self.waiting), self.ecfg.max_waiting,
                self.retry_after_estimate(),
            )
        limit = self.ecfg.max_window
        if len(req.prompt_ids) + 1 > limit:
            raise ValueError(
                f"prompt of {len(req.prompt_ids)} tokens exceeds the "
                f"attention window ({limit}); compact the conversation first"
            )
        if req.max_new_tokens is None:
            req.max_new_tokens = self.ecfg.max_new_tokens_default
        if len(req.prompt_ids) + req.max_new_tokens > limit:
            req.max_new_tokens = max(1, limit - len(req.prompt_ids))
        if req.grammar is not None and (
            getattr(req.grammar, "vocab_size", None) != self.cfg.vocab_size
        ):
            # an artifact compiled for another model's vocab cannot index
            # this engine's tables: host mask path
            req.grammar = None
        if req.logits_mask_fn is not None and hasattr(
            req.logits_mask_fn, "set_budget"
        ):
            # constrained decoding: tell the mask the post-clamp budget so
            # it can wrap the JSON up before tokens run out
            req.logits_mask_fn.set_budget(req.max_new_tokens)
        req.prefill_ids = list(req.prompt_ids)
        if req.handoff and (
            req.prefix_key is None or self.prefix_cache is None
        ):
            # a hand-off run is named by the radix cache at both ends;
            # without a key (or cache) there is nothing to register —
            # serve the request in place instead
            req.handoff = False
        if (
            self.ecfg.speculative_k > 0
            and (req.logits_mask_fn is None or req.grammar is not None)
            and req.spec is None
        ):
            # Free lanes and DEVICE-FSM constrained lanes speculate;
            # grammar text is the most predictable output the server
            # emits (the verify step masks every position with the FSM
            # state reached through the candidate prefix).  Only
            # HOST-masked lanes are excluded — their masks need per-token
            # host turnaround, the opposite of a K-token device run.
            req.spec = LaneSpeculator(req.prompt_ids)
        req.submit_time = time.monotonic()
        self.metrics.record_submit(len(req.prompt_ids))
        req.state = WAITING
        if req.prefix_key is not None and (
            self._agent_gaps or self._awaiting_demoted
        ):
            # the thread is back (whether or not the return hint fired):
            # a pending gap demote must not race the new turn's admission
            self._agent_gaps.pop(req.prefix_key, None)
            self._awaiting_demoted.pop(req.prefix_key, None)
        if req.background:
            self.waiting_bg.append(req)
        else:
            self.waiting.append(req)
        self._requests[req.request_id] = req

    def warmup_verify(self) -> None:
        """Compile the speculative verify program outside serving.

        Organic engagement depends on *generated* repetition, which a
        warm prompt cannot guarantee, so server warmup triggers the
        compile with an all-inactive dispatch: every write is masked to
        the trash page, seq_lens don't advance, and no scheduler state
        changes.  No-op when speculative_k is 0 (the program must never
        exist then)."""
        if self.ecfg.speculative_k <= 0:
            return
        B, K = self.ecfg.max_batch, self.ecfg.speculative_k
        if self._d_table is None or self._ctl_dirty:
            self._refresh_ctl()
        self._warm_verify(self._dev(np.zeros(B, bool)), fsm=False)

    def warmup_grammar(self, grammar) -> None:
        """Compile the on-device grammar FSM programs outside serving.

        Mirrors warmup_verify: registers `grammar` and runs the fsm
        decode variant (and the fsm verify variant when speculative_k>0)
        with an all-inactive dispatch — KV writes hit the trash page,
        seq_lens and FSM lanes don't advance, no scheduler state changes.
        Without this the first tool_choice-constrained request compiles
        the fsm decode program on the scheduler thread, stalling every
        in-flight stream.  The fused multi-step fsm variant still
        compiles on its first >=3-lane engagement, and a LATER schema
        registering at a larger padded shape retraces once — both noted
        costs, not warmed here.  No-op when the grammar cannot register
        (those requests use the host mask path anyway)."""
        g_idx = self._grammars.register(grammar)
        if g_idx is None:
            return
        B = self.ecfg.max_batch
        if self._d_table is None or self._ctl_dirty:
            self._refresh_ctl()
        inactive = self._dev(np.zeros(B, bool))
        fsm = self._fsm(True)
        (self.k_pool, self.v_pool, toks, self._d_seq_lens,
         *fsm_out, _) = self._programs.decode(fsm)(
            self.params, self.k_pool, self.v_pool, self._lanes(inactive),
            None, None, fsm,
        )
        self._keep_fsm(fsm_out)
        np.asarray(toks)  # block until the compile + dispatch complete
        # the activation-time advance of _set_fsm_lane and its scatter of
        # a device scalar into the lane state (result discarded)
        nxt = _fsm_advance(
            self._grammars.token_class, self._grammars.trans,
            self._d_last, g_idx, self._grammars.offsets[g_idx], 0,
        )
        np.asarray(self._d_fsm.at[0].set(nxt))
        if self.ecfg.speculative_k > 0:
            self._warm_verify(inactive, fsm=True)

    def _warm_verify(self, inactive, fsm: bool) -> None:
        """One all-inactive dispatch of the verify program (plain or fsm),
        blocking until the compile + dispatch complete."""
        B, K = self.ecfg.max_batch, self.ecfg.speculative_k
        fsm_arg = self._fsm(fsm)
        (self.k_pool, self.v_pool, out, self._d_last, self._d_seq_lens,
         *fsm_out) = self._programs.verify(K, fsm_arg)(
            self.params, self.k_pool, self.v_pool, self._lanes(inactive),
            self._arg(np.zeros((B, K), np.int32)),
            self._arg(np.zeros(B, np.int32)),
            fsm_arg,
        )
        self._keep_fsm(fsm_out)
        np.asarray(out)

    def warmup_kv_tier(self) -> None:
        """Compile the tier's ship (gather/scatter) programs outside
        serving.  Page runs ship in fixed bucket sizes (kv_tier.
        SHIP_BUCKETS); without this the first demotion under pressure —
        or worse, the first returning thread's promotion — pays an XLA
        compile on the scheduler thread.  Warmed against the trash page:
        gathers read garbage, scatters write garbage INTO the trash page
        (its contract), no pool state changes.  No-op without a tier."""
        if self.kv_tier is None:
            return
        from .kv_tier import SHIP_BUCKETS

        ship = self.kv_tier.shipper
        for b in SHIP_BUCKETS:
            pending = ship.export_run([TRASH_PAGE] * b)
            k_leaves, v_leaves = ship.resolve(pending)
            ship.import_run(k_leaves, v_leaves, b, [TRASH_PAGE] * b)

    def warmup_state(self) -> None:
        """Compile the snapshot-restore program (the trash slot copied onto
        itself).  No-op for a model without a recurrent state."""
        if self.state_pool is None:
            return
        trash = self._arg(np.int32(self.state_pool.trash))
        self.v_pool = self._programs.state_copy()(self.v_pool, trash, trash)

    def _object_fingerprint(self) -> str:
        """The object tier's content-address fingerprint: model name +
        page geometry + per-slot pool layout (+ an operator namespace,
        KAFKA_TPU_KV_OBJECT_NAMESPACE — bump it when weights change
        under an unchanged config, since the hash cannot see weights).
        Two engines agreeing on this can exchange KV runs byte-for-byte
        through a shared store; any mismatch partitions the store."""
        leaves = jax.tree.leaves(self.k_pool) + jax.tree.leaves(self.v_pool)
        geo = ",".join(
            f"{a.dtype}:{a.shape[0]}x{tuple(a.shape[2:])}" for a in leaves
        )
        ns = os.environ.get("KAFKA_TPU_KV_OBJECT_NAMESPACE", "")
        return f"{self.cfg.name}|ps{self.ecfg.page_size}|{geo}|{ns}"

    def sleep_to_object(self) -> Dict[str, Any]:
        """Flush this engine's warm KV state (every cached radix run +
        per-thread sleep manifests) into the shared object store — the
        POST /admin/drain/{replica} seam, used by the autoscaler's
        drain-then-shrink scale-in.  Non-destructive; see
        PrefixCache.sleep_to_object for the contract.  Must run with the
        scheduler quiesced (single-writer: the provider parks the
        worker first)."""
        if self.state_pool is not None:
            raise RecurrentStateUnsupported(
                "a sleep manifest (sleep_to_object)",
                "a thread woken from its pages alone would have no state")
        if (
            self.prefix_cache is None
            or self.kv_tier is None
            or self.kv_tier.object is None
        ):
            return {"enabled": False}
        return self.prefix_cache.sleep_to_object()

    def take_waiting(self) -> List[GenRequest]:
        """Remove and return every WAITING request (they own no device
        state).  Replica supervision seam: the DP router migrates a
        quarantined/dead replica's queue onto healthy replicas, and
        topology rebuilds carry the queue across engine generations.
        Must run on the thread that drives step() (single-writer)."""
        taken = list(self.waiting) + list(self.waiting_bg)
        self.waiting.clear()
        self.waiting_bg.clear()
        for req in taken:
            if req.seq is not None:  # defensive: a waiting req owns no pages
                self.pool.free_sequence(req.seq)
                req.seq = None
            self._requests.pop(req.request_id, None)
        return taken

    def adopt(self, req: GenRequest) -> None:
        """Requeue a WAITING request taken from another replica.

        Unlike submit() this skips admission bounds and submission metrics
        — the request was already admitted and counted once; migration
        must neither double-count it nor bounce it off the target's queue
        bound (a migrated request losing its slot in line would turn a
        replica failure into client-visible rejections)."""
        req.state = WAITING
        if req.background:
            self.waiting_bg.append(req)
            self.waiting_bg.sort(key=lambda r: r.submit_time)
        else:
            self.waiting.append(req)
            self.waiting.sort(key=lambda r: r.submit_time)
        self._requests[req.request_id] = req

    def cancel(self, request_id: str, reason: str = "cancelled") -> bool:
        """Abort a request (client disconnect); frees its slot and pages.

        Must run on the thread that drives `step()` (the engine is
        single-writer; EngineWorker routes cancels through its inbox for
        this reason). Returns False for unknown/already-finished ids.
        In-flight fetches for the request are simply discarded as they
        mature.  `reason` lets failure paths (worker._fail_all) record the
        finish as an engine error rather than a client cancel.
        """
        req = self._requests.get(request_id)
        if req is None or req.state == FINISHED:
            return False
        if req.state == WAITING:
            try:
                (self.waiting_bg if req.background
                 else self.waiting).remove(req)
            except ValueError:
                pass
        req.state = FINISHED
        req.finish_reason = reason
        self._finalize_slo(req, reason)
        if req.slot >= 0 or req.seq is not None:
            self._release_slot(req)
        self._requests.pop(request_id, None)
        return True

    # -- agent tool-call gaps (ISSUE 20) --------------------------------

    def note_tool_gap(self, prefix_key: Optional[str]) -> None:
        """The thread just finished a turn with finish_reason=tool_calls
        and is now idle for the tool's runtime (the provider signals this
        through the worker inbox, so it runs on the engine thread).
        Start the linger clock: after agent_linger_s with no return, the
        thread's KV demotes down the tier ladder.  No-op with the knob
        off or without the cache+tier to demote into."""
        if (
            not prefix_key
            or not self.ecfg.agent_demote
            or self.prefix_cache is None
            or self.kv_tier is None
        ):
            return
        self.agent_gaps += 1
        # re-noting an existing gap restarts its linger (dict order stays
        # due order only if we re-insert)
        self._agent_gaps.pop(prefix_key, None)
        self._agent_gaps[prefix_key] = (
            time.monotonic() + self.ecfg.agent_linger_s
        )

    def note_tool_return(self, prefix_key: Optional[str]) -> None:
        """The tool finished (sandbox SSE terminal -> agent loop -> the
        provider's return hint): the thread's follow-up turn is imminent.
        Cancel a still-lingering demote (sub-linger tools never pay the
        round trip), or — when the gap already demoted — protect the
        thread's tier runs from second-chance eviction and kick the wake
        prefetcher so promotion/object GETs overlap the tool's tail."""
        if not prefix_key or not self.ecfg.agent_demote:
            return
        pending = self._agent_gaps.pop(prefix_key, None)
        demoted = self._awaiting_demoted.pop(prefix_key, None)
        if pending is not None:
            self.agent_gap_cancelled += 1
            self.agent_hint_hits += 1
            return
        if demoted is None:
            self.agent_hint_misses += 1
            return
        self.agent_hint_hits += 1
        pc = self.prefix_cache
        if pc is None:
            return
        resident = pc.touch_thread(prefix_key)
        tier = self.kv_tier
        obj = getattr(tier, "object", None) if tier is not None else None
        pre = getattr(obj, "prefetcher", None) if obj is not None else None
        if pre is not None:
            # object GETs for any runs NOT locally resident (a drained or
            # rebuilt replica's threads) start now, overlapping the tail
            pre.prefetch_thread(prefix_key, min_depth=resident)

    def _process_agent_gaps(self) -> None:
        """Demote threads whose tool-call linger expired (step() entry).
        Insertion order == due order (constant linger), so the scan stops
        at the first not-yet-due key."""
        now = time.monotonic()
        while self._agent_gaps:
            key, due = next(iter(self._agent_gaps.items()))
            if due > now:
                break
            del self._agent_gaps[key]
            self._demote_gap_thread(key)

    def _demote_gap_thread(self, key: str) -> None:
        pc, tier = self.prefix_cache, self.kv_tier
        if pc is None or tier is None:
            return
        stats = pc.demote_thread(
            key, archive=(self.ecfg.agent_demote == "object")
        )
        pages = stats.get("pages", 0)
        if pages:
            self.agent_gap_demotions += 1
            self.agent_gap_pages_demoted += pages
            self.agent_gap_bytes_demoted += tier.bytes_for_pages(pages)
            if self.flight is not None:
                self.flight.note_cause("agent_demote")
        # 0-page sweeps still register the awaiting state: the thread IS
        # mid-gap (its KV may already be tier-resident from pressure)
        self._awaiting_demoted[key] = (
            self._awaiting_demoted.get(key, 0) + pages
        )

    def awaiting_tool_keys(self) -> List[str]:
        """Threads currently mid-tool-call-gap (linger pending or
        demoted-awaiting) — the flightview lane flag's source."""
        return list(self._agent_gaps) + [
            k for k in self._awaiting_demoted if k not in self._agent_gaps
        ]

    def agent_section(self) -> Dict[str, int]:
        """AGENT_METRIC_KEYS snapshot section (runtime/metrics.py owns
        the registry; /admin/signals v9 and /metrics both read this)."""
        pages = sum(self._awaiting_demoted.values())
        tier = self.kv_tier
        return {
            "agent_gaps": self.agent_gaps,
            "agent_gap_demotions": self.agent_gap_demotions,
            "agent_gap_pages_demoted": self.agent_gap_pages_demoted,
            "agent_gap_bytes_demoted": self.agent_gap_bytes_demoted,
            "agent_gap_cancelled": self.agent_gap_cancelled,
            "agent_hint_hits": self.agent_hint_hits,
            "agent_hint_misses": self.agent_hint_misses,
            "agent_awaiting_threads": (
                len(self._agent_gaps) + len([
                    k for k in self._awaiting_demoted
                    if k not in self._agent_gaps
                ])
            ),
            "agent_awaiting_bytes": (
                tier.bytes_for_pages(pages) if tier is not None else 0
            ),
            "bg_queue_depth": len(self.waiting_bg),
            "bg_admitted": self.bg_admitted,
            "bg_chunks": self.bg_chunks,
            "bg_yields": self.bg_yields,
        }

    def retry_after_estimate(self) -> float:
        """Seconds until queue relief is plausible, for 429 Retry-After.

        Derived from current decode throughput: the batch retires roughly
        max_batch requests per (default token budget x per-token latency);
        a full waiting queue drains one admission per retirement.  Recent
        TPOT is the honest per-token figure (wall-clock throughput goes to
        zero while idle); with no samples yet fall back to a conservative
        guess.  Clamped to [1, 120] — this is a hint, not a promise.
        """
        tpot_s = self.metrics.recent_tpot_s() or 0.05
        per_request_s = self.ecfg.max_new_tokens_default * tpot_s
        drain_rate = self.ecfg.max_batch / max(per_request_s, 1e-3)
        excess = max(1, len(self.waiting) - self.ecfg.max_batch)
        return float(min(120.0, max(1.0, excess / max(drain_rate, 1e-3))))

    def _check_deadlines(self) -> None:
        """Time out requests past their TTFT/total deadline (step() entry).

        A timeout is a cancel with a client-visible reason: the request
        finishes with finish_reason="timeout", its slot and pages free
        immediately, and in-flight fetches for it are discarded as they
        mature.  DRAINING requests are exempt — their dispatching already
        stopped and a terminal event is imminent.
        """
        ecfg = self.ecfg
        now = time.monotonic()
        for req in list(self._requests.values()):
            if req.state in (FINISHED, DRAINING):
                continue
            total = req.deadline_s if req.deadline_s is not None \
                else ecfg.max_total_s
            ttft = req.deadline_ttft_s if req.deadline_ttft_s is not None \
                else ecfg.max_ttft_s
            age = now - req.submit_time
            if (total is not None and age > total) or (
                ttft is not None
                and req.first_token_time is None
                and age > ttft
            ):
                self._timeout(req)

    def _timeout(self, req: GenRequest) -> None:
        logger.warning(
            "request %s timed out after %.2fs (state %s)",
            req.request_id, time.monotonic() - req.submit_time, req.state,
        )
        if req.state == WAITING:
            try:
                (self.waiting_bg if req.background
                 else self.waiting).remove(req)
            except ValueError:
                pass
        req.state = FINISHED
        req.finish_reason = "timeout"
        self._finalize_slo(req, "timeout")
        if self.flight is not None:
            self.flight.note_cause("timeout")
        if req.slot >= 0 or req.seq is not None or req in self.parked:
            self._release_slot(req)
        self._requests.pop(req.request_id, None)
        self._out_events.append(
            TokenEvent(req.request_id, None, finished=True,
                       finish_reason="timeout")
        )

    @property
    def num_active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def has_work(self) -> bool:
        return (
            self.num_active > 0
            or bool(self.waiting)
            or bool(self.waiting_bg)
            or bool(self.parked)
            or bool(self._pending)
            # a pending tool-call-gap linger needs step() to keep running
            # on an otherwise-idle engine, or the demote never fires
            or bool(self._agent_gaps)
        )

    def step(self) -> List[TokenEvent]:
        """One scheduler iteration: drain fetches, admit, advance one
        prefill chunk per prefilling request, decode every active lane.

        Prefill is interleaved, not inlined: a long prompt advances one
        chunk per iteration while the decode batch keeps stepping, so a
        2k-token (or 32k-token) admission never stalls co-scheduled streams
        for its whole prefill — their inter-token gap is bounded by ~one
        chunk's compute.
        """
        mark = self.sched.mark
        mark("house")
        failpoint("engine.step")
        if self.memory_monitor is not None:
            self.memory_monitor.poll()  # throttled to ~1 Hz internally
        if self.kv_tier is not None:
            # resolve completed D2H demotions so their gather buffers
            # leave HBM promptly (cheap: a list scan, usually empty)
            self.kv_tier.drain()
        if self._park_cooldown > 0:
            self._park_cooldown -= 1
        self._check_deadlines()
        if self._agent_gaps:
            self._process_agent_gaps()
        self.metrics.record_queue_depth(len(self.waiting))
        mark("drain")
        self._drain(block=False)
        mark("admit")
        self._admit()
        mark("prefill")
        self._advance_prefills()
        mark("hold_check")
        if not any(s is not None and s.state == ACTIVE for s in self.slots):
            self.decode_held = False
        elif not self._hold_decode():
            mark("decode")
            self._dispatch_decode()
            mark("drain")
            self._drain(block=False)
        mark("house")
        if not self.num_active and not self.waiting and self._pending:
            # Nothing left to dispatch: flush the pipeline — EXCEPT when
            # the pending work is a prefill-and-hand-off.  The DP router
            # drives every replica from ONE thread, and a prefill-pool
            # replica blocking here would stall every other replica's
            # dispatch cadence for the full chunk compute — exactly the
            # interference disaggregation exists to remove.  Hand-off
            # entries drain non-blocking on a later step (has_work spans
            # them, so the drive loop keeps coming back).
            if not any(r.handoff and r.state == DRAINING
                       for r in self._requests.values()):
                mark("flush")
                self._drain(block=True)
                mark("house")
        if not self.num_active:
            self.metrics.mark_idle()  # idle gaps are not TPOT
            self._last_ready_t = None  # measured-latency chain restarts
            self._starve = None  # a device without work is not starved
        if self.flight is not None and not (
                self.decode_held and self.flight.quiet()):
            # commit this iteration's record + run the anomaly detectors
            # (iterations that only held decode, a millisecond apart,
            # would fill the ring with nothing: they commit ten a second)
            mark("flight")
            self.flight.finish_step(self)
        # whatever the caller does before its next wait or step() is the
        # loop's own bookkeeping
        mark("inbox")
        out, self._out_events = self._out_events, []
        return out

    def run_to_completion(self) -> Dict[str, GenRequest]:
        """Drain all requests (testing/bench convenience)."""
        registry = dict(self._requests)
        done: Dict[str, GenRequest] = {}
        while self.has_work:
            for ev in self.step():
                if ev.finished:
                    done[ev.request_id] = registry[ev.request_id]
            if self.decode_held:
                self.sched.nap(_HOLD_NAP_S)
        return done

    def generate(self, prompt_ids: List[int], **kw) -> GenRequest:
        """Single-request synchronous generation (BASELINE config 1)."""
        req = GenRequest(
            request_id=f"gen-{next(self._counter)}", prompt_ids=list(prompt_ids), **kw
        )
        self.submit(req)
        while req.state != FINISHED:
            self.step()
            if self.decode_held:
                self.sched.nap(_HOLD_NAP_S)
        return req

    # ------------------------------------------------------------------
    # failure handling & self-check
    # ------------------------------------------------------------------

    def _expected_page_owners(self) -> Dict[int, int]:
        """Per-page live reference counts from host bookkeeping: every
        registered request's sequence plus the prefix cache's retains.
        This is what the pool's refcounts must equal — any page above it
        is leaked, any below is double-freed."""
        owners: Dict[int, int] = {}
        for req in self._requests.values():
            if req.seq is not None:
                for p in req.seq.pages:
                    owners[p] = owners.get(p, 0) + 1
        if self.prefix_cache is not None:
            for p, n in self.prefix_cache.page_owners().items():
                owners[p] = owners.get(p, 0) + n
        return owners

    def self_check(self, repair: bool = False) -> List[str]:
        """Verify scheduler/pool invariants; returns problems (empty=ok).

        Checks: slot occupancy (every seated request knows its slot and
        vice versa, no finished request holds a slot), parked-list states,
        allocator internal consistency, and page accounting against the
        live owner set.  With `repair`, page discrepancies are fixed in
        place (leaks released, double frees re-pinned) so the engine can
        keep serving after a step failure instead of slowly wedging.
        """
        problems: List[str] = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            if s.slot != i:
                problems.append(
                    f"slot {i} holds {s.request_id} whose slot field is "
                    f"{s.slot}"
                )
            if s.state not in (ACTIVE, PREFILLING):
                problems.append(
                    f"slot {i} holds {s.request_id} in state {s.state}"
                )
            if self._requests.get(s.request_id) is not s:
                problems.append(
                    f"slot {i} holds unregistered request {s.request_id}"
                )
        for req in self._requests.values():
            if req.slot >= 0 and self.slots[req.slot] is not req:
                problems.append(
                    f"{req.request_id} claims slot {req.slot} but the slot "
                    "holds someone else"
                )
        for req in self.parked:
            if req.state not in (PARKED, PREFILLING):
                problems.append(
                    f"parked lane {req.request_id} in state {req.state}"
                )
        problems += self.pool.check_consistency()
        problems += self.pool.reconcile(
            self._expected_page_owners(), repair=repair
        )
        if self.state_pool is not None:
            problems += self.state_pool.check_consistency()
            owners = (self.prefix_cache.snapshot_owners()
                      if self.prefix_cache is not None else {})
            for req in self._requests.values():
                if req.state_snapshot is not None:
                    owners[req.state_snapshot] = owners.get(
                        req.state_snapshot, 0) + 1
            sp = self.state_pool
            for slot in range(sp.lanes + 1, sp.n_slots):
                if int(sp.refcount[slot]) != owners.get(slot, 0):
                    problems.append(
                        f"state slot {slot}: refcount {sp.refcount[slot]}, "
                        f"{owners.get(slot, 0)} live owners")
        return problems

    def lane_table(self) -> List[Dict[str, Any]]:
        """The active-lane table for postmortems: every registered
        request's scheduler-visible state, readable without the engine."""
        now = time.monotonic()
        tier = getattr(self, "kv_tier", None)
        obj = getattr(tier, "object", None) if tier is not None else None
        pre = getattr(obj, "prefetcher", None) if obj is not None else None
        out: List[Dict[str, Any]] = []
        for req in self._requests.values():
            out.append({
                "request_id": req.request_id,
                "state": req.state,
                "slot": req.slot,
                "age_s": round(now - req.submit_time, 3)
                if req.submit_time else None,
                "prompt_tokens": len(req.prompt_ids),
                "output_tokens": len(req.output_ids),
                "dispatched": req.dispatched,
                "drained": req.drained,
                "spec_ahead": req.spec_ahead,
                "cached_tokens": req.cached_tokens,
                "cache_source": req.cache_source,
                # wake-prefetch staging ready for this lane's thread
                # (ISSUE 19): nonzero = an admission would consume these
                # bytes with zero fetch RTT
                "prefetch_staged_bytes": (
                    pre.staged_bytes_for(req.prefix_key)
                    if pre is not None and req.prefix_key else 0
                ),
                "grammar": req.grammar is not None,
                "host_constrained": self._host_constrained(req),
                "predicted": len(req.predicted),
                "pages": len(req.seq.pages) if req.seq is not None else 0,
                "seq_len": req.seq.length if req.seq is not None else 0,
                "finish_reason": req.finish_reason,
                "background": req.background,
            })
        # Threads mid-tool-call gap (ISSUE 20) have NO registered request
        # — the turn finished with tool_calls — but their state is what a
        # postmortem reader needs to see: synthetic rows carry the linger
        # / demoted-pages standing so "where did that thread's KV go?"
        # is answerable from the dump alone.
        for key in self.awaiting_tool_keys():
            out.append({
                "request_id": f"thread:{key[:40]}",
                "state": "awaiting_tool",
                "slot": -1,
                "awaiting_tool": True,
                "lingering": key in self._agent_gaps,
                "demoted_pages": self._awaiting_demoted.get(key, 0),
                "prefetch_staged_bytes": (
                    pre.staged_bytes_for(key) if pre is not None else 0
                ),
            })
        return out

    def dump_postmortem(self, reason: str) -> Optional[str]:
        """Write a flight-recorder postmortem (ring + metrics snapshot +
        active-lane table) for this replica.  Best-effort and exception-
        free — this runs on failure paths.  None when the recorder is
        off or no dump directory is configured."""
        if self.flight is None:
            return None
        try:
            # flush the failing iteration's partial staging into the ring
            # so the dump's LAST record describes the step that died
            self.flight.finish_step(self)
        except Exception:  # pragma: no cover - defensive
            pass
        try:
            lanes = self.lane_table()
        except Exception:  # pragma: no cover - defensive
            lanes = []
        try:
            snap = self.metrics.snapshot(self, reset_peak=False)
        except Exception:  # pragma: no cover - defensive
            snap = {}
        self.flight.replica = self.replica
        return self.flight.dump_postmortem(
            reason, lanes=lanes, metrics_snapshot=snap,
        )

    def recover_from_failure(self) -> List[TokenEvent]:
        """Rebuild a servable engine after a step() exception.

        Contract (chaos-tested): every request that had started compute
        gets exactly one terminal error event; WAITING requests are kept
        queued (they own no device state and can still be served); page
        accounting is verified and repaired; decode control state is
        rebuilt from scratch.  The caller (EngineWorker) dispatches the
        returned events.
        """
        # black-box first: capture the ring + lane table BEFORE recovery
        # mutates them (the postmortem must explain the failing step)
        self.dump_postmortem("engine_failure")
        events: List[TokenEvent] = list(self._out_events)
        self._out_events = []
        # In-flight fetches reference arrays whose producing computation
        # may have died mid-flight: discard them all (their tokens become
        # speculative waste, same as a cancel).
        self._pending.clear()
        self._pending_steps = 0
        self._constrained_fetch = None
        self._last_ready_t = None
        self._starve = None
        self._prefill_modeled_acc = None  # its chunks died with the step
        for req in list(self._requests.values()):
            if req.state == WAITING:
                # never started compute: keep it queued, but make sure a
                # half-attached prefix share doesn't pin pages.  A request
                # popped from the queue whose prefill start died before
                # changing its state is still WAITING but off-queue —
                # re-insert it or it would orphan (registered, never
                # scheduled, no terminal event).
                if req.seq is not None:
                    self.pool.free_sequence(req.seq)
                    req.seq = None
                req.spec_ahead = 0  # any in-flight verify was discarded
                if req not in self.waiting:
                    self.waiting.append(req)
                continue
            req.state = FINISHED
            req.finish_reason = "error:engine"
            self._finalize_slo(req, "error:engine")
            add_event(req.trace, "engine.recover",
                      {"reason": "error:engine", **self._tattrs()})
            self._release_slot(req)
            self._requests.pop(req.request_id, None)
            events.append(
                TokenEvent(req.request_id, None, finished=True,
                           finish_reason="error:engine")
            )
        # submit-order FIFO must survive the re-inserts above
        self.waiting.sort(key=lambda r: r.submit_time)
        # device control state: all lanes are gone, rebuild from zero (the
        # next _dispatch_decode re-uploads tables via _refresh_ctl; _d_last
        # lanes are re-seeded at each admission)
        B = self.ecfg.max_batch
        self._d_last = self._dev(np.zeros(B, np.int32))
        self._d_seq_lens = self._dev(np.zeros(B, np.int32))
        self._d_fsm = self._dev(np.full(B, -1, np.int32))
        self._d_fsm_g = self._dev(np.zeros(B, np.int32))
        self._d_budget = self._dev(np.zeros(B, np.int32))
        self._ctl_dirty = True
        self._park_cooldown = 0
        problems = self.self_check(repair=True)
        if problems:
            logger.error(
                "post-failure self-check repaired %d problem(s): %s",
                len(problems), "; ".join(problems),
            )
        return events

    # ------------------------------------------------------------------
    # fetch pipeline
    # ------------------------------------------------------------------

    def _drain(self, block: bool) -> None:
        """Process matured token fetches into events (self._out_events).

        Non-blocking mode pops an entry once it has aged (`_emit_wait`) and
        its transfer has landed (seen compute-done for ~an RTT), so the
        np.asarray below is effectively free.  `is_ready` alone cannot be
        the signal: it reports *compute* completion, not transfer
        completion, and popping on it would reintroduce the blocking round
        trip per step.  The `fetch_lag` depth force-pops whatever the
        rules above left, as the memory backstop; the host's run-ahead is
        held far under it by step() (`_hold_decode`: at most one program's
        steps unfinished at a decode dispatch), so with three or more
        streams that pop does not fire.
        """
        emitted = 0
        wait = self._emit_wait()
        self._stamp_ready()
        while self._pending:
            if not block:
                entry = self._pending[0]
                within_lag = self._pending_steps <= self.ecfg.fetch_lag
                now = time.monotonic()
                aged = now - entry.t0 >= wait
                landed = (
                    entry.t_ready is not None
                    and now - entry.t_ready >= self._rtt_est
                )
                if within_lag and not aged:
                    # Speculation trades a little host batching for
                    # context freshness: a lane can only propose its next
                    # candidate run once its history is fully drained, so
                    # with speculative_k on, LANDED entries pop
                    # immediately (popping a landed transfer never blocks
                    # the dispatch thread — the age bound exists to avoid
                    # blocking, not to delay free pops).
                    if not (self.ecfg.speculative_k > 0 and landed):
                        break
                # Aged is necessary but not sufficient: the host dispatch
                # loop runs several entries ahead of device execution, so
                # an aged entry may not have EXECUTED yet — and even once
                # compute finishes, the async host copy lands ~RTT later.
                # Popping earlier blocks the single scheduler thread on
                # the device backlog + transfer, freezing admissions/
                # retirement/prefill while the batch churns (emission
                # gaps, and a lower concurrent-turnover rate, grow with the
                # copy latency).  Pop only once the entry
                # has been observed compute-done for ~an RTT (the copy
                # has landed; np.asarray is then free); the fetch_lag
                # depth bound still force-pops as the memory backstop.
                elif within_lag and not landed:
                    break
            popped = self._pending.pop(0)
            self._pending_steps -= popped.steps
            self.fetch_pops["blocking" if block else
                            "aged" if within_lag else "depth"] += 1
            emitted += self._process_entry(popped)
        if not self._pending:
            # empty pipeline: the next completion's measured latency
            # baselines on its own enqueue time, not a stale completion
            self._last_ready_t = None
        if emitted:
            self.metrics.record_emit_burst(emitted)
            if self.flight is not None:
                self.flight.note_pop(emitted)

    def _push_entry(self, entry: _Fetch) -> None:
        entry.seq = self._dispatch_seq
        if entry.kind != "prefill":
            self.fetch_depth_steps_sum += self._backlog_steps()
            self.fetch_depth_samples += 1
        self._pending.append(entry)
        self._pending_steps += entry.steps

    def _hold_decode(self) -> bool:
        """Should this iteration withhold decode?  Yes while more than one
        program's worth of steps is queued that the device has not been
        seen to finish: the device then has the program it runs and one
        behind it, a third would only lengthen the wait of the next
        prefill chunk (never held: _advance_prefills ran already), and
        the device cannot run dry before the next poll.  step() returns
        with `decode_held` set and blocks nowhere; whoever drives it
        waits a moment before the next call.  One or two streams (the
        rule of _emit_wait and of _pick_multi_step: nothing fuses, and
        nobody queues behind them) keep the cadence they have."""
        self._stamp_ready()
        held = self.num_active > 2 and self._backlog_steps() > max(
            self.ecfg.multi_step, _HOLD_FLOOR_STEPS)
        now = time.monotonic()
        if self.decode_held:
            # the last iteration held: the time since was spent waiting
            self.decode_hold_s += now - self._hold_t
        if held:
            self.decode_holds += 1
            self._hold_t = now
        self.decode_held = held
        return held

    def _backlog_steps(self) -> int:
        """The host's run-ahead: steps dispatched into the FIFO that the
        device has not been seen to finish, i.e. what a dispatch enqueued
        now waits behind.  Completion is seen at poll cadence
        (_stamp_ready), so this reads high by what finished since."""
        return self._pending_steps - sum(
            e.steps for e in self._pending if e.t_ready is not None)

    def _stamp_ready(self) -> None:
        """Record compute-completion times for the in-flight fetches the
        device has finished (is_ready is a cheap non-blocking probe).
        The device runs its queue in order, so the probe stops at the
        first entry that is not done: entries that finished but have not
        aged out of the FIFO never hide later completions from
        _backlog_steps."""
        now = time.monotonic()
        for e in self._pending:
            if e.t_ready is not None:
                continue
            if not getattr(e.arr, "is_ready", lambda: True)():
                if e.seq == self._dispatch_seq:
                    self._seen_running = self.sched.seen_running(now)
                break
            self._note_ready(e, now)

    def _note_ready(self, entry: _Fetch, now: float,
                    observed: bool = True) -> None:
        """Stamp one fetch's compute completion and derive its MEASURED
        device time (ISSUE 11): with in-order device execution a dispatch
        starts at max(its enqueue, the previous dispatch's completion),
        so completion - that start is the wall time the device spent on
        it.  Completions are observed at scheduler-poll cadence —
        several dispatches finishing between polls telescope into the
        first one's sample — so the per-kind SUMS (not the individual
        samples) are the calibrated quantity the skew gauge reads.
        `observed` False: the entry was popped before a poll saw it done,
        and `now` is the return of its read, which also holds the copy:
        the stamps are kept for the first-fetch ledger and the chain,
        and nothing is billed to the gauge."""
        entry.t_ready = now
        start = entry.t0
        if self._last_ready_t is not None and self._last_ready_t > start:
            start = self._last_ready_t
        entry.t_start = start
        self._last_ready_t = now
        if entry.seq == self._dispatch_seq:
            # the device runs its queue in order, and nothing was
            # dispatched after this program: nothing is queued behind its
            # completion, which happened no later than `now` and no
            # earlier than the program was last known to be running
            self._starve = self.sched.emptied(now, self._seen_running)
        measured = now - start
        if not observed or measured < 0.0 or measured > 10.0:
            return  # clock weirdness / wedged device: not a calibration
        if entry.modeled_s is not None:
            self.metrics.record_measured_dispatch(
                entry.kind, entry.modeled_s, measured
            )
        if self.flight is not None:
            self.flight.note_measured(measured)

    def _rtt_age_bound(self) -> float:
        """Age at which an in-flight fetch's transfer has presumably landed
        (popping then is effectively free for the dispatch thread)."""
        return max(1.25 * self._rtt_est, 0.002)

    def _emit_wait(self) -> float:
        """Age at which a fetch is popped without depth pressure.

        The pipeline does not reach fetch_lag depth (one or two streams
        never fill it, and step() holds a busier batch's run-ahead far
        under it: _hold_decode), so this age bound IS the token cadence
        the user sees; cap it near the measured device→host RTT so a lone
        interactive stream gets smooth ~RTT-latency tokens instead of
        fetch_wait_s-sized bursts (popping at ≥RTT age means the transfer
        has already landed, so the dispatch thread still never blocks).
        Busy batches keep the configured bound.
        """
        if self.num_active <= 2:
            return min(self.ecfg.fetch_wait_s, self._rtt_age_bound())
        return self.ecfg.fetch_wait_s

    def _pop_entry_now(self, entry: _Fetch) -> None:
        """Take one entry out of the FIFO and process it immediately.

        Safe out of FIFO order only when the entry's requests have no older
        in-flight entries (true for a just-admitted prefill, whose request
        appears in no earlier entry).
        """
        self._pending.remove(entry)
        self._pending_steps -= entry.steps
        self.fetch_pops["now"] += 1
        n = self._process_entry(entry)
        if n:
            self.metrics.record_emit_burst(n)

    def _pop_through(self, entry: _Fetch) -> None:
        """Process pending entries in FIFO order up to AND including
        `entry`.  Per-request token order must hold: with singleton-mask
        chaining a constrained lane appears in several in-flight entries,
        so popping its latest fetch ahead of its older ones would emit its
        tokens out of order (and trip prediction reconciliation).
        """
        n = 0
        while self._pending:
            e = self._pending.pop(0)
            self._pending_steps -= e.steps
            self.fetch_pops["now"] += 1
            n += self._process_entry(e)
            if e is entry:
                break
        if n:
            self.metrics.record_emit_burst(n)

    def _process_entry(self, entry: _Fetch) -> int:
        """Materialize one fetch (blocks if the transfer hasn't landed).
        Returns the number of tokens processed."""
        t0 = entry.t_pop = time.monotonic()
        if profiler_annotations_enabled():
            # the scheduler thread's reads on the profiler's clock, by
            # kind of entry (KAFKA_TPU_PROFILING; one bool read otherwise)
            with jax.profiler.TraceAnnotation(f"kafka.fetch[{entry.kind}]"):
                raw = np.asarray(entry.arr)
        else:
            raw = np.asarray(entry.arr)
        now = time.monotonic()
        if entry.t_ready is None:
            # popped before any poll saw its compute done (a blocking or
            # forced pop): the return of the read is the first the host
            # knows of the completion, and stands in for it
            self._note_ready(entry, now, observed=False)
        if now - t0 > 0.001:
            self.fetch_blocked_s += now - t0
            # The transfer hadn't landed when we popped.  dispatch→landed
            # (now - entry.t0) bounds the copy latency from above but also
            # includes device compute backlog, so an unclamped EWMA ratchets
            # upward under load and the adaptive emit wait re-creates the
            # bursts it exists to remove.  Shrink freely on fast evidence;
            # grow slowly and never past 2x the compute-free init probe.
            sample = now - entry.t0
            if sample < self._rtt_est:
                self._rtt_est = 0.75 * self._rtt_est + 0.25 * sample
            else:
                self._rtt_est = min(
                    0.9 * self._rtt_est + 0.1 * sample,
                    max(2.0 * self._rtt_probe, 0.001),
                )
        if entry.spec is not None:
            return self._finish_verify_entry(entry, raw)
        if entry.reads is not None:
            # (computed by the program that made `arr`: landed with it)
            read, held, routed = (int(n) for n in np.sum(
                np.asarray(entry.reads).reshape(-1, 3), axis=0))
            self.moe_experts_read += read
            self.moe_experts_held += (
                entry.steps * self._programs.experts_held())
            self.moe_picks_held += held
            self.moe_picks_routed += routed
        vals = raw.reshape(entry.steps, -1)
        n = 0
        for j in range(entry.steps):
            row = vals[j]
            finals = entry.final[j]
            for i, req in enumerate(entry.items):
                if req is None:
                    continue
                if req.state == FINISHED:
                    # dispatched after the request finished (stop token
                    # discovered in flight / cancel): speculative waste
                    self.metrics.record_wasted_token()
                    continue
                n += 1
                self._process_token(
                    req, int(row[i if row.size > 1 else 0]), finals[i],
                    entry,
                )
        return n

    def _finish_verify_entry(self, entry: _Fetch, raw: np.ndarray) -> int:
        """Drain one speculative verify dispatch: reconcile each proposing
        lane's host accounting to the ACTUAL accepted run (the device
        already clamped seq_lens/last_tokens at dispatch) and emit the
        1..K+1 tokens through the normal per-token path (stop detection,
        TTFT, metrics).  Rider lanes (cand_len 0) drain exactly like a
        plain decode row."""
        meta = entry.spec
        vals = raw.reshape(len(entry.items), meta.width + 1)
        finals = entry.final[0]
        n = 0
        for i, req in enumerate(entry.items):
            if req is None:
                continue
            row = vals[i]
            cl = meta.cand_lens[i]
            if cl == 0:
                # rider: one ordinary decode token (at-dispatch accounting)
                if req.state == FINISHED:
                    self.metrics.record_wasted_token()
                    continue
                n += 1
                self._process_token(req, int(row[0]), finals[i], entry)
                continue
            m = int(row[meta.width])  # accepted candidates (0..cl)
            req.spec_ahead = 0
            if req.state == FINISHED:
                # cancelled/timed out while the verify was in flight: the
                # whole run is discarded — candidates all count rejected
                # (monotone identity proposed == accepted+rejected+inflight)
                # and the would-be emissions are fetch-pipeline waste
                self.metrics.record_verify_drain(0, cl)
                self.metrics.record_wasted_token(m + 1)
                continue
            emit = m + 1  # accepted run + the bonus token
            old_len, old_disp = req.seq.length, req.dispatched
            req.seq.length += emit
            req.dispatched += emit
            self.metrics.record_verify_drain(m, cl - m)
            if req.spec is not None:
                req.spec.observe(m, cl)
            if req.trace is not None:
                now_mono = time.monotonic()
                prev = (req.trace_last_t or req.t_first_dispatch
                        or now_mono)
                record_span(
                    req.trace, "engine.decode", now_mono - prev,
                    attrs=self._pass_attrs(steps=1, proposed=cl, accepted=m),
                )
                req.trace_last_t = now_mono
            for j in range(emit):
                # host-known limits, applied with sequential semantics: a
                # budget/window boundary inside the accepted run truncates
                # it exactly where single-step dispatching would have
                final = None
                if old_disp + j + 1 >= req.max_new_tokens:
                    final = "length"
                elif old_len + j + 2 >= self.ecfg.max_window:
                    final = "length"
                n += 1
                self._process_token(req, int(row[j]), final, entry)
                if req.state == FINISHED:
                    # stop/limit cut the run short: the rest is discarded
                    self.metrics.record_wasted_token(emit - (j + 1))
                    break
        return n

    def _process_token(self, req: GenRequest, token: int,
                       final_reason: Optional[str], entry: _Fetch) -> None:
        req.drained += 1
        if req.predicted:
            # singleton-mask chain reconciliation: the dispatch ran with a
            # one-id mask, so the sampled value is exactly the prediction
            expected = req.predicted.pop(0)
            assert expected == token, (
                f"constrained prediction diverged: {expected} != {token}"
            )
        req.output_ids.append(token)
        if req.grammar is not None:
            self.metrics.constrained_ondevice_tokens += 1
        if req.spec is not None:
            req.spec.push(token)  # keep the n-gram index tail-accurate
        if req.first_token_time is None:
            req.first_token_time = time.monotonic()
            self.metrics.record_first_token(
                req.first_token_time - req.submit_time
            )
            stages = self.metrics.record_ttft_breakdown(
                req.submit_time, req.t_prefill_start,
                req.t_first_dispatch, req.first_token_time,
                fetch_marks=(entry.t_start, entry.t_ready, entry.t_pop),
            )
            if req.trace is not None and stages is not None:
                # the first-fetch stage of TTFT (last prefill chunk
                # dispatched -> first token on the host) as four
                # contiguous spans; `emit`, the last, ends at the first
                # token and carries the request's TTFT
                wait, run, hold, emit = stages
                t = time.time() - (run + hold + emit)
                record_span(req.trace, "engine.dev_wait", wait, end=t,
                            attrs=self._tattrs())
                t += run
                record_span(req.trace, "engine.dev_exec", run, end=t,
                            attrs=self._tattrs())
                t += hold
                record_span(req.trace, "engine.hold", hold, end=t,
                            attrs=self._tattrs())
                record_span(
                    req.trace, "emit", emit, end=t + emit,
                    attrs=self._tattrs(
                        ttft_ms=round(
                            (req.first_token_time - req.submit_time) * 1e3,
                            2,
                        )
                    ),
                )
        self.metrics.record_token()
        if token in req.stop_token_ids:
            reason = "stop"
        elif final_reason is not None:
            reason = final_reason
        else:
            self._out_events.append(TokenEvent(req.request_id, token))
            return
        if reason == "handoff":
            # Prefill-and-hand-off (disaggregated serving): the request
            # leaves this engine with its pages intact — the DP router
            # ships the run to a decode replica and requeues the request
            # there, so no terminal event and no SLO verdict here (the
            # decode replica finalizes with the true finish).  The run IS
            # stored into this replica's radix cache first: a fan-out
            # shared prefix stays warm on the prefill pool, and the
            # cache's retains keep the pages alive through the ship even
            # after the router frees the sequence.
            req.state = FINISHED
            if req.seq is not None and self.prefix_cache is not None:
                self.prefix_cache.store(
                    req.prefix_key,
                    (req.prompt_ids + req.output_ids)[: req.seq.length],
                    req.seq.pages,
                )
            self._requests.pop(req.request_id, None)
            self.handoffs.append((req, token))
            return
        req.finish_reason = reason
        req.state = FINISHED
        self._finalize_slo(req, reason)
        if (
            req.seq is not None
            and req.prefix_key is not None
            and self.prefix_cache is not None
        ):
            # Cache the thread's KV before the pages go back to the pool
            # (the cache takes its own retains).  Store only tokens whose KV
            # is actually materialized: seq.length counts them exactly — the
            # final sampled token's KV is never written (it is the pending
            # decode input), so on length-finishes the stored list must drop
            # it or a page-aligned next turn would share a page containing
            # an unwritten slot.  Positions past the stored range may hold
            # discarded in-flight KV, but only whole pages strictly inside
            # the stored range are ever shared.
            self.prefix_cache.store(
                req.prefix_key,
                (req.prompt_ids + req.output_ids)[: req.seq.length],
                req.seq.pages,
            )
        if req.slot >= 0 or req.seq is not None:
            self._release_slot(req)  # stop token found while still ACTIVE
        self._requests.pop(req.request_id, None)
        self._out_events.append(
            TokenEvent(req.request_id, token, finished=True, finish_reason=reason)
        )

    # ------------------------------------------------------------------
    # scheduler internals
    # ------------------------------------------------------------------

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _pages_needed(self, req: GenRequest) -> int:
        """Fresh pages the next prefill must allocate (net of shared ones)."""
        total = len(req.prefill_ids) + 1  # +1 so decode always has a slot
        have = len(req.seq.pages) if req.seq is not None else 0
        return max(0, -(-total // self.ecfg.page_size) - have)

    def _attach_prefix(self, req: GenRequest) -> None:
        """Attach shared prefix pages before the admission capacity gate.

        Doing the lookup here (retaining the pages) rather than inside
        prefill means the gate sizes `needed` net of the share — and a
        subsequent cache reclaim under pressure cannot pull the entry this
        request is about to reuse out from under it.
        """
        if (
            req.prefix_key is None
            or self.prefix_cache is None
            or req.seq is not None
        ):
            return
        req.cached_tokens = 0
        req.cache_source = None
        req.promoted_tokens = 0
        req.object_tokens = 0
        if self.kv_tier is not None:
            # kv.promote / kv.object_get / thread.wake spans inside the
            # lookup attach to this request
            self.kv_tier.trace_ctx = req.trace
        try:
            hit = self.prefix_cache.lookup(req.prefix_key, req.prefill_ids)
        finally:
            if self.kv_tier is not None:
                self.kv_tier.trace_ctx = None
        if hit is not None and self.state_pool is not None:
            # the hit was shortened to the deepest snapshot under the page
            # match: resume there, cut the first chunk where the pages
            # ended (so that boundary's snapshot is stored for the next
            # request), and hold the snapshot until the restore is enqueued
            req.state_snapshot = hit.snapshot
            req.state_matched = hit.matched_tokens
            req.state_cut = (hit.matched_tokens
                             if hit.matched_tokens > hit.tokens else 0)
            if not hit.tokens:
                hit = None
        if hit is not None:
            req.seq = SequencePages(seq_id=req.request_id)
            req.seq.pages, req.seq.length = hit.pages, hit.tokens
            req.cached_tokens = hit.tokens
            req.cache_source = hit.source
            req.promoted_tokens = hit.promoted_tokens
            req.object_tokens = hit.object_tokens

    def _reclaim_cache(self, pages_needed: int,
                       req: Optional[GenRequest] = None) -> bool:
        """prefix_cache.reclaim with kv.demote spans attached to the
        request whose page pressure drives the eviction (None = untraced;
        the span site is then one branch inside the tier manager)."""
        if self.prefix_cache is None:
            return False
        if self.kv_tier is not None:
            self.kv_tier.trace_ctx = req.trace if req is not None else None
        try:
            return self.prefix_cache.reclaim(pages_needed)
        finally:
            if self.kv_tier is not None:
                self.kv_tier.trace_ctx = None

    def _detach_prefix(self, req: GenRequest) -> None:
        """Roll back a page-blocked _attach_prefix: free the retains and
        clear the hit record.  Nothing was counted yet — hit counters
        commit only when the prefill starts (prefix_cache.commit_hit), so
        a head blocked for many scheduler iterations leaves no trace in
        the exported hit/reuse figures."""
        if req.seq is not None:
            self.pool.free_sequence(req.seq)
            req.seq = None
        req.cached_tokens = 0
        req.cache_source = None
        req.promoted_tokens = 0
        req.object_tokens = 0
        self._drop_state_hit(req)

    def _drop_state_hit(self, req: GenRequest) -> None:
        """Give back the snapshot reference a prefix hit took and forget
        where its pages matched (a rolled-back attach, a preemption)."""
        if req.state_snapshot is not None:
            self.prefix_cache.release_snapshot(req.state_snapshot)
            req.state_snapshot = None
        req.state_cut = req.state_matched = 0

    def _restore_state(self, req: GenRequest, slot: int) -> None:
        """Copy the snapshot the prefix hit found into the lane's state slot
        (a state is mutated in place by every pass, pages are not), count
        the admission, and give the snapshot's reference back: program order
        keeps the copy ahead of whatever overwrites the snapshot later."""
        self.state_tokens_matched += req.state_matched
        self.state_tokens_skipped += req.cached_tokens
        snap, req.state_snapshot = req.state_snapshot, None
        req.state_restored = snap
        if snap is None:
            return
        t0 = time.monotonic()
        annotate = (jax.profiler.TraceAnnotation("kafka.state_restore")
                    if profiler_annotations_enabled()
                    else contextlib.nullcontext())
        with annotate:
            self.v_pool = self._programs.state_copy()(
                self.v_pool, self._arg(np.int32(snap)),
                self._arg(np.int32(slot)))
        self.state_restores += 1
        self.prefix_cache.release_snapshot(snap)
        if req.trace is not None:
            record_span(req.trace, "kafka.state_restore",
                        time.monotonic() - t0,
                        attrs=self._tattrs(tokens=req.cached_tokens))

    def _snapshot_slot(self, req: GenRequest, end: int) -> Optional[int]:
        """A state slot for the snapshot a prefill chunk ending at token
        `end` leaves: only a page boundary can be shared from.  A chunk of a
        split plan that does not finish its remainder asks for none: the one
        launch the split stands for would have left no snapshot there, the
        position is shared with no other request, and each such snapshot
        pushes one that is out of the pool (Phi-4's cell: 66 of 96 snapshot
        slots after 51 s instead of 5-8)."""
        if (self.state_pool is None or self.prefix_cache is None
                or req.prefix_key is None or end % self.ecfg.page_size):
            return None
        if req.split_now and end < (req.seq.length
                                    + self._prefill_remaining(req)):
            return None
        return self.prefix_cache.alloc_snapshot()

    def _store_prefill(self, req: GenRequest, end: int,
                       snap: Optional[int] = None) -> None:
        """A model with a recurrent state stores a prompt's whole pages as
        they are dispatched, not at the thread's finish: with the snapshot
        a chunk left at `end`, and at the prompt's end the pages alone, so
        that the next request's pages match as far as this prompt shares
        and its cut chunk stores the snapshot there.  Program order keeps
        any later reader behind the writes."""
        if (self.state_pool is None or self.prefix_cache is None
                or req.prefix_key is None):
            return
        ps = self.ecfg.page_size
        n_full = end // ps
        if n_full:
            self.prefix_cache.store(
                req.prefix_key, req.prefill_ids[:n_full * ps],
                req.seq.pages[:n_full],
                snapshot=None if snap is None else (end, snap))
        elif snap is not None:
            self.prefix_cache.release_snapshot(snap)

    def _prefill_remaining(self, req: GenRequest) -> int:
        """Tokens the next prefill chunk may hold: the rest of the prompt,
        or (a model with a recurrent state) up to where the hit's pages
        matched."""
        start = req.seq.length
        if req.state_cut > start:
            return req.state_cut - start
        return len(req.prefill_ids) - start

    def _admit(self) -> None:
        # Strict submit-order FIFO across BOTH queues: each free slot goes
        # to the older of (waiting head, oldest parked lane) — a preemption
        # victim re-inserted at waiting[0] keeps its place ahead of parked
        # lanes submitted after it, and parked lanes keep theirs ahead of
        # younger waiting requests.  One liveness exception: a PAGE-BLOCKED
        # waiting head yields the slot to parked lanes — seating them needs
        # no new pages, and their completions are what will free pages for
        # the blocked head (holding the slot for it could otherwise spin
        # with an idle slot and never-seated parked lanes).
        while True:
            slot = self._free_slot()
            if slot is None:
                break
            oldest = (
                min(self.parked, key=lambda r: r.submit_time)
                if self.parked else None
            )
            head = self.waiting[0] if self.waiting else None
            if head is None and oldest is None:
                break
            head_first = head is not None and (
                oldest is None or head.submit_time < oldest.submit_time
            )
            if head_first and self._admit_waiting_head(slot):
                continue
            if head_first and oldest is None:
                break  # head page-blocked, nothing parked to seat
            if oldest is None:
                break
            self.parked.remove(oldest)
            self._seat(oldest, slot)
            if self.flight is not None:
                self.flight.note_cause("admit_parked")
        self._admit_offslot()
        if self.waiting_bg:
            self._admit_background()

    def _admit_waiting_head(self, slot: int) -> bool:
        """Try to start the waiting head's prefill in `slot`.

        Returns False (leaving the queue untouched) when page-blocked.
        Waiting requests must not pin pool pages: prefix retains taken for
        the page estimate are dropped on failure, else a blocked head could
        deadlock a preempted victim ahead of it under extreme pressure
        (the cache keeps its own retains; _attach_prefix re-acquires).
        """
        req = self.waiting[0]
        self._attach_prefix(req)
        needed = self._pages_needed(req)
        if needed > self.pool.free_pages and not self._reclaim_cache(
            needed, req
        ):
            self._detach_prefix(req)
            if self.flight is not None:
                self.flight.note_cause("page_blocked")
            return False
        self.waiting.pop(0)
        try:
            self._start_prefill(req, slot)
        except OutOfPagesError:
            # couldn't reserve the prompt's pages; roll back, retry later
            self._detach_prefix(req)
            req.state = WAITING
            self.waiting.insert(0, req)
            if self.flight is not None:
                self.flight.note_cause("page_blocked")
            return False
        if self.flight is not None:
            self.flight.note_cause("admit")
        return True

    def _seat(self, req: GenRequest, slot: int) -> None:
        """Move an off-slot lane into a decode slot.  A PARKED lane joins
        decode directly (its pages and first token already exist); a
        still-PREFILLING lane adopts the slot and finishes its chunks as
        an ordinary slot lane."""
        req.slot = slot
        self.slots[slot] = req
        self._ctl_dirty = True
        self.sched.did("admit")
        if req.state == PARKED:
            req.state = ACTIVE
            pending = (
                req.pending_tok if req.pending_tok is not None
                else req.output_ids[-1]  # resumed: host-known
            )
            self._d_last = self._d_last.at[slot].set(pending)
            req.pending_tok = None
            self._set_fsm_lane(req, slot)

    def _admit_offslot(self) -> None:
        """Start off-slot prefills for waiting requests when slots are full.

        TTFT under oversubscription (EngineConfig.max_parked): the first
        token comes from the prefill dispatch itself, which needs pages but
        no decode slot — so a queued request's first token need not wait
        for a slot to free.  Gated on pool headroom: a reserve stays free
        for active lanes' decode growth, and parked pages are reclaimed
        (rolled back to waiting) before any active lane would be preempted
        (_ensure_pages).
        """
        ecfg = self.ecfg
        if ecfg.max_parked <= 0 or not self.waiting:
            return
        if self.state_pool is not None:
            return  # a lane's state slot is its decode slot: no slot, no state
        if self._park_cooldown > 0:
            return  # recent page-pressure rollback: let ACTIVE lanes grow
        if self._free_slot() is not None:
            return  # slot admission (or its page gate) owns the queue head
        reserve = (
            ecfg.park_reserve_pages
            if ecfg.park_reserve_pages is not None
            else 2 * ecfg.max_batch
        )
        while self.waiting and len(self.parked) < ecfg.max_parked:
            req = self.waiting[0]
            self._attach_prefix(req)
            needed = self._pages_needed(req)
            if needed > self.pool.free_pages - reserve:
                # parking must never eat the decode-growth headroom
                self._detach_prefix(req)
                break
            self.waiting.pop(0)
            try:
                self._start_prefill(req, -1)
            except OutOfPagesError:
                self._detach_prefix(req)
                req.state = WAITING
                self.waiting.insert(0, req)
                break
            self.parked.append(req)
            if self.flight is not None:
                self.flight.note_cause("park")

    def _admit_background(self) -> None:
        """Admit at most ONE background-class request per iteration, and
        only into capacity nobody interactive wants: a free decode slot
        with the interactive queue empty, pages outside the park reserve
        (background prefill must never eat decode-growth headroom).
        Tool-result prefill and compaction summarization ride this class
        (ISSUE 20) — bulk work that should soak idle capacity, never
        convoy a TTFT."""
        if self.waiting:
            return  # interactive demand owns admission
        slot = self._free_slot()
        if slot is None:
            return
        ecfg = self.ecfg
        reserve = (
            ecfg.park_reserve_pages
            if ecfg.park_reserve_pages is not None
            else 2 * ecfg.max_batch
        )
        req = self.waiting_bg[0]
        self._attach_prefix(req)
        needed = self._pages_needed(req)
        if needed > self.pool.free_pages - reserve:
            # cold radix cache is idle capacity too: reclaim it (the same
            # eviction interactive admission would run) but keep the park
            # reserve untouched — without this a cache-saturated engine
            # starves its background queue forever even when fully idle
            if not self._reclaim_cache(needed + reserve, req):
                self._detach_prefix(req)
                return
        self.waiting_bg.pop(0)
        try:
            self._start_prefill(req, slot)
        except OutOfPagesError:
            self._detach_prefix(req)
            req.state = WAITING
            self.waiting_bg.insert(0, req)
            return
        self.bg_admitted += 1
        if self.flight is not None:
            self.flight.note_cause("bg_admit")

    def _start_prefill(self, req: GenRequest, slot: int) -> None:
        """Reserve pages + the batch slot; chunks run via _advance_prefill.

        The lane is masked out of decode (state PREFILLING) until the last
        chunk lands; decode for other lanes proceeds between chunks.
        """
        self.sched.did("admit")
        if req.t_prefill_start is None:  # keep the FIRST start on resume
            req.t_prefill_start = time.monotonic()
            # queue wait ends here (untraced requests: record_span is one
            # branch; _tattrs only built for traced ones)
            if req.trace is not None:
                record_span(
                    req.trace, "engine.queue",
                    req.t_prefill_start - req.submit_time,
                    attrs=self._tattrs(depth=len(self.waiting)),
                )
        elif req.trace is not None:
            # re-prefill after preemption or a disaggregated hand-off: an
            # instant event carrying the radix-cache share, so a shipped
            # thread's zero-re-prefill admission (cache_source="shipped")
            # is provable from its trace
            add_event(req.trace, "resume", self._prefill_attrs(req))
        req.seq = req.seq or SequencePages(seq_id=req.request_id)
        self.pool.ensure_capacity(req.seq, len(req.prefill_ids) + 1)
        if self.state_pool is not None:
            self._restore_state(req, slot)
        if req.cached_tokens and self.prefix_cache is not None:
            # the attach survived the page gate: NOW the hit counts (a
            # blocked head's repeated lookups never did — see commit_hit)
            self.prefix_cache.commit_hit(req.cached_tokens, req.cache_source)
        if req.usage_cached_tokens is None:
            # freeze the FIRST admission's share for usage reporting —
            # resume re-attaches (preemption / hand-off) must not bill
            # the re-attached prefix as client-saved compute
            req.usage_cached_tokens = req.cached_tokens
        # constrained decoding: the mask depends only on output_ids, which
        # is constant across prefill chunks — build it once.  Grammar
        # lanes derive the row from the compiled table (identical to the
        # mask fn's by construction, and no automaton walk).
        req.prefill_allowed = None
        if req.grammar is not None:
            state = req.grammar.walk(req.output_ids)
            if state >= 0:
                # budget-aware: the prefill-sampled token obeys the same
                # wrap-up rule the decode step enforces (a resume near the
                # budget must not waste its token on a dist-neutral step)
                row = req.grammar.allowed_row(
                    state,
                    budget_left=req.max_new_tokens - req.dispatched,
                )[None, :]
                req.prefill_allowed = self._dev(row)
            else:
                logger.warning(
                    "grammar replay for %s stopped validating at prefill; "
                    "degrading to the host mask path", req.request_id,
                )
                req.grammar = None
                if self.flight is not None:
                    self.flight.note_cause("degrade")
        if req.logits_mask_fn is not None and req.prefill_allowed is None \
                and req.grammar is None:
            allowed_ids = req.logits_mask_fn(req.output_ids)
            if allowed_ids is not None:
                ids = self._in_vocab(allowed_ids)
                if len(ids) == 0:
                    self._record_overtight(req)
                row = np.zeros((1, self.cfg.vocab_size), bool)
                row[0, ids] = True
                req.prefill_allowed = self._dev(row)
        req.state = PREFILLING
        req.slot = slot
        if slot >= 0:
            self.slots[slot] = req
            self._ctl_dirty = True  # decode must mask this lane immediately

    def _prefill_bucket_for(self, req: GenRequest) -> int:
        remaining = self._prefill_remaining(req)
        req.split_now = False
        if req.background and any(
            s is not None and s.state == ACTIVE and not s.background
            for s in self.slots
        ):
            # background chunks shrink to the smallest bucket while any
            # interactive lane is decoding: the added inter-token gap is
            # bounded by one SMALL chunk's compute, not a 512-token one
            return self.ecfg.prefill_buckets[0]
        bucket, req.split_now = self._first_bucket(remaining, req.seq.length)
        req.plan_split = req.plan_split or req.split_now
        return bucket

    def _first_bucket(self, remaining: int, start: int) -> Tuple[int, bool]:
        """(the bucket of the chunk plan's first launch, whether the plan
        makes more launches than the first bucket that holds `remaining`
        would): a pure function of the remainder rounded up to a page, its
        position, the ladder and the price, so each is planned once."""
        buckets = self.ecfg.prefill_buckets
        if self._launch_price is None:
            return first_fit_bucket(remaining, buckets), False
        ps = self.ecfg.page_size
        whole = -(-remaining // ps) * ps
        found = self._first_buckets.get((whole, start))
        if found is None:
            if len(self._first_buckets) >= 65536:  # a few MB of keys
                self._first_buckets.clear()
            plan = prefill_launches(whole, buckets, self._launch_price,
                                    start, ps)
            found = self._first_buckets[whole, start] = (
                plan[0][0],
                len(plan) > len(first_fit_launches(whole, buckets)))
        return found

    def _count_prefill_plan(self, req: GenRequest) -> None:
        """A request's last prefill chunk is out: count it, and whether any
        of its remainders went out in more launches than the first bucket
        that holds it would have made."""
        self.prefill_plans += 1
        self.prefill_plans_split += req.plan_split
        req.plan_split = False

    def batches_prefill(self, bucket: int) -> bool:
        """May same-bucket prefill chunks of this size fuse into one
        batched dispatch?  (_advance_prefills' rule; server warm-up reads
        it to know which batched-prefill programs to compile.)"""
        return (
            min(4, self.ecfg.max_batch) >= 2
            and self._sp == 1
            and self._pp == 1
            # on pallas backends the single-sequence path runs the flash
            # prefill kernel; forfeit it only for small chunks where
            # dispatch overhead dominates the attention work
            and (self.cfg.attention_backend != "pallas" or bucket <= 128)
        )

    def _advance_prefills(self) -> None:
        """Advance the OLDEST <=W prefilling lanes one chunk this iteration.

        FIFO window, not round-robin: advancing every lane each iteration
        makes all N prefills finish together at the END of the aggregate
        prefill work, so a storm of long prompts gives every request the
        worst-case TTFT (measured: 24 concurrent 9k-token prompts all got
        their first token at ~13s).  Advancing only the oldest W staggers
        completions at identical total cost — request k's first token
        arrives at ~k/N of the aggregate time, strictly better at every
        percentile.  W matches the batched-prefill width so a same-bucket
        window still fuses into ONE dispatch (admission storms of short
        thread turns are exactly this shape); constrained lanes and sp/pp
        meshes take the single-sequence path.
        """
        prefilling = [
            s for s in self.slots if s is not None and s.state == PREFILLING
        ] + [r for r in self.parked if r.state == PREFILLING]
        if not prefilling:
            return
        # Background class (ISSUE 20): background lanes yield their chunk
        # to ANY interactive prefill this iteration — a tool-result dump
        # or compaction prompt must never convoy an interactive TTFT.
        # With no interactive prefill pending, at most ONE background
        # lane advances one (decode-capped) chunk.
        bg = [r for r in prefilling if r.background]
        if bg:
            interactive = [r for r in prefilling if not r.background]
            if interactive:
                prefilling = interactive
                self.bg_yields += 1
                if self.flight is not None:
                    self.flight.note_cause("bg_yield")
            else:
                bg.sort(key=lambda r: r.submit_time)
                prefilling = bg[:1]
                self.bg_chunks += 1
                if self.flight is not None:
                    self.flight.note_cause("bg_prefill")
        W = min(4, self.ecfg.max_batch)
        if len(prefilling) > W:
            prefilling.sort(key=lambda r: r.submit_time)
            prefilling = prefilling[:W]
        groups: Dict[int, List[GenRequest]] = {}
        singles: List[GenRequest] = []
        for req in prefilling:
            bucket = self._prefill_bucket_for(req)
            if (
                self.batches_prefill(bucket)
                # constrained lanes need the single path end to end: the
                # batched program samples unmasked, and the first token
                # must come through the masked prefill (host-masked lanes
                # additionally pop it synchronously at the final chunk)
                and req.logits_mask_fn is None
                and req.grammar is None
            ):
                groups.setdefault(bucket, []).append(req)
            else:
                singles.append(req)
        for bucket, reqs in groups.items():
            while len(reqs) >= 2:
                take, reqs = reqs[:W], reqs[W:]
                self._advance_prefill_batch(bucket, take, W)
            singles.extend(reqs)
        for req in singles:
            self._advance_prefill(req)

    def _advance_prefill_batch(
        self, bucket: int, reqs: List[GenRequest], W: int
    ) -> None:
        """One fused chunk dispatch for 2..W same-bucket lanes."""
        failpoint("engine.prefill")
        ecfg = self.ecfg
        page_rows = np.full((W, ecfg.max_pages_per_seq), TRASH_PAGE, np.int32)
        chunks = np.zeros((W, bucket), np.int32)
        starts = np.zeros(W, np.int32)
        chunk_lens = np.zeros(W, np.int32)
        temps = np.zeros(W, np.float32)
        top_ks = np.zeros(W, np.int32)
        top_ps = np.ones(W, np.float32)
        seeds = np.zeros(W, np.uint32)
        lane_active = np.zeros(W, bool)
        if self.state_pool is not None:
            # (an idle lane reads, writes and snapshots the trash slot)
            slots = np.full(W, self.state_pool.trash, np.int32)
            snaps = slots.copy()
        for i, req in enumerate(reqs):
            start = req.seq.length
            prompt = req.prefill_ids
            clen = min(self._prefill_remaining(req), bucket)
            chunks[i, :clen] = prompt[start:start + clen]
            page_rows[i, : len(req.seq.pages)] = req.seq.pages
            starts[i] = start
            chunk_lens[i] = clen
            temps[i] = req.temperature
            top_ks[i] = req.top_k
            top_ps[i] = req.top_p
            seeds[i] = req.seed
            lane_active[i] = True
            if self.state_pool is not None:
                slots[i] = req.slot
                snap = self._snapshot_slot(req, start + clen)
                if snap is not None:
                    snaps[i] = snap
        vis = ()
        if self.state_pool is not None:
            vis = (self._arg(slots), self._arg(snaps))
        if self.cfg.vision is not None:
            chunk_ovs = [
                self._chunk_override(req, int(starts[i]), bucket)
                for i, req in enumerate(reqs)
            ]
            if all(co is None for co in chunk_ovs):
                vis = self._zero_override((W, bucket))
            else:
                ovs = np.zeros((W, bucket, self.cfg.hidden_size), np.float32)
                ons = np.zeros((W, bucket), bool)
                for i, co in enumerate(chunk_ovs):
                    if co is not None:
                        ovs[i], ons[i] = co
                vis = (self._arg(ovs), self._arg(ons))
        fn = self._programs.batched_prefill(bucket, W)
        with self._dispatch_scope("prefill", reqs):
            self.k_pool, self.v_pool, toks = fn(
                self.params, self.k_pool, self.v_pool,
                self._arg(page_rows), self._arg(chunks), self._arg(starts),
                self._arg(chunk_lens), self._arg(temps), self._arg(top_ks),
                self._arg(top_ps), self._arg(seeds), self._arg(lane_active),
                *vis,
            )
        self.prefill_rows_dispatched += W * bucket
        self.ssd_rows_dispatched += self._programs.ssd_rows(W, bucket)
        self.prefill_rows_filled += int(chunk_lens.sum())
        self._count_walk_trips(
            [(int(starts[i]), int(chunk_lens[i])) for i in range(len(reqs))],
            W, bucket)
        self._accrue_prefill_modeled(self._record_prefill_cost([
            (int(chunk_lens[i]), int(starts[i])) for i in range(len(reqs))
        ]))
        if self.flight is not None:
            self.flight.note_prefill(
                len(reqs), int(chunk_lens.sum()), W * bucket,
                sum(r.plan_split for r in reqs))
        items: List[Optional[GenRequest]] = [None] * W
        finals_row: List[Optional[str]] = [None] * W
        for i, req in enumerate(reqs):
            req.seq.length += int(chunk_lens[i])
            if self.state_pool is not None:
                self._store_prefill(
                    req, req.seq.length,
                    int(snaps[i]) if snaps[i] != self.state_pool.trash
                    else None)
            if req.seq.length < len(req.prefill_ids):
                continue  # more chunks to go
            self._count_prefill_plan(req)
            req.prefill_allowed = None
            if req.t_first_dispatch is None:
                # stamp the fused path too: the TTFT breakdown and the
                # engine.prefill span must not depend on which prefill
                # program (single vs batched) served the request
                req.t_first_dispatch = time.monotonic()
                if req.trace is not None:
                    record_span(
                        req.trace, "engine.prefill",
                        req.t_first_dispatch - (req.t_prefill_start
                                                or req.t_first_dispatch),
                        attrs=self._prefill_attrs(
                            req, fused=True,
                            backlog_steps=self._backlog_steps()),
                    )
            if req.slot < 0:
                # off-slot lane: park until a decode slot frees (_admit);
                # its first token still ships through the fetch below
                req.state = PARKED
                if req.resumed:
                    req.resumed = False
                    req.pending_tok = None  # host-known: output_ids[-1]
                    continue
                req.pending_tok = toks[i]
            else:
                req.state = ACTIVE
                self._ctl_dirty = True
                if req.resumed:
                    # pending token already known host-side
                    req.resumed = False
                    self._d_last = self._d_last.at[req.slot].set(
                        req.output_ids[-1]
                    )
                    self._set_fsm_lane(req, req.slot)
                    continue
                self._d_last = self._d_last.at[req.slot].set(toks[i])
            req.dispatched += 1
            if req.slot >= 0:
                self._set_fsm_lane(req, req.slot)
            fin = self._limit_reason_after_dispatch(req)
            items[i] = req
            finals_row[i] = fin
        if any(m is not None for m in items):
            toks.copy_to_host_async()
            self._push_entry(_Fetch(
                arr=toks, items=items, final=[finals_row],
                t0=time.monotonic(), kind="prefill",
                modeled_s=self._take_prefill_modeled(),
            ))
            for req, fin in zip(items, finals_row):
                if req is not None and fin is not None:
                    self._to_draining(req)

    def _zero_override(self, shape: Tuple[int, ...]) -> Tuple[Any, Any]:
        """Device-resident all-zero (ov, ov_on) pair, cached per shape.

        Vision engines pass override args on EVERY prefill dispatch (one
        compiled program, constant arity); for text-only chunks a fresh
        host zeros array would ship bucket*H floats per chunk for
        nothing — the cached device buffers upload once."""
        key = ("zov", shape)
        if key not in self._zero_ov_cache:
            self._zero_ov_cache[key] = (
                self._dev(np.zeros(shape + (self.cfg.hidden_size,),
                                   np.float32)),
                self._dev(np.zeros(shape, bool)),
            )
        return self._zero_ov_cache[key]

    def _chunk_override(self, req: GenRequest, start: int,
                        bucket: int) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Per-chunk (ov [S, H], ov_on [S]) embed-override slices for the
        prompt span [start, start+bucket); None when the span holds no
        override rows (caller substitutes the cached device zeros)."""
        if req.override_pos is None:
            return None
        sel = (req.override_pos >= start) & (req.override_pos < start + bucket)
        if not sel.any():
            return None
        H = self.cfg.hidden_size
        ov = np.zeros((bucket, H), np.float32)
        on = np.zeros((bucket,), bool)
        idx = req.override_pos[sel] - start
        ov[idx] = req.override_rows[sel]
        on[idx] = True
        return ov, on

    def _advance_prefill(self, req: GenRequest) -> None:
        """Dispatch ONE prefill chunk; the final chunk activates the lane."""
        failpoint("engine.prefill")
        ecfg = self.ecfg
        start = req.seq.length  # >0 after a prefix-cache hit (_attach_prefix)
        prompt = req.prefill_ids
        total = len(prompt)
        remaining = self._prefill_remaining(req)
        bucket = self._prefill_bucket_for(req)
        chunk_len = min(remaining, bucket)
        chunk = np.zeros(bucket, np.int32)
        chunk[:chunk_len] = prompt[start : start + chunk_len]
        page_row = np.full(ecfg.max_pages_per_seq, TRASH_PAGE, np.int32)
        page_row[: len(req.seq.pages)] = req.seq.pages
        vis = ()
        if self.cfg.vision is not None:
            co = self._chunk_override(req, start, bucket)
            if co is None:
                vis = self._zero_override((bucket,))
            else:
                vis = (self._arg(co[0]), self._arg(co[1]))
        snap = None
        if self.state_pool is not None:
            # the lane's state slot and where this chunk leaves a snapshot
            snap = self._snapshot_slot(req, start + chunk_len)
            vis = (self._arg(np.int32(req.slot)),
                   self._arg(np.int32(
                       self.state_pool.trash if snap is None else snap)))
        fn = self._programs.prefill(bucket)
        with self._dispatch_scope("prefill", (req,)):
            self.k_pool, self.v_pool, tok = fn(
                self.params, self.k_pool, self.v_pool,
                self._arg(page_row), self._arg(chunk),
                self._arg(np.int32(start)), self._arg(np.int32(chunk_len)),
                self._arg(np.float32(req.temperature)),
                self._arg(np.int32(req.top_k)),
                self._arg(np.float32(req.top_p)),
                self._arg(np.asarray([req.seed], np.uint32)),
                # unconstrained requests pass an all-True row (logits come
                # through bit-identical): ONE prefill program per bucket,
                # so a first forced tool call never compiles a masked
                # variant on the scheduler thread
                (req.prefill_allowed if req.prefill_allowed is not None
                 else self._all_allowed),
                *vis,
            )
        self.prefill_rows_dispatched += bucket
        self.ssd_rows_dispatched += self._programs.ssd_rows(1, bucket)
        self.prefill_rows_filled += chunk_len
        self._count_walk_trips([(start, chunk_len)], 1, bucket)
        self._accrue_prefill_modeled(
            self._record_prefill_cost([(chunk_len, start)])
        )
        if self.flight is not None:
            self.flight.note_prefill(1, chunk_len, bucket, req.plan_split)
        req.seq.length = start + chunk_len
        self._store_prefill(req, req.seq.length, snap)
        if req.seq.length < total:
            return  # more chunks to go; decode proceeds meanwhile
        self._finish_prefill(req, tok)

    def _finish_prefill(self, req: GenRequest, tok) -> None:
        """Last chunk dispatched: the lane joins the decode batch (or parks
        awaiting a slot when it prefilled off-slot)."""
        slot = req.slot
        self._count_prefill_plan(req)
        req.prefill_allowed = None
        if req.t_first_dispatch is None:
            req.t_first_dispatch = time.monotonic()
            if req.trace is not None:
                record_span(
                    req.trace, "engine.prefill",
                    req.t_first_dispatch - (req.t_prefill_start
                                            or req.t_first_dispatch),
                    attrs=self._prefill_attrs(
                        req, backlog_steps=self._backlog_steps()),
                )
        if slot < 0:
            req.state = PARKED
            if req.resumed:
                req.resumed = False
                req.pending_tok = None  # host-known: output_ids[-1]
                return
            req.pending_tok = tok
        else:
            req.state = ACTIVE
            self._ctl_dirty = True
            if req.resumed:
                # Re-entry after preemption: the pending last token is
                # already in output_ids (outputs are complete — preemption
                # drains the pipeline); the freshly sampled token is its
                # deterministic duplicate (same seed, same position) — drop
                # it and seed the device last-token lane from the
                # host-known value.
                req.resumed = False
                self._d_last = self._d_last.at[slot].set(req.output_ids[-1])
                self._set_fsm_lane(req, slot)
                return
            # Seed the device last-token lane directly from the device
            # scalar — the token value itself is fetched asynchronously.
            self._d_last = self._d_last.at[slot].set(tok)
        req.dispatched += 1
        if slot >= 0:
            self._set_fsm_lane(req, slot)
        final = self._limit_reason_after_dispatch(req)
        tok.copy_to_host_async()
        entry = _Fetch(arr=tok, items=[req], final=[[final]],
                       t0=time.monotonic(), kind="prefill",
                       modeled_s=self._take_prefill_modeled())
        self._push_entry(entry)
        if final is not None:
            self._to_draining(req)
        if self._host_constrained(req):
            # Host-masked: the first decode mask needs this token in
            # output_ids.  Only this request's scalar fetch blocks; the
            # rest of the batch pipeline is untouched.  Device-FSM lanes
            # skip the synchronous pop — their state was advanced by the
            # device scalar above, so the first decode mask needs nothing
            # from the host.
            self._pop_entry_now(entry)

    def _limit_reason_after_dispatch(self, req: GenRequest) -> Optional[str]:
        """After a dispatch, has the request hit a host-known limit?

        Mirrors the emission-side rules: `dispatched` counts every sampled
        token, and the window check matches "the cache is full after this
        token's KV lands".  Stop tokens are the only finish the host cannot
        predict; those are discovered when the fetch matures.
        """
        if req.dispatched >= req.max_new_tokens:
            return "length"
        if req.seq is not None and req.seq.length + 1 >= self.ecfg.max_window:
            return "length"
        if req.handoff:
            # prefill-and-hand-off: terminate at the first token (checked
            # AFTER the genuine limits — a 1-token request finishes for
            # real and never pays a ship)
            return "handoff"
        return None

    def _to_draining(self, req: GenRequest) -> None:
        """Stop dispatching for a request; its tokens are still in flight.

        The batch slot frees immediately.  The sequence's pages free too —
        unless the request carries a prefix_key, in which case they are kept
        until the final fetch matures so the exact materialized tokens can
        be stored into the prefix cache alongside them.
        """
        req.state = DRAINING
        if req.slot >= 0:
            self.slots[req.slot] = None
            req.slot = -1
            self._ctl_dirty = True
        elif req in self.parked:
            self.parked.remove(req)  # finished at prefill (e.g. 1-token cap)
        req.pending_tok = None
        if req.prefix_key is None or self.prefix_cache is None:
            if req.seq is not None:
                self.pool.free_sequence(req.seq)
                req.seq = None

    def _dispatch_decode(self) -> None:
        ecfg = self.ecfg

        if ecfg.speculative_k > 0 and self.spec_k_cap != 0:
            self._drain_for_proposals()

        # grow pages for sequences about to write past their capacity.
        # Lanes with an in-flight verify dispatch are skipped: their host
        # seq.length is confirmed-only (stale-low) and their pages were
        # already grown to cover the whole speculative span at dispatch.
        for req in list(s for s in self.slots if s is not None):
            if req.state != ACTIVE or req.seq is None or req.spec_ahead:
                continue  # already preempted/retired by an earlier iteration
            if self._ensure_pages(req):
                continue

        # PREFILLING lanes are masked out of decode entirely (they are
        # mid-chunk; their seq state must not be touched by decode
        # bookkeeping).  So are lanes awaiting a speculative verify drain
        # (spec_ahead > 0; always 0 with speculative_k=0): dispatching
        # them again before the drain would double-advance their state.
        active_slots = [
            s for s in self.slots
            if s is not None and s.state == ACTIVE and s.spec_ahead == 0
        ]
        spec_wait = any(
            s is not None and s.state == ACTIVE and s.spec_ahead > 0
            for s in self.slots
        )
        if not active_slots:
            return
        if self.ecfg.speculative_k > 0 and self._try_dispatch_verify(
            active_slots
        ):
            return
        k = 1 if spec_wait else self._pick_multi_step(active_slots)
        if k > 1:
            self._dispatch_multi(k)
            return
        if self._ctl_dirty:
            self._refresh_ctl()
        full_batch = [
            s if (s is not None and s.state == ACTIVE
                  and s.spec_ahead == 0) else None
            for s in self.slots
        ]
        # Device-FSM grammar lanes are PIPELINED lanes: their masks live
        # on device, so they ride the common dispatch (and fused
        # multi-step / verify) exactly like free lanes — the fsm program
        # variant is selected whenever any rides.
        fsm_any = any(s.grammar is not None for s in active_slots)
        if not any(self._host_constrained(s) for s in active_slots):
            # common case: every decodable lane is pipelined
            if spec_wait:
                # _d_active marks spec-waiting lanes active; mask them out
                # with an explicit group mask for this dispatch
                d_act = self._dev(
                    np.array([m is not None for m in full_batch])
                )
                entry = self._dispatch_group(full_batch, d_act, None,
                                             full=False, fsm=fsm_any)
            else:
                entry = self._dispatch_group(full_batch, self._d_active,
                                             None, full=True, fsm=fsm_any)
            self.metrics.record_decode_step(len(active_slots))
            self._record_decode_cost(active_slots, entry=entry)
            return
        # Mixed/host-constrained batch.  A host-masked lane's next mask
        # depends on every token it has emitted so far, so its decode
        # cannot be pipelined — but that is no reason to stall anyone else
        # (one agent doing a forced tool call must not degrade
        # co-scheduled streams).  The pipelined lanes (free + device-FSM)
        # dispatch every scheduler step exactly as in the common case; the
        # host-masked lanes run as their own micro-batch at fetch cadence:
        # dispatch once, wait for the token fetch to mature through the
        # normal aging rules, then build the next mask from the
        # now-complete output_ids and redispatch.
        uncon = [
            s if (s is not None and s.state == ACTIVE
                  and s.spec_ahead == 0
                  and not self._host_constrained(s)) else None
            for s in self.slots
        ]
        n_uncon = sum(1 for m in uncon if m is not None)
        if n_uncon:
            # device copy (not _arg): the where-merge of _d_last reuses it
            d_act = self._dev(np.array([m is not None for m in uncon]))
            self._dispatch_group(
                uncon, d_act, None, full=False,
                fsm=any(m is not None and m.grammar is not None
                        for m in uncon),
            )
        if self._constrained_inflight():
            # The constrained fetch matures at ~RTT age (the transfer has
            # landed; popping is then effectively free), NOT at the general
            # fetch_wait_s bound — gating on the latter would throttle
            # constrained lanes to 1/fetch_wait_s tok/s in busy batches.
            # RTT is also the floor: the next mask cannot be built before
            # the previous token reaches the host.  Age alone is not enough
            # under load: dispatch→landed time includes device compute
            # backlog, so an aged-but-unfinished fetch would block the
            # single scheduler thread and stall the unconstrained lanes'
            # dispatch cadence — require the device compute to be done too
            # (is_ready; the async copy then lands within ~RTT, which the
            # age bound already covers).  With no unconstrained lanes
            # nobody is stalled by blocking, so fetch immediately.
            entry = self._constrained_fetch
            now = time.monotonic()
            if entry.t_ready is None and getattr(
                entry.arr, "is_ready", lambda: True
            )():
                entry.t_ready = now
            landed = (
                entry.t_ready is not None
                and now - entry.t_ready >= self._rtt_est
            )
            if landed or not n_uncon:
                self._pop_through(entry)
                self._constrained_fetch = None
        # Per-lane partition: lanes whose NEXT token is grammar-FORCED
        # (singleton mask over output_ids + predicted — ~97% of tool-call
        # JSON: braces, quotes, key names) have a host-known value, so
        # they dispatch every scheduler iteration as a chained group
        # without awaiting a device->host round trip; only lanes at a
        # genuine choice point join the awaited micro-batch.  Lanes inside
        # the still-in-flight awaited fetch sit out this iteration (their
        # next mask needs that token).
        awaiting = (
            {id(r) for r in self._constrained_fetch.items if r is not None}
            if self._constrained_inflight() else set()
        )
        V = self.cfg.vocab_size
        B = self.ecfg.max_batch
        chain_m: List[Optional[GenRequest]] = []
        amb_m: List[Optional[GenRequest]] = []
        amb_ids: Dict[int, Optional[np.ndarray]] = {}  # slot -> allowed ids
        chain_toks: List[Tuple[GenRequest, int]] = []
        forced_tok = np.zeros(B, np.int32)
        forced_on = np.zeros(B, bool)
        n_chain = n_amb = 0
        for slot_i, s in enumerate(self.slots):
            c_req = a_req = None
            if (
                s is not None and s.state == ACTIVE
                and self._host_constrained(s)
                and id(s) not in awaiting
                # a lane that just degraded off the device-FSM path may
                # still have undrained pipelined tokens; the host mask
                # needs complete output_ids (+ the predicted chain), so it
                # sits out until the pipeline catches up
                and s.dispatched - s.drained == len(s.predicted)
                # a forced stop token means the lane is logically finished
                # and retires when its fetch drains: stop dispatching, and
                # never call the mask fn past the grammar's end
                and not any(t in s.stop_token_ids for t in s.predicted)
            ):
                pos = len(s.output_ids) + len(s.predicted)
                if s.mask_cache is not None and s.mask_cache[0] == pos:
                    kind, val = s.mask_cache[1]  # blocked lane: no re-walk
                else:
                    kind, val = self._next_constraint(s)
                    s.mask_cache = (pos, (kind, val))
                if kind == "forced":
                    c_req = s
                    forced_tok[slot_i] = val
                    forced_on[slot_i] = True
                    chain_toks.append((s, val))
                    n_chain += 1
                else:
                    a_req = s
                    amb_ids[slot_i] = val  # None = free step
                    n_amb += 1
            chain_m.append(c_req)
            amb_m.append(a_req)
        if n_chain:
            d_act = self._dev(np.array([m is not None for m in chain_m]))
            # no [B, V] mask: the known token overrides the sample on
            # device, so the upload is two [B] vectors
            self._dispatch_group(chain_m, d_act, None, full=False,
                                 forced=(forced_tok, forced_on))
            for req, tok in chain_toks:
                if req.state in (ACTIVE, DRAINING):
                    req.predicted.append(tok)
        n_amb_dispatched = 0
        if n_amb and not self._constrained_inflight():
            # Rows materialize only when actually dispatching, and only
            # when some lane has a concrete mask (all-free steps skip the
            # [B, V] build + upload entirely).  A lane's len-0 (fully
            # clipped) id list builds an all-False row: the sampler's
            # fully-masked fallback decides, the same semantics as the
            # prefill mask path.
            allowed_arr = None
            if any(v is not None for v in amb_ids.values()):
                rows = []
                for i in range(B):
                    ids = amb_ids.get(i)
                    if ids is None:
                        rows.append(np.ones(V, bool))
                    else:
                        if len(ids) == 0 and amb_m[i] is not None:
                            # fully clipped allow-list: the sampler will
                            # degrade this all-False row to unconstrained
                            self._record_overtight(amb_m[i])
                        row = np.zeros(V, bool)
                        row[ids] = True
                        rows.append(row)
                allowed_arr = np.stack(rows)
            d_act = self._dev(np.array([m is not None for m in amb_m]))
            self._constrained_fetch = self._dispatch_group(
                amb_m, d_act, allowed_arr, full=False
            )
            n_amb_dispatched = n_amb
            for m in amb_m:
                if m is not None:
                    # this lane now awaits a device->host round trip for
                    # its next mask: a genuine choice point
                    m.constrained_roundtrips += 1
                    self.metrics.constrained_roundtrips += 1
        if self.flight is not None and (n_chain or n_amb_dispatched):
            # host-constrained groups this iteration: chained (grammar-
            # forced, no round trip) vs awaited (genuine choice points)
            self.flight.note_constrained(n_chain, n_amb_dispatched)
        if n_uncon or n_chain or n_amb_dispatched:
            # one scheduler iteration = one TPOT sample / occupancy record,
            # however many dispatch groups it took (group dispatches land
            # microseconds apart and are not per-token latency)
            self.metrics.record_decode_step(
                n_uncon + n_chain + n_amb_dispatched
            )
            # cost model: same convention — the iteration's groups count
            # as one dispatch over exactly the lanes they ADVANCED
            # (awaiting/degraded lanes sat this iteration out and must not
            # inflate MFU or dispatch_tokens)
            dispatched = [m for m in uncon if m is not None]
            dispatched += [req for req, _tok in chain_toks]
            if n_amb_dispatched:
                dispatched += [m for m in amb_m if m is not None]
            self._record_decode_cost(dispatched)

    def _assert_private_tail(self, req: GenRequest, cl: int) -> None:
        """Speculative writes only ever land in the lane's PRIVATE tail
        pages — never in radix-shared prefix pages (PR 4 invariant).  The
        verify step writes positions seq_len..seq_len+cl; every page in
        that span must be solely owned by this sequence (refcount 1) and
        unknown to the prefix cache.  This holds by construction (cache
        lookups share only whole pages strictly before the prefill resume
        point, and store() only retains pages at finish), so the assert is
        a cheap tripwire over a handful of tail pages per dispatch."""
        ps = self.ecfg.page_size
        first = req.seq.length // ps
        last = (req.seq.length + cl) // ps
        pages = req.seq.pages[first:last + 1]
        assert all(int(self.pool.refcount[p]) == 1 for p in pages), (
            f"speculative write span of {req.request_id} covers shared "
            f"pages {[p for p in pages if self.pool.refcount[p] != 1]}"
        )
        assert self.prefix_cache is None or not \
            self.prefix_cache.owns_any(pages), (
                f"speculative write span of {req.request_id} covers "
                "radix-cached pages"
            )

    def _drain_for_proposals(self) -> None:
        """Block on the newest in-flight fetch of any speculating lane.

        A lane proposes only from fully drained history (the n-gram
        anchor must be the true tail), but the host loop dispatches ahead
        of the device and _drain pops only aged or landed entries — so a
        decoding lane is never drained at the moment it could propose,
        plain decode dispatches instead, and speculation silently never
        engages.  With speculative_k > 0 the lanes that carry a
        speculator therefore decode synchronously: their tokens are
        popped (FIFO, like the host-constrained path's _pop_through)
        before this iteration's lanes are chosen, so retirements land
        first.  Lanes without a speculator, and every lane at the default
        speculative_k = 0, keep the pipelined path untouched."""
        for entry in reversed(self._pending):
            if any(
                r is not None and r.spec is not None and r.state == ACTIVE
                # proposers book their tokens at drain: spec_ahead marks
                # their in-flight verify entry instead
                and (r.dispatched != r.drained or r.spec_ahead)
                for r in entry.items
            ):
                self._pop_through(entry)
                return

    def _try_dispatch_verify(self, lanes: List[GenRequest]) -> bool:
        """Propose + dispatch one [B, K+1] speculative verify step.

        Returns False when no lane has a usable candidate run this
        iteration (the plain decode paths then dispatch exactly as
        without speculation).  A lane proposes only when its token history
        is fully drained (the n-gram anchor must be the true tail) and
        its acceptance EWMA hasn't throttled it; candidate runs are
        clamped so even a fully-accepted run stays inside the token
        budget and the attention window.  Lanes without proposals ride
        the same dispatch as ordinary 1-token decode (cand_len 0) and
        keep the plain path's at-dispatch accounting.
        """
        ecfg = self.ecfg
        K = ecfg.speculative_k
        cap = self.spec_k_cap
        if cap is not None:
            # overload degradation (autoscaler ladder rung 2): proposals
            # throttled; 0 = paused entirely, plain decode dispatches
            K = min(K, cap)
            if K <= 0:
                return False
        proposals: Dict[int, List[int]] = {}
        for s in lanes:
            if (
                s.spec is None
                or self._host_constrained(s)
                or s.dispatched != s.drained
            ):
                continue
            room = min(
                K,
                s.max_new_tokens - s.dispatched - 1,
                ecfg.max_window - 2 - s.seq.length,
            )
            cands = s.spec.propose(room)
            if cands:
                proposals[id(s)] = [int(c) for c in cands]
        if not proposals:
            return False
        # grow pages to cover each proposer's whole speculative span
        # (positions seq_len..seq_len+cl) BEFORE the ctl refresh; riders
        # already got their +1 from the _dispatch_decode growth loop.  A
        # page-blocked proposal shrinks to a plain ride rather than
        # invoking the preemption machinery for speculative work.
        for s in lanes:
            cands = proposals.get(id(s))
            if not cands:
                continue
            try:
                if self.pool.ensure_capacity(
                    s.seq, s.seq.length + len(cands) + 1
                ):
                    self._ctl_dirty = True
            except OutOfPagesError:
                # reclaim() takes PAGES: evicting a candidate-count of
                # pages would cold-start other threads' warm prefixes for
                # a span that needs at most a page or two
                pages_short = (
                    -(-(s.seq.length + len(cands) + 1) // ecfg.page_size)
                    - len(s.seq.pages)
                )
                if not self._reclaim_cache(max(1, pages_short), s):
                    proposals.pop(id(s))
                    continue
                try:
                    if self.pool.ensure_capacity(
                        s.seq, s.seq.length + len(cands) + 1
                    ):
                        self._ctl_dirty = True
                except OutOfPagesError:
                    proposals.pop(id(s))
        if not proposals:
            return False
        if self._ctl_dirty:
            self._refresh_ctl()
        B = ecfg.max_batch
        members: List[Optional[GenRequest]] = [None] * B
        for s in lanes:
            # HOST-masked lanes never ride a verify dispatch: their masks
            # need per-token host turnaround, so a riding lane would emit
            # grammar-violating tokens (and a lane awaiting its
            # constrained micro-batch fetch would be double-advanced).
            # They sit this iteration out and dispatch through the mixed
            # path next iteration, exactly at the fetch cadence they
            # already run at.  Device-FSM grammar lanes DO ride — and
            # propose: the fsm verify variant masks every position with
            # the state reached through the candidate prefix.
            if not self._host_constrained(s):
                members[s.slot] = s
        cand_arr = np.zeros((B, K), np.int32)
        cand_lens = [0] * B
        n_proposed = 0
        for s in lanes:
            cands = proposals.get(id(s))
            if not cands:
                continue
            cl = len(cands)
            cand_arr[s.slot, :cl] = cands
            cand_lens[s.slot] = cl
            n_proposed += cl
            self._assert_private_tail(s, cl)
            s.spec_ahead = cl + 1
        d_act = self._dev(np.array([m is not None for m in members]))
        fsm = self._fsm(
            any(m is not None and m.grammar is not None for m in members))
        fn = self._programs.verify(K, fsm)
        with self._dispatch_scope("verify", members):
            (self.k_pool, self.v_pool, out, new_last, new_lens,
             *fsm_out) = fn(
                self.params, self.k_pool, self.v_pool, self._lanes(d_act),
                self._arg(cand_arr),
                self._arg(np.asarray(cand_lens, np.int32)),
                fsm,
            )
        self._keep_fsm(fsm_out)
        # device-resident truth: the fn already clamped per-lane advances
        # to the accepted length and kept inactive lanes' values
        self._d_last = new_last
        self._d_seq_lens = new_lens
        out.copy_to_host_async()
        self._step_count += 1
        self._count_moe_dispatch(B * (K + 1))
        finals: List[Optional[str]] = [None] * B
        now_mono: Optional[float] = None
        busy = sum(1 for m in members if m is not None)
        for i, req in enumerate(members):
            if req is None or cand_lens[i] > 0:
                continue  # proposers: accounting + span at drain
            req.seq.length += 1
            req.dispatched += 1
            finals[i] = self._limit_reason_after_dispatch(req)
            if req.trace is not None:
                if now_mono is None:
                    now_mono = time.monotonic()
                record_span(
                    req.trace, "engine.decode",
                    now_mono - (req.trace_last_t or req.t_first_dispatch
                                or now_mono),
                    attrs=self._pass_attrs(steps=1, busy=busy),
                )
                req.trace_last_t = now_mono
        entry = _Fetch(
            arr=out, items=list(members), final=[finals],
            t0=time.monotonic(),
            # the FIFO depth bound is in tokens-per-dispatch: a verify
            # entry counts its candidate width (ISSUE 5)
            steps=max(cand_lens) + 1,
            spec=_SpecMeta(cand_lens=cand_lens, width=K + 1),
        )
        self._push_entry(entry)
        if self.flight is not None:
            self.flight.note_dispatch(KIND_VERIFY, busy,
                                      busy + n_proposed)
            self.flight.note_spec(n_proposed)
        for req, fin in zip(members, finals):
            if req is not None and fin is not None:
                self._to_draining(req)
        self.metrics.record_decode_step(busy)
        self.metrics.record_verify_dispatch(n_proposed)
        # verify cost: every lane advances >= 1 query plus its candidates
        self._record_decode_cost(members, kind="verify",
                                 queries=busy + n_proposed, entry=entry)
        return True

    def _pick_multi_step(self, active_slots: List[GenRequest]) -> int:
        """How many decode steps to fuse into the next dispatch.

        Multi-step trades scheduling granularity for amortized dispatch
        overhead, so it engages only when granularity is cheap: no
        HOST-masked lanes (their masks need per-token host turnaround;
        device-FSM grammar lanes thread their state through the scan
        carry and fuse), no lane
        mid-prefill (chunks advance once per iteration; bursts would slow
        TTFT by k), and enough active streams that per-token emission
        cadence is burst-dominated anyway.  A non-empty waiting queue does
        NOT disengage fusion: with every slot busy, admission can only
        happen at an iteration boundary regardless, so fusing costs a
        waiting request at most k-1 steps (~35ms) of extra queueing while
        the whole batch keeps its amortized-dispatch throughput — under
        sustained load (BASELINE config 3's regime) someone is ALWAYS
        waiting, which is exactly when throughput matters most.  k is
        capped so no lane can hit a budget/window limit mid-burst (stop
        tokens may still land mid-burst; the speculative-decode
        reconciliation already truncates those).
        """
        ecfg = self.ecfg
        if (
            ecfg.multi_step <= 1
            or len(active_slots) < 3
            # host-masked lanes need per-token host turnaround; device-FSM
            # grammar lanes fuse fine (their state threads the scan carry)
            or any(self._host_constrained(s) for s in active_slots)
            or any(s is not None and s.state == PREFILLING
                   for s in self.slots)
            # off-slot prefills advance one chunk per iteration; fusing
            # would slow the very TTFT parking exists to protect
            or any(r.state == PREFILLING for r in self.parked)
            # a free slot + waiting queue means admission is page-blocked;
            # stay fine-grained so relief (retire/reclaim) happens sooner
            or (self.waiting and self._free_slot() is not None)
        ):
            return 1
        # ONE fused depth only: every distinct k is a separate ~30s XLA
        # compile of the whole model scan, so variable k would compile the
        # tail of every batch.  When any lane's remaining budget/window is
        # under k, fall back to single steps (the lane retires soon).
        k = ecfg.multi_step
        for req in active_slots:
            if (
                req.max_new_tokens - req.dispatched < k
                or ecfg.max_window - 1 - req.seq.length < k
            ):
                return 1
        grew = False
        try:
            for req in active_slots:
                if self.pool.ensure_capacity(req.seq, req.seq.length + k):
                    grew = True
        except OutOfPagesError:
            # page pressure: fall back to single steps (whose growth path
            # knows how to reclaim/drain/preempt)
            if grew:
                self._ctl_dirty = True
            return 1
        if grew:
            self._ctl_dirty = True
        return k

    def _dispatch_multi(self, k: int) -> None:
        """One fused k-step decode dispatch (all lanes; grammar lanes take
        the fsm scan variant so their masks apply inside the burst)."""
        if self._ctl_dirty:
            self._refresh_ctl()
        fsm = self._fsm(any(
            s is not None and s.state == ACTIVE and s.grammar is not None
            for s in self.slots
        ))
        fn = self._programs.multi_decode(k, fsm)
        with self._dispatch_scope("decode", self.slots, fused=True):
            (self.k_pool, self.v_pool, toks_seq, last, lens,
             *fsm_out, reads) = fn(
                self.params, self.k_pool, self.v_pool,
                self._lanes(self._d_active), fsm,
            )
        self._keep_fsm(fsm_out)
        self._d_last = last
        self._d_seq_lens = lens
        entry = self._book_dispatch(toks_seq, list(self.slots), steps=k)
        self._count_expert_reads(entry, reads)
        self.metrics.record_decode_step(
            sum(1 for m in entry.items if m is not None), steps=k
        )
        self._record_decode_cost(entry.items, steps=k, entry=entry)

    def _constrained_inflight(self) -> bool:
        """Is the constrained micro-batch still waiting on its last fetch?"""
        e = self._constrained_fetch
        if e is None:
            return False
        if any(p is e for p in self._pending):
            return True
        self._constrained_fetch = None  # matured (or force-drained)
        return False

    def _dispatch_group(
        self,
        members: List[Optional[GenRequest]],
        d_active: jnp.ndarray,
        allowed: Optional[np.ndarray],
        full: bool,
        forced: Optional[Tuple[np.ndarray, np.ndarray]] = None,
        fsm: bool = False,
    ) -> _Fetch:
        """Dispatch one decode for the lanes in `members` (slot-aligned;
        None = not in this group).  Lanes outside the group are masked
        inactive for this call: their KV writes go to the trash page, their
        seq_lens don't advance, and their device last-token lanes keep their
        previous value via the where-merge below.  `forced` = ([B] int32
        tokens, [B] bool on-mask): grammar-forced lanes whose sampled token
        is overridden device-side (no [B, V] mask upload).  `fsm` selects
        the grammar-FSM program variant (some member carries a device
        automaton state); the fn itself gates state/budget updates on the
        group's active mask, so out-of-group lanes keep theirs.
        """
        fsm_arg = self._fsm(fsm)
        fn = self._programs.decode(fsm_arg)
        with self._dispatch_scope("decode", members):
            (self.k_pool, self.v_pool, toks, self._d_seq_lens,
             *fsm_out, reads) = fn(
                self.params, self.k_pool, self.v_pool,
                self._lanes(d_active),
                None if allowed is None else self._arg(allowed),
                None if forced is None else tuple(map(self._arg, forced)),
                fsm_arg,
            )
        self._keep_fsm(fsm_out)
        self._d_last = toks if full else jnp.where(d_active, toks, self._d_last)
        entry = self._book_dispatch(toks, members, steps=1)
        self._count_expert_reads(entry, reads)
        return entry

    def _count_expert_reads(self, entry: _Fetch,
                            reads: Optional[jnp.ndarray]) -> None:
        """Account a decode dispatch's expert reads and picks: `reads`, the
        program's own count (its last output: `StepPrograms.tallies`), rides
        the entry and is added when its tokens are fetched (_process_entry);
        a program that returns None read every held expert and holds every
        expert its lanes picked, counted here (0 for a model with no routed
        block)."""
        if reads is not None:
            reads.copy_to_host_async()
            entry.reads = reads
            return
        held = entry.steps * self._programs.experts_held()
        self.moe_experts_read += held
        self.moe_experts_held += held
        picks = entry.steps * self._programs.picks_a_pass(
            sum(m is not None for m in entry.items))
        self.moe_picks_held += picks
        self.moe_picks_routed += picks

    def _count_walk_trips(self, spans, width: int, bucket: int) -> None:
        trips, folded = self._programs.prefill_walk_trips(spans, width, bucket)
        self.prefill_walk_trips += trips
        self.prefill_walk_kernel_trips += folded
        self.delta_chunk_trips += self._programs.delta_chunk_trips(
            len(spans), bucket)
        self.ssd_chunk_trips += self._programs.ssd_chunk_trips(
            len(spans), bucket)
        self.hc_site_rows += self._hc_sites * width * bucket
        self._count_moe_dispatch(width * bucket)
        self._count_state_launches(bucket, True)

    def _count_state_launches(self, rows: int, own_slots: bool,
                              passes: int = 1) -> None:
        for op, form in self._programs.state_forms(rows, own_slots).items():
            self.state_launches[f"{op}_{form}"] += (
                passes * self.cfg.state_layers)

    def _count_moe_dispatch(self, rows: int, passes: int = 1) -> None:
        form = self._programs.moe_dispatch(rows)
        if form is not None:
            self.moe_dispatch[form + "_launches"] += 1
            self.moe_dispatch[form + "_rows"] += rows * passes

    def _book_dispatch(
        self,
        toks: jnp.ndarray,
        members: List[Optional[GenRequest]],
        steps: int,
    ) -> _Fetch:
        """Shared post-dispatch accounting for single and fused dispatches:
        advance each member's seq/dispatched counters by `steps`, enqueue
        the async fetch, and start draining lanes that hit a host-known
        limit.  `steps` is chosen so limits can only trigger on the final
        row (see _pick_multi_step); stop tokens may still land on any row
        and are reconciled when the fetch matures.
        """
        toks.copy_to_host_async()
        self._step_count += steps
        seqs = [m.seq for m in members if m is not None]
        tables = [(seq.pages, seq.length) for seq in seqs]
        walked, window = self._programs.decode_keys(
            max((seq.length for seq in seqs), default=0), steps)
        self.decode_keys_walked += walked
        self.decode_keys_window += window
        self.decode_keys_shared += self._programs.decode_keys_shared(
            tables, steps)
        walked, run, every, ahead = self._programs.decode_steps(
            [m and m.seq for m in members], steps)
        self.decode_steps_walked += walked
        self.decode_steps_run += run
        self.decode_steps_all += every
        self.decode_steps_ahead += ahead
        if self.cfg.delta_heads:
            self.delta_state_bytes += self._programs.delta_state_bytes(
                len(seqs), steps)
        if self.cfg.ssd_heads:
            self.ssd_state_bytes += self._programs.ssd_state_bytes(
                len(seqs), steps)
        self.hc_site_rows += self._hc_sites * len(members) * steps
        self._count_moe_dispatch(len(members), steps)
        self._count_state_launches(1, False, steps)
        if self.cfg.index_topk:
            scored, kept = self._programs.index_keys(
                [seq.length for seq in seqs], steps)
            self.index_keys_scored += scored
            self.index_keys_kept += kept
            self.index_keys_shared += self._programs.index_keys_shared(
                tables, steps)
        # decode-span inputs, computed lazily on the FIRST traced member:
        # an all-untraced dispatch pays one branch per lane, nothing else
        now_mono: Optional[float] = None
        busy = 0
        items: List[Optional[GenRequest]] = []
        last_final: List[Optional[str]] = []
        for req in members:
            if req is None:
                items.append(None)
                last_final.append(None)
                continue
            req.seq.length += steps  # the dispatched tokens' kv slots
            req.dispatched += steps
            if req.trace is not None:
                # burst-granularity decode span: the window since this
                # lane's previous dispatch, annotated with the fused-step
                # count and batch occupancy
                if now_mono is None:
                    now_mono = time.monotonic()
                    busy = sum(1 for m in members if m is not None)
                prev = (req.trace_last_t or req.t_first_dispatch
                        or now_mono)
                record_span(
                    req.trace, "engine.decode", now_mono - prev,
                    attrs=self._pass_attrs(steps=steps, busy=busy),
                )
                req.trace_last_t = now_mono
            items.append(req)
            last_final.append(self._limit_reason_after_dispatch(req))
        finals = [[None] * len(items) for _ in range(steps - 1)] + [last_final]
        entry = _Fetch(arr=toks, items=items, final=finals,
                       t0=time.monotonic(), steps=steps)
        self._push_entry(entry)
        if self.flight is not None:
            lanes = sum(1 for m in items if m is not None)
            self.flight.note_dispatch(
                KIND_MULTI if steps > 1 else KIND_DECODE,
                lanes, lanes * steps, steps=steps,
            )
        for req, fin in zip(members, last_final):
            if req is not None and fin is not None:
                self._to_draining(req)
        return entry

    def _ensure_pages(self, req: GenRequest) -> bool:
        """Grow req's pages for one more token.  Returns True if req was
        retired/preempted and must be skipped this step."""
        try:
            if self.pool.ensure_capacity(req.seq, req.seq.length + 1):
                self._ctl_dirty = True  # table grew
            return False
        except OutOfPagesError:
            pass
        # Remedies in order of cost: evict cache entries (rebuild = one
        # prefill, no victim), then drain the pipeline (stop tokens hiding
        # in flight may retire slots), then preempt.
        if self._reclaim_cache(1, req):
            try:
                self.pool.ensure_capacity(req.seq, req.seq.length + 1)
                self._ctl_dirty = True
                return False
            except OutOfPagesError:
                pass
        self._drain(block=True)
        if req.state != ACTIVE or req.seq is None:
            return True
        # parked lanes' pages are reclaimable before any ACTIVE lane pays:
        # roll them back to the waiting queue, YOUNGEST BY SUBMIT TIME first
        # (not list tail: a re-parked preemption victim sits at the tail
        # with the largest prefill investment — rolling it back by position
        # would re-run its whole prefill every page-pressure cycle)
        while True:
            try:
                self.pool.ensure_capacity(req.seq, req.seq.length + 1)
                self._ctl_dirty = True
                return False
            except OutOfPagesError:
                if self.parked:
                    self._preempt(
                        max(self.parked, key=lambda r: r.submit_time)
                    )
                    # hysteresis: pages just freed must feed ACTIVE growth,
                    # not an immediate re-park of the same lane (which
                    # would burn a full prefill per reclaimed page)
                    self._park_cooldown = 32
                    continue
                break
        self._preempt_youngest()
        if req.state != ACTIVE or req.seq is None:
            return True
        try:
            self.pool.ensure_capacity(req.seq, req.seq.length + 1)
            self._ctl_dirty = True
            return False
        except OutOfPagesError:
            # still no room: roll this one back too rather than let it
            # write into the trash page and corrupt its state
            self._preempt(req)
            return True

    def _refresh_ctl(self) -> None:
        """Re-upload host-authored control arrays after a scheduling change.

        `_d_last` is never rebuilt from host state — the latest tokens may
        still be in flight; it is maintained on device (decode feeds it
        forward, admits patch single lanes).
        """
        slots = self.slots
        self._d_table = self._dev(page_table_array(
            [s.seq if s else None for s in slots], self.ecfg.max_pages_per_seq
        ))
        host_lens = self._dev(np.array(
            [s.seq.length if s is not None and s.seq else 0 for s in slots],
            np.int32,
        ))
        keep = [
            s is not None and s.state == ACTIVE and s.spec_ahead > 0
            for s in slots
        ]
        if any(keep):
            # lanes with an in-flight verify dispatch: the device value is
            # the truth-after-dispatch (the verify fn clamped it to the
            # accepted length); host seq.length is confirmed-only until
            # the entry drains — re-uploading it would roll the lane back
            self._d_seq_lens = jnp.where(
                self._dev(np.array(keep)), self._d_seq_lens, host_lens
            )
        else:
            self._d_seq_lens = host_lens
        self._d_active = self._dev(np.array(
            [s is not None and s.state == ACTIVE for s in slots], bool
        ))
        self._d_temps = self._dev(np.array(
            [s.temperature if s else 0.0 for s in slots], np.float32))
        self._d_top_ks = self._dev(np.array(
            [s.top_k if s else 0 for s in slots], np.int32))
        self._d_top_ps = self._dev(np.array(
            [s.top_p if s else 1.0 for s in slots], np.float32))
        self._d_seeds = self._dev(np.array(
            [s.seed if s else 0 for s in slots], np.uint32))
        self._ctl_dirty = False

    @staticmethod
    def _host_constrained(s: GenRequest) -> bool:
        """Does this lane take the HOST mask path (awaited micro-batch /
        forced-token chaining)?  Grammar lanes advance their FSM inside
        the jitted step instead and ride the pipelined dispatch."""
        return s.logits_mask_fn is not None and s.grammar is None

    def _set_fsm_lane(self, req: GenRequest, slot: int) -> None:
        """Seed the lane's device FSM state/budget at activation.

        Called whenever a lane takes a decode slot (prefill finish, parked
        seat, resume): non-grammar lanes park the slot at the -1
        unconstrained sentinel (a previous occupant's state must never
        leak); grammar lanes replay their host-known output prefix through
        the host copy of the table, then — if their latest token is still
        an in-flight device scalar — advance by it lazily on device (no
        round trip).  A grammar that cannot register (table-set cap,
        vocab mismatch) or a replay that stops validating degrades the
        lane to the host mask path.
        """
        if req.grammar is None:
            self._d_fsm = self._d_fsm.at[slot].set(-1)
            return
        g_idx = self._grammars.register(req.grammar)
        if g_idx is None:
            logger.warning(
                "grammar for %s cannot register (table set full or vocab "
                "mismatch); degrading to the host mask path",
                req.request_id,
            )
            req.grammar = None
            self._d_fsm = self._d_fsm.at[slot].set(-1)
            if self.flight is not None:
                self.flight.note_cause("degrade")
            return
        off = self._grammars.offsets[g_idx]
        # at activation at most ONE token (the prefill's sample, still a
        # device scalar in _d_last) can be in flight beyond output_ids
        drained_all = req.drained == req.dispatched
        state = req.grammar.walk(req.output_ids)
        if state < 0:
            logger.warning(
                "grammar replay for %s stopped validating; degrading to "
                "the host mask path", req.request_id,
            )
            req.grammar = None
            self._d_fsm = self._d_fsm.at[slot].set(-1)
            if self.flight is not None:
                self.flight.note_cause("degrade")
            return
        if drained_all:
            self._d_fsm = self._d_fsm.at[slot].set(off + state)
        else:
            # exactly the prefill's sampled token is in flight: advance
            # the replayed state by the device scalar without fetching it
            nxt = _fsm_advance(
                self._grammars.token_class, self._grammars.trans,
                self._d_last, g_idx, off + state, slot,
            )
            self._d_fsm = self._d_fsm.at[slot].set(nxt)
        self._d_fsm_g = self._d_fsm_g.at[slot].set(g_idx)
        self._d_budget = self._d_budget.at[slot].set(
            req.max_new_tokens - req.dispatched
        )

    def _record_overtight(self, req: GenRequest) -> None:
        """An over-tight constrained mask row (no token satisfies the
        grammar here): ops/sampling degrades the row to unconstrained —
        count it, and log once per request with the mask's state."""
        self.metrics.constrained_mask_overtight += 1
        if self.flight is not None:
            self.flight.note_cause("overtight")
        if req.overtight_logged:
            return
        req.overtight_logged = True
        desc = "?"
        fn = req.logits_mask_fn
        if fn is not None and hasattr(fn, "state_desc"):
            try:
                desc = fn.state_desc()
            except Exception:
                pass
        logger.warning(
            "over-tight constrained mask for %s (fsm state %s): sampler "
            "degrades this row to unconstrained", req.request_id, desc,
        )

    def _finalize_slo(self, req: GenRequest, reason: Optional[str]) -> None:
        """Terminal metrics + SLO verdict for one request (ISSUE 10).

        TTFT and mean TPOT come from the request's own stamps (mean TPOT
        spans first token -> finalize, so it includes the fetch-pipeline
        drain the client actually experienced); the verdict is classified
        against the configured targets in metrics.record_finish, goodput
        is credited for met requests, and the verdict is stamped onto the
        request's http.request root span for /debug/trace and the
        slow-request log."""
        now = time.monotonic()
        ttft_s = (req.first_token_time - req.submit_time
                  if req.first_token_time is not None else None)
        n_out = len(req.output_ids)
        tpot_s = None
        if req.first_token_time is not None and n_out > 1:
            tpot_s = (now - req.first_token_time) / (n_out - 1)
        met = self.metrics.record_finish(
            reason, ttft_s=ttft_s, tpot_s=tpot_s, tokens=n_out
        )
        req.slo_met = met
        if met is not None and req.trace is not None:
            annotate(req.trace, {
                "slo_met": met,
                "slo_ttft_ms": round(ttft_s * 1e3, 1)
                if ttft_s is not None else None,
                "slo_tpot_ms": round(tpot_s * 1e3, 2)
                if tpot_s is not None else None,
                "goodput_tokens": n_out if met else 0,
            })

    def _modeled_dispatch_s(self, flops: float,
                            bytes_: float) -> Optional[float]:
        """Roofline execution time for one dispatch (None = no roofline):
        the slower of the compute and bandwidth bounds — the denominator
        of the modeled-vs-measured skew gauge."""
        m = self.metrics
        if not m.peak_flops or not m.peak_hbm_bps:
            return None
        return max(flops / m.peak_flops, bytes_ / m.peak_hbm_bps)

    def _accrue_prefill_modeled(self, modeled: Optional[float]) -> None:
        """Bank one prefill chunk dispatch's modeled seconds until a
        prefill FETCH ENTRY exists to carry them (only final chunks ship
        one; see _prefill_modeled_acc)."""
        if modeled is not None:
            self._prefill_modeled_acc = (
                (self._prefill_modeled_acc or 0.0) + modeled
            )

    def _take_prefill_modeled(self) -> Optional[float]:
        """Consume the banked prefill modeled time for the entry being
        created — its measured span covers every unobserved chunk since
        the previous observed completion, so it gets their modeled SUM."""
        modeled = self._prefill_modeled_acc
        self._prefill_modeled_acc = None
        return modeled

    def _record_prefill_cost(self, lanes) -> Optional[float]:
        """Report one prefill dispatch's modeled cost: `lanes` is
        [(chunk_tokens, start_pos), ...] for every lane the dispatch
        advanced.  Weights stream once per dispatch, so the per-lane
        weight-byte term is de-duplicated here.  Returns the modeled
        roofline seconds (None = no model/roofline) so final-chunk
        dispatches can tag their fetch entry for the skew gauge."""
        cm = self._cost_model
        if cm is None or not self.metrics.enabled:
            return None
        if self._have_roofline and self.metrics.peak_source == "unknown":
            # fresh metrics object (warmup/bench reset): restore the
            # roofline so MFU/HBM ratios don't silently flatline at 0
            self.metrics.set_roofline(*self._roofline)
        flops = bytes_ = 0.0
        toks = 0
        for chunk, start in lanes:
            lf, lb = cm.prefill_cost(chunk, start)
            flops += lf
            bytes_ += lb - cm.weight_bytes
            toks += chunk
        bytes_ += cm.weight_bytes
        self.metrics.record_dispatch_cost("prefill", toks, flops, bytes_)
        modeled = self._modeled_dispatch_s(flops, bytes_)
        if self.flight is not None:
            self.flight.note_cost(flops, bytes_, modeled)
        return modeled

    def _record_decode_cost(self, members, steps: int = 1,
                            kind: str = "decode",
                            queries: Optional[int] = None,
                            entry: Optional[_Fetch] = None) -> None:
        """Report one decode/verify dispatch's modeled cost.  `members`
        is the slot-aligned lane list (None = masked out); context is the
        host-known per-lane KV length sum.  `queries` overrides the
        query-token count for verify dispatches (sum of candidate widths
        across lanes).  `entry` tags the dispatch's in-flight fetch with
        the modeled time so its maturation feeds the skew gauge (mixed
        host-constrained iterations pass None — several groups share one
        cost record, so no single fetch can carry it honestly)."""
        cm = self._cost_model
        if cm is None or not self.metrics.enabled:
            return
        if self._have_roofline and self.metrics.peak_source == "unknown":
            self.metrics.set_roofline(*self._roofline)  # survive resets
        lanes = [m for m in members if m is not None]
        if not lanes:
            return
        ctx = sum(m.seq.length if m.seq is not None else 0 for m in lanes)
        if kind == "verify":
            toks = queries if queries is not None else len(lanes)
            # each lane's K+1-wide query block attends its whole context:
            # pairs ~= ctx x mean query width (uniform-width estimate)
            flops, bytes_ = cm.verify_cost(
                toks, ctx, attn_pairs=ctx * toks / len(lanes)
            )
        else:
            toks = len(lanes) * steps
            flops, bytes_ = cm.decode_cost(toks, ctx, steps)
        self.metrics.record_dispatch_cost(kind, toks, flops, bytes_)
        modeled = self._modeled_dispatch_s(flops, bytes_)
        if entry is not None:
            entry.kind = kind
            entry.modeled_s = modeled
        if self.flight is not None:
            self.flight.note_cost(flops, bytes_, modeled)

    def _next_constraint(self, s: GenRequest):
        """Classify the next constrained step for a lane.

        Returns ("forced", token_id) — the value is host-known and the
        dispatch may chain without awaiting (grammar-forced: either the
        mask fn's forced_id hook resolved a deterministic text run to one
        canonical token, or the allowed list is a single id) — or
        ("ids", np array) for a genuine choice point, or ("free", None)
        for an unconstrained step.  A raising mask fn degrades the lane
        to unconstrained permanently (one log line), never the engine
        thread.
        """
        fn = s.logits_mask_fn
        ctx = s.output_ids + s.predicted
        try:
            if hasattr(fn, "forced_id"):
                fid = fn.forced_id(ctx)
                if fid is not None and 0 <= int(fid) < self.cfg.vocab_size:
                    return ("forced", int(fid))
            allowed = fn(ctx)
        except Exception:
            logger.exception(
                "logits_mask_fn failed for %s; degrading the lane to "
                "unconstrained", s.request_id,
            )
            s.logits_mask_fn = None
            return ("free", None)
        if allowed is None:
            return ("free", None)
        ids = self._in_vocab(allowed)
        if len(ids) == 1:
            return ("forced", int(ids[0]))
        return ("ids", ids)

    def _in_vocab(self, allowed_ids) -> np.ndarray:
        """Clip a constrained-decoding allow-list to the model vocab.

        A tokenizer whose id space exceeds the model's embedding table
        (e.g. special ids atop a smaller checkpoint vocab) must degrade to
        a tighter mask, not crash the single engine thread — a step-loop
        exception fails EVERY in-flight request (worker._fail_all).
        """
        ids = np.asarray(allowed_ids, np.int64)
        return ids[(ids >= 0) & (ids < self.cfg.vocab_size)]

    def _release_slot(self, req: GenRequest) -> None:
        """Free a request's batch slot and pages (it may keep draining).

        Pages freed here can be re-allocated while older dispatched steps
        still write into them; that is safe by program order — any later
        prefill/decode for the new owner executes after those writes and
        either overwrites the slots or leaves them masked by kv_valid.
        """
        if req.slot >= 0:
            self.slots[req.slot] = None
            req.slot = -1
            self._ctl_dirty = True
        if req in self.parked:
            self.parked.remove(req)
        req.pending_tok = None
        if req.seq is not None:
            self.pool.free_sequence(req.seq)
            req.seq = None
        if self.state_pool is not None:
            # the lane's state slot goes with its decode slot; a hit not
            # yet restored gives its snapshot back
            self._drop_state_hit(req)

    def state_section(self) -> Dict[str, int]:
        """STATE_METRIC_KEYS snapshot section (runtime/metrics.py): the
        state slots of a model with a recurrent state."""
        sp, pc = self.state_pool, self.prefix_cache
        return {
            "state_slots_total": sp.n_slots,
            "state_slots_live": sum(s is not None for s in self.slots)
            + sp.snapshots_live,
            "state_snapshots": sp.snapshots_live,
            "state_snapshot_slots": sp.snapshot_slots,
            "state_restores": self.state_restores,
            "state_tokens_matched": self.state_tokens_matched,
            "state_tokens_skipped": self.state_tokens_skipped,
            "state_snapshots_stored": pc.snapshots_stored if pc else 0,
            "state_snapshots_evicted": pc.snapshots_evicted if pc else 0,
            "state_bytes_per_slot": self.cfg.state_bytes_per_slot,
        }

    def _preempt_youngest(self) -> None:
        """Roll the most recent request back to the waiting queue."""
        cands = [s for s in self.slots if s is not None]
        if len(cands) <= 1:
            return
        # background lanes are the first victims: their whole contract is
        # to soak idle capacity, never to hold pages an interactive lane
        # needs (ISSUE 20)
        bg = [r for r in cands if r.background]
        self._preempt(max(bg or cands, key=lambda r: r.submit_time))

    def _preempt(self, victim: GenRequest) -> None:
        logger.warning("preempting %s (out of KV pages)", victim.request_id)
        self.metrics.record_preempt()
        if self.flight is not None:
            self.flight.note_cause(
                "park_rollback" if victim in self.parked else "preempt"
            )
        add_event(victim.trace, "preempt",
                  {"generated": len(victim.output_ids),
                   **self._tattrs()})
        # Preemption needs complete outputs (prefill_ids below); the caller
        # (_ensure_pages) has already drained the pipeline.
        assert not self._pending, "preempt with in-flight fetches"
        assert victim.dispatched == victim.drained, (
            "preempt victim has unprocessed dispatched tokens"
        )
        # a drained pipeline implies every verify entry reconciled; the
        # victim's n-gram history survives preemption (outputs never
        # rewind), so speculation resumes cleanly after re-prefill
        victim.spec_ahead = 0
        self._release_slot(victim)
        # Re-prefill later over prompt + generated-so-far, derived from the
        # immutable prompt (idempotent across repeated preemptions). The
        # final output token stays out: its KV was never written (it is the
        # pending decode input) — the resume prefill's sampled token is
        # discarded and decode continues from output_ids[-1] (see `resumed`).
        # A victim caught mid-prefill has no outputs yet: it restarts as a
        # plain fresh prefill (resumed=False — there is no pending token).
        victim.prefill_ids = victim.prompt_ids + victim.output_ids[:-1]
        victim.state = WAITING
        victim.resumed = bool(victim.output_ids)
        victim.prefill_allowed = None
        if victim.background:
            self.waiting_bg.insert(0, victim)
        else:
            self.waiting.insert(0, victim)
