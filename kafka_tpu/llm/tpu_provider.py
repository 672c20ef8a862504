"""`TPULLMProvider` — the LLMProvider served by the local TPU engine.

This is the component that replaces the reference's remote gateway provider
(reference: src/llm/portkey.py:62-701, an HTTPS proxy to provider GPUs).
Requests go straight into the continuous-batching engine via the dispatch
thread (llm/worker.py) and tokens stream back per-request with no network
in the loop.

Differences from the reference, by design:

* **Pre-flight context checking.** The engine tokenizes locally, so context
  overflow raises a typed `ContextLengthError` *before* any compute — the
  reference could only string-match a remote 400 after the fact
  (src/llm/context_compaction/base.py:10-65).
* **True per-token streaming.** Chunks are yielded as the decode loop emits
  tokens (the reference buffered whole completions, src/agents/base.py:231).
* **Real usage accounting** on every path, including streaming.
* **Native tool-call decoding.** Generated text that opens a JSON object or
  array is buffered and parsed into OpenAI tool_calls; plain text streams
  through immediately.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import logging
import time
from typing import Any, AsyncIterator, Dict, List, Optional, Sequence, Tuple

from ..core.types import (
    CompletionResponse,
    ContextLengthError,
    LLMProviderError,
    ServerOverloadedError,
    StreamChunk,
    UnsupportedContentError,
    Usage,
    new_completion_id,
)
from ..models.config import ModelConfig
from ..models.tokenizer import BaseTokenizer, parse_tool_call_text
from ..runtime.engine import GenRequest, InferenceEngine, TokenEvent
from ..tracing import current as current_trace
from .base import LLMProvider, MessageLike, to_message_dicts
from .constrained import grammar_ondevice_enabled as _grammar_ondevice_enabled
from .utils import count_images
from .worker import EngineWorker

logger = logging.getLogger("kafka_tpu.llm.tpu")

# resize_dp `roles` default: KEEP the current role-pool spec (re-derived
# for the new dp by the router, today's behavior).  Distinct from None,
# which explicitly dissolves the pools back to colocated serving.
_ROLES_KEEP = object()


def _torn_items(d) -> list:
    """Snapshot a dict the engine thread mutates concurrently.

    list(dict.items()) can raise "dictionary changed size" mid-copy —
    retry (the runtime/metrics.py policy); torn reads are fine (a request
    finishing during the copy no longer needs attention)."""
    for _ in range(8):
        try:
            return list(d.items())
        except RuntimeError:
            continue
    return []


class IncrementalDetokenizer:
    """Streams token ids to text without re-decoding the whole output.

    Standard two-offset scheme: hold back the tail while it decodes to an
    incomplete UTF-8 sequence (replacement char), emit once it stabilizes.
    """

    def __init__(self, tokenizer: BaseTokenizer):
        self._tok = tokenizer
        self._ids: List[int] = []
        # decode window: [prefix, read) is already-emitted context kept so
        # tokenizers whose decode depends on neighbors (sentencepiece space
        # handling) produce stable text; [read, end) is pending.
        self._prefix = 0
        self._read = 0

    def push(self, token_id: int) -> str:
        self._ids.append(token_id)
        emitted = self._tok.decode(self._ids[self._prefix : self._read])
        full = self._tok.decode(self._ids[self._prefix :])
        if len(full) > len(emitted) and not full.endswith("�"):
            delta = full[len(emitted) :]
            self._prefix = self._read
            self._read = len(self._ids)
            return delta
        return ""

    def flush(self) -> str:
        """Emit whatever remains (end of stream), replacement chars and all."""
        emitted = self._tok.decode(self._ids[self._prefix : self._read])
        full = self._tok.decode(self._ids[self._prefix :])
        self._read = self._prefix = len(self._ids)
        return full[len(emitted) :] if len(full) > len(emitted) else ""

    @property
    def ids(self) -> List[int]:
        return self._ids


class TPULLMProvider(LLMProvider):
    """Serves chat completions from the in-process TPU engine."""

    provider_name = "tpu"
    # Agent-native scheduling (ISSUE 20): callers that own an agent loop
    # feature-detect these before passing background=True or firing
    # note_tool_return — OpenAI-shaped providers have neither.
    supports_background = True

    def __init__(
        self,
        engine: InferenceEngine,
        tokenizer: BaseTokenizer,
        model_name: str = "llama",
        worker: Optional[EngineWorker] = None,
        vision_params: Any = None,
        ignore_eos: bool = False,
    ):
        self.engine = engine
        self.tokenizer = tokenizer
        # ServingConfig.ignore_eos: no request of this provider stops at a
        # stop token
        self.stop_token_ids: Tuple[int, ...] = (
            () if ignore_eos else tuple(tokenizer.stop_ids))
        self.model_name = model_name
        self.worker = worker or EngineWorker(engine)
        self.worker.start()
        self._counter = itertools.count()
        # topology-rebuild coordination: one resize at a time; while held
        # (or waited on) the admission gate turns new traffic away, which
        # is what makes the resize drain work a finite set and converge
        self._resize_lock = asyncio.Lock()
        # True while a CANCELLED rebuild thread still runs: its completion
        # callback owns the worker resume, and the orphaned future below
        # gates the next resize (see _resize_locked)
        self._rebuild_owns_resume = False
        self._orphan_rebuild: Optional[Any] = None
        # the autoscaler control loop (runtime/autoscaler.py) attaches
        # itself here; /admin/signals v4 echoes its state when present
        self.autoscaler: Optional[Any] = None
        # Vision tower params (models/vision.py) — present iff the model
        # config has a VisionConfig; image requests 400 otherwise.
        self.vision_params = vision_params
        self._encode_images = None
        if vision_params is not None and self.model_cfg.vision is not None:
            import functools as _ft

            import jax as _jax

            from ..models.vision import encode_images as _enc

            # the sentinel scheme requires a tokenizer where NUL is one
            # token that round-trips (the byte tokenizer's id 0); a
            # subword checkpoint tokenizer must bring its own native
            # image token instead of silently mis-splitting the sentinel
            nul = tokenizer.encode("\x00")
            if len(nul) != 1 or tokenizer.decode(nul) != "\x00":
                raise ValueError(
                    "vision serving requires a tokenizer with a "
                    "single-token NUL sentinel (byte-level); this "
                    f"tokenizer encodes NUL as {nul!r}"
                )
            self._encode_images = _jax.jit(
                _ft.partial(_enc, vision_params, self.model_cfg.vision)
            )
        # pre-build the constrained-decoding vocab index off the event loop
        # so the first tool_choice-constrained request doesn't stall serving
        from .constrained import TokenIndex

        TokenIndex.warm(tokenizer)

    # ------------------------------------------------------------------

    @property
    def model_cfg(self) -> ModelConfig:
        return self.engine.cfg

    def count_prompt_tokens(
        self,
        messages: Sequence[MessageLike],
        tools: Optional[List[Dict[str, Any]]] = None,
    ) -> int:
        """Token count of the rendered prompt (compaction pre-flight).

        Vision prompts are priced with their expansion: each surviving
        image costs num_patches placeholder tokens (its 1-token sentinel
        is replaced), after the same newest-N pruning serving applies."""
        dicts = to_message_dicts(messages)
        # gate on the SERVING capability (encode fn), not just the config:
        # pricing must agree with what stream_completion will accept
        if self._encode_images is not None and count_images(dicts):
            from .images import sentinelize_images
            from .utils import prune_images

            dicts, parts = sentinelize_images(prune_images(dicts))
            n = len(self.tokenizer.encode_chat(dicts, tools=tools))
            return n + len(parts) * (self.model_cfg.vision.num_patches - 1)
        return len(self.tokenizer.encode_chat(dicts, tools=tools))

    @property
    def max_prompt_tokens(self) -> int:
        """Largest admissible prompt (engine window, minus 1 for decode)."""
        return min(self.engine.ecfg.max_window, self.model_cfg.max_context) - 1

    def note_tool_return(self, prefix_key: Optional[str]) -> None:
        """The thread's tool finished: fire its expected-return hint.

        Called by the agent loop (or the sandbox SSE terminal event) the
        moment tool execution completes — BEFORE the follow-up turn is
        even composed — so a demote-in-linger cancels and a demoted
        thread's wake prefetch overlaps the tool's tail.  Engine-thread
        op via the worker inbox; no-op with KAFKA_TPU_AGENT_DEMOTE
        unset."""
        self.worker.note_tool_return(prefix_key)

    # -- lifecycle hardening (server/app.py admission gate + drain) ------

    def _replicas(self):
        """The engine as a replica list (DataParallelEngines unwraps to
        its .engines; a single engine is its own one-element set)."""
        return getattr(self.engine, "engines", [self.engine])

    def admission_check(self) -> Optional[float]:
        """None = admit; else a Retry-After estimate in seconds.

        Reads the engine thread's queue length without synchronization —
        torn reads only make the gate a step stale, and the engine-side
        submit bound (EngineConfig.max_waiting) is the authoritative
        backstop for the race.  With DP replicas, admit while ANY replica
        has room (the router picks per-thread).
        """
        if self._resize_lock.locked():
            # topology rebuild in flight (or queued): turn new traffic
            # away (429 + Retry-After) so the resize drain works a
            # FINITE set
            return 5.0
        limit = self.engine.ecfg.max_waiting
        if limit <= 0:
            return None
        replicas = self._replicas()
        # a quarantined replica's empty queue is not capacity — the
        # router will not place anything there; gate on ROUTABLE
        # replicas or overload 429s are replaced by admission churn
        health = getattr(self.engine, "health", None)
        if health is not None:
            routable = [e for e, h in zip(replicas, health) if h.routable]
            replicas = routable or replicas
        if any(len(e.waiting) < limit for e in replicas):
            return None
        return min(e.retry_after_estimate() for e in replicas)

    def record_rejection(self) -> None:
        """Count a gate-level HTTP 429 in requests.rejected (the engine
        backstop counts its own; without this, sustained overload — where
        the gate catches nearly everything — would show ~0 rejections).
        A rejection is also an SLO miss (metrics.record_rejected), so the
        attainment gauges see shed load, and a flight-recorder "reject"
        cause (drained into the next ring record), so an overload
        burst's postmortem shows the shed traffic.  Cross-thread int
        increment: GIL-atomic enough for a counter."""
        replica = self._replicas()[0]
        replica.metrics.record_rejected()
        flight = getattr(replica, "flight", None)
        if flight is not None:
            flight.note_gate_reject()

    def signals(self) -> Dict[str, Any]:
        """One coherent autoscaler-input snapshot (GET /admin/signals,
        ISSUE 10).  This is the INPUT CONTRACT for the coming resize
        control loop — the fields below are stable:

        * ``queue``: dp-wide waiting depth, peak since last snapshot, and
          the 60s depth slope (``trend_per_s`` > 0 = demand outrunning
          capacity).
        * ``batch``: decode-slot occupancy (mean busy slots per step /
          max_batch), active lanes, configured max_batch x dp.
        * ``slo``: window attainment (1m/5m), the configured targets, and
          goodput (tokens from SLO-met requests) — scale up when
          attainment_1m sags under the target with a rising queue; scale
          down when attainment holds at 1.0 with idle occupancy.
        * ``utilization``: per-dispatch-kind MFU / HBM-bandwidth
          utilization against the chip roofline (since-boot + 1m) — how
          close each replica runs to the hardware, i.e. whether more
          replicas or bigger batches is the right lever.
        * ``replicas``: per-replica health state (quarantined replicas
          are capacity the router cannot use), load, KV-page headroom,
          and utilization.
        * ``pools`` (version 3, ISSUE 12): one entry per role pool —
          role ("prefill" / "decode", or "colocated" when
          KAFKA_TPU_DP_ROLES is unset), replica ids, queue depth, batch
          occupancy, and per-kind MFU / HBM-BW utilization — so the
          autoscaler can size the prefill pool (compute-bound) and the
          decode pool (bandwidth-bound) INDEPENDENTLY: grow prefill on
          prefill-pool queue growth with high prefill MFU, grow decode
          on decode-pool attainment collapse with high HBM-BW
          utilization.  ``disagg`` carries the router's ship counters
          (runs/pages/bytes, failures, fallbacks) when pools are
          configured, else null.
        * ``anomalies`` (version 2, ISSUE 11): the flight recorder's
          step-cadence detector state — edge-triggered firing counters
          plus the CURRENTLY-ACTIVE list (queue stall, fetch-pipeline
          starvation, MFU collapse, prefill convoy), each active entry
          naming the replica it fires on.  This is the "something is
          wrong, don't scale on stale math" input: while any anomaly is
          active the utilization/attainment numbers describe a sick
          replica, and a controller must hold rather than resize on
          them.  The ``utilization`` section also carries the measured
          dispatch timing (``measured_busy_s``/``modeled_busy_s``/
          ``model_skew``) calibrating the modeled MFU/HBM-BW figures.
        * ``autoscaler`` (version 4, ISSUE 13): the in-process control
          loop's state when one runs (mode, degradation-ladder rung,
          resize cooldowns, last decision) — null when
          KAFKA_TPU_AUTOSCALE is off.  Version 4 also adds
          ``slo.window_1m_requests`` (how many MET/MISSED verdicts back
          the 1m attainment gauge, so a reader can tell "1.0 because
          everything met" from "1.0 because nothing finished").
        * ``object_tier`` (version 5, ISSUE 14): the shared object
          store's occupancy, cross-host dedupe ratio, and sleep-manifest
          wake counts — with the tier mounted, scale-in is
          drain-then-shrink (warm state survives the removed replica),
          so a controller can shrink more aggressively.  Null when
          KAFKA_TPU_KV_OBJECT_DIR is unset.  Version 6 (ISSUE 17) adds
          store HEALTH to the section: ``breaker_state``
          ("closed"/"half_open"/"open" — the dp max, so any replica's
          open breaker surfaces), ``breaker_opens``,
          ``store_available`` (False = the store is fast-failing and
          the pre-scale-in drain will be SKIPPED: shrink decisions
          should assume dormant threads re-prefill), and the
          retry/timeout/error/negative-probe counters behind it.
        * ``compiles`` (version 7, ISSUE 18): the compile observatory's
          ring summary — compiles_total, seconds, cache hit/miss/off
          split, current phase, and ``storm_active``: True means XLA is
          recompiling under live traffic (a shape regression or cache
          wipe) and EVERY resize must hold — latency numbers during a
          storm measure the compiler, not capacity.  Null when
          KAFKA_TPU_COMPILE_RING=0.
        * zero-host-copy movement (version 8, ISSUE 19):
          ``object_tier.prefetch`` carries the wake-prefetch
          hits/wasted/bytes/inflight counters (all zeros when
          KAFKA_TPU_WAKE_PREFETCH_MB is unset), and ``disagg`` gains the
          ship-transport split (``disagg_ship_host_runs`` /
          ``disagg_ship_device_runs``) plus the host-staging peak gauge
          (``disagg_ship_staging_bytes``).
        * ``memory`` (version 7, ISSUE 18): measured HBM against the
          startup MemoryPlan — worst-case ``headroom_bytes`` (min over
          replicas), ``plan_skew`` (measured bytes_in_use / planned
          total; > 1 = the plan under-charges, so size scale-ups from
          the device numbers, not the plan), ``pressure`` (headroom
          under the watermark — the degradation ladder's shed input),
          plus the per-replica rows.  Null before the first poll.
        * ``agent`` (version 9, ISSUE 20): agent-native scheduling —
          ``awaiting_threads`` / ``awaiting_bytes`` (threads mid
          tool-call gap and the demoted KV bytes parked for them in
          lower tiers), the expected-return hint hit/miss split, gap
          demotion counters, and the background-class queue depth /
          admit / chunk / yield counters.  CONTRACT NOTE for
          controllers: awaiting-tool threads are NOT load — their KV
          sits in host/disk/object tiers and they occupy no decode
          slot, so they must not count toward queue depth or occupancy
          when sizing the fleet (scale on ``queue`` and ``batch`` as
          before; ``awaiting_threads`` only predicts FUTURE wake
          traffic).  All zeros when KAFKA_TPU_AGENT_DEMOTE is unset
          and no background-class work ran.

        Everything is read torn-tolerantly from the engine thread's
        single-writer metrics; no locks, safe at scrape frequency.
        """
        engine = self.engine
        # reset_peak=False: the ~1 Hz signal poll must not consume the
        # /metrics scraper's peak-since-last-snapshot window
        snap = engine.metrics.snapshot(engine, reset_peak=False)
        replicas = self._replicas()
        health = getattr(engine, "health", None)
        occupancy = snap.get("decode", {}).get("batch_occupancy", 0.0)
        max_batch = engine.ecfg.max_batch
        per_replica: List[Dict[str, Any]] = []
        rep_snaps = snap.get("replicas")
        for i, e in enumerate(replicas):
            rs = (rep_snaps[i] if rep_snaps and i < len(rep_snaps)
                  else snap)
            util = rs.get("utilization") or {}
            per_replica.append({
                "replica": i,
                "state": health[i].state if health else "healthy",
                "active": e.num_active,
                "waiting": len(e.waiting),
                "parked": len(e.parked),
                "pages_free": e.pool.free_pages,
                "pages_total": e.pool.num_pages,
                "batch_occupancy": rs.get("decode", {}).get(
                    "batch_occupancy", 0.0
                ),
                "anomalies_active": (rs.get("anomalies") or {}).get(
                    "anomalies_active", 0
                ),
                "utilization": {
                    kind: {
                        "mfu": util.get(kind, {}).get("mfu", 0.0),
                        "mfu_1m": util.get(kind, {}).get("mfu_1m", 0.0),
                        "hbm_bw_util": util.get(kind, {}).get(
                            "hbm_bw_util", 0.0
                        ),
                        "hbm_bw_util_1m": util.get(kind, {}).get(
                            "hbm_bw_util_1m", 0.0
                        ),
                        # measured/modeled calibration (ISSUE 11): >1 =
                        # this replica runs slower than the cost model
                        # assumes, so its MFU figures read high
                        "model_skew": util.get(kind, {}).get(
                            "model_skew", 0.0
                        ),
                    }
                    for kind in ("prefill", "decode", "verify")
                },
            })
        # anomalies: the aggregate section already attributes active
        # entries to replicas (dp); a single engine's lacks the field —
        # stamp replica 0 so the contract shape is dp-independent
        anomalies = dict(snap.get("anomalies") or {})
        if anomalies.get("active"):
            anomalies["active"] = [
                {**a, "replica": a.get("replica", 0)}
                for a in anomalies["active"]
            ]
        # Per-pool section (version 3, ISSUE 12): the aggregate snapshot
        # carries it when role pools are configured; otherwise the whole
        # fleet is one "colocated" pool so the contract shape is
        # role-independent.
        disagg = snap.get("disagg") or {}
        if disagg.get("pools"):
            pools = disagg["pools"]
        else:
            pools = [{
                "role": "colocated",
                "replicas": list(range(len(replicas))),
                "queue_depth": sum(len(e.waiting) for e in replicas),
                "active": engine.num_active,
                "parked": sum(len(e.parked) for e in replicas),
                "batch_occupancy": occupancy,
                "utilization": {
                    kind: {
                        k: (snap.get("utilization") or {}).get(
                            kind, {}
                        ).get(k, 0.0)
                        for k in ("mfu", "mfu_1m", "hbm_bw_util",
                                  "hbm_bw_util_1m")
                    }
                    for kind in ("prefill", "decode", "verify")
                },
            }]
        # SLO section: the raw window dicts stay internal to /metrics,
        # but the controller needs to know whether the 1m attainment
        # gauge rests on enough verdicts to act on — version 4 exports
        # that one scalar (met + missed in the 60s window)
        slo_src = snap.get("slo") or {}
        slo_out = {
            k: v for k, v in slo_src.items()
            if not k.startswith("window_")
        }
        w1 = slo_src.get("window_1m") or {}
        slo_out["window_1m_requests"] = int(
            (w1.get("met") or 0) + (w1.get("missed") or 0)
        )
        scaler = self.autoscaler
        # Object-store tier (version 5, ISSUE 14): shared-store occupancy,
        # the cross-host dedupe ratio, and wake counts — the autoscaler's
        # "drain-then-shrink is cheap here" signal.  Version 6 (ISSUE 17)
        # adds store health: breaker state (the dp-aggregate max, so any
        # replica's open breaker surfaces), retry/timeout counters, and
        # store_available — False tells a controller the pre-scale-in
        # drain will be skipped (capacity beats warm state).  Null when
        # KAFKA_TPU_KV_OBJECT_DIR is unset.
        obj = snap.get("object_tier") or None
        object_section = None
        if obj:
            tried = (obj.get("object_puts", 0)
                     + obj.get("dedupe_hits", 0))
            breaker_gauge = int(obj.get("store_breaker_state", 0))
            object_section = {
                "store_bytes": obj.get("store_bytes", 0),
                "store_objects": obj.get("store_objects", 0),
                "dedupe_ratio": round(
                    obj.get("dedupe_hits", 0) / tried, 4
                ) if tried else 0.0,
                "wake_threads": obj.get("wake_threads", 0),
                "wake_tokens": obj.get("wake_tokens", 0),
                "breaker_state": {0: "closed", 1: "half_open",
                                  2: "open"}.get(breaker_gauge, "open"),
                "breaker_opens": obj.get("store_breaker_opens", 0),
                "store_available": breaker_gauge != 2,
                "store_retries": obj.get("store_retries", 0),
                "store_timeouts": obj.get("store_timeouts", 0),
                "store_errors": (obj.get("object_put_failures", 0)
                                 + obj.get("object_get_failures", 0)),
                "probe_neg_cached": obj.get("store_probe_neg_cached", 0),
                # version 8 (ISSUE 19): wake-prefetch effectiveness —
                # hits vs wasted tells a controller whether the staging
                # budget is sized right (all zeros = prefetch off)
                "prefetch": {
                    "hits": obj.get("prefetch_hits", 0),
                    "wasted": obj.get("prefetch_wasted", 0),
                    "bytes": obj.get("prefetch_bytes", 0),
                    "inflight": obj.get("prefetch_inflight", 0),
                },
            }
        # Device-truth sections (version 7, ISSUE 18).  compiles: the
        # process-wide observatory ring summary — storm_active is the
        # "XLA is recompiling under live traffic" veto input (null when
        # KAFKA_TPU_COMPILE_RING=0).  memory: measured HBM per replica
        # plus the worst-case aggregate — a controller sizes scale-up
        # against MEASURED headroom (min across replicas) and treats
        # plan_skew > 1 as "the plan under-charges, trust the device".
        from ..runtime import compile_log

        obs = compile_log.get()
        compiles_section = (
            obs.signals_section() if obs is not None else None
        )
        mem_reps: List[Dict[str, Any]] = []
        for i, e in enumerate(replicas):
            mm = getattr(e, "memory_monitor", None)
            sec = mm.section() if mm is not None else None
            if not sec or sec.get("source") == "none":
                continue
            mem_reps.append({
                "replica": i,
                "source": sec["source"],
                "hbm_bytes_in_use": sec["hbm_bytes_in_use"],
                "hbm_bytes_limit": sec["hbm_bytes_limit"],
                "hbm_headroom_bytes": sec["hbm_headroom_bytes"],
                "hbm_plan_skew": sec["hbm_plan_skew"],
                "hbm_pressure": sec["hbm_pressure"],
            })
        memory_section = None
        if mem_reps:
            memory_section = {
                "headroom_bytes": min(
                    r["hbm_headroom_bytes"] for r in mem_reps
                ),
                "plan_skew": max(r["hbm_plan_skew"] for r in mem_reps),
                "pressure": max(r["hbm_pressure"] for r in mem_reps),
                "replicas": mem_reps,
            }
        # Agent-native scheduling (version 9, ISSUE 20).  awaiting_*
        # describes threads parked mid-tool-gap: NOT load (no decode
        # slot, KV in lower tiers) — a controller must exclude them
        # from demand sizing and read them only as a wake-traffic
        # forecast.  All zeros knobs-off.
        ag = snap.get("agent") or {}
        agent_section = {
            "awaiting_threads": ag.get("agent_awaiting_threads", 0),
            "awaiting_bytes": ag.get("agent_awaiting_bytes", 0),
            "gaps": ag.get("agent_gaps", 0),
            "gap_demotions": ag.get("agent_gap_demotions", 0),
            "gap_pages_demoted": ag.get("agent_gap_pages_demoted", 0),
            "gap_bytes_demoted": ag.get("agent_gap_bytes_demoted", 0),
            "gap_cancelled": ag.get("agent_gap_cancelled", 0),
            "hint_hits": ag.get("agent_hint_hits", 0),
            "hint_misses": ag.get("agent_hint_misses", 0),
            "bg_queue_depth": ag.get("bg_queue_depth", 0),
            "bg_admitted": ag.get("bg_admitted", 0),
            "bg_chunks": ag.get("bg_chunks", 0),
            "bg_yields": ag.get("bg_yields", 0),
        }
        return {
            # version 9 (ISSUE 20): agent-native scheduling — the
            # ``agent`` section (awaiting-tool threads + demoted bytes,
            # expected-return hint hit/miss, gap-demotion counters,
            # background-class queue/admit/chunk/yield).  Contract:
            # awaiting-tool threads are NOT load — exclude them when
            # sizing; they only forecast wake traffic.
            # version 8 (ISSUE 19): zero-host-copy movement — the
            # object_tier section gains ``prefetch`` (wake-prefetch
            # hits/wasted/bytes/inflight: zeros when
            # KAFKA_TPU_WAKE_PREFETCH_MB is unset) and the disagg
            # section carries the ship-transport split
            # (disagg_ship_host_runs / disagg_ship_device_runs — host +
            # device sum to disagg_shipped_runs) plus the host-staging
            # peak gauge (disagg_ship_staging_bytes, 0 under the
            # device transport).
            # version 7 (ISSUE 18): device-truth sections — compiles
            # (observatory ring summary + storm_active, null when
            # KAFKA_TPU_COMPILE_RING=0) and memory (measured HBM
            # headroom/plan_skew/pressure, per replica + worst-case
            # aggregate, null before the first poll or without a
            # monitor).  version 6 (ISSUE 17): object_tier section gains store
            # health — breaker_state/breaker_opens/store_available plus
            # retry/timeout/error and negative-probe counters (the
            # StoreGuard resilience layer).  Version 5 (ISSUE 14) added
            # the object_tier section (shared-store bytes/objects,
            # dedupe ratio, wake counts — null without
            # KAFKA_TPU_KV_OBJECT_DIR).  Version 4 (ISSUE 13) added the
            # autoscaler section (control-loop mode, degradation-ladder
            # rung, cooldowns, last decision — null when
            # KAFKA_TPU_AUTOSCALE is off) and slo.window_1m_requests
            # (verdict count behind the 1m attainment gauge).  Version 3
            # (ISSUE 12) added the pools section and disagg ship
            # counters; version 2 (ISSUE 11) the anomalies section,
            # per-replica anomalies_active, and the
            # measured-utilization fields under utilization.*.
            "version": 9,
            "dp": len(replicas),
            "queue": dict(snap.get("queue") or {}),
            "anomalies": anomalies,
            "agent": agent_section,
            "compiles": compiles_section,
            "memory": memory_section,
            "pools": pools,
            "object_tier": object_section,
            "disagg": {
                k: v for k, v in disagg.items()
                if k not in ("pools", "ship_ms")
            } or None,
            "autoscaler": (
                scaler.signals_section() if scaler is not None else None
            ),
            "batch": {
                "occupancy": occupancy,
                "occupancy_frac": round(occupancy / max_batch, 4)
                if max_batch else 0.0,
                "active": engine.num_active,
                "max_batch": max_batch,
                "slots_total": max_batch * len(replicas),
            },
            "slo": slo_out,
            "utilization": snap.get("utilization") or {},
            "replicas": per_replica,
            "supervisor": {
                k: v for k, v in snap["replica_supervisor"].items()
                if k != "health"
            } if snap.get("replica_supervisor") else None,
        }

    async def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful drain: let in-flight requests finish, then cancel.

        Returns True when everything completed within the timeout.  The
        caller (server shutdown) has already stopped admitting, so
        has_work is monotone-decreasing except for requests racing through
        the worker inbox — those get their terminal events either by
        finishing or by the cancel sweep below.
        """
        deadline = time.monotonic() + timeout_s
        replicas = self._replicas()
        while time.monotonic() < deadline:
            if not any(e.has_work for e in replicas):
                return True
            await asyncio.sleep(0.05)

        leftover = [rid for e in replicas
                    for rid, _ in _torn_items(e._requests)]
        if leftover:
            logger.warning(
                "drain timeout after %.1fs: cancelling %d in-flight "
                "request(s)", timeout_s, len(leftover),
            )
            for rid in leftover:
                self.worker.cancel(rid)
            # give the engine thread a moment to process the cancels so
            # every stream sees its terminal event before teardown
            settle = time.monotonic() + min(2.0, timeout_s)
            while time.monotonic() < settle and any(
                e.has_work for e in replicas
            ):
                await asyncio.sleep(0.02)
        return not leftover

    async def resize_dp(self, dp: int, drain_timeout_s: float = 30.0,
                        roles: Any = _ROLES_KEEP) -> bool:
        """Rebuild the DP replica set at a new dp count (replica loss /
        scale-down) while WAITING requests survive the rebuild.

        `roles` (ISSUE 13 satellite) optionally re-shapes the role pools
        in the same rebuild: a "prefill:P,decode:D" spec validated by the
        same parse_dp_roles rules (P + D must equal `dp`), None/""
        dissolves the pools back to colocated serving, and the default
        keeps the current spec (re-derived for the new dp, today's
        behavior) — the autoscaler and /admin/resize operators share
        this one path.

        The drain/restart topology story (ISSUE 2): started lanes own
        device state that cannot move across engines, so they get
        `drain_timeout_s` to retire naturally; leftovers are cancelled
        (each still receives its terminal event).  Queued requests are
        never touched — they ride through the rebuild and serve from the
        new replicas.  Returns True when no request had to be cancelled.

        Engine restructuring happens with the worker thread PARKED
        (EngineWorker.pause): the single-writer invariant means a parked
        worker cannot race the rebuild, and queued submits/cancels simply
        wait in the inbox for resume().  One resize runs at a time
        (asyncio lock), and the admission gate 429s new serving traffic
        for the duration — the drain then works a finite set and must
        converge.
        """
        rebuild = getattr(self.engine, "rebuild", None)
        if rebuild is None:
            raise ValueError(
                "resize_dp requires a DataParallelEngines engine "
                "(single-engine deployments have no replica topology)"
            )
        # validate the device budget BEFORE draining: an impossible dp
        # must fail up front, not after in-flight requests were cancelled
        validate = getattr(self.engine, "validate_dp", None)
        if validate is not None:
            validate(dp)
        if roles is not _ROLES_KEEP:
            # validate the role spec BEFORE draining too: a bad spec
            # must fail up front, not after in-flight work was cancelled
            from ..runtime.dp_router import validate_roles_spec

            validate_roles_spec(roles, dp)
        async with self._resize_lock:
            if self._orphan_rebuild is not None:
                # a previous resize was cancelled mid-rebuild: its thread
                # may STILL be mutating engines.  Starting a second
                # rebuild now would run two concurrent mutators (and the
                # orphan's completion would resume the worker mid-rebuild)
                # — wait the orphan out first.  Its done-callback was
                # added before this await's, so by the time we continue
                # the worker resume/flag-clear has already run.
                try:
                    await asyncio.shield(self._orphan_rebuild)
                except Exception:
                    # already logged by the orphan's done-callback; the
                    # NEW resize proceeds and rebuilds from current state
                    pass
                self._orphan_rebuild = None
            try:
                return await self._resize_locked(
                    rebuild, dp, drain_timeout_s, roles
                )
            finally:
                # a cancelled resize (client timeout mid-drain) must never
                # leave the worker parked — resume() is idempotent, and a
                # permanently paused worker is a total serving outage.
                # EXCEPT while a cancelled rebuild thread is still
                # mutating engines: then the rebuild's done-callback owns
                # the resume (resuming earlier would race the rebuild).
                if not self._rebuild_owns_resume:
                    self.worker.resume()

    async def _resize_locked(self, rebuild, dp: int,
                             drain_timeout_s: float,
                             roles: Any = _ROLES_KEEP) -> bool:
        def _started(e) -> bool:
            # pending disaggregated hand-offs are started work too: their
            # pages + un-emitted first token complete at step cadence, so
            # the drain loop below resumes the worker until they clear
            return bool(e.num_active or e.parked or e._pending
                        or getattr(e, "handoffs", None))

        clean = True
        deadline = time.monotonic() + drain_timeout_s
        while True:
            # park first, then look: an unparked worker could seat a
            # waiting request between our check and the rebuild
            if not await asyncio.to_thread(self.worker.pause):
                self.worker.resume()  # half-engaged pause must not linger
                raise RuntimeError("engine worker did not pause")
            busy = [e for e in self._replicas() if _started(e)]
            if not busy:
                break
            self.worker.resume()
            if time.monotonic() >= deadline:
                if time.monotonic() >= deadline + drain_timeout_s + 5.0:
                    # cancels were dispatched and still didn't land
                    raise RuntimeError(
                        "resize_dp: started work did not drain"
                    )
                # sweep EVERY iteration past the deadline: requests the
                # worker seated after an earlier sweep (inbox stragglers)
                # get cancelled too, so the finite set keeps shrinking.
                # Worker is resumed, hence the torn-tolerant snapshot.
                clean = False
                ids = [rid for e in busy
                       for rid, req in _torn_items(e._requests)
                       if req.state != "waiting"]
                if ids:
                    logger.warning(
                        "resize_dp: drain timeout; cancelling %d started "
                        "request(s)", len(ids),
                    )
                    for rid in ids:
                        self.worker.cancel(rid)
            await asyncio.sleep(0.02)
        # Engine reconstruction compiles/places device arrays for seconds;
        # with the worker parked the rebuild is single-writer safe from
        # ANY thread, so run it off the event loop — /health (and every
        # other handler) stays responsive during the rebuild instead of
        # blocking behind it.
        from ..runtime import compile_log

        # rebuild compiles are expected, not a storm: phase the compile
        # observatory here (not in the HTTP handler) so act-mode
        # autoscaler resizes get the same treatment (ISSUE 18)
        compile_log.set_phase("rebuild")
        fut = asyncio.get_running_loop().run_in_executor(
            None, lambda: (
                rebuild(dp=dp) if roles is _ROLES_KEEP
                else rebuild(dp=dp, roles=roles)
            )
        )
        try:
            await asyncio.shield(fut)
        except asyncio.CancelledError:
            if not fut.done():
                # the rebuild thread is STILL mutating engines: resuming
                # the worker now (the callers' finally blocks) would race
                # it — hand the resume to the rebuild's completion, and
                # leave the future behind so the NEXT resize waits it out
                # before touching the topology
                self._rebuild_owns_resume = True
                self._orphan_rebuild = fut

                def _resume(f) -> None:
                    self._rebuild_owns_resume = False
                    self.worker.resume()
                    compile_log.set_phase("first_traffic")
                    # the cancelled caller never sees the rebuild's fate:
                    # a silent rebuild failure (old/half topology still
                    # serving) must at least reach the logs
                    exc = None if f.cancelled() else f.exception()
                    if exc is not None:
                        logger.error(
                            "orphaned topology rebuild (resize was "
                            "cancelled mid-flight) FAILED: %s — the "
                            "previous topology may still be serving; "
                            "retry /admin/resize", exc,
                        )

                fut.add_done_callback(_resume)
            raise
        finally:
            if not self._rebuild_owns_resume:
                self.worker.resume()
                compile_log.set_phase("first_traffic")
        return clean

    async def drain_replica(self, replica: int) -> Dict[str, Any]:
        """Flush one replica's warm KV state into the shared object
        store (POST /admin/drain/{replica}, ISSUE 14): every cached
        radix run archived content-addressed + every thread's sleep
        manifest written, so a subsequent scale-in removing the replica
        discards no warm conversation — dormant threads wake on the
        survivors (cache_source="object_tier") instead of
        re-prefilling.  Non-destructive and idempotent (re-archiving
        present content is a reference-only dedupe).

        Runs with the worker PARKED (the flush gathers pool pages and
        walks the radix tree — both single-writer engine state) and
        serialized against resizes via the same lock, so a drain can
        never race the rebuild that follows it."""
        return (await self.drain_replicas([replica]))[0]

    async def drain_replicas(self, indices) -> List[Dict[str, Any]]:
        """drain_replica over several replicas under ONE worker pause —
        the autoscaler's pre-scale-in drain covers the whole fleet (the
        rebuild recreates every engine), and one pause/flush cycle per
        replica would stall serving N times for N flushes."""
        indices = list(indices)
        async with self._resize_lock:
            # resolve the replicas UNDER the lock: a resize rebuilds the
            # replica list wholesale, and a pre-lock snapshot could pass
            # a stale bounds check and then flush a torn-down engine
            replicas = self._replicas()
            sleeps = []
            for i in indices:
                if not 0 <= i < len(replicas):
                    raise ValueError(
                        f"replica {i} out of range (dp={len(replicas)})"
                    )
                sleep = getattr(replicas[i], "sleep_to_object", None)
                if sleep is None:
                    raise ValueError(
                        "this engine cannot drain to an object store"
                    )
                sleeps.append(sleep)
            if not await asyncio.to_thread(self.worker.pause):
                self.worker.resume()
                raise RuntimeError("engine worker did not pause")
            try:
                # the tree walks + D2H gathers can take seconds on warm
                # replicas: run off the event loop so /health stays live
                # (sequential inside one executor job — the flushes
                # mutate device state under the single-writer contract)
                all_stats = await asyncio.get_running_loop(
                ).run_in_executor(None, lambda: [s() for s in sleeps])
            finally:
                self.worker.resume()
        for i, stats in zip(indices, all_stats):
            stats["replica"] = i
        return all_stats

    def get_model_info(self, model: Optional[str] = None) -> Dict[str, Any]:
        return {
            "id": model or self.model_name,
            "provider": self.provider_name,
            "max_context": self.model_cfg.max_context,
            "max_window": self.engine.ecfg.max_window,
            "vocab_size": self.model_cfg.vocab_size,
            "supports_tools": True,
            "supports_streaming": True,
            # draft-free speculative decoding depth (0 = off): surfaced so
            # operators can confirm the serving shape without reading env
            "speculative_k": self.engine.ecfg.speculative_k,
            # on-device grammar FSM for constrained tool-call decoding
            # (KAFKA_TPU_GRAMMAR_ONDEVICE; llm/constrained.py)
            "grammar_ondevice": _grammar_ondevice_enabled(),
        }

    def build_tool_call_mask_fn(
        self,
        tools: Optional[List[Dict[str, Any]]],
        tool_choice: Any = "required",
    ):
        """Constrained decoding over the local sampler (llm/constrained.py):
        the returned fn plugs into GenRequest.logits_mask_fn and forces
        schema-valid tool-call JSON."""
        from .constrained import build_tool_call_mask_fn

        return build_tool_call_mask_fn(self.tokenizer, tools or [], tool_choice)

    def get_available_models(self) -> List[Dict[str, Any]]:
        return [
            {
                "id": self.model_name,
                "object": "model",
                "owned_by": "kafka-tpu",
                "created": 0,
            }
        ]

    # ------------------------------------------------------------------

    async def stream_completion(
        self,
        messages: Sequence[MessageLike],
        model: Optional[str] = None,
        temperature: float = 0.7,
        max_tokens: Optional[int] = None,
        tools: Optional[List[Dict[str, Any]]] = None,
        top_p: float = 1.0,
        top_k: int = 0,
        seed: Optional[int] = None,
        logits_mask_fn=None,
        prefix_key: Optional[str] = None,
        background: bool = False,
        **kwargs: Any,
    ) -> AsyncIterator[StreamChunk]:
        self.validate_messages(messages)
        dicts = to_message_dicts(messages)
        # Image parts: served through the vision tower when the model has
        # one (Llava-style soft prompt, models/vision.py — newest-19
        # pruning first, reference src/llm/portkey.py:276); a text-only
        # model rejects loudly with a typed 400 rather than silently
        # flattening (the model must not answer as if it saw an image).
        n_images = count_images(dicts)
        override_pos = override_rows = None
        if n_images:
            if self._encode_images is None:
                raise UnsupportedContentError(
                    n_images, provider=self.provider_name
                )
            import numpy as _np

            from .images import expand_placeholders, extract_images
            from .utils import prune_images

            vcfg = self.model_cfg.vision
            dicts = prune_images(dicts)

            def _prep():
                # PIL decode + ViT forward (first call also jit-compiles)
                # are CPU/TPU-blocking: off the event loop, or every
                # in-flight stream stalls for the duration
                d2, pixels = extract_images(dicts, vcfg.image_size)
                emb = self._encode_images(_np.stack(pixels))
                return d2, len(pixels), _np.asarray(emb, _np.float32)

            dicts, n_pix, embeds = await asyncio.to_thread(_prep)
            ids = self.tokenizer.encode_chat(dicts, tools=tools)
            sentinel_id = self.tokenizer.encode("\x00")[0]
            prompt_ids, override_pos = expand_placeholders(
                ids, sentinel_id, self.model_cfg.image_token_id,
                vcfg.num_patches, n_pix,
            )
            override_rows = embeds.reshape(-1, self.model_cfg.hidden_size)
            # identical placeholder ids for DIFFERENT image bytes must
            # never share prefix-cached KV (the cache keys on token ids)
            prefix_key = None
        else:
            prompt_ids = self.tokenizer.encode_chat(dicts, tools=tools)
        if len(prompt_ids) > self.max_prompt_tokens:
            raise ContextLengthError(
                len(prompt_ids), self.max_prompt_tokens, self.provider_name
            )

        # On-device grammar FSM (ISSUE 7, KAFKA_TPU_GRAMMAR_ONDEVICE):
        # lower the tool-call mask into a device-resident token DFA so the
        # constrained lane advances inside the jitted decode step with
        # zero host round trips.  Cached per (tokenizer, schema, vocab);
        # small-vocab compiles run synchronously off the event loop, while
        # LARGE-vocab schemas (> KAFKA_TPU_GRAMMAR_SYNC_VOCAB) compile on
        # a background worker — the first call returns None immediately
        # (host-mask path, no multi-second stall) and later calls flip to
        # on-device once the table lands (constrained_compile_pending
        # gauge).  None (disabled, a custom mask fn, or an uncompilable
        # grammar) keeps the host micro-batch path.
        grammar = None
        if logits_mask_fn is not None:
            from .constrained import compile_grammar_for_mask_fn

            grammar = await asyncio.to_thread(
                compile_grammar_for_mask_fn, logits_mask_fn,
                self.model_cfg.vocab_size,
            )

        completion_id = new_completion_id()
        model_id = model or self.model_name
        req = GenRequest(
            request_id=f"{completion_id}-{next(self._counter)}",
            prompt_ids=prompt_ids,
            max_new_tokens=max_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            seed=seed if seed is not None else 0,
            stop_token_ids=self.stop_token_ids,
            logits_mask_fn=logits_mask_fn,
            grammar=grammar,
            prefix_key=prefix_key,
            # background class (ISSUE 20): tool-result prefill and
            # in-engine compaction ride idle capacity, yielding to
            # interactive work at every scheduler iteration
            background=background,
            override_pos=override_pos,
            override_rows=override_rows,
            # carry the ambient trace context across the thread boundary:
            # the engine thread records queue/prefill/decode/emit spans
            # against it (None = untraced, one branch per span site)
            trace=current_trace(),
        )
        loop = asyncio.get_running_loop()
        events = self.worker.submit(req, loop)

        # role header first (OpenAI convention)
        yield StreamChunk(role="assistant", id=completion_id, model=model_id)

        detok = IncrementalDetokenizer(self.tokenizer)
        # tool-call detection: undecided until the first non-space char;
        # "{" / "[" switches to buffering mode, anything else streams.
        mode = "undecided"
        buffered: List[str] = []
        n_tokens = 0
        try:
            while True:
                ev: TokenEvent = await events.get()
                if ev.finish_reason and ev.finish_reason.startswith(
                    "rejected:"
                ):
                    # engine-thread admission backstop (queue filled
                    # between the server gate's check and our submit)
                    parts = ev.finish_reason.split(":", 2)
                    try:
                        retry = float(parts[1])
                    except (IndexError, ValueError):
                        retry = 5.0
                    raise ServerOverloadedError(
                        retry, provider=self.provider_name
                    )
                if ev.finish_reason and ev.finish_reason.startswith("error:"):
                    raise LLMProviderError(
                        ev.finish_reason[len("error:") :],
                        provider=self.provider_name,
                    )
                if ev.finish_reason == "cancelled":
                    raise asyncio.CancelledError("generation cancelled")
                text = ""
                if ev.token_id is not None:
                    n_tokens += 1
                    text = detok.push(ev.token_id)
                if ev.finished:
                    text += detok.flush()
                if text:
                    if mode == "undecided":
                        probe = ("".join(buffered) + text).lstrip()
                        if not probe:
                            buffered.append(text)
                        elif probe[0] in "[{":
                            mode = "tool"
                            buffered.append(text)
                        else:
                            mode = "text"
                            pending = "".join(buffered) + text
                            buffered = []
                            yield StreamChunk(
                                content=pending, id=completion_id, model=model_id
                            )
                    elif mode == "tool":
                        buffered.append(text)
                    else:
                        yield StreamChunk(
                            content=text, id=completion_id, model=model_id
                        )
                if ev.finished:
                    final = self._finalize(
                        mode, buffered, ev, completion_id, model_id,
                        len(prompt_ids), n_tokens,
                        # FIRST-admission radix share (frozen at prefill
                        # start): a preemption or disaggregated-hand-off
                        # resume re-attaches the whole prefix, which must
                        # not read as client-saved compute
                        cached_tokens=req.usage_cached_tokens or 0,
                    )
                    if any(c.finish_reason == "tool_calls" for c in final):
                        # the thread is about to leave for a tool call:
                        # start the demote linger + expected-return hint
                        # (engine-thread op via the inbox; no-op with
                        # KAFKA_TPU_AGENT_DEMOTE unset)
                        self.worker.note_tool_gap(req.prefix_key)
                    for chunk in final:
                        yield chunk
                    return
        finally:
            if req.state != "finished":
                self.worker.cancel(req.request_id)

    def _finalize(
        self,
        mode: str,
        buffered: List[str],
        ev: TokenEvent,
        completion_id: str,
        model_id: str,
        prompt_tokens: int,
        completion_tokens: int,
        cached_tokens: int = 0,
    ) -> List[StreamChunk]:
        """Terminal chunks: flush buffers, resolve tool calls, report usage."""
        chunks: List[StreamChunk] = []
        finish = ev.finish_reason or "stop"
        text = "".join(buffered)
        tool_calls = parse_tool_call_text(text) if mode == "tool" else None
        if tool_calls:
            deltas = [
                {
                    "index": i,
                    "id": tc["id"],
                    "type": "function",
                    "function": tc["function"],
                }
                for i, tc in enumerate(tool_calls)
            ]
            chunks.append(
                StreamChunk(tool_calls=deltas, id=completion_id, model=model_id)
            )
            finish = "tool_calls"
        elif text:
            # buffered text that didn't parse as a tool call: emit verbatim
            chunks.append(
                StreamChunk(content=text, id=completion_id, model=model_id)
            )
        usage = Usage(
            prompt_tokens=prompt_tokens,
            completion_tokens=completion_tokens,
            total_tokens=prompt_tokens + completion_tokens,
            # OpenAI-compatible prompt_tokens_details.cached_tokens: the
            # prompt span served from radix-cached KV pages (own- or
            # cross-thread) instead of prefill compute
            cached_prompt_tokens=cached_tokens,
        )
        chunks.append(
            StreamChunk(
                finish_reason=finish,
                id=completion_id,
                model=model_id,
                usage=usage.to_dict(),
            )
        )
        return chunks

    async def aclose(self) -> None:
        self.worker.stop()
