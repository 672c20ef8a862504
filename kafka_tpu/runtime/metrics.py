"""Serving metrics: the SLO telemetry plane BASELINE's autoscaler reads.

The reference's observability was print statements (SURVEY §5.1/5.5 — it
even returned zeroed token usage on the agent path).  Here the engine
records real counters as it schedules, the server exports them at
GET /metrics (JSON + Prometheus text), and bench.py reads the same
numbers — one source of truth.

Three layers (ISSUE 10):

* **Streaming histograms** — every latency/size distribution (TTFT, TPOT,
  the TTFT phase breakdown, emission bursts) is a fixed log-spaced-bucket
  histogram: constant memory, cumulative bucket counts that are monotone
  by construction, and MERGEABLE across DP replicas (identical bounds,
  counts add).  The last-512-sample deques this replaces were
  window-biased under bursty load (a quiet minute evicted the burst that
  mattered), reset on snapshot races, and could not be combined across
  replicas without shipping raw samples.  JSON quantiles are derived from
  the buckets (upper-bound-biased within one bucket — bounds are spaced
  sqrt(2) apart, so the bias is <= ~41% of the value and one-sided);
  Prometheus gets the true ``_bucket``/``_sum``/``_count`` families.

* **SLO classification + goodput** — each request is classified MET/MISSED
  at finalize against KAFKA_TPU_SLO_TTFT_MS (default 200, the BASELINE
  north star) and KAFKA_TPU_SLO_TPOT_MS (default 0 = TTFT-only).
  Timeouts, engine failures, and 429 rejections are MISSED; client
  cancels are excluded (the server didn't fail them).  Goodput = tokens
  generated by SLO-met requests (DistServe's operating-point metric) vs
  raw throughput, plus 1m/5m sliding-window attainment gauges — the
  autoscaler's primary inputs.

* **Device-utilization estimator** — per-dispatch FLOP and HBM-byte
  costs (computed by the engine from runtime/planner.py's cost model)
  accumulate per dispatch kind (prefill/decode/verify) against measured
  inter-dispatch wall time, yielding MFU and HBM-bandwidth utilization
  against the chip's datasheet roofline.  An ESTIMATE by design: wall
  time is enqueue-to-enqueue (the same assumption TPOT makes — valid
  while the device, not the host, is the bottleneck).

Everything is designed for the single-writer engine thread: recording is
plain attribute math (no locks on the hot path); `snapshot()` is called
from other threads and reads are torn-tolerant (worst case a metric is one
step stale).  KAFKA_TPU_TELEMETRY=0 disables the per-dispatch recording
(histograms, utilization attribution, queue trail); plain counters AND
the per-request SLO accounting — including the window gauges the
autoscaler reads — keep working, so the escape hatch the bench
telemetry-overhead A/B measures against can never blind the control loop.
"""

from __future__ import annotations

import bisect
import dataclasses
import math
import operator
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..tracing import BOOT_STAGES, SCHED_ITER_CLASSES, SCHED_PHASES

TELEMETRY_ENV = "KAFKA_TPU_TELEMETRY"
SLO_TTFT_ENV = "KAFKA_TPU_SLO_TTFT_MS"
SLO_TPOT_ENV = "KAFKA_TPU_SLO_TPOT_MS"

# ---------------------------------------------------------------------------
# THE metric table: every scalar key of the /metrics snapshot is declared
# here once, and its three consumers are loops over these entries — the
# replica snapshot (EngineMetrics.snapshot: the `read` / `field` of an
# entry), the dp merge (merge_snapshots: its `merge`) and the Prometheus
# text (server/prometheus.py: its `family`).  A new counter is its field
# where it is counted plus one entry here.
# ---------------------------------------------------------------------------

COUNTER, GAUGE = "counter", "gauge"
# rides beside a section's metrics (raw window sums, lists, strings): in
# the JSON and merged like any entry, but not one of the section's metrics
DETAIL = "detail"

# how replicas combine into the dp aggregate
SUM, MAX, MIN = "sum", "max", "min"
FIRST = "first"      # deployment-wide: every replica reports it alike
CONCAT = "concat"    # lists chained in replica order
DERIVED = "derived"  # recomputed from merged counters (_DERIVE below, the
#                      functions the replica snapshot uses too)
# merge=None: not in the aggregate (a replica's own detail, or a section
# that the router or the server lays over the merged snapshot)

UTILIZATION_KINDS = ("prefill", "decode", "verify")


@dataclasses.dataclass(frozen=True)
class Metric:
    section: str                   # "" = the snapshot's top level
    key: str
    kind: str = COUNTER
    merge: Optional[str] = SUM
    # exposition: the Prometheus family and this key's labels in it, None
    # = JSON only.  TYPE is "counter" for a *_total family, else "gauge";
    # HELP stands on the family's first entry.
    family: Optional[str] = None
    labels: Tuple[Tuple[str, str], ...] = ()
    help: str = ""
    digits: Optional[int] = None   # rounding, in `read` and in a SUM merge
    # where the replica snapshot takes the value: an attribute path or a
    # function of the section's source object (the engine; its prefix
    # cache; its flight recorder)
    read: Any = None
    field: Optional[str] = None    # the EngineMetrics field behind the key
    # DERIVED from an EngineMetrics accumulator that is not exported raw
    accumulator: Optional[str] = None
    per_kind: bool = False         # one value per UTILIZATION_KINDS sub-dict
    per_replica: bool = False      # the text also labels each replica's
    each: Optional[str] = None     # dict-valued: one sample a item, its
    #                                name under this label

    def read_from(self, source) -> Any:
        value = (self.read(source) if callable(self.read)
                 else operator.attrgetter(self.read)(source))
        if isinstance(value, dict):
            return dict(value)
        return value if self.digits is None else round(value, self.digits)

    def samples(self, snap: Dict[str, Any]):
        """(labels, value) for each sample this entry has in `snap`."""
        node = snap if not self.section else snap.get(self.section)
        if not isinstance(node, dict):
            return
        subs = ([(k, node[k]) for k in UTILIZATION_KINDS if k in node]
                if self.per_kind else [(None, node)])
        for kind, sub in subs:
            value = sub.get(self.key)
            if value is None:
                continue
            labels = {"kind": kind} if kind else {}
            labels.update(self.labels)
            if self.each:
                for name, v in value.items():
                    yield {**labels, self.each: name}, v
            else:
                yield labels, value


_FAMILY_SPEC = re.compile(r"^(\w+)(?:\{(.*)\})?$")


def _m(key: str, expo: Optional[str] = None, help: str = "",
       kind: Optional[str] = None, merge: Optional[str] = SUM,
       **kw) -> Dict[str, Any]:
    """One row: `expo` is 'family' or 'family{label=value,...}'; the kind
    defaults to the family's TYPE (COUNTER where there is none)."""
    family, labels = None, ()
    if expo is not None:
        family, raw = _FAMILY_SPEC.match(expo).groups()
        labels = tuple(tuple(p.split("=")) for p in raw.split(",")) \
            if raw else ()
    if kind is None:
        kind = family_type(family) if family else COUNTER
    return dict(key=key, kind=kind, merge=merge, family=family,
                labels=labels, help=help, **kw)


def _section(name: str, *rows: Dict[str, Any]) -> Tuple[Metric, ...]:
    return tuple(Metric(section=name, **row) for row in rows)


def family_type(family: str) -> str:
    return COUNTER if family.endswith("_total") else GAUGE


METRICS: Tuple[Metric, ...] = (
    *_section(
        "",
        _m("uptime_s", "kafka_tpu_uptime_seconds", "Engine uptime.",
           merge=FIRST),
        _m("dp", "kafka_tpu_dp_replicas", "Configured DP replica count.",
           merge=None),
        # legacy alias of constrained.constrained_roundtrips
        _m("constrained_roundtrips", merge=DERIVED),
    ),
    *_section(
        "requests",
        _m("submitted", "kafka_tpu_requests_total{state=submitted}",
           "Requests by terminal state (submitted counts ingress).",
           field="requests_submitted"),
        _m("finished", "kafka_tpu_requests_total{state=finished}",
           field="requests_finished"),
        _m("cancelled", "kafka_tpu_requests_total{state=cancelled}",
           field="requests_cancelled"),
        _m("preempted", "kafka_tpu_requests_total{state=preempted}",
           field="requests_preempted"),
        _m("timeout", "kafka_tpu_requests_total{state=timeout}",
           field="requests_timeout"),
        _m("rejected", "kafka_tpu_requests_total{state=rejected}",
           field="requests_rejected"),
        _m("failed", "kafka_tpu_requests_total{state=failed}",
           field="requests_failed"),
        # requests over the slow-request thresholds (server/app.py adds it
        # from the tracing counters)
        _m("slow", "kafka_tpu_requests_total{state=slow}", merge=None),
    ),
    *_section(
        "queue",
        _m("depth", "kafka_tpu_queue_depth",
           "Engine waiting-queue depth (last scheduler iteration).",
           field="queue_depth"),
        _m("peak", "kafka_tpu_queue_depth_peak",
           "Peak waiting-queue depth since the previous snapshot (each "
           "scrape re-arms the high-water mark).",
           merge=MAX, field="queue_depth_peak"),
        # depth slopes add: the dp-wide queue's growth rate
        _m("trend_per_s", "kafka_tpu_queue_depth_trend_per_second",
           "Queue-depth slope over the last minute (>0 = growing; an "
           "autoscaler scale-up signal).", digits=4),
    ),
    *_section(
        "tokens",
        _m("prompt", "kafka_tpu_tokens_total{kind=prompt}",
           "Token counters by kind.", field="prompt_tokens"),
        _m("generated", "kafka_tpu_tokens_total{kind=generated}",
           field="generated_tokens"),
        # rates sum across replicas (tokens over the same wall clock);
        # ratios do not, they are DERIVED
        _m("generated_per_s", "kafka_tpu_tokens_generated_per_second",
           "Decode throughput over uptime.", digits=2),
        # tokens dispatched for a lane that had finished when the fetch
        # matured: the cost of dispatch running ahead of drain
        _m("fetch_pipeline_wasted",
           "kafka_tpu_tokens_total{kind=fetch_pipeline_wasted}",
           field="fetch_pipeline_wasted_tokens"),
        _m("fetch_pipeline_waste_frac", kind=GAUGE, merge=DERIVED),
    ),
    *_section(
        "constrained",
        _m("constrained_roundtrips",
           "kafka_tpu_constrained_roundtrips_total",
           "Constrained choice points that awaited a device fetch.",
           field="constrained_roundtrips"),
        _m("constrained_mask_overtight",
           "kafka_tpu_constrained_overtight_total",
           "Over-tight constrained mask rows degraded to unconstrained "
           "sampling.", field="constrained_mask_overtight"),
        _m("constrained_ondevice_tokens",
           "kafka_tpu_constrained_ondevice_tokens_total",
           "Tokens emitted through the device-resident grammar FSM "
           "(zero-roundtrip constrained decoding).",
           field="constrained_ondevice_tokens"),
        # the deferred-compile queue is one per process
        _m("constrained_compile_pending",
           "kafka_tpu_constrained_compile_pending",
           "Grammar compiles queued/running on the background "
           "deferred-compile worker (requests use the host-mask path "
           "until their table lands).", merge=FIRST),
    ),
    *_section(
        "speculation",
        # proposed counts candidates at dispatch; accepted / rejected
        # reconcile at drain, so all three stay monotone across rollback
        _m("speculation_proposed_tokens",
           "kafka_tpu_speculation_tokens_total{kind=proposed}",
           "Speculative candidate tokens by outcome.",
           field="speculation_proposed_tokens"),
        _m("speculation_accepted_tokens",
           "kafka_tpu_speculation_tokens_total{kind=accepted}",
           field="speculation_accepted_tokens"),
        _m("speculation_rejected_tokens",
           "kafka_tpu_speculation_tokens_total{kind=rejected}",
           field="speculation_rejected_tokens"),
        _m("speculation_verify_steps",
           "kafka_tpu_speculation_verify_steps_total",
           "Speculative verify dispatches.",
           field="speculation_verify_steps"),
        _m("speculation_acceptance_rate",
           "kafka_tpu_speculation_acceptance_rate",
           "Accepted / (accepted + rejected) candidate tokens.",
           merge=DERIVED),
        _m("speculation_accepted_per_step",
           "kafka_tpu_speculation_accepted_per_step",
           "Mean accepted candidates per verify dispatch.",
           merge=DERIVED),
    ),
    *_section(
        "slo",
        _m("slo_ttft_target_ms",
           "kafka_tpu_slo_target_milliseconds{kind=ttft}",
           "Configured SLO targets (0 = target disabled).", merge=FIRST),
        _m("slo_tpot_target_ms",
           "kafka_tpu_slo_target_milliseconds{kind=tpot}", merge=FIRST),
        _m("slo_met_requests", "kafka_tpu_slo_requests_total{result=met}",
           "Requests by SLO verdict at finalize (timeouts, engine "
           "failures and 429 rejections count as missed; client "
           "cancels are excluded).", field="slo_met_requests"),
        _m("slo_missed_requests",
           "kafka_tpu_slo_requests_total{result=missed}",
           field="slo_missed_requests"),
        _m("slo_ttft_violations",
           "kafka_tpu_slo_violations_total{kind=ttft}",
           "Missed-SLO attributions by violated target.",
           field="slo_ttft_violations"),
        _m("slo_tpot_violations",
           "kafka_tpu_slo_violations_total{kind=tpot}",
           field="slo_tpot_violations"),
        _m("slo_attainment", "kafka_tpu_slo_attainment{window=total}",
           "Fraction of finalized requests meeting every SLO target, "
           "by window (1.0 when the window saw none).", merge=DERIVED),
        _m("slo_attainment_1m", "kafka_tpu_slo_attainment{window=1m}",
           merge=DERIVED),
        _m("slo_attainment_5m", "kafka_tpu_slo_attainment{window=5m}",
           merge=DERIVED),
        _m("goodput_tokens", "kafka_tpu_goodput_tokens_total",
           "Tokens generated by SLO-met requests.",
           field="goodput_tokens"),
        _m("goodput_tok_s",
           "kafka_tpu_goodput_tokens_per_second{window=total}",
           "Goodput rate by window (SLO-met tokens only).",
           merge=DERIVED),
        _m("goodput_tok_s_1m",
           "kafka_tpu_goodput_tokens_per_second{window=1m}",
           merge=DERIVED),
        _m("goodput_frac", "kafka_tpu_goodput_fraction",
           "Goodput tokens / raw generated tokens.", merge=DERIVED),
        # raw window sums: what the aggregate adds before the windowed
        # ratios are recomputed (an idle replica must not weigh like a
        # loaded one)
        _m("window_1m", kind=DETAIL),
        _m("window_5m", kind=DETAIL),
    ),
    # Per dispatch kind, the planner's modeled cost over attributed busy
    # time.  mfu / hbm_bw_util are ratios against the roofline, recomputed
    # from merged sums (replica-seconds: per-chip, not fleet-total); the
    # measured_* keys pair the flight recorder's fetch-maturation timing
    # with the modeled time of the SAME dispatches.
    *_section(
        "utilization",
        _m("peak_tflops", "kafka_tpu_device_peak_teraflops",
           "Roofline peak FLOP/s per chip (datasheet or env override), "
           "in TFLOP/s.", merge=FIRST),
        _m("peak_hbm_gbps",
           "kafka_tpu_device_peak_hbm_gigabytes_per_second",
           "Roofline peak HBM bandwidth per chip, in GB/s.", merge=FIRST),
        _m("peak_source", kind=DETAIL, merge=FIRST),
        _m("dispatches", "kafka_tpu_dispatches_total",
           "Device dispatches by kind.", per_kind=True),
        _m("tokens", "kafka_tpu_dispatch_tokens_total",
           "Tokens processed by dispatch kind.", per_kind=True),
        _m("flops", "kafka_tpu_device_flops_total",
           "Modeled device FLOPs by dispatch kind (planner cost model).",
           per_kind=True),
        _m("hbm_bytes", "kafka_tpu_device_hbm_bytes_total",
           "Modeled HBM bytes moved by dispatch kind.", per_kind=True),
        _m("busy_s", "kafka_tpu_dispatch_busy_seconds_total",
           "Wall time attributed to dispatch execution by kind.",
           per_kind=True, digits=3),
        _m("mfu", "kafka_tpu_mfu{window=total}",
           "Model FLOPs utilization vs the chip roofline, by dispatch "
           "kind and window (0 when no roofline known).",
           merge=DERIVED, per_kind=True, per_replica=True),
        _m("hbm_bw_util",
           "kafka_tpu_hbm_bandwidth_utilization{window=total}",
           "HBM bandwidth utilization vs the chip roofline, by "
           "dispatch kind and window.",
           merge=DERIVED, per_kind=True, per_replica=True),
        _m("mfu_1m", "kafka_tpu_mfu{window=1m}",
           merge=DERIVED, per_kind=True, per_replica=True),
        _m("hbm_bw_util_1m",
           "kafka_tpu_hbm_bandwidth_utilization{window=1m}",
           merge=DERIVED, per_kind=True, per_replica=True),
        _m("measured_dispatches", "kafka_tpu_measured_dispatches_total",
           "Dispatches with a measured device-time sample by kind.",
           per_kind=True),
        _m("measured_busy_s", "kafka_tpu_dispatch_measured_seconds_total",
           "Measured device execution time by dispatch kind "
           "(fetch-maturation timing).", per_kind=True, digits=4),
        _m("modeled_busy_s", "kafka_tpu_dispatch_modeled_seconds_total",
           "Modeled roofline execution time for the SAME measured "
           "dispatches, by kind.", per_kind=True, digits=4),
        _m("model_skew", "kafka_tpu_dispatch_model_skew",
           "Measured / modeled dispatch time by kind (>1 = the device "
           "runs slower than the cost model assumes, so the modeled "
           "MFU/HBM-BW figures read high by this factor; 0 = no "
           "samples yet).", merge=DERIVED, per_kind=True),
        # the raw 60 s sums behind the *_1m ratios
        _m("window_1m", kind=DETAIL, per_kind=True, digits=4),
        _m("achieved_tflops", kind=DETAIL, merge=None, per_kind=True),
        _m("achieved_gb_s", kind=DETAIL, merge=None, per_kind=True),
    ),
    *_section(
        "decode",
        _m("steps", "kafka_tpu_decode_steps_total",
           "Decode steps dispatched (fused steps count k).",
           field="decode_steps"),
        # busy slots summed over steps / steps; the raw accumulator alone
        # would mislead dashboards
        _m("batch_occupancy", "kafka_tpu_batch_occupancy",
           "Mean busy decode slots per step.", merge=DERIVED,
           accumulator="decode_busy_slots"),
    ),
    *_section(
        "engine",
        _m("active", "kafka_tpu_engine_active",
           "Requests holding a decode slot.", read="num_active"),
        _m("waiting", "kafka_tpu_engine_waiting",
           "Requests in the waiting queue.",
           read=lambda e: len(e.waiting)),
        _m("in_flight_fetches", kind=GAUGE, merge=None,
           read=lambda e: len(e._pending)),
        _m("pages_total", "kafka_tpu_kv_pages{state=total}",
           "KV pool pages by state.", read="pool.num_pages"),
        _m("pages_free", "kafka_tpu_kv_pages{state=free}",
           read="pool.free_pages"),
        _m("pages_in_use", "kafka_tpu_kv_pages{state=in_use}",
           read=lambda e: e.pool.num_pages - 1 - e.pool.free_pages),
        _m("kv_bytes_per_token", "kafka_tpu_kv_bytes_per_token",
           "Bytes one cached token holds in the KV pool, all layers, "
           "as allocated.", merge=FIRST, read="kv_bytes_per_token"),
        _m("max_batch", kind=GAUGE, merge=None, read="ecfg.max_batch"),
        _m("attention_backend", kind=DETAIL, merge=None,
           read="cfg.attention_backend"),
        _m("rtt_est_ms", "kafka_tpu_device_rtt_milliseconds",
           "Estimated device-to-host fetch round trip.", merge=None,
           read=lambda e: e._rtt_est * 1e3, digits=3),
        _m("kv_window_dead_share", kind=GAUGE, merge=None,
           read=lambda e: e.kv_window_dead_share(), digits=6),
        _m("prefill_rows_dispatched", read="prefill_rows_dispatched"),
        _m("prefill_rows_filled", read="prefill_rows_filled"),
        _m("prefill_plans", "kafka_tpu_engine_prefill_plans_total{plan=all}",
           "Requests prefilled, and those whose uncached suffix the chunk "
           "plan launched in more launches than the first bucket that "
           "holds it makes.", read="prefill_plans"),
        _m("prefill_plans_split",
           "kafka_tpu_engine_prefill_plans_total{plan=split}",
           read="prefill_plans_split"),
        _m("decode_keys_walked", read="decode_keys_walked"),
        _m("decode_keys_window", read="decode_keys_window"),
        _m("decode_keys_shared", read="decode_keys_shared"),
        _m("decode_steps_walked", read="decode_steps_walked"),
        _m("decode_steps_run", read="decode_steps_run"),
        _m("decode_steps_all", read="decode_steps_all"),
        _m("decode_steps_ahead", read="decode_steps_ahead"),
        _m("index_keys_scored",
           "kafka_tpu_engine_index_keys_total{kind=scored}",
           "Keys a layer's indexer scored over the decode steps, those "
           "attention then read, and those scored once for every lane "
           "(pages the lanes share).", read="index_keys_scored"),
        _m("index_keys_kept", "kafka_tpu_engine_index_keys_total{kind=kept}",
           read="index_keys_kept"),
        _m("index_keys_shared",
           "kafka_tpu_engine_index_keys_total{kind=shared}",
           read="index_keys_shared"),
        _m("prefill_walk_trips", read="prefill_walk_trips"),
        _m("prefill_walk_kernel_trips", read="prefill_walk_kernel_trips"),
        _m("delta_chunk_trips", read="delta_chunk_trips"),
        _m("delta_state_bytes", read="delta_state_bytes"),
        _m("ssd_chunk_trips", read="ssd_chunk_trips"),
        _m("ssd_state_bytes", read="ssd_state_bytes"),
        # passes x state layers of the dispatched programs by the form each
        # op of a state layer took (engine.state_launches)
        *(_m(f"state_launches_{op}_{form}",
             f"kafka_tpu_engine_state_launches_total{{op={op},form={form}}}",
             "Passes x state layers of the dispatched programs by the form "
             "each op of a state layer took: the recurrence and the "
             "convolution's tail, as a Pallas kernel or in XLA."
             if (op, form) == ("recurrence", "kernel") else "",
             read=lambda e, k=f"{op}_{form}": e.state_launches[k])
          for op in ("recurrence", "tail") for form in ("kernel", "xla")),
        # rows x SSD layers of the dispatched launches, padding included,
        # counted where prefill_rows_dispatched is (0 without an SSD mixer)
        _m("ssd_rows_dispatched", read="ssd_rows_dispatched"),
        # the model's layers by what they hold between passes and by their
        # feed-forward: a recurrent state, rows in the paged pool, a routed
        # block (a one-sublayer pattern's layers are one of the three)
        _m("state_layers", kind=GAUGE, merge=None, read="cfg.state_layers"),
        _m("row_layers", kind=GAUGE, merge=None, read="cfg.kv_layers"),
        _m("routed_layers", kind=GAUGE, merge=None,
           read="cfg.routed_layers"),
        # rows of the residual stream a token (1: the plain residual), and
        # rows x mapping sites the dispatched launches ran, padding included
        # (0 for a model with one row)
        _m("residual_streams", kind=GAUGE, merge=None, read="cfg.hc_mult"),
        _m("hc_site_rows", read="hc_site_rows"),
        _m("moe_dispatch", read="moe_dispatch"),
        _m("moe_experts_read", "kafka_tpu_engine_moe_experts_total{kind=read}",
           "Held experts over the routed layers of the decode passes: those "
           "whose weights a pass read, and those held.",
           read="moe_experts_read"),
        _m("moe_experts_held", "kafka_tpu_engine_moe_experts_total{kind=held}",
           read="moe_experts_held"),
        _m("moe_picks_held", "kafka_tpu_engine_moe_picks_total{kind=held}",
           "Picks (active rows x top-k) of the routed layers of the decode "
           "passes: those that fell on an expert this chip holds, and all.",
           read="moe_picks_held"),
        _m("moe_picks_routed", "kafka_tpu_engine_moe_picks_total{kind=routed}",
           read="moe_picks_routed"),
        # the fetch pipeline's run-ahead (steps, summed over samples at
        # decode dispatches), the scheduler thread's time in unlanded
        # reads, and pops by what released them
        _m("fetch_depth_steps_sum", read="fetch_depth_steps_sum"),
        _m("fetch_depth_samples", read="fetch_depth_samples"),
        _m("fetch_blocked_s", read="fetch_blocked_s", digits=6),
        _m("fetch_pops", read="fetch_pops"),
        _m("decode_holds", "kafka_tpu_engine_decode_holds_total",
           "Scheduler iterations that withheld decode because more "
           "than one program's steps were queued behind the device.",
           read="decode_holds"),
        _m("decode_hold_s", "kafka_tpu_engine_decode_hold_seconds_total",
           "Seconds the scheduler waited with decode withheld.",
           read="decode_hold_s", digits=6),
        # the experts this engine holds and computes, of those its router
        # chooses among (equal unless the model is one chip's share of an
        # expert-parallel layer)
        _m("experts_held", kind=GAUGE, read="cfg.num_experts"),
        _m("experts_routed", kind=GAUGE, read="cfg.num_router_experts"),
    ),
    # The text carries each replica's radix tree beside the summed one
    # (replica="<i>"), so a dashboard sees WHERE the trees are hot.
    *_section(
        "prefix_cache",
        _m("entries", "kafka_tpu_prefix_cache_entries",
           "Live prefix-cache entries (radix nodes; legacy name).",
           read=len),
        _m("nodes", "kafka_tpu_prefix_cache_nodes",
           "Radix-tree nodes (page-aligned token runs).",
           read=len, per_replica=True),
        _m("cached_pages", "kafka_tpu_prefix_cache_pages",
           "KV pages the prefix cache currently retains.",
           read="total_pages", per_replica=True),
        *(_m(k, "kafka_tpu_prefix_cache_total{kind=%s}" % k,
             "Prefix-cache events by kind." if k == "hits" else "",
             read=k, per_replica=True)
          for k in ("hits", "misses", "tokens_reused", "cross_thread_hits",
                    "host_tier_hits", "shipped_hits", "object_tier_hits")),
        _m("host_nodes",
           "kafka_tpu_prefix_cache_host_resident{kind=host_nodes}",
           "Radix runs currently demoted to the KV tier (still "
           "matchable; promoted back on lookup).", read="host_nodes"),
        _m("host_pages",
           "kafka_tpu_prefix_cache_host_resident{kind=host_pages}",
           read="host_pages"),
        _m("evictions", "kafka_tpu_prefix_cache_total{kind=evictions}",
           read="evictions", per_replica=True),
        _m("pages_evicted",
           "kafka_tpu_prefix_cache_total{kind=pages_evicted}",
           read="pages_evicted", per_replica=True),
    ),
    # runtime/kv_tier.py snapshot(): each replica owns an independent
    # tier, so the occupancy gauges add up like the counters
    *_section(
        "kv_tier",
        _m("host_budget_bytes",
           "kafka_tpu_kv_tier_bytes{tier=host,kind=budget}",
           "Tiered-KV occupancy and budget by tier."),
        _m("host_bytes", "kafka_tpu_kv_tier_bytes{tier=host,kind=used}"),
        _m("host_runs", "kafka_tpu_kv_tier_runs{tier=host}",
           "Demoted page runs resident per tier."),
        _m("disk_bytes", "kafka_tpu_kv_tier_bytes{tier=disk,kind=used}"),
        _m("disk_runs", "kafka_tpu_kv_tier_runs{tier=disk}"),
        _m("demotions", "kafka_tpu_kv_tier_total{event=demotions}",
           "Tiered-KV events by kind."),
        _m("pages_demoted", "kafka_tpu_kv_tier_pages_total{dir=demoted}",
           "Pages shipped between tiers by direction."),
        _m("bytes_demoted", "kafka_tpu_kv_tier_bytes_total{dir=demoted}",
           "Bytes shipped between tiers by direction."),
        _m("demote_failures",
           "kafka_tpu_kv_tier_total{event=demote_failures}"),
        _m("promotions", "kafka_tpu_kv_tier_total{event=promotions}"),
        _m("pages_promoted",
           "kafka_tpu_kv_tier_pages_total{dir=promoted}"),
        _m("bytes_promoted",
           "kafka_tpu_kv_tier_bytes_total{dir=promoted}"),
        _m("promote_failures",
           "kafka_tpu_kv_tier_total{event=promote_failures}"),
        _m("host_evictions",
           "kafka_tpu_kv_tier_total{event=host_evictions}"),
        _m("disk_spills", "kafka_tpu_kv_tier_total{event=disk_spills}"),
        _m("disk_loads", "kafka_tpu_kv_tier_total{event=disk_loads}"),
    ),
    # runtime/object_tier.py snapshot(), present when
    # KAFKA_TPU_KV_OBJECT_DIR mounts the store.  store_* gauges describe
    # the ONE store every replica mounts; the rest is per owner.  The
    # store_guard keys are zero on a bare store, the prefetch keys while
    # KAFKA_TPU_WAKE_PREFETCH_MB is unset.
    *_section(
        "object_tier",
        _m("store_bytes", "kafka_tpu_object_tier_bytes{scope=store}",
           "Object-store occupancy: scope=store is the SHARED store "
           "(report once per store when aggregating scrapes); "
           "scope=owned is this replica's references.", merge=FIRST),
        _m("store_objects", "kafka_tpu_object_tier_objects",
           "Run objects resident in the shared store.", merge=FIRST),
        _m("owned_bytes", "kafka_tpu_object_tier_bytes{scope=owned}"),
        _m("object_puts", "kafka_tpu_object_tier_puts_total",
           "Run payloads archived into the store."),
        _m("object_put_failures",
           "kafka_tpu_object_tier_failures_total{op=put}",
           "Torn/failed store operations (put = archive degraded to "
           "plain eviction; get = wake aborted, pages freed)."),
        _m("object_bytes_put", "kafka_tpu_object_tier_bytes_total{dir=put}",
           "Object-store payload bytes moved by direction."),
        _m("object_gets", "kafka_tpu_object_tier_gets_total",
           "Run payloads fetched from the store (wakes)."),
        _m("object_get_failures",
           "kafka_tpu_object_tier_failures_total{op=get}"),
        _m("object_bytes_got",
           "kafka_tpu_object_tier_bytes_total{dir=get}"),
        _m("dedupe_hits", "kafka_tpu_object_tier_dedupe_hits_total",
           "Puts whose content was already present (cross-host prefix "
           "dedupe — only a reference was added)."),
        _m("wake_threads", "kafka_tpu_object_tier_wake_threads_total",
           "Dormant threads re-materialized from their sleep manifests "
           "(cache_source=\"object_tier\")."),
        _m("wake_tokens", "kafka_tpu_object_tier_wake_tokens_total",
           "Tokens re-materialized by sleep-manifest wakes (prompt "
           "tokens NOT re-prefilled)."),
        _m("manifests_written", "kafka_tpu_object_tier_manifests_total",
           "Per-thread sleep manifests written."),
        _m("objects_released", "kafka_tpu_object_tier_released_total",
           "Owner references dropped (budget eviction / thread "
           "invalidation; the last reference deletes the object)."),
        _m("store_retries", "kafka_tpu_object_store_retries_total",
           "Store ops retried by the guard (idempotent protocol ops, "
           "bounded exponential backoff)."),
        _m("store_timeouts", "kafka_tpu_object_store_timeouts_total",
           "Store op attempts that exceeded the per-op deadline "
           "(KAFKA_TPU_KV_OBJECT_TIMEOUT_S)."),
        _m("store_breaker_opens",
           "kafka_tpu_object_store_breaker_open_total",
           "Circuit-breaker open transitions (consecutive store "
           "failures crossed the trip threshold)."),
        # one replica's open breaker must stay visible in the fleet view
        _m("store_breaker_state", "kafka_tpu_object_store_breaker_state",
           "Store circuit-breaker state: 0=closed, 1=half-open, 2=open "
           "(ops fast-fail).", merge=MAX),
        _m("store_probe_neg_cached",
           "kafka_tpu_object_store_probe_neg_cached_total",
           "Manifest probes answered from the negative cache while the "
           "store is unhealthy (zero store RTT on the submit path)."),
        _m("store_scrub_repairs",
           "kafka_tpu_object_store_scrub_repairs_total",
           "Crash-window orphans repaired by the scrubber (ref-less "
           "objects, dangling refs, dead manifests)."),
        _m("prefetch_hits",
           "kafka_tpu_object_tier_prefetch_total{outcome=hit}",
           "Wake-prefetch outcomes: hit = staged payload consumed by "
           "admission (zero fetch RTT); wasted = staged/fetched but "
           "dropped (cancel, budget eviction, superseded)."),
        _m("prefetch_wasted",
           "kafka_tpu_object_tier_prefetch_total{outcome=wasted}"),
        _m("prefetch_bytes", "kafka_tpu_object_tier_prefetch_bytes_total",
           "Run payload bytes staged by wake prefetch."),
        _m("prefetch_inflight", "kafka_tpu_object_tier_prefetch_inflight",
           "Prefetch GETs scheduled but not yet resolved."),
    ),
    # The flight recorder's step-cadence detectors: edge-triggered firing
    # counts (zero forever with KAFKA_TPU_FLIGHT_RING=0).  Each active
    # anomaly carries the replica it fires on in the aggregate, so the
    # autoscaler can tell a sick replica from a sick fleet.
    *_section(
        "anomalies",
        *(_m("anomaly_" + k, "kafka_tpu_anomalies_total{kind=%s}" % k,
             "Scheduler anomaly detector firings by kind "
             "(edge-triggered)." if k == "queue_stall" else "",
             field="anomaly_" + k)
          for k in ("queue_stall", "fetch_starvation", "mfu_collapse",
                    "prefill_convoy", "compile_storm", "hbm_pressure")),
        _m("anomalies_active", "kafka_tpu_anomalies_active",
           "Anomaly detectors currently firing.", merge=DERIVED),
        _m("active", kind=DETAIL, merge=DERIVED),
    ),
    # ring state only: the records are at GET /debug/flight/{replica}
    *_section(
        "flight",
        _m("flight_ring_size", "kafka_tpu_flight_ring_size",
           "Configured flight-recorder ring length (records; summed "
           "across DP replicas).", read="size"),
        _m("flight_records", "kafka_tpu_flight_records_total",
           "Scheduler iterations recorded by the flight recorder.",
           read="next_seq"),
        _m("flight_postmortems", "kafka_tpu_flight_postmortems_total",
           "Flight-recorder postmortem dumps written.",
           read="postmortems"),
    ),
    # Engine.agent_section(): all zero unless KAFKA_TPU_AGENT_DEMOTE is
    # set or background-class requests ran; every key is per replica
    *_section(
        "agent",
        _m("agent_gaps", "kafka_tpu_agent_events_total{event=gap}",
           "Agent tool-gap scheduling events by kind."),
        _m("agent_gap_demotions",
           "kafka_tpu_agent_events_total{event=demote}"),
        _m("agent_gap_pages_demoted",
           "kafka_tpu_agent_gap_pages_demoted_total",
           "KV pages freed from HBM by tool-gap demotions."),
        _m("agent_gap_bytes_demoted",
           "kafka_tpu_agent_gap_bytes_demoted_total",
           "KV bytes moved down-tier by tool-gap demotions."),
        _m("agent_gap_cancelled",
           "kafka_tpu_agent_events_total{event=cancel}"),
        _m("agent_hint_hits",
           "kafka_tpu_agent_events_total{event=hint_hit}"),
        _m("agent_hint_misses",
           "kafka_tpu_agent_events_total{event=hint_miss}"),
        _m("agent_awaiting_threads", "kafka_tpu_agent_awaiting_threads",
           "Threads mid-tool-gap (lingering or demoted); not load — "
           "the autoscaler must not count them."),
        _m("agent_awaiting_bytes", "kafka_tpu_agent_awaiting_bytes",
           "Demoted KV bytes parked in lower tiers awaiting a tool "
           "return."),
        _m("bg_queue_depth", "kafka_tpu_bg_queue_depth",
           "Background-class requests queued (admit only into idle "
           "capacity)."),
        _m("bg_admitted", "kafka_tpu_bg_events_total{event=admit}",
           "Background-class scheduling events by kind."),
        _m("bg_chunks", "kafka_tpu_bg_events_total{event=chunk}"),
        _m("bg_yields", "kafka_tpu_bg_events_total{event=yield}"),
    ),
    # runtime/planner.MemoryMonitor.section(): the worst device's bytes
    # at step cadence against the boot-time MemoryPlan.  The plan is per
    # replica, so the tightest replica bounds the fleet.
    *_section(
        "memory",
        _m("source", kind=DETAIL, merge=FIRST),
        _m("hbm_bytes_in_use", "kafka_tpu_hbm_bytes_in_use",
           "Live HBM bytes in use (worst device; source=plan on chips "
           "without memory_stats).", merge=MAX),
        _m("hbm_bytes_peak", "kafka_tpu_hbm_bytes_peak",
           "Peak HBM bytes in use (worst device).", merge=MAX),
        _m("hbm_bytes_limit", "kafka_tpu_hbm_bytes_limit",
           "HBM byte limit (smallest device).", merge=MIN),
        _m("hbm_headroom_bytes", "kafka_tpu_hbm_headroom_bytes",
           "Measured HBM headroom: limit - in_use (size against this, "
           "not the plan).", merge=MIN),
        _m("hbm_plan_skew", "kafka_tpu_hbm_plan_skew",
           "Measured bytes / MemoryPlan predicted bytes (1.0 = the "
           "plan was right).", merge=MAX),
        _m("hbm_pressure", "kafka_tpu_hbm_pressure",
           "Headroom under the watermark (KAFKA_TPU_HBM_WATERMARK).",
           merge=MAX),
        _m("hbm_component_bytes", "kafka_tpu_hbm_component_bytes",
           "HBM attribution by MemoryPlan line item (unattributed = "
           "measured residual: gather staging, scratch, "
           "fragmentation).", merge=FIRST, each="component"),
        _m("devices", kind=DETAIL, merge=CONCAT),
    ),
    # The router's DisaggMetrics, present when KAFKA_TPU_DP_ROLES sets
    # role pools: one router a process, laid over the aggregate as it is.
    *_section(
        "disagg",
        _m("disagg_shipped_runs", "kafka_tpu_disagg_shipped_runs_total",
           "Page runs shipped from prefill-pool to decode-pool "
           "replicas.", merge=None),
        _m("disagg_shipped_pages", "kafka_tpu_disagg_shipped_pages_total",
           "KV pages shipped across replicas.", merge=None),
        _m("disagg_shipped_bytes", "kafka_tpu_disagg_shipped_bytes_total",
           "Bytes shipped across replicas (real, unpadded).", merge=None),
        _m("disagg_ship_failures", "kafka_tpu_disagg_ship_failures_total",
           "Torn/failed cross-replica ships (thread degraded to "
           "re-prefill; never partial KV).", merge=None),
        _m("disagg_ship_skips",
           "kafka_tpu_disagg_fallback_total{kind=ship_skip}",
           "Hand-off fallbacks by kind: prefill_in_place = short "
           "prompts served colocated on the decode pool; ship_skip = "
           "hand-offs completed without a copy (destination warm / no "
           "pages / sole survivor).", merge=None),
        _m("disagg_handoffs", "kafka_tpu_disagg_handoffs_total",
           "Prefill-and-hand-off completions (shipped or degraded).",
           merge=None),
        _m("disagg_prefill_in_place",
           "kafka_tpu_disagg_fallback_total{kind=prefill_in_place}",
           merge=None),
        # host + device sum to disagg_shipped_runs
        _m("disagg_ship_host_runs",
           "kafka_tpu_disagg_ship_runs_by_transport_total{transport=host}",
           "Shipped runs by transport: host = staged through a numpy "
           "copy; device = device-to-device (zero host "
           "materialization).", merge=None),
        _m("disagg_ship_device_runs",
           "kafka_tpu_disagg_ship_runs_by_transport_total"
           "{transport=device}", merge=None),
        _m("disagg_ship_staging_bytes",
           "kafka_tpu_disagg_ship_staging_bytes",
           "Peak host bytes pinned by host-staged ship chunks since "
           "the last scrape (peak-since-last, re-armed on read).",
           merge=None),
        _m("ship_ms", kind=DETAIL, merge=None),
        _m("pools", kind=DETAIL, merge=None),
    ),
    *_section(
        "replica_supervisor",
        *(_m(k, "kafka_tpu_replica_supervisor_total{event=%s}" % k,
             "Replica supervision events." if k == "quarantines" else "",
             merge=None)
          for k in ("quarantines", "readmits", "waiting_migrated",
                    "affinity_resteered", "rebuilds", "replica_rebuilds")),
        *(_m(k, kind=DETAIL, merge=None)
          for k in ("health", "states", "consecutive_failures",
                    "total_failures")),
    ),
    # engine.state_section(): the state slots of a model with a recurrent
    # state beside its pages (models/hybrid.py); the aggregate has never
    # carried the section
    *_section(
        "state",
        _m("state_slots_total", kind=GAUGE, merge=None),
        _m("state_slots_live", kind=GAUGE, merge=None),
        _m("state_snapshots", kind=GAUGE, merge=None),
        _m("state_snapshot_slots", kind=GAUGE, merge=None),
        _m("state_restores", merge=None),
        _m("state_tokens_matched", merge=None),
        _m("state_tokens_skipped", merge=None),
        _m("state_snapshots_stored", merge=None),
        _m("state_snapshots_evicted", merge=None),
        _m("state_bytes_per_slot", kind=GAUGE, merge=None),
    ),
    # The engine THREAD's account (runtime/phase_clock.SchedClock): one
    # section a thread.  Replicas that one thread steps share its clock and
    # report it once (dp_router lays the distinct clocks over the
    # aggregate); where each replica has a thread the aggregate sums them
    # and a reader divides by `threads`.
    *_section(
        "sched",
        _m("threads", kind=GAUGE),
        *(_m(p + "_s", "kafka_tpu_sched_phase_seconds_total{phase=%s}" % p,
             "Seconds of the engine thread by phase (tracing.SCHED_PHASES); "
             "every instant belongs to one phase, so they add up to the "
             "thread's wall time." if p == SCHED_PHASES[0] else "",
             digits=6)
          for p in SCHED_PHASES),
        # over the timed waits that ran out, elapsed less the timeout: what
        # the thread lost to the GIL and the OS after it asked to be woken
        _m("wait_over_s", digits=6),
        # events handed to the event loop: deliver_s / delivered is the
        # price of one call_soon_threadsafe
        _m("delivered"),
        _m("dev_starved_s",
           "kafka_tpu_sched_dev_starved_seconds_total{bound=lower}",
           "Seconds the device had nothing queued while lanes were active, "
           "as the host knows it: from the poll that saw the last program "
           "done (lower) or the last look that saw it running (upper) to "
           "the next dispatch.", digits=6),
        _m("dev_starved_hi_s",
           "kafka_tpu_sched_dev_starved_seconds_total{bound=upper}",
           digits=6),
        _m("dev_starved_gaps"),
        *(_m("starved_%s%s_s" % (hi, p),
             "kafka_tpu_sched_starved_seconds_total{bound=%s,phase=%s}"
             % (bound, p),
             "The device's starved seconds by what the engine thread was "
             "doing meanwhile, each bound over its own interval (the upper "
             "one also holds the phase in which the completion went "
             "unseen)." if (p, hi) == (SCHED_PHASES[0], "") else "",
             digits=6)
          for p in SCHED_PHASES
          for hi, bound in (("", "lower"), ("hi_", "upper"))),
    ),
    # Process-wide sections server/app.py lays over the snapshot: the boot
    # by stage (a PhaseClock on the booting thread), what building the
    # snapshots themselves costs, sandbox supervision, tracing, the
    # autoscaler's control loop (runtime/autoscaler.py, when
    # KAFKA_TPU_AUTOSCALE runs one) and the compile observatory
    # (runtime/compile_log.py).
    *_section(
        "boot",
        *(_m(stage + "_s", "kafka_tpu_boot_stage_seconds{stage=%s}" % stage,
             "Seconds of the boot by stage (tracing.BOOT_STAGES), from "
             "create_app's first line to its return."
             if stage == BOOT_STAGES[0] else "", merge=None, digits=3)
          for stage in BOOT_STAGES),
        # seconds the program store (runtime/program_store.py) spent
        # reading and loading step programs: what a warm boot pays in
        # place of tracing, lowering and looking them up
        _m("store_load_s", merge=None, digits=3),
    ),
    *_section(
        "metrics",
        # /metrics snapshots are built on the event loop's thread and hold
        # the GIL while they are
        _m("snapshot_s", merge=None, digits=6),
        _m("snapshots", merge=None),
    ),
    *_section(
        "sandbox",
        *(_m(k, "kafka_tpu_sandbox_total{event=%s}" % k,
             "Sandbox subprocess supervision events."
             if k == "crashes" else "", merge=None)
          for k in ("crashes", "restarts", "crash_loops", "reaped")),
    ),
    *_section(
        "tracing",
        _m("traces", "kafka_tpu_traces_total",
           "Traces started since boot.", merge=None),
        _m("stitched_spans", "kafka_tpu_stitched_spans_total",
           "Cross-process spans stitched into parent traces.",
           merge=None),
        _m("slow", merge=None),
    ),
    *_section(
        "autoscaler",
        *(_m(key, "kafka_tpu_autoscaler_events_total{event=%s}" % event,
             "Autoscaler control-loop events by kind."
             if event == "poll" else "", merge=None)
          for key, event in (
              ("autoscaler_polls", "poll"),
              ("autoscaler_scale_outs", "scale_out"),
              ("autoscaler_scale_ins", "scale_in"),
              ("autoscaler_resize_failures", "resize_failure"),
              ("autoscaler_degrades", "degrade"),
              ("autoscaler_recovers", "recover"),
              ("autoscaler_vetoes", "veto"),
              # replicas drained to the object store before scale-in
              ("autoscaler_drains", "drain"))),
        _m("autoscaler_ladder_level", "kafka_tpu_autoscaler_ladder_level",
           "Current degradation-ladder rung (0 = normal).", merge=None),
        _m("autoscaler_dp", "kafka_tpu_autoscaler_dp",
           "dp at the controller's last signal poll.", merge=None),
    ),
    *_section(
        "compiles",
        _m("compiles_total", merge=None),
        _m("compile_seconds_total", "kafka_tpu_compile_seconds_total",
           "Wall-clock seconds spent in XLA compilation.", merge=None),
        _m("compile_storm_active", "kafka_tpu_compile_storm_active",
           "Compile storm condition currently held (recompiles under "
           "live traffic).", merge=None),
        _m("compile_storms_total", "kafka_tpu_compile_storms_total",
           "Compile storm episodes entered.", merge=None),
        _m("by_cache", "kafka_tpu_compiles_total",
           "XLA compilations observed, by persistent-cache disposition "
           "and engine phase.", kind=DETAIL, merge=None, each="cache"),
        _m("by_phase", "kafka_tpu_compiles_total", kind=DETAIL,
           merge=None, each="phase"),
        # what a program costs at EVERY boot whatever the cache holds:
        # jax's jaxpr_trace_duration and jaxpr_to_mlir_module_duration
        _m("trace_seconds_total", merge=None),
        _m("lower_seconds_total", merge=None),
        _m("trace_seconds_by_phase", kind=DETAIL, merge=None),
        _m("lower_seconds_by_phase", kind=DETAIL, merge=None),
        # the program store beside the persistent cache: step-program
        # variants loaded with no trace, compiled and written, and handed
        # back to the jit by a call that did not match the stored one
        *(_m(key, "kafka_tpu_program_store_total{event=%s}" % event,
             "Step-program variants by what the program store did with "
             "them." if event == "hit" else "", merge=None)
          for key, event in (("store_hits", "hit"),
                             ("store_misses", "miss"),
                             ("store_fallbacks", "fallback"))),
    ),
)


def section_entries(section: str) -> List[Metric]:
    """The table's entries for one section, in table order.  Read from
    METRICS at each call (a few hundred rows, off the scheduler thread)."""
    return [m for m in METRICS if m.section == section]


def _registry(section: str, per_kind: bool = False) -> Tuple[str, ...]:
    return tuple(m.key for m in METRICS
                 if m.section == section and m.kind != DETAIL
                 and m.per_kind == per_kind)


# The sections' key sets, as views of the table (tests hold each
# subsystem's snapshot() to its view).
SPECULATION_METRIC_KEYS = _registry("speculation")
CONSTRAINED_METRIC_KEYS = _registry("constrained")
KV_TIER_METRIC_KEYS = _registry("kv_tier")
OBJECT_TIER_METRIC_KEYS = _registry("object_tier")
DISAGG_METRIC_KEYS = _registry("disagg")
SLO_METRIC_KEYS = _registry("slo")
UTILIZATION_METRIC_KEYS = _registry("utilization", per_kind=True)
FLIGHT_METRIC_KEYS = _registry("flight")
ANOMALY_METRIC_KEYS = _registry("anomalies")
AUTOSCALER_METRIC_KEYS = _registry("autoscaler")
COMPILE_METRIC_KEYS = _registry("compiles")
MEMORY_METRIC_KEYS = _registry("memory")
AGENT_METRIC_KEYS = _registry("agent")
STATE_METRIC_KEYS = _registry("state")
SCHED_METRIC_KEYS = _registry("sched")
BOOT_METRIC_KEYS = _registry("boot")
# every EngineMetrics field is exported under a key (field -> snapshot
# path) or is the accumulator behind a derived one
# (tests/test_prometheus.py holds dataclasses.fields(EngineMetrics) to the
# two, both directions)
ENGINE_METRIC_EXPORTS = {m.field: (m.section, m.key)
                         for m in METRICS if m.field}
ENGINE_METRIC_EXCLUDED = {
    m.accumulator: f"exported derived: {m.section}.{m.key}"
    for m in METRICS if m.accumulator
}


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        return default


# module-level SLO target overrides (server/config.py ServingConfig wires
# them through configure_slo at app build; None = read the env / default).
_slo_override: Dict[str, Optional[float]] = {"ttft_ms": None, "tpot_ms": None}


def configure_slo(ttft_ms: Optional[float] = None,
                  tpot_ms: Optional[float] = None) -> None:
    """Set deployment-wide SLO targets (ServingConfig -> every
    EngineMetrics built afterwards, including warmup resets).  None
    CLEARS that override back to the env/default source — each app build
    re-establishes its own targets, so a second deployment in the same
    process can never inherit the first one's (create_app always calls
    this).  0 disables a target."""
    _slo_override["ttft_ms"] = (
        float(ttft_ms) if ttft_ms is not None else None
    )
    _slo_override["tpot_ms"] = (
        float(tpot_ms) if tpot_ms is not None else None
    )


def slo_targets() -> Tuple[float, float]:
    """(ttft_ms, tpot_ms) targets; 0 = that target disabled."""
    ttft = _slo_override["ttft_ms"]
    if ttft is None:
        ttft = _env_float(SLO_TTFT_ENV, 200.0)
    tpot = _slo_override["tpot_ms"]
    if tpot is None:
        tpot = _env_float(SLO_TPOT_ENV, 0.0)
    return max(0.0, ttft), max(0.0, tpot)


# (id(tokenizer), schema key) of the grammar compiles queued or running on
# the background worker of llm/constrained.py, which adds and discards
# under its own lock: one queue a process, whatever the number of engines.
GRAMMAR_COMPILES_PENDING: set = set()


def compile_pending() -> int:
    """Gauge: constrained.constrained_compile_pending in /metrics."""
    return len(GRAMMAR_COMPILES_PENDING)


def telemetry_enabled() -> bool:
    return os.environ.get(TELEMETRY_ENV, "1") not in ("0", "false", "False")


# ---------------------------------------------------------------------------
# streaming histograms
# ---------------------------------------------------------------------------


def _log_bounds(lo: float, hi: float, ratio: float = math.sqrt(2.0)
                ) -> Tuple[float, ...]:
    """Fixed log-spaced bucket upper bounds from `lo` until >= `hi`."""
    out = [lo]
    while out[-1] < hi:
        out.append(out[-1] * ratio)
    return tuple(round(b, 6) for b in out)


# One latency layout for every *_ms histogram: sqrt(2)-spaced from 0.25ms
# to ~74s.  Shared bounds are what make replicas mergeable and let one
# PromQL recording rule serve every latency family.
LATENCY_MS_BOUNDS = _log_bounds(0.25, 65_536.0)
# Emission burst sizes (token counts): powers of two to one pipeline-full.
BURST_TOKEN_BOUNDS = _log_bounds(1.0, 1024.0, ratio=2.0)


class StreamingHistogram:
    """Fixed-bucket streaming histogram: single-writer record(), mergeable
    across replicas, monotone cumulative counts by construction.

    `bounds` are inclusive upper bounds (Prometheus ``le`` semantics); one
    implicit +Inf bucket catches overflow.  Reads from other threads are
    torn-tolerant: a snapshot may be one sample stale, so `count`/`sum`
    are recomputed from the copied bucket counts at export time to keep
    the exposition's sum/count/bucket invariants internally consistent.
    """

    __slots__ = ("bounds", "counts", "sum", "max")

    def __init__(self, bounds: Sequence[float] = LATENCY_MS_BOUNDS):
        self.bounds: Tuple[float, ...] = tuple(bounds)
        self.counts: List[int] = [0] * (len(self.bounds) + 1)
        self.sum: float = 0.0
        self.max: float = 0.0

    def record(self, value: float) -> None:
        self.counts[bisect.bisect_left(self.bounds, value)] += 1
        self.sum += value
        if value > self.max:
            self.max = value

    @property
    def count(self) -> int:
        return sum(self.counts)

    def merge_from(self, other: "StreamingHistogram") -> None:
        if other.bounds != self.bounds:
            raise ValueError("histogram bounds differ; cannot merge")
        snap = list(other.counts)
        for i, c in enumerate(snap):
            self.counts[i] += c
        self.sum += other.sum
        self.max = max(self.max, other.max)

    @classmethod
    def merged(cls, hists: Sequence["StreamingHistogram"]
               ) -> "StreamingHistogram":
        out = cls(hists[0].bounds if hists else LATENCY_MS_BOUNDS)
        for h in hists:
            out.merge_from(h)
        return out

    @classmethod
    def from_snapshot(cls, snap: Dict[str, Any]) -> "StreamingHistogram":
        h = cls(tuple(snap["le"]))
        counts = list(snap["counts"])
        h.counts = counts + [0] * (len(h.bounds) + 1 - len(counts))
        h.sum = float(snap.get("sum", 0.0))
        h.max = float(snap.get("max", 0.0))
        return h

    def quantile(self, q: float,
                 counts: Optional[List[int]] = None) -> float:
        """Approximate q-quantile by linear interpolation inside the
        enclosing bucket (histogram_quantile semantics: upper-bound-biased
        within one bucket, exact at bucket boundaries).  The +Inf bucket
        reports the largest recorded value (tracked exactly)."""
        counts = list(self.counts) if counts is None else counts
        total = sum(counts)
        if total == 0:
            return 0.0
        rank = max(1, math.ceil(q * total))
        cum = 0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            if cum + c >= rank:
                if i >= len(self.bounds):
                    return self.max or self.bounds[-1]
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i]
                return lo + (hi - lo) * (rank - cum) / c
            cum += c
        return self.max or self.bounds[-1]

    def quantiles(self, pts=(50, 90, 99)) -> Dict[str, float]:
        counts = list(self.counts)  # one torn-tolerant copy for all points
        return {f"p{p}": round(self.quantile(p / 100.0, counts), 2)
                for p in pts}

    def snapshot(self) -> Dict[str, Any]:
        """Wire form: per-bucket (NON-cumulative) counts + bounds; the
        exposition layer computes the cumulative series (monotone by
        construction from non-negative addends)."""
        counts = list(self.counts)
        return {
            "le": list(self.bounds),
            "counts": counts,
            "count": sum(counts),
            "sum": round(self.sum, 3),
            "max": round(self.max, 3),
        }


def _percentiles(samples: List[float], pts=(50, 90, 99)) -> Dict[str, float]:
    """Nearest-rank percentiles over a raw sample list.  The engine-side
    distributions moved to StreamingHistogram (ISSUE 10); this survives for
    CLIENT-side sample lists (bench.py latency arrays)."""
    if not samples:
        return {f"p{p}": 0.0 for p in pts}
    s = sorted(samples)
    out = {}
    for p in pts:
        # nearest-rank: smallest value with at least p% of samples <= it
        idx = min(len(s) - 1, max(0, -(-p * len(s) // 100) - 1))
        out[f"p{p}"] = s[idx]
    return out


# ---------------------------------------------------------------------------
# sliding windows (SLO attainment, recent utilization, queue trend)
# ---------------------------------------------------------------------------


class _WindowRing:
    """Fixed-size ring of coarse time buckets accumulating N float fields —
    the storage behind 1m/5m sliding-window gauges.  add() assumes one
    writer at a time: either a genuinely single-writer owner (the
    utilization rings, engine thread only) or writers serialized by the
    owner's lock (the SLO ring under EngineMetrics._slo_lock — finalize
    runs on the engine thread but gate 429s arrive on the event loop).
    Cross-thread sums() copies bucket rows torn-tolerantly (worst case
    one bucket is one sample stale)."""

    __slots__ = ("width_s", "slots", "_ids", "_vals", "_n")

    def __init__(self, nfields: int, span_s: float = 300.0,
                 width_s: float = 2.0):
        self.width_s = width_s
        self.slots = int(span_s / width_s)
        self._n = nfields
        self._ids = [-1] * self.slots
        self._vals = [[0.0] * nfields for _ in range(self.slots)]

    def add(self, values: Sequence[float],
            now: Optional[float] = None) -> None:
        b = int((time.monotonic() if now is None else now) / self.width_s)
        i = b % self.slots
        if self._ids[i] != b:
            # retire the stale row atomically-enough: fresh list first
            self._vals[i] = [0.0] * self._n
            self._ids[i] = b
        row = self._vals[i]
        for j, v in enumerate(values):
            row[j] += v

    def sums(self, window_s: float,
             now: Optional[float] = None) -> List[float]:
        now_b = int((time.monotonic() if now is None else now)
                    / self.width_s)
        lo = now_b - int(window_s / self.width_s)
        out = [0.0] * self._n
        for i in range(self.slots):
            if lo < self._ids[i] <= now_b:
                row = self._vals[i]
                for j in range(self._n):
                    out[j] += row[j]
        return out


def build_slo_section(
    *,
    ttft_target_ms: float,
    tpot_target_ms: float,
    met: int,
    missed: int,
    ttft_violations: int,
    tpot_violations: int,
    goodput_tokens: int,
    generated_tokens: int,
    uptime_s: float,
    window_1m: Dict[str, float],
    window_5m: Dict[str, float],
) -> Dict[str, object]:
    """The SLO/goodput section (SLO_METRIC_KEYS) from raw counters — the
    ONE place the attainment/goodput ratio math lives.  Both a single
    engine's slo_snapshot and the DP aggregate (which sums per-replica
    counters and window sums first) render through this, so the two
    expositions cannot drift.  Window attainments recompute from the
    summed raw windows, never from averaged per-replica ratios (an idle
    replica must not weigh like a loaded one)."""

    def attain(m, x):
        return round(m / (m + x), 4) if (m + x) else 1.0

    return {
        "slo_ttft_target_ms": ttft_target_ms,
        "slo_tpot_target_ms": tpot_target_ms,
        "slo_met_requests": met,
        "slo_missed_requests": missed,
        "slo_ttft_violations": ttft_violations,
        "slo_tpot_violations": tpot_violations,
        "slo_attainment": attain(met, missed),
        "slo_attainment_1m": attain(window_1m["met"],
                                    window_1m["missed"]),
        "slo_attainment_5m": attain(window_5m["met"],
                                    window_5m["missed"]),
        "goodput_tokens": goodput_tokens,
        "goodput_tok_s": round(goodput_tokens / uptime_s, 2)
        if uptime_s > 0 else 0.0,
        "goodput_tok_s_1m": round(window_1m["goodput_tokens"] / 60.0, 2),
        "goodput_frac": round(goodput_tokens / generated_tokens, 4)
        if generated_tokens else 0.0,
        "window_1m": window_1m,
        "window_5m": window_5m,
    }


def waste_frac(generated: int, wasted: int) -> float:
    """Share of dispatched decode tokens the fetch pipeline threw away."""
    total = generated + wasted
    return round(wasted / total, 4) if total else 0.0


def speculation_rates(accepted: int, rejected: int,
                      verify_steps: int) -> Dict[str, float]:
    """Acceptance over drained rounds, and accepted candidates per verify
    step (the amortization factor the weight stream gains)."""
    drained = accepted + rejected
    return {
        "speculation_acceptance_rate":
            round(accepted / drained, 4) if drained else 0.0,
        "speculation_accepted_per_step":
            round(accepted / verify_steps, 3) if verify_steps else 0.0,
    }


def batch_occupancy(busy_slots: float, steps: int) -> float:
    return round(busy_slots / steps, 3) if steps else 0.0


def utilization_ratios(flops: float, hbm_bytes: float, busy_s: float,
                       window: Sequence[float], peak_flops: Optional[float],
                       peak_hbm_bps: Optional[float]) -> Dict[str, float]:
    """MFU / HBM-bandwidth share since boot and over the 60 s `window`
    (flops, bytes, busy seconds); 0.0 without busy time or a roofline.
    Busy time summed over replicas is replica-seconds, so the ratio of
    sums is per chip."""

    def ratio(amount, secs, peak):
        return round(amount / (secs * peak), 4) if secs > 0 and peak else 0.0

    wf, wb, ws = window
    return {
        "mfu": ratio(flops, busy_s, peak_flops),
        "hbm_bw_util": ratio(hbm_bytes, busy_s, peak_hbm_bps),
        "mfu_1m": ratio(wf, ws, peak_flops),
        "hbm_bw_util_1m": ratio(wb, ws, peak_hbm_bps),
    }


def model_skew(measured_s: float, modeled_s: float) -> float:
    """>1 = the device runs slower than the cost model thinks, so the
    modeled MFU / HBM-BW figures read HIGH by this factor."""
    return round(measured_s / modeled_s, 3) if modeled_s > 0 else 0.0


def quantile_sections(hists: Dict[str, "StreamingHistogram"]
                      ) -> Dict[str, Any]:
    """The JSON's quantile sections, derived from the histograms (the
    math PromQL's histogram_quantile does on the _bucket series)."""
    return {
        "ttft_ms": hists["ttft_ms"].quantiles(),
        "ttft_breakdown_ms": {
            "queue_wait": hists["ttft_queue_ms"].quantiles(),
            "prefill": hists["ttft_prefill_ms"].quantiles(),
            "first_fetch": hists["ttft_fetch_ms"].quantiles(),
        },
        "tpot_ms": hists["tpot_ms"].quantiles(),
        "emission": {
            "burst_tokens": hists["burst_tokens"].quantiles(),
            "burst_gap_ms": hists["burst_gap_ms"].quantiles(),
        },
    }


def _merged_value(m: Metric, values: List[Any]) -> Any:
    if m.merge == FIRST:
        return dict(values[0]) if isinstance(values[0], dict) else values[0]
    if m.merge == MAX:
        return max(values)
    if m.merge == MIN:
        return min(values)
    if m.merge == CONCAT:
        return [x for v in values for x in v]

    def rounded(x):
        return x if m.digits is None else round(x, m.digits)

    if isinstance(values[0], dict):
        return {k: rounded(sum(v[k] for v in values)) for k in values[0]}
    return rounded(sum(values))


def _merge_rows(entries: List[Metric],
                rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    out = {}
    for m in entries:
        values = [r[m.key] for r in rows if m.key in r]
        if values and m.merge not in (None, DERIVED):
            out[m.key] = _merged_value(m, values)
    return out


def merge_snapshots(snaps: List[Dict[str, Any]],
                    decode_busy_slots: Optional[float] = None
                    ) -> Dict[str, Any]:
    """Replica snapshots -> the dp aggregate: each table entry by its
    `merge`, histograms bucket by bucket, then every ratio recomputed
    from the merged counters (never averaged: an idle replica must not
    weigh like a loaded one).  A section is merged over the replicas that
    carry it.  `decode_busy_slots` is the one accumulator the JSON does
    not carry (summed over the replicas' EngineMetrics); without it the
    occupancies are weighted by their steps."""
    out: Dict[str, Any] = {}
    for section in dict.fromkeys(m.section for m in METRICS):
        entries = section_entries(section)
        rows = snaps if not section else [
            s[section] for s in snaps if section in s]
        merged = _merge_rows([m for m in entries if not m.per_kind], rows)
        per_kind = [m for m in entries if m.per_kind]
        for kind in UTILIZATION_KINDS if per_kind else ():
            merged[kind] = _merge_rows(
                per_kind, [r[kind] for r in rows if kind in r])
        if not section:
            out.update(merged)
        elif merged:
            out[section] = merged
    # distributions merge exactly: same fixed bounds, bucket counts add
    # (the thread's iteration histograms ride on the replicas that carry a
    # thread's account: dp_router leaves one copy a thread)
    names = HISTOGRAM_NAMES + tuple(
        n for n in SCHED_ITER_HISTOGRAMS
        if any(n in s["histograms"] for s in snaps))
    hists = {
        name: StreamingHistogram.merged([
            StreamingHistogram.from_snapshot(s["histograms"][name])
            for s in snaps if name in s["histograms"]])
        for name in names
    }
    out["histograms"] = {name: h.snapshot() for name, h in hists.items()}
    out.update(quantile_sections(hists))
    if "sched" in out:
        out["sched_iter_ms"] = sched_iter_quantiles(out["histograms"])

    tokens, decode = out["tokens"], out["decode"]
    tokens["fetch_pipeline_waste_frac"] = waste_frac(
        tokens["generated"], tokens["fetch_pipeline_wasted"])
    out["constrained_roundtrips"] = \
        out["constrained"]["constrained_roundtrips"]
    spec = out["speculation"]
    spec.update(speculation_rates(spec["speculation_accepted_tokens"],
                                  spec["speculation_rejected_tokens"],
                                  spec["speculation_verify_steps"]))
    if decode_busy_slots is None:
        decode_busy_slots = sum(
            s["decode"]["batch_occupancy"] * s["decode"]["steps"]
            for s in snaps)
    decode["batch_occupancy"] = batch_occupancy(decode_busy_slots,
                                                decode["steps"])
    slo = out["slo"]
    out["slo"] = build_slo_section(
        ttft_target_ms=slo["slo_ttft_target_ms"],
        tpot_target_ms=slo["slo_tpot_target_ms"],
        met=slo["slo_met_requests"],
        missed=slo["slo_missed_requests"],
        ttft_violations=slo["slo_ttft_violations"],
        tpot_violations=slo["slo_tpot_violations"],
        goodput_tokens=slo["goodput_tokens"],
        generated_tokens=tokens["generated"],
        uptime_s=out["uptime_s"],
        window_1m=slo["window_1m"],
        window_5m=slo["window_5m"],
    )
    util = out["utilization"]
    peak_flops = (util["peak_tflops"] or 0) * 1e12
    peak_hbm_bps = (util["peak_hbm_gbps"] or 0) * 1e9
    for kind in UTILIZATION_KINDS:
        sec, w = util[kind], util[kind]["window_1m"]
        sec.update(utilization_ratios(
            sec["flops"], sec["hbm_bytes"], sec["busy_s"],
            (w["flops"], w["hbm_bytes"], w["busy_s"]),
            peak_flops, peak_hbm_bps))
        sec["model_skew"] = model_skew(sec["measured_busy_s"],
                                       sec["modeled_busy_s"])
    # each active anomaly carries the replica it fires on
    active = [{**entry, "replica": i} for i, s in enumerate(snaps)
              for entry in s["anomalies"]["active"]]
    out["anomalies"].update(anomalies_active=len(active), active=active)
    return out


@dataclasses.dataclass
class _UtilCounters:
    """Per-dispatch-kind utilization accumulators (single-writer).

    busy_s is the MODELED-attribution wall time (inter-dispatch gaps);
    measured_s / modeled_s pair the flight recorder's fetch-maturation
    measurement with the planner roofline time for the SAME dispatches,
    so measured_s / modeled_s is the model-skew calibration gauge."""

    dispatches: int = 0
    tokens: int = 0
    flops: float = 0.0
    hbm_bytes: float = 0.0
    busy_s: float = 0.0
    measured_dispatches: int = 0
    measured_s: float = 0.0
    modeled_s: float = 0.0


@dataclasses.dataclass
class ReplicaSupervisorMetrics:
    """Counters owned by the DP replica supervisor (runtime/dp_router.py).

    Single-writer like EngineMetrics: the engine/worker thread that drives
    DataParallelEngines.step() is the only mutator; snapshot() is read
    from serving threads and is torn-tolerant."""

    quarantines: int = 0  # circuit-breaker trips (healthy/probation -> out)
    readmits: int = 0  # probation -> healthy promotions (warm re-admit)
    waiting_migrated: int = 0  # queued requests moved off a sick replica
    affinity_resteered: int = 0  # prefix_key pins moved to a new replica
    rebuilds: int = 0  # topology rebuilds (dp resize / replica loss)
    # quarantine escalation (ISSUE 12 satellite, PR 2 follow-up): single
    # replicas whose engine was rebuilt after repeated quarantine trips
    # (KAFKA_TPU_REPLICA_REBUILD_THRESHOLD) instead of re-admitted forever
    replica_rebuilds: int = 0

    def snapshot(self) -> Dict[str, int]:
        return {
            "quarantines": self.quarantines,
            "readmits": self.readmits,
            "waiting_migrated": self.waiting_migrated,
            "affinity_resteered": self.affinity_resteered,
            "rebuilds": self.rebuilds,
            "replica_rebuilds": self.replica_rebuilds,
        }


@dataclasses.dataclass
class DisaggMetrics:
    """Counters owned by the DP router's disaggregated prefill/decode
    path (runtime/dp_router.py, ISSUE 12).  Single-writer like the
    supervisor metrics: the worker thread driving step() is the only
    mutator; snapshot() reads are torn-tolerant.  One instance per
    router, so the DP aggregate reports these once, unsummed."""

    shipped_runs: int = 0
    shipped_pages: int = 0
    shipped_bytes: int = 0
    ship_failures: int = 0
    ship_skips: int = 0
    handoffs: int = 0
    prefill_in_place: int = 0
    # per-transport run counts (ISSUE 19): host + device == shipped_runs
    ship_host_runs: int = 0
    ship_device_runs: int = 0
    ship_ms: "StreamingHistogram" = dataclasses.field(
        default_factory=lambda: StreamingHistogram(LATENCY_MS_BOUNDS)
    )

    def record_ship(self, pages: int, nbytes: int, dur_s: float,
                    transport: str = "host") -> None:
        self.shipped_runs += 1
        self.shipped_pages += pages
        self.shipped_bytes += nbytes
        if transport == "device":
            self.ship_device_runs += 1
        else:
            self.ship_host_runs += 1
        self.ship_ms.record(dur_s * 1e3)

    def snapshot(self) -> Dict[str, Any]:
        """The aggregate snapshot's "disagg" section (DISAGG_METRIC_KEYS
        plus the ship-latency histogram)."""
        from .kv_tier import ship_staging_peak

        return {
            "disagg_shipped_runs": self.shipped_runs,
            "disagg_shipped_pages": self.shipped_pages,
            "disagg_shipped_bytes": self.shipped_bytes,
            "disagg_ship_failures": self.ship_failures,
            "disagg_ship_skips": self.ship_skips,
            "disagg_handoffs": self.handoffs,
            "disagg_prefill_in_place": self.prefill_in_place,
            "disagg_ship_host_runs": self.ship_host_runs,
            "disagg_ship_device_runs": self.ship_device_runs,
            # process-wide staging high-water mark (kv_tier accounting),
            # re-armed at the current level on every read
            "disagg_ship_staging_bytes": ship_staging_peak(reset=True),
            "ship_ms": self.ship_ms.snapshot(),
        }


# histogram attribute name -> JSON/exposition name (the "histograms"
# snapshot section and server/prometheus.py's family map key off these)
HISTOGRAM_NAMES = (
    "ttft_ms",
    "tpot_ms",
    "ttft_queue_ms",
    "ttft_prefill_ms",
    "ttft_fetch_ms",
    "ttft_dev_wait_ms",
    "ttft_dev_exec_ms",
    "ttft_hold_ms",
    "ttft_emit_ms",
    "burst_tokens",
    "burst_gap_ms",
)
# one `_run` iteration of the engine thread by what it dispatched: the
# thread's clock keeps them (runtime/phase_clock.SchedClock), not
# EngineMetrics, and a snapshot taken off an engine carries them beside
# the others
SCHED_ITER_HISTOGRAMS = tuple(
    f"sched_iter_{c}_ms" for c in SCHED_ITER_CLASSES)


def sched_iter_quantiles(hists: Dict[str, Any]) -> Dict[str, Any]:
    """The JSON's `sched_iter_ms` section: quantiles by class, from
    histogram snapshots."""
    return {c: StreamingHistogram.from_snapshot(hists[name]).quantiles()
            for c, name in zip(SCHED_ITER_CLASSES, SCHED_ITER_HISTOGRAMS)
            if name in hists}


def ttft_fetch_stages(first_dispatch: float, dev_start: Optional[float],
                      ready: Optional[float], pop: Optional[float],
                      first_token: float
                      ) -> Tuple[float, float, float, float]:
    """Tile first_dispatch -> first_token (the ttft_fetch_ms phase) into
    (dev_wait, dev_exec, hold, emit) seconds at the three marks of the
    entry that carried the first token:

      dev_wait  -> the device starts the last prefill chunk: work the host
                   had already queued ahead of it
      dev_exec  -> its completion, as observed (a poll's is_ready, or the
                   return of the read where the entry was popped unseen)
      hold      -> the entry is popped: FIFO order, the age and landed
                   rules, a scheduler thread blocked in another read
      emit      -> the first token on the host: the read itself and the
                   rows processed before this request's

    Each mark is held between its predecessor and first_token, so the
    four are never negative and add up to first_token - first_dispatch
    whatever the marks: one the engine never stamped collapses onto its
    predecessor, and a pop that came before the completion was seen (the
    completion is then the read's return) leaves hold empty."""
    out, prev = [], first_dispatch
    for mark in (dev_start, ready, pop):
        mark = prev if mark is None else min(max(mark, prev), first_token)
        out.append(mark - prev)
        prev = mark
    return (*out, first_token - prev)


@dataclasses.dataclass
class EngineMetrics:
    """Counters owned by the engine; distributions are streaming
    histograms (constant memory, replica-mergeable — ISSUE 10)."""

    requests_submitted: int = 0
    requests_finished: int = 0
    requests_cancelled: int = 0
    requests_preempted: int = 0
    # lifecycle hardening counters: deadline expiries, admission-gate
    # rejections (HTTP 429), and engine-failure terminations
    requests_timeout: int = 0
    requests_rejected: int = 0
    requests_failed: int = 0
    # waiting-queue gauge, recorded once per scheduler iteration.
    # queue_depth_peak is PEAK-SINCE-LAST-SNAPSHOT: every snapshot() reads
    # it and re-arms it at the current depth, so each scrape interval
    # reports its own high-water mark instead of a process-lifetime
    # maximum that stops carrying information after the first burst.
    queue_depth: int = 0
    queue_depth_peak: int = 0
    prompt_tokens: int = 0
    generated_tokens: int = 0
    decode_steps: int = 0
    decode_busy_slots: int = 0  # sum over steps -> occupancy = /steps/B
    # Tokens dispatched for a lane whose request was already finished when
    # the fetch matured (stop token discovered in flight, or a cancel) —
    # the cost of the pipelined/fused dispatch running ahead of drain.
    # These occupied batch slots; wasted/(generated+wasted) is the
    # throughput tax.  RENAMED from speculative_wasted_tokens (PR 5): this
    # is FETCH-PIPELINE waste, not speculative-decoding waste — the old
    # /metrics JSON keys survive one release as deprecated aliases.
    fetch_pipeline_wasted_tokens: int = 0
    # Real speculative decoding (draft-free n-gram proposals + batched
    # verify, runtime/speculative.py + engine verify step).  proposed
    # counts candidate tokens at dispatch; accepted/rejected reconcile at
    # drain (a discarded entry counts all its candidates rejected), so
    # proposed == accepted + rejected + in-flight and every counter stays
    # monotone across preemption/rollback.
    speculation_proposed_tokens: int = 0
    speculation_accepted_tokens: int = 0
    speculation_rejected_tokens: int = 0
    speculation_verify_steps: int = 0  # verify dispatches (1 per step)
    # constrained decoding (CONSTRAINED_METRIC_KEYS): awaited host
    # round trips, over-tight mask degrades, and device-FSM tokens
    constrained_roundtrips: int = 0
    constrained_mask_overtight: int = 0
    constrained_ondevice_tokens: int = 0
    # SLO accounting (SLO_METRIC_KEYS): per-request MET/MISSED verdicts
    # at finalize, violation attribution, and goodput — tokens generated
    # by requests that met their SLO (cancelled requests are excluded
    # from attainment entirely: the client walked away, the server did
    # not miss).
    slo_met_requests: int = 0
    slo_missed_requests: int = 0
    slo_ttft_violations: int = 0
    slo_tpot_violations: int = 0
    goodput_tokens: int = 0
    # Flight-recorder anomaly detectors (ISSUE 11, ANOMALY_METRIC_KEYS):
    # edge-triggered firing counts, incremented by the recorder's
    # step-cadence checks (runtime/flight_recorder.py).  Zero forever
    # when the recorder is off (KAFKA_TPU_FLIGHT_RING=0).
    anomaly_queue_stall: int = 0
    anomaly_fetch_starvation: int = 0
    anomaly_mfu_collapse: int = 0
    anomaly_prefill_convoy: int = 0
    # device-truth detectors (ISSUE 18): compile_storm edge-counts the
    # observatory's level-held storm condition; hbm_pressure fires when
    # MEASURED headroom drops under the watermark
    anomaly_compile_storm: int = 0
    anomaly_hbm_pressure: int = 0

    def __post_init__(self) -> None:
        self.enabled = telemetry_enabled()
        self.slo_ttft_ms, self.slo_tpot_ms = slo_targets()
        # latency/size distributions: streaming histograms on the shared
        # log-spaced layouts (mergeable across replicas)
        self.ttft_ms = StreamingHistogram(LATENCY_MS_BOUNDS)
        self.tpot_ms = StreamingHistogram(LATENCY_MS_BOUNDS)
        # TTFT decomposition (queue wait / prefill / fetch+emit) — the
        # three phases whose confounding made r4's oversubscribed-TTFT
        # numbers one unexplainable figure (VERDICT r4 weak #3)
        self.ttft_queue_ms = StreamingHistogram(LATENCY_MS_BOUNDS)
        self.ttft_prefill_ms = StreamingHistogram(LATENCY_MS_BOUNDS)
        self.ttft_fetch_ms = StreamingHistogram(LATENCY_MS_BOUNDS)
        # the fetch phase tiled into four stages (ttft_fetch_stages):
        # per request they sum to its ttft_fetch_ms sample, so the four
        # means add up to that histogram's mean
        self.ttft_dev_wait_ms = StreamingHistogram(LATENCY_MS_BOUNDS)
        self.ttft_dev_exec_ms = StreamingHistogram(LATENCY_MS_BOUNDS)
        self.ttft_hold_ms = StreamingHistogram(LATENCY_MS_BOUNDS)
        self.ttft_emit_ms = StreamingHistogram(LATENCY_MS_BOUNDS)
        # token-emission cadence as the client sees it: how many tokens
        # arrive together when the fetch pipeline pops (burst size) and how
        # far apart those arrivals are (gap) — the honest view of stream
        # smoothness that step-interval TPOT cannot give under pipelining
        self.burst_tokens = StreamingHistogram(BURST_TOKEN_BOUNDS)
        self.burst_gap_ms = StreamingHistogram(LATENCY_MS_BOUNDS)
        self._last_burst_t: Optional[float] = None
        self._last_step_t: Optional[float] = None
        self._last_step_steps: int = 1
        self._tpot_ewma_ms: Optional[float] = None
        # SLO sliding windows: (met, missed, goodput_tokens) per 2s bucket.
        # Unlike every other recorder, SLO accounting has TWO writers —
        # the engine thread (request finalize) and the serving event loop
        # (gate-level 429s via record_rejected) — so the counter+window
        # updates take a lock.  Finalize cadence (per request, not per
        # dispatch): an uncontended lock here is noise, and a lost
        # "missed" increment during an overload burst is exactly the
        # blind spot the attainment gauges exist to close.
        self._slo_lock = threading.Lock()
        self._slo_window = _WindowRing(3)
        # queue-depth time series for the trend gauge: (t, depth) sampled
        # at most once per second, bounded — NOT a percentile source (the
        # sample-deque percentile paths are gone, ISSUE 10)
        self._queue_trail: List[Tuple[float, int]] = []
        self._queue_trail_cap = 300
        # utilization estimator: per-kind accumulators + 60s windows,
        # attributing each inter-dispatch gap to the PREVIOUS dispatch
        # (back-to-back enqueues overlap that dispatch's device execution
        # — the TPOT assumption applied to flops/bytes)
        self.util: Dict[str, _UtilCounters] = {
            k: _UtilCounters() for k in UTILIZATION_KINDS
        }
        self._util_window: Dict[str, _WindowRing] = {
            k: _WindowRing(3, span_s=60.0) for k in UTILIZATION_KINDS
        }
        self._last_cost: Optional[Tuple[str, float, float, int]] = None
        self._last_cost_t: Optional[float] = None
        # roofline (set by the engine from runtime/planner.device_peaks)
        self.peak_flops: Optional[float] = None
        self.peak_hbm_bps: Optional[float] = None
        self.peak_source: str = "unknown"
        self._started = time.monotonic()

    # -- engine-thread recording ----------------------------------------

    def set_roofline(self, peak_flops: Optional[float],
                     peak_hbm_bps: Optional[float], source: str) -> None:
        self.peak_flops = peak_flops
        self.peak_hbm_bps = peak_hbm_bps
        self.peak_source = source

    def record_submit(self, prompt_tokens: int) -> None:
        self.requests_submitted += 1
        self.prompt_tokens += prompt_tokens

    def record_first_token(self, latency_s: float) -> None:
        if self.enabled:
            self.ttft_ms.record(latency_s * 1e3)

    def record_ttft_breakdown(self, submit, prefill_start, first_dispatch,
                              first_token, fetch_marks=(None, None, None),
                              ) -> Optional[Tuple[float, float, float, float]]:
        """Split one request's TTFT into queue / prefill / fetch phases,
        and the fetch phase into its four stages (`fetch_marks`: the
        device start, observed completion and pop of the entry that
        carried the first token).  Returns the stages in seconds, for the
        request's spans.  Missing stamps (cancelled mid-phase, legacy
        paths) record nothing."""
        if None in (submit, prefill_start, first_dispatch, first_token):
            return None
        stages = ttft_fetch_stages(first_dispatch, *fetch_marks, first_token)
        if self.enabled:
            self.ttft_queue_ms.record((prefill_start - submit) * 1e3)
            self.ttft_prefill_ms.record(
                (first_dispatch - prefill_start) * 1e3)
            self.ttft_fetch_ms.record((first_token - first_dispatch) * 1e3)
            for hist, dur in zip(
                (self.ttft_dev_wait_ms, self.ttft_dev_exec_ms,
                 self.ttft_hold_ms, self.ttft_emit_ms), stages):
                hist.record(dur * 1e3)
        return stages

    def record_token(self) -> None:
        self.generated_tokens += 1

    def record_wasted_token(self, n: int = 1) -> None:
        self.fetch_pipeline_wasted_tokens += n

    def record_verify_dispatch(self, proposed: int) -> None:
        """One verify step dispatched with `proposed` candidate tokens."""
        self.speculation_verify_steps += 1
        self.speculation_proposed_tokens += proposed

    def record_verify_drain(self, accepted: int, rejected: int) -> None:
        """One proposing lane's verify result reconciled at drain."""
        self.speculation_accepted_tokens += accepted
        self.speculation_rejected_tokens += rejected

    def record_decode_step(self, busy_slots: int, steps: int = 1) -> None:
        """steps>1 = a fused multi-step dispatch.  The gap between this
        call and the previous one spans the PREVIOUS dispatch's tokens
        (back-to-back dispatches overlap that dispatch's device execution),
        so the TPOT sample divides by the steps recorded last time."""
        now = time.monotonic()
        if self._last_step_t is not None:
            # inter-step time while decoding == per-token latency for every
            # active stream (the definition of TPOT under continuous
            # batching); long gaps (idle engine) are not TPOT — drop them
            dt = (now - self._last_step_t) * 1e3 / self._last_step_steps
            if dt < 2_000:
                if self.enabled:
                    self.tpot_ms.record(dt)
                # recency EWMA for retry_after_estimate: the cumulative
                # histogram's median stops tracking current load after
                # hours of history, but the 429 Retry-After hint must
                # reflect the overload moment it is issued in
                self._tpot_ewma_ms = (
                    dt if self._tpot_ewma_ms is None
                    else 0.95 * self._tpot_ewma_ms + 0.05 * dt
                )
        self._last_step_t = now
        self._last_step_steps = steps
        self.decode_steps += steps
        self.decode_busy_slots += busy_slots * steps

    def record_dispatch_cost(self, kind: str, tokens: int,
                             flops: float, hbm_bytes: float) -> None:
        """One device dispatch of `kind` with modeled flop/byte cost
        (runtime/planner.DispatchCostModel, computed by the engine).  The
        inter-dispatch wall gap is attributed to the PREVIOUS dispatch,
        mirroring the TPOT convention; gaps > 2s are idle, not execution,
        and the chain restarts (mark_idle also restarts it)."""
        if not self.enabled:
            return
        now = time.monotonic()
        prev, prev_t = self._last_cost, self._last_cost_t
        if prev is not None and prev_t is not None:
            dt = now - prev_t
            if dt < 2.0:
                k, f, b, tk = prev
                u = self.util[k]
                u.dispatches += 1
                u.tokens += tk
                u.flops += f
                u.hbm_bytes += b
                u.busy_s += dt
                self._util_window[k].add((f, b, dt), now=now)
        self._last_cost = (kind, flops, hbm_bytes, tokens)
        self._last_cost_t = now

    def record_measured_dispatch(self, kind: str, modeled_s: float,
                                 measured_s: float) -> None:
        """One dispatch's MEASURED device time (fetch-maturation timing,
        runtime/flight_recorder.py) paired with its modeled roofline
        time.  Per-kind sums feed the modeled-vs-measured skew gauge
        (kafka_tpu_dispatch_model_skew) that calibrates MFU/HBM-BW."""
        if not self.enabled:
            return
        u = self.util.get(kind)
        if u is None:
            return
        u.measured_dispatches += 1
        u.measured_s += measured_s
        u.modeled_s += modeled_s

    def mark_idle(self) -> None:
        """The engine drained: the gap until the next dispatch is idle
        time, not TPOT / execution time — drop every timing baseline."""
        self._last_step_t = None
        self._last_burst_t = None
        self._last_cost = None
        self._last_cost_t = None

    def record_emit_burst(self, n_tokens: int) -> None:
        if not self.enabled:
            return
        now = time.monotonic()
        self.burst_tokens.record(float(n_tokens))
        if self._last_burst_t is not None:
            gap = (now - self._last_burst_t) * 1e3
            if gap < 2_000:
                self.burst_gap_ms.record(gap)
        self._last_burst_t = now

    def record_finish(
        self,
        reason: Optional[str],
        ttft_s: Optional[float] = None,
        tpot_s: Optional[float] = None,
        tokens: int = 0,
    ) -> Optional[bool]:
        """Terminal accounting + SLO verdict for one request.

        Returns True (met) / False (missed) / None (excluded — client
        cancel).  Callers that know the request's timing pass it; legacy
        callers without stamps still classify (a clean finish with no
        TTFT stamp under an active TTFT target counts as a violation,
        which only happens for zero-token finishes)."""
        if reason == "cancelled":
            self.requests_cancelled += 1
            return None  # client walked away: not a serving SLO event
        if reason == "timeout":
            self.requests_timeout += 1
        elif reason is not None and reason.startswith("error"):
            self.requests_failed += 1
        else:
            self.requests_finished += 1
        return self._classify_slo(reason, ttft_s, tpot_s, tokens)

    def _classify_slo(self, reason, ttft_s, tpot_s, tokens) -> bool:
        ttft_bad = tpot_bad = False
        if self.slo_ttft_ms > 0:
            ttft_bad = ttft_s is None or ttft_s * 1e3 > self.slo_ttft_ms
        if self.slo_tpot_ms > 0 and tpot_s is not None:
            tpot_bad = tpot_s * 1e3 > self.slo_tpot_ms
        hard_fail = reason == "timeout" or (
            reason is not None and reason.startswith("error")
        )
        met = not (ttft_bad or tpot_bad or hard_fail)
        if ttft_bad:
            self.slo_ttft_violations += 1
        if tpot_bad:
            self.slo_tpot_violations += 1
        # the window rings feed the attainment_1m/5m gauges the
        # autoscaler keys on — fed whenever the counters are (NOT gated
        # on the telemetry switch: an empty window reads as a perfect
        # 1.0, which must never happen while total attainment collapses)
        with self._slo_lock:
            if met:
                self.slo_met_requests += 1
                self.goodput_tokens += tokens
            else:
                self.slo_missed_requests += 1
            self._slo_window.add(
                (1.0 if met else 0.0, 0.0 if met else 1.0,
                 float(tokens) if met else 0.0)
            )
        return met

    def record_preempt(self) -> None:
        self.requests_preempted += 1

    def record_rejected(self) -> None:
        """An admission rejection (HTTP 429, gate or engine backstop).
        A rejected request IS a missed SLO: the autoscaler must see
        overload shed as attainment loss, not as a clean sheet."""
        # gate rejections arrive on the EVENT LOOP thread while the
        # engine-backstop path rejects on the worker thread — the shared
        # lock keeps ALL the rejection counters and the window bucket
        # loss-free under exactly the overload burst they exist to expose
        with self._slo_lock:
            self.requests_rejected += 1
            self.slo_missed_requests += 1
            self._slo_window.add((0.0, 1.0, 0.0))

    def record_queue_depth(self, depth: int) -> None:
        self.queue_depth = depth
        if depth > self.queue_depth_peak:
            self.queue_depth_peak = depth
        if not self.enabled:
            return
        now = time.monotonic()
        trail = self._queue_trail
        if not trail or now - trail[-1][0] >= 1.0:
            trail.append((now, depth))
            if len(trail) > self._queue_trail_cap:
                del trail[: len(trail) - self._queue_trail_cap]

    def queue_trend_per_s(self, window_s: float = 60.0) -> float:
        """Queue-depth slope over the recent window (depth units per
        second; >0 = the queue is growing — the autoscaler's scale-up
        trigger alongside low attainment)."""
        now = time.monotonic()
        trail = list(self._queue_trail)
        old = next(((t, d) for t, d in trail if t >= now - window_s), None)
        if old is None:
            return 0.0
        dt = now - old[0]
        if dt < 1.0:
            return 0.0
        return round((self.queue_depth - old[1]) / dt, 4)

    def recent_tpot_s(self) -> Optional[float]:
        """RECENT TPOT in seconds (None = no data): an EWMA over the last
        ~dozens of steps, not the cumulative histogram's lifetime median
        — retry_after_estimate's 429 hint must track the overload moment
        it is issued in, and cumulative buckets never age out.  Safe to
        call from other threads (one float read)."""
        ewma = self._tpot_ewma_ms
        return ewma / 1e3 if ewma is not None else None

    # -- cross-thread export --------------------------------------------

    def _own(self, section: str, **computed: Any) -> Dict[str, Any]:
        """One section in table order: this object's fields under their
        keys, and `computed` for the keys that are not a field."""
        return {
            m.key: getattr(self, m.field) if m.field else computed[m.key]
            for m in section_entries(section)
            if m.field or m.key in computed
        }

    def constrained_snapshot(self) -> Dict[str, int]:
        return self._own("constrained",
                         constrained_compile_pending=compile_pending())

    def speculation_snapshot(self) -> Dict[str, object]:
        """Raw monotone counters plus the two rates dashboards want."""
        return self._own("speculation", **speculation_rates(
            self.speculation_accepted_tokens,
            self.speculation_rejected_tokens,
            self.speculation_verify_steps))

    def slo_snapshot(self, uptime_s: Optional[float] = None
                     ) -> Dict[str, object]:
        """The SLO/goodput section (SLO_METRIC_KEYS + the raw window sums
        the DP aggregate needs to recompute windowed attainment)."""
        up = (time.monotonic() - self._started
              if uptime_s is None else uptime_s)
        w1 = self._slo_window.sums(60.0)
        w5 = self._slo_window.sums(300.0)
        return build_slo_section(
            ttft_target_ms=self.slo_ttft_ms,
            tpot_target_ms=self.slo_tpot_ms,
            met=self.slo_met_requests,
            missed=self.slo_missed_requests,
            ttft_violations=self.slo_ttft_violations,
            tpot_violations=self.slo_tpot_violations,
            goodput_tokens=self.goodput_tokens,
            generated_tokens=self.generated_tokens,
            uptime_s=up,
            window_1m={"met": w1[0], "missed": w1[1],
                       "goodput_tokens": w1[2]},
            window_5m={"met": w5[0], "missed": w5[1],
                       "goodput_tokens": w5[2]},
        )

    def utilization_snapshot(self) -> Dict[str, object]:
        """The device-utilization section: per dispatch kind, modeled
        flop/byte totals over attributed busy time, plus MFU / HBM-BW
        ratios against the roofline when one is known."""
        out: Dict[str, object] = {
            "peak_tflops": round(self.peak_flops / 1e12, 1)
            if self.peak_flops else None,
            "peak_hbm_gbps": round(self.peak_hbm_bps / 1e9, 1)
            if self.peak_hbm_bps else None,
            "peak_source": self.peak_source,
        }
        for kind in UTILIZATION_KINDS:
            u = self.util[kind]
            w = self._util_window[kind].sums(60.0)
            sec: Dict[str, object] = {
                "dispatches": u.dispatches,
                "tokens": u.tokens,
                "flops": round(u.flops, 0),
                "hbm_bytes": round(u.hbm_bytes, 0),
                "busy_s": round(u.busy_s, 3),
                **utilization_ratios(u.flops, u.hbm_bytes, u.busy_s, w,
                                     self.peak_flops, self.peak_hbm_bps),
                # flight-recorder measured timing: wall time these
                # dispatches measurably took vs the modeled roofline
                # time for the same set
                "measured_dispatches": u.measured_dispatches,
                "measured_busy_s": round(u.measured_s, 4),
                "modeled_busy_s": round(u.modeled_s, 4),
                "model_skew": model_skew(u.measured_s, u.modeled_s),
            }
            if u.busy_s > 0:
                sec["achieved_tflops"] = round(u.flops / u.busy_s / 1e12, 3)
                sec["achieved_gb_s"] = round(
                    u.hbm_bytes / u.busy_s / 1e9, 2
                )
            # raw 60s sums: what the DP aggregate combines before
            # recomputing the windowed ratios (never averages ratios)
            sec["window_1m"] = {
                "flops": round(w[0], 0),
                "hbm_bytes": round(w[1], 0),
                "busy_s": round(w[2], 4),
            }
            out[kind] = sec
        return out

    def anomalies_snapshot(self, flight=None) -> Dict[str, object]:
        """The anomaly section (ANOMALY_METRIC_KEYS): edge-triggered
        firing counters plus, when the engine's flight recorder is
        attached, the currently-active anomaly list the autoscaler's
        "don't scale on stale math" guard reads from /admin/signals."""
        active = flight.active_anomalies() if flight is not None else []
        return self._own("anomalies", anomalies_active=len(active),
                         active=active)

    def histograms_snapshot(self) -> Dict[str, Dict[str, Any]]:
        return {
            name: getattr(self, name).snapshot()
            for name in HISTOGRAM_NAMES
        }

    def snapshot(self, engine=None,
                 reset_peak: bool = True) -> Dict[str, object]:
        """Full metrics snapshot.  `reset_peak=True` (the /metrics scrape
        path) consumes queue.peak — peak-since-last-consuming-snapshot
        semantics; readers that must not steal the scraper's window
        (/admin/signals) pass False.  With several consuming scrapers the
        window is split between them — point ONE collector at /metrics.
        The re-arm is a cross-thread store the engine's record path may
        race; worst case one burst lands in the next interval (gauge
        precision, same torn-tolerance contract as every other read)."""
        up = time.monotonic() - self._started
        flight = getattr(engine, "flight", None)
        # quantiles DERIVED from the streaming histograms: cumulative
        # over the process lifetime, not a biased last-512 window
        quantiles = quantile_sections(
            {name: getattr(self, name) for name in HISTOGRAM_NAMES})
        snap: Dict[str, object] = {
            "uptime_s": round(up, 1),
            "requests": self._own("requests"),
            "queue": self._own("queue",
                               trend_per_s=self.queue_trend_per_s()),
            "tokens": self._own(
                "tokens",
                generated_per_s=round(self.generated_tokens / up, 2)
                if up > 0 else 0.0,
                fetch_pipeline_waste_frac=waste_frac(
                    self.generated_tokens,
                    self.fetch_pipeline_wasted_tokens)),
            "ttft_ms": quantiles["ttft_ms"],
            "ttft_breakdown_ms": quantiles["ttft_breakdown_ms"],
            # legacy top-level key kept for dashboards; the full family
            # lives in the "constrained" section
            "constrained_roundtrips": self.constrained_roundtrips,
            "constrained": self.constrained_snapshot(),
            "speculation": self.speculation_snapshot(),
            "slo": self.slo_snapshot(up),
            "utilization": self.utilization_snapshot(),
            "anomalies": self.anomalies_snapshot(flight),
            "tpot_ms": quantiles["tpot_ms"],
            "decode": self._own("decode", batch_occupancy=batch_occupancy(
                self.decode_busy_slots, self.decode_steps)),
            "emission": quantiles["emission"],
            # raw bucket data: the Prometheus renderer's source for the
            # _bucket/_sum/_count families, and what the DP aggregate
            # merges (counts add; identical fixed bounds)
            "histograms": self.histograms_snapshot(),
        }
        # peak-since-last-snapshot semantics: this scrape consumed the
        # high-water mark; re-arm at the current depth
        if reset_peak:
            self.queue_depth_peak = self.queue_depth
        if engine is not None:
            # sections read off the engine, its prefix cache and its
            # flight recorder (ring state only) by the entries' `read`
            for section, source in (("engine", engine),
                                    ("prefix_cache", engine.prefix_cache),
                                    ("flight", flight)):
                if source is not None:
                    snap[section] = {
                        m.key: m.read_from(source)
                        for m in section_entries(section)
                    }
            tier = getattr(engine, "kv_tier", None)
            if tier is not None:
                # tiered KV cache (KV_TIER_METRIC_KEYS)
                snap["kv_tier"] = tier.snapshot()
                if getattr(tier, "object", None) is not None:
                    # object-store tier (OBJECT_TIER_METRIC_KEYS)
                    snap["object_tier"] = tier.object.snapshot()
            mm = getattr(engine, "memory_monitor", None)
            if mm is not None:
                # live HBM accounting (MEMORY_METRIC_KEYS): latest
                # step-cadence device sample reconciled against the
                # boot-time MemoryPlan (runtime/planner.MemoryMonitor)
                mem = mm.section()
                if mem is not None:
                    snap["memory"] = mem
            if getattr(engine, "state_pool", None) is not None:
                # state slots of a model with a recurrent state
                # (STATE_METRIC_KEYS)
                snap["state"] = engine.state_section()
            clock = getattr(engine, "sched", None)
            if clock is not None:
                # the account of the thread that drives this engine
                # (SCHED_METRIC_KEYS; runtime/phase_clock.SchedClock)
                snap["sched"] = clock.section()
                snap["histograms"].update(clock.histograms())
                snap["sched_iter_ms"] = sched_iter_quantiles(
                    snap["histograms"])
            agent_fn = getattr(engine, "agent_section", None)
            if agent_fn is not None:
                # agent-native scheduling (AGENT_METRIC_KEYS): tool-gap
                # demotions, expected-return hints, background class
                snap["agent"] = agent_fn()
        return snap
