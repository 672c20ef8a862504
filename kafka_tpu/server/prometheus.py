"""Prometheus text exposition for the /metrics snapshot.

``GET /metrics?format=prometheus`` renders the same snapshot the JSON
endpoint serves (one source of truth — the engine's EngineMetrics, plus
sandbox-supervision and tracing counters merged by server/app.py) in the
classic text format (version 0.0.4): ``# TYPE`` lines, stable metric
names, label escaping per the spec.  Percentile families render as
summaries with ``quantile`` labels (p50 → 0.5 etc.).

What a scalar key is called here (family, labels, HELP) stands in the
metric table of runtime/metrics.py beside how it merges across replicas;
this module renders that table and hand-writes only what is not a scalar
under a key: the histogram and summary families, the role pools and the
replica health list.  Both snapshot shapes render, a single engine's and
the DP aggregate's, since an entry whose key is absent renders nothing.
A tier-1 test parses the output with a minimal format checker (no
duplicate series, every family typed, values float-parseable) so the
endpoint stays scrapeable.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from ..runtime import metrics
from ..runtime.metrics import Metric

_QUANTILE = {"p50": "0.5", "p90": "0.9", "p99": "0.99"}


def _escape(value: str) -> str:
    """Label-value escaping per the exposition format spec."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _fmt(value: Any) -> str:
    try:
        f = float(value)
    except (TypeError, ValueError):
        return "0"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


class _Writer:
    def __init__(self) -> None:
        self.lines: List[str] = []
        self._typed: set = set()

    def family(self, name: str, kind: str, help_text: str) -> None:
        if name in self._typed:
            return
        self._typed.add(name)
        self.lines.append(f"# HELP {name} {help_text}")
        self.lines.append(f"# TYPE {name} {kind}")

    def sample(
        self, name: str, value: Any,
        labels: Optional[Dict[str, Any]] = None,
    ) -> None:
        if labels:
            rendered = ",".join(
                f'{k}="{_escape(v)}"' for k, v in labels.items()
            )
            self.lines.append(f"{name}{{{rendered}}} {_fmt(value)}")
        else:
            self.lines.append(f"{name} {_fmt(value)}")

    def summary(
        self, name: str, quantiles: Dict[str, Any], help_text: str,
        labels: Optional[Dict[str, Any]] = None,
    ) -> None:
        self.family(name, "summary", help_text)
        for p, q in _QUANTILE.items():
            if p in quantiles:
                self.sample(name, quantiles[p],
                            {**(labels or {}), "quantile": q})

    def histogram_family(
        self, name: str, help_text: str,
        rows: List[tuple],
    ) -> None:
        """One histogram family from StreamingHistogram snapshots
        (ISSUE 10): true ``_bucket`` series with CUMULATIVE counts per
        ``le`` bound (monotone by construction — the wire snapshot holds
        non-negative per-bucket counts), a ``+Inf`` bucket equal to
        ``_count``, and ``_sum``.  `rows` is [(labels, hist_snapshot)] —
        all bucket series render before the sums/counts so each sample
        NAME stays one contiguous group (exposition single-group rule,
        enforced by the in-tree parser)."""
        self.family(name, "histogram", help_text)
        for labels, h in rows:
            cum = 0
            for le, c in zip(h["le"], h["counts"]):
                cum += c
                self.sample(f"{name}_bucket", cum,
                            {**labels, "le": _fmt(le)})
            self.sample(f"{name}_bucket", sum(h["counts"]),
                        {**labels, "le": "+Inf"})
        for labels, h in rows:
            self.sample(f"{name}_sum", h["sum"], labels or None)
        for labels, h in rows:
            self.sample(f"{name}_count", sum(h["counts"]), labels or None)

    def render(self) -> str:
        return "\n".join(self.lines) + "\n"


# histogram snapshot name -> (family, extra labels).  The three TTFT
# phases share ONE family distinguished by the phase label, mirroring the
# JSON breakdown section.
_HISTOGRAM_FAMILIES = (
    ("ttft_ms", "kafka_tpu_ttft_milliseconds",
     "Time to first token.", {}),
    ("tpot_ms", "kafka_tpu_tpot_milliseconds",
     "Time per output token.", {}),
    ("ttft_queue_ms", "kafka_tpu_ttft_phase_milliseconds",
     "TTFT decomposition by phase.", {"phase": "queue_wait"}),
    ("ttft_prefill_ms", "kafka_tpu_ttft_phase_milliseconds",
     "TTFT decomposition by phase.", {"phase": "prefill"}),
    ("ttft_fetch_ms", "kafka_tpu_ttft_phase_milliseconds",
     "TTFT decomposition by phase.", {"phase": "first_fetch"}),
    # the first_fetch phase tiled into its four stages
    ("ttft_dev_wait_ms", "kafka_tpu_ttft_phase_milliseconds",
     "TTFT decomposition by phase.", {"phase": "dev_wait"}),
    ("ttft_dev_exec_ms", "kafka_tpu_ttft_phase_milliseconds",
     "TTFT decomposition by phase.", {"phase": "dev_exec"}),
    ("ttft_hold_ms", "kafka_tpu_ttft_phase_milliseconds",
     "TTFT decomposition by phase.", {"phase": "hold"}),
    ("ttft_emit_ms", "kafka_tpu_ttft_phase_milliseconds",
     "TTFT decomposition by phase.", {"phase": "emit"}),
    ("burst_tokens", "kafka_tpu_emission_burst_tokens",
     "Tokens arriving together per emission burst.", {}),
    ("burst_gap_ms", "kafka_tpu_emission_burst_gap_milliseconds",
     "Gap between emission bursts.", {}),
    # one iteration of the engine thread's loop by what it dispatched
    *((name, "kafka_tpu_sched_iteration_milliseconds",
       "One iteration of the engine thread's loop, end of its wait to "
       "end of delivery, by what it did: seated a request (admit), "
       "dispatched a prefill chunk, a fused decode (multi), single "
       "decode steps, or nothing (held).", {"did": did})
      for name, did in zip(metrics.SCHED_ITER_HISTOGRAMS,
                           metrics.SCHED_ITER_CLASSES)),
)


def _render_histograms(w: "_Writer", snap: Dict[str, Any]) -> None:
    """The latency/size histogram families: aggregate series plus one
    replica-labeled series per DP replica (contiguous per family)."""
    hists = snap.get("histograms") or {}
    if not hists:
        return
    replica_hists = [
        (idx, rs.get("histograms") or {})
        for idx, rs in enumerate(snap.get("replicas") or [])
        if rs.get("histograms")
    ]
    by_family: Dict[str, List[tuple]] = {}
    help_by_family: Dict[str, str] = {}
    for key, family, help_text, labels in _HISTOGRAM_FAMILIES:
        if key not in hists:
            continue
        help_by_family[family] = help_text
        rows = by_family.setdefault(family, [])
        rows.append((dict(labels), hists[key]))
        for idx, rh in replica_hists:
            if key in rh:
                rows.append(({**labels, "replica": idx}, rh[key]))
    for family, rows in by_family.items():
        w.histogram_family(family, help_by_family[family], rows)


def _render_table(w: "_Writer", snap: Dict[str, Any]) -> None:
    """Every entry of the metric table that names a family, family by
    family (the exposition wants a family's samples contiguous): the
    snapshot's own sample, then one replica-labelled sample a DP replica
    for the entries that ask for it.  An entry whose key the snapshot
    lacks (a stale client, a partial shape) renders nothing."""
    families: Dict[str, List[Metric]] = {}
    for m in metrics.METRICS:
        if m.family:
            families.setdefault(m.family, []).append(m)
    replicas = list(enumerate(snap.get("replicas") or []))
    for family, entries in families.items():
        rows = [s for m in entries for s in m.samples(snap)]
        rows += [({"replica": idx, **labels}, v)
                 for idx, rs in replicas
                 for m in entries if m.per_replica
                 for labels, v in m.samples(rs)]
        if rows:
            w.family(family, metrics.family_type(family),
                     next(m.help for m in entries if m.help))
            for labels, value in rows:
                w.sample(family, value, labels)


def render_prometheus(snap: Dict[str, Any]) -> str:
    w = _Writer()
    _render_table(w, snap)

    # Latency/size distributions: TRUE histogram families (_bucket with
    # le labels, _sum, _count) from the streaming-histogram snapshots —
    # cumulative since boot, mergeable in PromQL, per replica and
    # aggregated.  When the snapshot predates histograms (stale client),
    # fall back to the summary form so the endpoint never goes dark.
    if snap.get("histograms"):
        _render_histograms(w, snap)
    else:
        if "ttft_ms" in snap:
            w.summary("kafka_tpu_ttft_milliseconds", snap["ttft_ms"],
                      "Time to first token (percentiles).")
        for phase, q in (snap.get("ttft_breakdown_ms") or {}).items():
            w.summary("kafka_tpu_ttft_phase_milliseconds", q,
                      "TTFT decomposition by phase.",
                      labels={"phase": phase})
        if "tpot_ms" in snap:
            w.summary("kafka_tpu_tpot_milliseconds", snap["tpot_ms"],
                      "Time per output token (percentiles).")
        emission = snap.get("emission") or {}
        if "burst_tokens" in emission:
            w.summary("kafka_tpu_emission_burst_tokens",
                      emission["burst_tokens"],
                      "Tokens arriving together per emission burst.")
        if "burst_gap_ms" in emission:
            w.summary("kafka_tpu_emission_burst_gap_milliseconds",
                      emission["burst_gap_ms"],
                      "Gap between emission bursts.")

    # the router's own shapes (KAFKA_TPU_DP_ROLES): the ship-latency
    # histogram and one gauge a role pool, which the pool-sizing
    # autoscaler reads
    disagg = snap.get("disagg") or {}
    if "ship_ms" in disagg:
        w.histogram_family(
            "kafka_tpu_disagg_ship_milliseconds",
            "Cross-replica page-run ship latency (host-staged "
            "gather+scatter, per run).",
            [({}, disagg["ship_ms"])],
        )
    pools = disagg.get("pools") or []
    for name, help_text, value in (
        ("kafka_tpu_disagg_pool_replicas", "Replicas per role pool.",
         lambda pool: len(pool.get("replicas") or [])),
        ("kafka_tpu_disagg_pool_queue_depth",
         "Waiting-queue depth per role pool.",
         lambda pool: pool.get("queue_depth", 0)),
        ("kafka_tpu_disagg_pool_occupancy",
         "Mean busy decode slots per step, per role pool.",
         lambda pool: pool.get("batch_occupancy", 0)),
    ) if pools else ():
        w.family(name, "gauge", help_text)
        for pool in pools:
            w.sample(name, value(pool), {"role": pool.get("role", "")})

    health = (snap.get("replica_supervisor") or {}).get("health")
    if health is not None:
        w.family("kafka_tpu_replica_health", "gauge",
                 "Per-replica health (1 healthy, 0.5 probation, 0 out).")
        for i, g in enumerate(health):
            w.sample("kafka_tpu_replica_health", g, {"replica": i})

    return w.render()
