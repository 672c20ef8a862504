"""Prometheus text exposition (ISSUE 3 satellite, histogram families +
SLO registry ISSUE 10): a minimal format parser validates
/metrics?format=prometheus output — TYPE lines present for every family,
no duplicate series, values parse, labels escape, histogram families
carry ordered le buckets with +Inf and consistent sum/count — so the
endpoint stays scrapeable as metrics evolve."""

import asyncio
import dataclasses
import os
import re

import pytest

from kafka_tpu.runtime.metrics import EngineMetrics
from kafka_tpu.server.prometheus import render_prometheus

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?"
    r" (?P<value>[^ ]+)$"
)
_LABEL_RE = re.compile(
    r'^([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\["\\n])*)"$'
)


def parse_exposition(text: str):
    """Minimal Prometheus text-format checker; returns {family: kind} and
    the list of (name, labels, value) samples.  Raises AssertionError on
    format violations (the test's teeth)."""
    families = {}
    samples = []
    seen = set()
    closed = set()  # families whose sample group has ended
    current = None  # family of the previous sample line
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert name not in families, f"duplicate TYPE for {name}"
            assert kind in ("counter", "gauge", "summary", "histogram"), kind
            families[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, labels_raw, value = m.group("name", "labels", "value")
        labels = {}
        if labels_raw:
            for part in labels_raw.split(","):
                lm = _LABEL_RE.match(part)
                assert lm, f"bad label pair {part!r} in {line!r}"
                labels[lm.group(1)] = lm.group(2)
        float(value)  # must parse
        key = (name, tuple(sorted(labels.items())))
        assert key not in seen, f"duplicate series: {key}"
        seen.add(key)
        # every sample belongs to a TYPEd family (summary samples share
        # the family's base name; histogram samples carry the _bucket/
        # _sum/_count suffixes of a histogram-typed base family)
        base = name
        if name not in families:
            for suffix in ("_bucket", "_sum", "_count"):
                stem = name[: -len(suffix)] if name.endswith(suffix) \
                    else None
                if stem and stem in families:
                    assert families[stem] == "histogram", (
                        f"{name} suffix on non-histogram family {stem}"
                    )
                    base = stem
                    break
        assert base in families, f"sample {name} has no TYPE line"
        # all samples of one family must form a single contiguous group
        if name != current:
            assert name not in closed, f"non-contiguous family: {name}"
            if current is not None:
                closed.add(current)
            current = name
        samples.append((name, labels, float(value)))
    return families, samples


def validate_histogram_family(families, samples, family):
    """Histogram-family invariants (ISSUE 10): per labelset, le bounds
    strictly increase and end at +Inf, cumulative bucket counts are
    monotone, the +Inf bucket equals _count, and _sum exists."""
    assert families.get(family) == "histogram", family
    by = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
    groups = {}
    for n, labels, v in samples:
        if n == f"{family}_bucket":
            key = tuple(sorted(
                (k, lv) for k, lv in labels.items() if k != "le"
            ))
            groups.setdefault(key, []).append((labels["le"], v))
    assert groups, f"no _bucket series for {family}"
    for key, rows in groups.items():
        les = [le for le, _ in rows]
        assert les[-1] == "+Inf", f"{family}{dict(key)}: no +Inf bucket"
        finite = [float(le) for le in les[:-1]]
        assert finite == sorted(finite) and len(set(finite)) == len(
            finite
        ), f"{family}{dict(key)}: le bounds not strictly increasing"
        counts = [v for _, v in rows]
        assert counts == sorted(counts), (
            f"{family}{dict(key)}: bucket counts not monotone"
        )
        assert by[(f"{family}_count", key)] == counts[-1], (
            f"{family}{dict(key)}: +Inf bucket != _count"
        )
        assert (f"{family}_sum", key) in by, (
            f"{family}{dict(key)}: missing _sum"
        )
    return groups


def populated_snapshot():
    m = EngineMetrics()
    m.record_submit(10)
    m.record_first_token(0.05)
    for _ in range(5):
        m.record_token()
    m.record_decode_step(3)
    m.record_decode_step(2)
    m.record_emit_burst(3)
    m.record_emit_burst(2)
    m.record_finish("stop", ttft_s=0.05, tokens=5)  # SLO met -> goodput
    m.record_finish("timeout")
    m.record_rejected()
    m.record_queue_depth(4)
    m.record_dispatch_cost("decode", 3, 1e9, 2e9)
    m.record_dispatch_cost("decode", 3, 1e9, 2e9)
    snap = m.snapshot()
    snap["requests"]["slow"] = 1
    snap["sandbox"] = {"crashes": 2, "restarts": 1, "crash_loops": 0,
                       "reaped": 2}
    snap["tracing"] = {"traces": 7, "stitched_spans": 3, "slow": 1}
    snap["prefix_cache"] = {
        "entries": 3, "nodes": 3, "cached_pages": 11,
        "hits": 5, "misses": 2, "tokens_reused": 96,
        "cross_thread_hits": 4, "evictions": 1, "pages_evicted": 2,
    }
    return snap


class TestRenderer:
    def test_output_parses_with_format_checker(self):
        families, samples = parse_exposition(
            render_prometheus(populated_snapshot())
        )
        names = {s[0] for s in samples}
        # the stable core families bench/scrape configs rely on
        for expected in (
            "kafka_tpu_uptime_seconds",
            "kafka_tpu_requests_total",
            "kafka_tpu_queue_depth",
            "kafka_tpu_tokens_total",
            "kafka_tpu_decode_steps_total",
            "kafka_tpu_batch_occupancy",
            "kafka_tpu_sandbox_total",
            "kafka_tpu_traces_total",
            # radix prefix-cache families (ISSUE 4): node/page gauges +
            # the event counter carrying cross-thread hits and evictions
            "kafka_tpu_prefix_cache_entries",
            "kafka_tpu_prefix_cache_nodes",
            "kafka_tpu_prefix_cache_pages",
            "kafka_tpu_prefix_cache_total",
            # SLO telemetry plane (ISSUE 10)
            "kafka_tpu_slo_requests_total",
            "kafka_tpu_goodput_tokens_total",
            "kafka_tpu_queue_depth_trend_per_second",
            "kafka_tpu_mfu",
        ):
            assert expected in names, expected
        assert families["kafka_tpu_requests_total"] == "counter"
        # the latency families are TRUE histograms now (ISSUE 10)
        assert families["kafka_tpu_ttft_milliseconds"] == "histogram"
        assert families["kafka_tpu_tpot_milliseconds"] == "histogram"
        assert "kafka_tpu_ttft_milliseconds_bucket" in names

    def test_counter_values_and_histograms(self):
        families, samples = parse_exposition(
            render_prometheus(populated_snapshot())
        )
        by = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
        assert by[("kafka_tpu_requests_total",
                   (("state", "finished"),))] == 1
        assert by[("kafka_tpu_requests_total",
                   (("state", "timeout"),))] == 1
        assert by[("kafka_tpu_requests_total",
                   (("state", "slow"),))] == 1
        assert by[("kafka_tpu_tokens_total",
                   (("kind", "generated"),))] == 5
        assert by[("kafka_tpu_ttft_milliseconds_count", ())] == 1
        assert by[("kafka_tpu_ttft_milliseconds_sum", ())] == 50.0
        assert by[("kafka_tpu_queue_depth", ())] == 4
        assert by[("kafka_tpu_stitched_spans_total", ())] == 3
        assert by[("kafka_tpu_prefix_cache_total",
                   (("kind", "cross_thread_hits"),))] == 4
        assert by[("kafka_tpu_prefix_cache_total",
                   (("kind", "evictions"),))] == 1
        assert by[("kafka_tpu_prefix_cache_pages", ())] == 11
        assert by[("kafka_tpu_prefix_cache_nodes", ())] == 3
        # SLO families carry the verdicts populated_snapshot recorded
        assert by[("kafka_tpu_slo_requests_total",
                   (("result", "met"),))] == 1
        # timeout + rejection both count as missed
        assert by[("kafka_tpu_slo_requests_total",
                   (("result", "missed"),))] == 2
        assert by[("kafka_tpu_goodput_tokens_total", ())] == 5

    def test_dp_aggregate_snapshot_renders(self):
        """The renderer must also swallow the DP aggregate shape (extra
        replica_supervisor section, per-replica lists, no breakdown)."""
        snap = populated_snapshot()
        snap["dp"] = 2
        snap["replicas"] = [{}, {}]  # per-replica detail is skipped
        snap["replica_supervisor"] = {
            "health": [1.0, 0.5],
            "states": ["healthy", "probation"],
            "quarantines": 1, "readmits": 1, "waiting_migrated": 2,
            "affinity_resteered": 0, "rebuilds": 0,
        }
        snap.pop("ttft_breakdown_ms", None)
        families, samples = parse_exposition(render_prometheus(snap))
        by_name = {}
        for n, l, v in samples:
            by_name.setdefault(n, []).append((l, v))
        assert len(by_name["kafka_tpu_replica_health"]) == 2
        assert ({"replica": "1"}, 0.5) in by_name["kafka_tpu_replica_health"]
        assert families["kafka_tpu_replica_supervisor_total"] == "counter"
        assert by_name["kafka_tpu_dp_replicas"] == [({}, 2.0)]

    def test_speculation_families_render(self):
        """Speculative-decoding counters/gauges (ISSUE 5) render as typed
        families, and the token counter carries the RENAMED
        fetch_pipeline_wasted kind (old kind gone from the exposition;
        JSON keeps deprecated aliases instead)."""
        m = EngineMetrics()
        m.record_verify_dispatch(8)
        m.record_verify_drain(5, 3)
        m.record_wasted_token(2)
        for _ in range(5):
            m.record_token()
        families, samples = parse_exposition(
            render_prometheus(m.snapshot())
        )
        by = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
        assert families["kafka_tpu_speculation_tokens_total"] == "counter"
        assert by[("kafka_tpu_speculation_tokens_total",
                   (("kind", "proposed"),))] == 8
        assert by[("kafka_tpu_speculation_tokens_total",
                   (("kind", "accepted"),))] == 5
        assert by[("kafka_tpu_speculation_tokens_total",
                   (("kind", "rejected"),))] == 3
        assert by[("kafka_tpu_speculation_verify_steps_total", ())] == 1
        assert families["kafka_tpu_speculation_acceptance_rate"] == "gauge"
        assert by[("kafka_tpu_tokens_total",
                   (("kind", "fetch_pipeline_wasted"),))] == 2
        assert ("kafka_tpu_tokens_total",
                (("kind", "speculative_wasted"),)) not in by

    def test_per_replica_prefix_cache_label_families(self):
        """DP aggregates export each replica's prefix cache as labeled
        series (replica="<i>") ALONGSIDE the summed aggregate series
        (ISSUE 5 satellite — PR 4 follow-up)."""
        snap = populated_snapshot()
        snap["dp"] = 2
        rep = {
            "prefix_cache": {
                "entries": 1, "nodes": 1, "cached_pages": 4,
                "hits": 2, "misses": 1, "tokens_reused": 32,
                "cross_thread_hits": 1, "evictions": 0,
                "pages_evicted": 0,
            }
        }
        snap["replicas"] = [rep, {}]  # replica 1 has no cache section
        families, samples = parse_exposition(render_prometheus(snap))
        by = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
        # aggregate (unlabeled) series survive for existing dashboards
        assert by[("kafka_tpu_prefix_cache_pages", ())] == 11
        assert by[("kafka_tpu_prefix_cache_total",
                   (("kind", "hits"),))] == 5
        # per-replica labeled series
        assert by[("kafka_tpu_prefix_cache_pages",
                   (("replica", "0"),))] == 4
        assert by[("kafka_tpu_prefix_cache_total",
                   (("kind", "hits"), ("replica", "0")))] == 2
        assert ("kafka_tpu_prefix_cache_pages",
                (("replica", "1"),)) not in by

    def test_label_escaping(self):
        from kafka_tpu.server.prometheus import _escape

        assert _escape('a"b\\c\nd') == 'a\\"b\\\\c\\nd'


class TestHistogramExposition:
    """ISSUE 10: the latency/size families are true histograms — the
    parser extension validates le ordering, +Inf, monotone cumulative
    counts, and sum/count consistency."""

    def test_all_histogram_families_valid(self):
        families, samples = parse_exposition(
            render_prometheus(populated_snapshot())
        )
        for family in ("kafka_tpu_ttft_milliseconds",
                       "kafka_tpu_tpot_milliseconds",
                       "kafka_tpu_ttft_phase_milliseconds",
                       "kafka_tpu_emission_burst_tokens",
                       "kafka_tpu_emission_burst_gap_milliseconds"):
            validate_histogram_family(families, samples, family)

    def test_phase_family_one_series_per_phase(self):
        families, samples = parse_exposition(
            render_prometheus(populated_snapshot())
        )
        groups = validate_histogram_family(
            families, samples, "kafka_tpu_ttft_phase_milliseconds"
        )
        phases = {dict(k)["phase"] for k in groups}
        # the three phases, and first_fetch's four stages beside it
        assert phases == {"queue_wait", "prefill", "first_fetch",
                          "dev_wait", "dev_exec", "hold", "emit"}

    def test_per_replica_histogram_series(self):
        """DP aggregates export each replica's histograms as labeled
        series (replica="<i>") alongside the merged aggregate, contiguous
        per family (exposition single-group rule)."""
        from kafka_tpu.runtime.metrics import StreamingHistogram

        snap = populated_snapshot()
        r0 = EngineMetrics()
        r0.record_first_token(0.01)
        r0.record_first_token(0.02)
        rep_snap = {"histograms": r0.histograms_snapshot()}
        snap["dp"] = 2
        snap["replicas"] = [rep_snap, {}]  # replica 1: no detail
        families, samples = parse_exposition(render_prometheus(snap))
        groups = validate_histogram_family(
            families, samples, "kafka_tpu_ttft_milliseconds"
        )
        assert () in groups  # aggregate
        assert (("replica", "0"),) in groups
        assert (("replica", "1"),) not in groups
        by = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
        assert by[("kafka_tpu_ttft_milliseconds_count",
                   (("replica", "0"),))] == 2

    def test_aggregate_merge_equals_sum(self):
        """The DP aggregate's merged histogram is the bucket-wise sum of
        the replica histograms — the mergeability the deques could never
        offer."""
        from kafka_tpu.runtime.metrics import (
            LATENCY_MS_BOUNDS,
            StreamingHistogram,
        )

        a, b = EngineMetrics(), EngineMetrics()
        for v in (0.01, 0.05, 0.4):
            a.record_first_token(v)
        for v in (0.02, 0.8):
            b.record_first_token(v)
        merged = StreamingHistogram.merged([a.ttft_ms, b.ttft_ms])
        assert merged.count == 5
        assert merged.counts == [
            x + y for x, y in zip(a.ttft_ms.counts, b.ttft_ms.counts)
        ]

    def test_utilization_families_render(self):
        m = EngineMetrics()
        m.set_roofline(100e12, 800e9, "env")
        m.record_dispatch_cost("prefill", 128, 5e12, 1e10)
        m.record_dispatch_cost("decode", 8, 1e12, 8e9)
        m.record_dispatch_cost("decode", 8, 1e12, 8e9)
        families, samples = parse_exposition(render_prometheus(
            m.snapshot()
        ))
        by = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
        assert families["kafka_tpu_device_flops_total"] == "counter"
        assert families["kafka_tpu_mfu"] == "gauge"
        # the first two dispatches have been attributed (gap to the next
        # record); the in-flight last one has not
        assert by[("kafka_tpu_dispatches_total",
                   (("kind", "prefill"),))] == 1
        assert by[("kafka_tpu_device_flops_total",
                   (("kind", "prefill"),))] == 5e12
        assert by[("kafka_tpu_device_peak_teraflops", ())] == 100.0
        # synthetic costs over microsecond gaps produce MFU >> 1; only
        # presence/shape is asserted here (real ratios are engine-tested)
        assert by[("kafka_tpu_mfu",
                   (("kind", "prefill"), ("window", "total")))] >= 0
        assert ("kafka_tpu_mfu",
                (("kind", "decode"), ("window", "1m"))) in by


class TestSLORegistry:
    """ISSUE 10 satellite: SLO_METRIC_KEYS and UTILIZATION_METRIC_KEYS
    are both-directions registries across runtime/metrics.py and
    server/prometheus.py, and every EngineMetrics field is either
    exported or on the explicit exclusion list."""

    def _source(self, relpath):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "kafka_tpu", relpath)) as f:
            return f.read()

    def test_registry_both_directions(self):
        from kafka_tpu.runtime.metrics import (
            SLO_METRIC_KEYS,
            UTILIZATION_METRIC_KEYS,
        )

        metrics_src = self._source("runtime/metrics.py")
        prom_src = self._source("server/prometheus.py")
        for key in SLO_METRIC_KEYS + UTILIZATION_METRIC_KEYS:
            assert f'"{key}"' in metrics_src, (
                f"{key} missing from runtime/metrics.py"
            )
            assert f'"{key}"' in prom_src, (
                f"{key} missing from server/prometheus.py"
            )

    def test_no_unregistered_slo_metrics(self):
        """Neither file invents slo_*/goodput_* names outside the
        registry (the invent-proof direction)."""
        from kafka_tpu.runtime.metrics import SLO_METRIC_KEYS

        pattern = re.compile(r'"((?:slo|goodput)_[a-z0-9_]+)"')
        allowed = set(SLO_METRIC_KEYS) | {
            # request-local span attrs / config knobs, not metric keys
            "slo_met", "slo_ttft_ms", "slo_tpot_ms",
        }
        for rel in ("runtime/metrics.py", "server/prometheus.py"):
            for name in pattern.findall(self._source(rel)):
                assert name in allowed, f"{name} in {rel} not registered"

    def test_slo_snapshot_matches_registry(self):
        from kafka_tpu.runtime.metrics import SLO_METRIC_KEYS

        snap = EngineMetrics().slo_snapshot()
        flat = {k for k in snap if not k.startswith("window_")}
        assert flat == set(SLO_METRIC_KEYS)

    def test_utilization_snapshot_matches_registry(self):
        from kafka_tpu.runtime.metrics import (
            UTILIZATION_KINDS,
            UTILIZATION_METRIC_KEYS,
        )

        m = EngineMetrics()
        m.record_dispatch_cost("decode", 1, 1.0, 1.0)
        m.record_dispatch_cost("decode", 1, 1.0, 1.0)
        snap = m.utilization_snapshot()
        for kind in UTILIZATION_KINDS:
            keys = {k for k in snap[kind]
                    if not k.startswith(("window_", "achieved_"))}
            assert keys == set(UTILIZATION_METRIC_KEYS), kind

    def test_every_engine_metrics_field_accounted(self):
        """Lint (ISSUE 10 satellite): a new EngineMetrics counter must be
        wired into the exposition (ENGINE_METRIC_EXPORTS, with its
        snapshot path verified live) or explicitly excluded with a reason
        — silent drops from /metrics are a test failure now."""
        from kafka_tpu.runtime.metrics import (
            ENGINE_METRIC_EXCLUDED,
            ENGINE_METRIC_EXPORTS,
        )

        fields = {f.name for f in dataclasses.fields(EngineMetrics)}
        exported = set(ENGINE_METRIC_EXPORTS)
        excluded = set(ENGINE_METRIC_EXCLUDED)
        assert not exported & excluded, exported & excluded
        missing = fields - exported - excluded
        assert not missing, (
            f"EngineMetrics fields neither exported nor excluded: "
            f"{sorted(missing)}"
        )
        stale = (exported | excluded) - fields
        assert not stale, f"registry names without fields: {sorted(stale)}"
        # every declared export path resolves in a live snapshot
        snap = EngineMetrics().snapshot()
        for field, path in ENGINE_METRIC_EXPORTS.items():
            node = snap
            for part in path:
                assert part in node, (
                    f"{field}: snapshot path {path} broken at {part!r}"
                )
                node = node[part]


class TestPrometheusHTTP:
    def test_metrics_prometheus_format_end_to_end(self, tmp_path):
        """A real engine-backed app serves scrapeable text at
        /metrics?format=prometheus (and JSON without the param)."""
        import jax
        import jax.numpy as jnp

        from aiohttp.test_utils import TestClient, TestServer
        from kafka_tpu.db.local import LocalDBClient
        from kafka_tpu.llm import TPULLMProvider
        from kafka_tpu.models import ModelConfig, init_params
        from kafka_tpu.models.tokenizer import ByteTokenizer
        from kafka_tpu.runtime import EngineConfig, InferenceEngine
        from kafka_tpu.server.app import create_app
        from kafka_tpu.server.config import ServingConfig

        cfg = ModelConfig(name="prom-test", vocab_size=300, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=4,
                          num_kv_heads=2, head_dim=16, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(3))
        engine = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch=2, page_size=8, num_pages=64,
                         max_pages_per_seq=8, prefill_buckets=(8, 16, 32)),
            kv_dtype=jnp.float32,
        )
        provider = TPULLMProvider(engine, ByteTokenizer(), model_name="m")

        async def go():
            app = await create_app(
                cfg=ServingConfig(db_path=str(tmp_path / "p.db")),
                llm_provider=provider,
                db=LocalDBClient(str(tmp_path / "p.db")),
                tools=[],
            )
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                r = await client.get("/metrics?format=prometheus")
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/plain")
                assert "version=0.0.4" in r.headers["Content-Type"]
                text = await r.text()
                families, samples = parse_exposition(text)
                assert "kafka_tpu_kv_pages" in families
                by = {(n, tuple(sorted(l.items()))): v
                      for n, l, v in samples}
                assert by[("kafka_tpu_kv_pages",
                           (("state", "total"),))] == 64
                # JSON stays the default
                j = await client.get("/metrics")
                assert (await j.json())["engine"]["pages_total"] == 64
            finally:
                await client.close()
                provider.worker.stop()

        asyncio.run(go())
