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
    """The SLO and utilization sections carry exactly their views of the
    metric table, and every EngineMetrics field is either exported or the
    accumulator behind a derived key."""

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


# the eighteen sections a subsystem fills, by their names in the snapshot
# (the compile observatory's is "compiles")
TABLE_SECTIONS = (
    "engine", "speculation", "constrained", "slo", "utilization",
    "anomalies", "flight", "kv_tier", "object_tier", "disagg",
    "autoscaler", "compiles", "memory", "agent", "state",
    # the engine thread's account, the boot by stage, the replies' own cost
    "sched", "boot", "metrics",
)
# of those, the ones that hold scalars under keys and nothing else
SCALAR_SECTIONS = (
    "engine", "speculation", "constrained", "anomalies", "flight",
    "kv_tier", "object_tier", "autoscaler", "agent", "state",
    "sched", "boot", "metrics",
)


@pytest.fixture(scope="module")
def full_snapshots():
    """The recorded dp=2 aggregate as GET /metrics serves it, every
    optional section present, and its replica 0 (a one-engine shape, with
    the keys the aggregate leaves out)."""
    import _metric_goldens as G

    replicas = G.load("metrics_replicas.json")
    served = G.served_snapshot(G.load("metrics_aggregate.json"), replicas)
    return served, replicas[0]


@pytest.fixture(scope="module")
def live_engine():
    """A tiny engine after tests/_metric_goldens.py's script of requests."""
    import _metric_goldens as G

    eng = G.tiny_engine()
    G.run_script(eng)
    return eng


class TestMetricTable:
    """runtime/metrics.METRICS is the one place a key is declared: what a
    full snapshot carries has an entry, and what an entry exposes comes
    out of the renderer (ISSUE 46; these two cases a section replace the
    twelve tests that grepped metrics.py and prometheus.py for literals)."""

    @pytest.mark.parametrize("section", TABLE_SECTIONS)
    def test_every_snapshot_key_has_an_entry(self, section, full_snapshots):
        from kafka_tpu.runtime.metrics import UTILIZATION_KINDS, METRICS

        entries = [m for m in METRICS if m.section == section]
        flat = {m.key for m in entries if not m.per_kind}
        per_kind = {m.key for m in entries if m.per_kind}
        seen = False
        for snap in full_snapshots:
            body = dict(snap.get(section) or {})
            seen = seen or bool(body)
            for kind in UTILIZATION_KINDS if per_kind else ():
                extra = set(body.pop(kind)) - per_kind
                assert not extra, (kind, extra)
            assert set(body) <= flat, set(body) - flat
        assert seen, f"no recorded snapshot carries {section!r}"

    @pytest.mark.parametrize("section", TABLE_SECTIONS)
    def test_every_exposed_entry_yields_its_family(self, section,
                                                   full_snapshots):
        from kafka_tpu.runtime.metrics import METRICS, family_type

        families, samples = {}, set()
        for snap in full_snapshots:
            fams, rows = parse_exposition(render_prometheus(snap))
            families.update(fams)
            samples |= {(n, tuple(sorted(l.items()))) for n, l, _ in rows}
        for m in METRICS:
            if m.section != section or not m.family:
                continue
            assert families.get(m.family) == family_type(m.family), m
            want = set(m.labels)
            assert any(name == m.family and want <= set(labels)
                       for name, labels in samples), (
                f"{section}.{m.key}: no {m.family} sample with {m.labels}")

    def test_family_type_is_the_kind_of_its_entries(self):
        """TYPE comes from the family's name (counter = *_total); every
        counter or gauge entry agrees with the family it sits in, and a
        family has one HELP."""
        from kafka_tpu.runtime.metrics import DETAIL, METRICS, family_type

        helps = {}
        for m in METRICS:
            if not m.family:
                continue
            if m.kind != DETAIL:
                assert m.kind == family_type(m.family), m
            if m.help:
                assert helps.setdefault(m.family, m.help) == m.help, m
        assert set(helps) == {m.family for m in METRICS if m.family}

    def test_scalar_keys_are_named_in_the_table_only(self):
        """No key of a purely scalar section is a string literal in the
        renderer or the router: what it is called and how it merges is the
        table's to say."""
        import ast

        import kafka_tpu
        from kafka_tpu.runtime.metrics import METRICS

        keys = {m.key for m in METRICS if m.section in SCALAR_SECTIONS}
        # a role pool's own count of seated requests (disagg.pools[].active)
        keys -= {"active"}
        root = os.path.dirname(kafka_tpu.__file__)
        for rel in ("server/prometheus.py", "runtime/dp_router.py"):
            with open(os.path.join(root, rel)) as f:
                tree = ast.parse(f.read())
            named = {n.value for n in ast.walk(tree)
                     if isinstance(n, ast.Constant)
                     and isinstance(n.value, str)} & keys
            assert not named, f"{rel} names {sorted(named)}"

    def test_one_entry_reaches_all_three_consumers(self, live_engine,
                                                   monkeypatch):
        """A counter is its field plus ONE entry: added to the table and
        nothing else, it is in the replica snapshot, in the merge of two
        and in the text."""
        from kafka_tpu.runtime import metrics as M

        live_engine.zz_probe = 7
        monkeypatch.setattr(M, "METRICS", M.METRICS + (M.Metric(
            section="engine", key="zz_probe", read="zz_probe",
            family="kafka_tpu_zz_probe_total", labels=(("who", "me"),),
            help="A throwaway."),))
        snap = live_engine.metrics.snapshot(live_engine, reset_peak=False)
        assert snap["engine"]["zz_probe"] == 7
        merged = M.merge_snapshots([snap, snap])
        assert merged["engine"]["zz_probe"] == 14
        families, samples = parse_exposition(render_prometheus(merged))
        assert families["kafka_tpu_zz_probe_total"] == "counter"
        assert ("kafka_tpu_zz_probe_total", {"who": "me"}, 14.0) in samples

    @pytest.mark.parametrize("kind", ["scored", "kept", "shared"])
    def test_the_indexer_key_counts_reach_all_three(self, live_engine,
                                                    monkeypatch, kind):
        """engine.index_keys_scored / _kept / _shared (the last PR 49's: the
        keys a decode pass scored once for every lane) are the engine's
        fields under one family: in the replica snapshot, summed by the
        merge of two, one sample a kind in the text."""
        from kafka_tpu.runtime import metrics as M

        key = "index_keys_" + kind
        monkeypatch.setattr(live_engine, key, 2048 * 3)
        snap = live_engine.metrics.snapshot(live_engine, reset_peak=False)
        assert snap["engine"][key] == 6144
        merged = M.merge_snapshots([snap, snap])
        assert merged["engine"][key] == 12288
        families, samples = parse_exposition(render_prometheus(merged))
        family = "kafka_tpu_engine_index_keys_total"
        assert families[family] == "counter"
        assert (family, {"kind": kind}, 12288.0) in samples
        assert sorted(l["kind"] for n, l, _ in samples if n == family) == [
            "kept", "scored", "shared"]


class TestGoldens:
    """Recorded at the parent commit 646876c by
    scripts/record_metric_goldens.py (its docstring lists the files), from
    the hand-written snapshot, dp merge and renderer this table replaced."""

    @pytest.mark.parametrize("which", ["served", "replica"])
    def test_text_is_the_recorded_set_of_lines(self, which, full_snapshots):
        import _metric_goldens as G

        snap = full_snapshots[0 if which == "served" else 1]
        text = render_prometheus(snap)
        parse_exposition(text)  # every family contiguous, typed, no dup
        assert sorted(text.splitlines()) == sorted(
            G.load(f"metrics_{which}.prom").splitlines())

    def test_dp_aggregate_is_the_recorded_one(self):
        """Every key, every rounding of what the parent's
        _AggregateMetrics.snapshot gave for the two recorded replicas."""
        import json

        import _metric_goldens as G

        got = G.aggregate(G.load("metrics_replicas.json"))
        assert json.loads(json.dumps(got)) == G.load(
            "metrics_aggregate.json")

    def test_live_engine_snapshot_has_the_recorded_shape(self, live_engine):
        """Nested key set, leaf types, and the integer counters the
        clock does not decide."""
        import _metric_goldens as G

        snap = live_engine.metrics.snapshot(live_engine, reset_peak=False)
        recorded = G.load("metrics_live.json")
        assert G.shape(snap) == recorded["shape"]
        assert G.int_leaves(snap) == recorded["ints"]


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
