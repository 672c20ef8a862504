"""Observability: engine counters, the streaming-histogram/SLO telemetry
plane (ISSUE 10), and the /metrics + /admin/signals endpoints."""

import asyncio
import importlib.util
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.ops.pallas.paged_attention import decode_step_runs
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.metrics import (
    BURST_TOKEN_BOUNDS,
    LATENCY_MS_BOUNDS,
    EngineMetrics,
    StreamingHistogram,
    _percentiles,
    ttft_fetch_stages,
)


def _bucket_bounds(h, value):
    """(lo, hi] bucket enclosing `value` under the histogram's bounds."""
    import bisect

    i = bisect.bisect_left(h.bounds, value)
    lo = h.bounds[i - 1] if i > 0 else 0.0
    hi = h.bounds[i] if i < len(h.bounds) else float("inf")
    return lo, hi


class TestStreamingHistogram:
    """Unit matrix for the fixed-bucket streaming histograms that replaced
    the last-512-sample deques (ISSUE 10)."""

    def test_bucket_boundaries_le_semantics(self):
        h = StreamingHistogram((1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 2.0, 4.0, 9.0):
            h.record(v)
        # le semantics: a value equal to a bound lands IN that bucket
        assert h.counts == [2, 2, 1, 1]
        assert h.count == 6
        assert h.sum == pytest.approx(18.0)
        assert h.max == 9.0

    def test_cumulative_monotone(self):
        h = StreamingHistogram(LATENCY_MS_BOUNDS)
        rng = np.random.default_rng(7)
        for v in rng.lognormal(3.0, 2.0, 500):
            h.record(float(v))
        cum = 0
        for c in h.counts:
            assert c >= 0
            cum += c
        assert cum == 500
        # cumulative series is monotone by construction
        running, prev = 0, -1
        for c in h.counts:
            running += c
            assert running >= prev
            prev = running

    def test_merge_across_replicas(self):
        a = StreamingHistogram(LATENCY_MS_BOUNDS)
        b = StreamingHistogram(LATENCY_MS_BOUNDS)
        for v in (1.0, 10.0, 100.0):
            a.record(v)
        for v in (5.0, 50.0):
            b.record(v)
        m = StreamingHistogram.merged([a, b])
        assert m.count == 5
        assert m.sum == pytest.approx(166.0)
        assert m.max == 100.0
        # merged counts are the element-wise sum
        assert m.counts == [x + y for x, y in zip(a.counts, b.counts)]
        # merging mismatched bounds must refuse, never mis-bucket
        with pytest.raises(ValueError):
            a.merge_from(StreamingHistogram((1.0, 2.0)))

    def test_quantile_within_enclosing_bucket(self):
        h = StreamingHistogram(LATENCY_MS_BOUNDS)
        values = [3.0, 7.0, 20.0, 45.0, 200.0]
        for v in values:
            h.record(v)
        for q, v in ((0.5, 20.0), (0.99, 200.0)):
            lo, hi = _bucket_bounds(h, v)
            assert lo < h.quantile(q) <= hi, (q, v, h.quantile(q))

    def test_quantile_empty_and_overflow(self):
        h = StreamingHistogram((1.0, 2.0))
        assert h.quantile(0.5) == 0.0
        h.record(1e9)  # +Inf bucket
        # the overflow bucket reports the tracked max, not a made-up bound
        assert h.quantile(0.99) == 1e9

    def test_snapshot_roundtrip(self):
        h = StreamingHistogram(BURST_TOKEN_BOUNDS)
        for v in (1, 2, 3, 700, 2000):
            h.record(float(v))
        snap = h.snapshot()
        assert snap["count"] == 5
        assert len(snap["counts"]) == len(snap["le"]) + 1
        back = StreamingHistogram.from_snapshot(snap)
        assert back.counts == h.counts
        assert back.sum == pytest.approx(h.sum)

    def test_log_spacing(self):
        ratios = [b / a for a, b in zip(LATENCY_MS_BOUNDS,
                                        LATENCY_MS_BOUNDS[1:])]
        assert all(r == pytest.approx(math.sqrt(2), rel=1e-4)
                   for r in ratios)


class TestMetricsUnit:
    def test_percentiles(self):
        # client-side helper (bench latency arrays) — still nearest-rank
        ps = _percentiles([float(i) for i in range(1, 101)])
        assert ps["p50"] == 50.0
        assert ps["p90"] == 90.0
        assert ps["p99"] == 99.0
        assert _percentiles([])["p50"] == 0.0

    def test_snapshot_shape(self):
        m = EngineMetrics()
        m.record_submit(10)
        m.record_first_token(0.05)
        m.record_token()
        m.record_decode_step(3)
        m.record_decode_step(2)
        m.record_finish("stop")
        snap = m.snapshot()
        assert snap["requests"]["submitted"] == 1
        assert snap["requests"]["finished"] == 1
        assert snap["tokens"]["generated"] == 1
        # quantiles are bucket-derived now: within the enclosing bucket
        lo, hi = _bucket_bounds(m.ttft_ms, 50.0)
        assert lo < snap["ttft_ms"]["p50"] <= hi
        assert snap["decode"]["steps"] == 2
        assert snap["decode"]["batch_occupancy"] == 2.5
        assert snap["histograms"]["ttft_ms"]["count"] == 1

    def test_queue_peak_resets_per_snapshot(self):
        """queue.peak is peak-SINCE-LAST-SNAPSHOT (ISSUE 10 satellite):
        each scrape consumes the high-water mark and re-arms at the
        current depth, so a boot-time burst stops dominating forever."""
        m = EngineMetrics()
        m.record_queue_depth(9)
        m.record_queue_depth(2)
        assert m.snapshot()["queue"]["peak"] == 9
        # no new burst since: the next scrape reports the current level
        assert m.snapshot()["queue"]["peak"] == 2
        m.record_queue_depth(5)
        m.record_queue_depth(3)
        # a non-consuming read (/admin/signals) must not steal the window
        assert m.snapshot(reset_peak=False)["queue"]["peak"] == 5
        assert m.snapshot()["queue"]["peak"] == 5

    def test_telemetry_off_keeps_slo_windows(self):
        """KAFKA_TPU_TELEMETRY=0 disables per-dispatch recording, but the
        SLO window gauges must keep tracking — an autoscaler reading a
        vacuous attainment_1m=1.0 during an outage would never scale."""
        m = EngineMetrics()
        m.enabled = False
        m.record_finish("timeout")
        m.record_rejected()
        snap = m.slo_snapshot()
        assert snap["slo_attainment"] == 0.0
        assert snap["slo_attainment_1m"] == 0.0
        assert snap["slo_attainment_5m"] == 0.0


class TestSLOAccounting:
    def _m(self, ttft_ms=200.0, tpot_ms=0.0):
        m = EngineMetrics()
        m.slo_ttft_ms, m.slo_tpot_ms = ttft_ms, tpot_ms
        return m

    def test_met_and_missed_classification(self):
        m = self._m()
        assert m.record_finish("stop", ttft_s=0.05, tpot_s=0.01,
                               tokens=10) is True
        assert m.record_finish("stop", ttft_s=0.5, tpot_s=0.01,
                               tokens=10) is False
        snap = m.slo_snapshot()
        assert snap["slo_met_requests"] == 1
        assert snap["slo_missed_requests"] == 1
        assert snap["slo_ttft_violations"] == 1
        assert snap["slo_attainment"] == 0.5
        # goodput counts ONLY the met request's tokens
        assert snap["goodput_tokens"] == 10
        assert snap["goodput_frac"] == 0.0  # no record_token calls

    def test_tpot_target(self):
        m = self._m(ttft_ms=0.0, tpot_ms=50.0)  # TTFT check disabled
        assert m.record_finish("stop", ttft_s=9.9, tpot_s=0.01,
                               tokens=4) is True
        assert m.record_finish("stop", ttft_s=0.01, tpot_s=0.2,
                               tokens=4) is False
        assert m.slo_tpot_violations == 1

    def test_timeout_and_error_always_miss(self):
        m = self._m()
        assert m.record_finish("timeout") is False
        assert m.record_finish("error:engine", ttft_s=0.01,
                               tokens=3) is False
        snap = m.slo_snapshot()
        assert snap["slo_missed_requests"] == 2
        assert snap["goodput_tokens"] == 0
        # a timeout that never produced a first token is a TTFT violation
        assert snap["slo_ttft_violations"] >= 1

    def test_cancel_excluded(self):
        m = self._m()
        assert m.record_finish("cancelled") is None
        snap = m.slo_snapshot()
        assert snap["slo_met_requests"] == 0
        assert snap["slo_missed_requests"] == 0
        assert m.requests_cancelled == 1

    def test_rejected_counts_as_miss(self):
        """A 429 admission rejection IS a missed SLO: shed load must show
        as attainment loss, or the autoscaler sees overload as health."""
        m = self._m()
        m.record_finish("stop", ttft_s=0.01, tokens=2)
        m.record_rejected()
        snap = m.slo_snapshot()
        assert snap["slo_missed_requests"] == 1
        assert snap["slo_attainment"] == 0.5
        assert m.requests_rejected == 1

    def test_window_attainment_moves(self):
        m = self._m()
        for _ in range(3):
            m.record_finish("stop", ttft_s=0.01, tokens=5)
        m.record_finish("stop", ttft_s=0.9, tokens=5)
        snap = m.slo_snapshot()
        assert snap["slo_attainment_1m"] == 0.75
        assert snap["slo_attainment_5m"] == 0.75
        assert snap["goodput_tok_s_1m"] == pytest.approx(15 / 60.0)

    def test_verdict_stamped_on_trace_root(self, engine):
        """The SLO verdict lands on the request's http.request root span
        at finalize (ISSUE 10): /debug/trace and the slow-request log
        carry slo_met / slo_ttft_ms without re-deriving them."""
        from kafka_tpu import tracing

        tracing.reset()
        tracing.configure(sample=1.0)
        root = tracing.start_trace(name="http.request")
        ctx = tracing.current()
        try:
            req = GenRequest(request_id="slo-span", prompt_ids=[4, 5, 6],
                             max_new_tokens=3, trace=ctx)
            engine.submit(req)
            engine.run_to_completion()
            assert req.slo_met is not None
            assert root.attrs["slo_met"] == req.slo_met
            assert root.attrs["slo_ttft_ms"] > 0
        finally:
            tracing.finish_trace(root)

    def test_gauges_survive_failpoint_chaos(self):
        """ISSUE 10: the gauges the autoscaler reads are chaos-tested
        against the existing failpoint sites — an engine.step failure
        storm must land in slo_missed (via the worker's fail-all path or
        engine recovery), never wedge the counters, and the snapshot the
        signal feed serves must stay coherent throughout."""
        from kafka_tpu import failpoints

        cfg = ModelConfig(name="chaos-slo", vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=4,
                          num_kv_heads=2, head_dim=16, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(11))
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch=2, page_size=8, num_pages=64,
                         max_pages_per_seq=8, prefill_buckets=(8, 16, 32)),
            kv_dtype=jnp.float32,
        )
        eng.generate([1, 2, 3], max_new_tokens=2)  # compile
        eng.submit(GenRequest(request_id="chaos-1", prompt_ids=[4, 5, 6],
                              max_new_tokens=8))
        # a STARTED lane is what engine recovery fail-stops (recovery
        # deliberately re-queues WAITING requests instead)
        while not eng.num_active:
            eng.step()
        failpoints.configure("engine.step", "error", "chaos", count=1)
        try:
            with pytest.raises(Exception):
                eng.run_to_completion()
        finally:
            failpoints.clear()
        events = eng.recover_from_failure()
        assert any(ev.finish_reason == "error:engine" for ev in events)
        snap = eng.metrics.snapshot(eng)
        # the failed request is an SLO miss with an intact snapshot
        assert snap["slo"]["slo_missed_requests"] >= 1
        assert snap["requests"]["failed"] >= 1
        assert snap["slo"]["slo_attainment"] < 1.0
        assert 0.0 <= snap["slo"]["slo_attainment_1m"] <= 1.0
        assert "utilization" in snap and "histograms" in snap
        # and the engine still serves cleanly afterwards (gauges recover)
        eng.metrics.slo_ttft_ms = 10_000.0
        r2 = eng.generate([7, 8, 9], max_new_tokens=2)
        assert r2.slo_met is True

    def test_roofline_survives_metrics_reset(self, monkeypatch):
        """Warmup/bench swap in fresh EngineMetrics objects; a known
        roofline (datasheet or env override) must be re-applied by the
        engine's cost recording, or MFU would flatline at 0 forever on
        the default (warmup=True) server path."""
        monkeypatch.setenv("KAFKA_TPU_PEAK_TFLOPS", "100")
        monkeypatch.setenv("KAFKA_TPU_PEAK_HBM_GBPS", "800")
        cfg = ModelConfig(name="roof-test", vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=4,
                          num_kv_heads=2, head_dim=16, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(12))
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch=2, page_size=8, num_pages=64,
                         max_pages_per_seq=8, prefill_buckets=(8, 16, 32)),
            kv_dtype=jnp.float32,
        )
        assert eng.metrics.peak_source == "env"
        eng.metrics = EngineMetrics()  # the warmup-reset pattern
        assert eng.metrics.peak_source == "unknown"
        eng.generate([1, 2, 3], max_new_tokens=3)
        assert eng.metrics.peak_source == "env"
        assert eng.metrics.peak_flops == pytest.approx(100e12)
        snap = eng.metrics.snapshot(eng)
        assert snap["utilization"]["peak_tflops"] == 100.0

    def test_engine_deadline_timeout_is_slo_miss(self):
        """End-to-end: a request expiring its TTFT deadline finalizes as
        an SLO miss through the engine path (ISSUE 10 satellite)."""
        cfg = ModelConfig(name="slo-test", vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=4,
                          num_kv_heads=2, head_dim=16, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(9))
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch=2, page_size=8, num_pages=64,
                         max_pages_per_seq=8, prefill_buckets=(8, 16, 32)),
            kv_dtype=jnp.float32,
        )
        eng.generate([1, 2, 3], max_new_tokens=2)  # compile
        met0 = eng.metrics.slo_met_requests
        # deadline 0: expired by the first _check_deadlines sweep
        req = GenRequest(request_id="slo-dl", prompt_ids=[4, 5, 6],
                         max_new_tokens=4, deadline_ttft_s=0.0)
        eng.submit(req)
        eng.run_to_completion()
        assert req.finish_reason == "timeout"
        assert req.slo_met is False
        assert eng.metrics.slo_missed_requests >= 1
        assert eng.metrics.slo_met_requests == met0
        # a clean request on the same engine is MET with goodput (target
        # widened so a loaded CI host can't flake the verdict)
        eng.metrics.slo_ttft_ms = 10_000.0
        good0 = eng.metrics.goodput_tokens
        r2 = eng.generate([7, 8, 9], max_new_tokens=3)
        assert r2.slo_met is True
        assert eng.metrics.goodput_tokens == good0 + len(r2.output_ids)


@pytest.fixture(scope="module")
def engine():
    cfg = ModelConfig(name="metrics-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(5))
    return InferenceEngine(
        cfg, params,
        EngineConfig(max_batch=2, page_size=8, num_pages=64,
                     max_pages_per_seq=8, prefill_buckets=(8, 16, 32)),
        kv_dtype=jnp.float32,
    )


class TestTTFTBreakdown:
    def test_phases_recorded_and_exported(self, engine):
        engine.metrics = EngineMetrics()  # phase-local histograms
        req = engine.generate([5, 9, 23, 4], max_new_tokens=4)
        assert req.t_prefill_start is not None
        assert req.t_first_dispatch is not None
        snap = engine.metrics.snapshot(engine)
        bd = snap["ttft_breakdown_ms"]
        assert set(bd) == {"queue_wait", "prefill", "first_fetch"}
        # bucket-derived quantiles: each phase histogram recorded exactly
        # one sample whose TRUE value comes from the request's stamps —
        # the reported p50 must land in that sample's enclosing bucket
        # (catches unit mismatches and swapped stamps at bucket precision)
        truths = {
            "queue_wait": (req.t_prefill_start - req.submit_time) * 1e3,
            "prefill": (req.t_first_dispatch - req.t_prefill_start) * 1e3,
            "first_fetch": (req.first_token_time
                            - req.t_first_dispatch) * 1e3,
        }
        for phase, truth in truths.items():
            lo, hi = _bucket_bounds(engine.metrics.ttft_queue_ms,
                                    max(truth, 1e-6))
            # + slack: the JSON export rounds to 2 decimals, which can
            # nudge a value sitting exactly on the bucket bound past it
            assert lo < bd[phase]["p50"] <= hi + max(0.01, hi * 1e-5), (
                phase, truth, bd[phase]
            )
        # and the sum/count invariants hold per histogram
        for name in ("ttft_queue_ms", "ttft_prefill_ms", "ttft_fetch_ms"):
            h = snap["histograms"][name]
            assert h["count"] == sum(h["counts"]) >= 1

    def test_missing_stamp_records_nothing(self):
        m = EngineMetrics()
        m.record_ttft_breakdown(1.0, None, 2.0, 3.0)
        assert m.ttft_queue_ms.count == 0


STAGE_HISTS = ("ttft_dev_wait_ms", "ttft_dev_exec_ms", "ttft_hold_ms",
               "ttft_emit_ms")


class _SlowTokens:
    """A fetch whose compute is never seen done and whose read blocks: what
    a busy device looks like to the scheduler thread."""

    def __init__(self, arr, read_s=0.004):
        self.arr, self.read_s = arr, read_s

    def is_ready(self):
        return False

    def __array__(self, dtype=None, copy=None):
        import time

        time.sleep(self.read_s)
        return np.asarray(self.arr)


def _ledger(engine):
    """Fresh metrics on `engine`, with every record_ttft_breakdown call's
    (fetch phase ms, its four stages ms) kept per request."""
    engine.metrics = m = EngineMetrics()
    rows = []
    orig = m.record_ttft_breakdown

    def keep(submit, start, dispatch, first, fetch_marks=(None,) * 3):
        stages = orig(submit, start, dispatch, first, fetch_marks)
        rows.append(((first - dispatch) * 1e3,
                     [d * 1e3 for d in stages], fetch_marks))
        return stages

    m.record_ttft_breakdown = keep
    return rows


def _assert_tiled(engine, rows, n):
    assert len(rows) == n
    for fetch_ms, stages, _ in rows:
        assert all(d >= 0.0 for d in stages), stages
        assert abs(sum(stages) - fetch_ms) < 1e-6, (stages, fetch_ms)
    snap = engine.metrics.snapshot(engine)["histograms"]
    for name in STAGE_HISTS:
        assert snap[name]["count"] == snap["ttft_fetch_ms"]["count"] == n
    assert abs(sum(getattr(engine.metrics, h).sum for h in STAGE_HISTS)
               - engine.metrics.ttft_fetch_ms.sum) < 1e-6 * n


class TestFetchStageLedger:
    """The first-fetch phase of TTFT tiled into dev_wait / dev_exec / hold
    / emit (PR 35): per request the four sum to its ttft_fetch_ms sample on
    every path a first token can take."""

    @pytest.mark.parametrize("marks,expect", [
        # every mark in order
        ((11.0, 12.5, 13.0), (1.0, 1.5, 0.5, 1.0)),
        # popped before the completion was seen: the read's return (13.5)
        # is the completion, and hold is empty
        ((11.0, 13.5, 13.0), (1.0, 2.5, 0.0, 0.5)),
        # the device start derived before the request's dispatch stamp
        ((9.0, 12.0, 13.0), (0.0, 2.0, 1.0, 1.0)),
        # marks the engine never stamped collapse onto their predecessor
        ((None, None, None), (0.0, 0.0, 0.0, 4.0)),
        ((11.0, None, 13.0), (1.0, 0.0, 2.0, 1.0)),
        # a mark past the first token (clock weirdness) is held to it
        ((11.0, 15.0, 16.0), (1.0, 3.0, 0.0, 0.0)),
    ])
    def test_the_tile_is_exact_whatever_the_marks(self, marks, expect):
        stages = ttft_fetch_stages(10.0, *marks, 14.0)
        assert stages == pytest.approx(expect)
        assert sum(stages) == pytest.approx(4.0, abs=1e-12)
        assert all(d >= 0.0 for d in stages)

    def test_single_prefill_path(self, engine):
        rows = _ledger(engine)
        pops = dict(engine.fetch_pops)
        engine.generate([5, 9, 23, 4], max_new_tokens=4)
        _assert_tiled(engine, rows, 1)
        # the entry was stamped on the way: a device start, a completion
        # (seen by a poll or by the read's return) and a pop
        assert None not in rows[0][2]
        assert sum(engine.fetch_pops.values()) > sum(pops.values())

    def test_batched_prefill_path(self, engine):
        rows = _ledger(engine)
        calls = []
        orig = engine._advance_prefill_batch
        engine._advance_prefill_batch = (
            lambda *a, **k: (calls.append(1), orig(*a, **k))[1])
        try:
            for i in range(2):
                engine.submit(GenRequest(
                    request_id=f"bp{i}", prompt_ids=[5 + i, 9, 23, 4],
                    max_new_tokens=3))
            engine.run_to_completion()
        finally:
            del engine._advance_prefill_batch
        assert calls, "the two prompts did not share a batched launch"
        _assert_tiled(engine, rows, 2)

    def test_multi_chunk_prefill(self, engine):
        rows = _ledger(engine)
        prompt = list(np.random.RandomState(3).randint(1, 128, 45))
        req = engine.generate(prompt, max_new_tokens=3)  # 32 + 16 buckets
        _assert_tiled(engine, rows, 1)
        # the phase starts at the LAST chunk's dispatch, and so does the
        # ledger: its first stage cannot predate that stamp
        assert req.t_first_dispatch > req.t_prefill_start

    def test_host_constrained_request_pops_now(self, engine):
        rows = _ledger(engine)
        before = engine.fetch_pops["now"]
        engine.submit(GenRequest(
            request_id="hc", prompt_ids=[5, 2, 9], max_new_tokens=3,
            logits_mask_fn=lambda out: [7, 11]))
        engine.run_to_completion()
        _assert_tiled(engine, rows, 1)
        assert engine.fetch_pops["now"] > before
        # popped at once, ahead of any poll: the completion is the return
        # of the read, so nothing was held
        assert rows[0][1][2] == 0.0

    def test_blocking_drain(self, engine):
        rows = _ledger(engine)
        before = engine.fetch_pops["blocking"]
        # one token: the request drains at its prefill, nothing is left to
        # dispatch, and step() flushes the pipeline with a blocking drain
        engine.generate([5, 9, 23, 4], max_new_tokens=1)
        _assert_tiled(engine, rows, 1)
        assert engine.fetch_pops["blocking"] > before

    def test_counters_rise_under_a_slow_fetch(self, engine):
        rows = _ledger(engine)
        orig = engine._push_entry

        def slow_push(entry):
            entry.arr = _SlowTokens(entry.arr)
            orig(entry)

        engine._push_entry = slow_push
        before = engine.metrics.snapshot(engine)["engine"]
        try:
            engine.generate([5, 9, 23, 4], max_new_tokens=12)
        finally:
            del engine._push_entry
        after = engine.metrics.snapshot(engine)["engine"]
        # every decode dispatch sampled the run-ahead, and with no
        # completion ever seen the steps in flight all counted
        assert after["fetch_depth_samples"] > before["fetch_depth_samples"]
        assert (after["fetch_depth_steps_sum"]
                > before["fetch_depth_steps_sum"])
        assert after["fetch_blocked_s"] >= before["fetch_blocked_s"] + 0.004
        assert (sum(after["fetch_pops"].values())
                > sum(before["fetch_pops"].values()))
        assert set(after["fetch_pops"]) == {"aged", "depth", "blocking",
                                            "now"}
        # unseen completions: each read's return stood in, hold is empty
        _assert_tiled(engine, rows, 1)
        assert rows[0][1][2] == 0.0

    def test_disabled_telemetry_still_tiles_for_the_spans(self):
        m = EngineMetrics()
        m.enabled = False
        stages = m.record_ttft_breakdown(1.0, 2.0, 3.0, 5.0,
                                         (3.5, 4.0, 4.5))
        assert stages == pytest.approx((0.5, 0.5, 0.5, 0.5))
        assert m.ttft_fetch_ms.count == m.ttft_hold_ms.count == 0

    def test_forced_grammar_chains_without_roundtrips(self, engine):
        """A fully-forced grammar (singleton masks) never awaits a round
        trip; a genuinely ambiguous mask does.  The counter separates
        them — the arithmetic behind the on-prem latency projection."""
        rt0 = engine.metrics.constrained_roundtrips
        forced = [7, 8, 9, 10]
        req = engine.generate(
            [3, 5, 2], max_new_tokens=4,
            logits_mask_fn=lambda out: [forced[len(out)]]
            if len(out) < 4 else None,
        )
        assert req.output_ids == forced
        assert req.constrained_roundtrips == 0
        assert engine.metrics.constrained_roundtrips == rt0

        rt0 = engine.metrics.constrained_roundtrips
        req = engine.generate(
            [3, 5, 2], max_new_tokens=3,
            logits_mask_fn=lambda out: [11, 12, 13],  # always ambiguous
        )
        # token 1's mask rides the prefill dispatch (no extra trip);
        # tokens 2 and 3 each await the previous token back — 2 trips
        assert req.constrained_roundtrips == 2
        assert engine.metrics.constrained_roundtrips == rt0 + 2


class TestEngineRecording:
    def test_generation_populates_counters(self, engine):
        for i in range(3):
            engine.submit(GenRequest(
                request_id=f"m{i}",
                prompt_ids=list(np.random.RandomState(i).randint(1, 128, 9)),
                max_new_tokens=5, prefix_key=f"t{i}"))
        engine.run_to_completion()
        snap = engine.metrics.snapshot(engine)
        assert snap["requests"]["submitted"] >= 3
        assert snap["requests"]["finished"] >= 3
        assert snap["tokens"]["generated"] >= 15
        assert snap["ttft_ms"]["p50"] > 0
        assert snap["tpot_ms"]["p50"] >= 0
        assert 0 < snap["decode"]["batch_occupancy"] <= 2
        assert snap["engine"]["pages_total"] == 64
        assert snap["prefix_cache"]["entries"] == 3
        assert snap["engine"]["rtt_est_ms"] >= 0
        # bucket-derived p50 interpolates from 0 inside the lowest (0,1]
        # bucket when every burst is a single token (histogram_quantile
        # semantics), so the honest floor is >0, not >=1
        assert snap["emission"]["burst_tokens"]["p50"] > 0
        assert engine.metrics.burst_tokens.max >= 1
        # utilization estimator moved (ISSUE 10): real dispatches ran, so
        # the cost model accumulated flops/bytes against busy wall time
        util = snap["utilization"]
        assert util["decode"]["dispatches"] > 0
        assert util["decode"]["flops"] > 0
        assert util["prefill"]["tokens"] >= 27  # 3 x 9-token prompts
        assert util["decode"]["busy_s"] > 0
        # SLO verdicts were classified for every finished request
        slo = snap["slo"]
        assert (slo["slo_met_requests"] + slo["slo_missed_requests"]
                >= 3)

    def test_solo_stream_emits_smoothly(self):
        """VERDICT r2 #7: a lone interactive stream must not receive its
        tokens in fetch_wait_s-sized bursts.  With <=2 active streams the
        emit age-bound tightens to ~1.25x the measured RTT, so on a local
        link tokens pop (nearly) one per step: median burst size 1."""
        cfg = ModelConfig(name="cadence-test", vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=4,
                          num_kv_heads=2, head_dim=16, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(6))
        eng = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch=2, page_size=8, num_pages=64,
                         max_pages_per_seq=8, prefill_buckets=(8, 16, 32),
                         fetch_wait_s=10.0),  # absurd cap: adaptivity must win
            kv_dtype=jnp.float32,
        )
        # without the adaptive bound every token would arrive in ONE
        # 40-token burst at the end (fetch_wait_s=10s, fetch_lag=96); with
        # it the typical pop is a single token across many emission events.
        # Non-adaptive behavior would be exactly two bursts: [1, 39].
        # Timing-sensitive on a loaded host (a hiccup groups tokens into a
        # larger burst), so allow a few attempts — non-adaptive code fails
        # ALL of them deterministically.
        from kafka_tpu.runtime.metrics import EngineMetrics

        last = None
        for _ in range(3):
            eng.metrics = EngineMetrics()
            eng.generate(list(range(1, 9)), max_new_tokens=40)
            snap = eng.metrics.snapshot(eng)
            last = (eng.metrics.burst_tokens.count,
                    eng.metrics.burst_tokens.max,
                    snap["emission"]["burst_gap_ms"]["p50"])
            if last[0] >= 3 and last[1] <= 30 and last[2] < 100:
                break
        else:
            raise AssertionError(f"emission stayed bursty: {last}")

    def test_emit_wait_tightens_only_when_quiet(self, engine):
        """The adaptive age bound applies at <=2 active streams and must
        NOT shrink the configured bound for busy batches (premature pops
        there would block the dispatch thread on unlanded transfers)."""
        saved_slots, saved_rtt = engine.slots, engine._rtt_est
        try:
            engine._rtt_est = 0.004
            engine.slots = [None] * engine.ecfg.max_batch
            quiet = engine._emit_wait()
            assert quiet == pytest.approx(0.005)  # 1.25 x rtt, under cap
            engine._rtt_est = 10.0
            assert engine._emit_wait() == engine.ecfg.fetch_wait_s  # capped
            engine._rtt_est = 0.004
            engine.slots = [object()] * 3 + [None] * (
                engine.ecfg.max_batch - 3
            )
            assert engine._emit_wait() == engine.ecfg.fetch_wait_s
        finally:
            engine.slots, engine._rtt_est = saved_slots, saved_rtt

    def test_burst_percentile_math(self):
        m = EngineMetrics()
        m.record_emit_burst(3)
        m.record_emit_burst(1)
        # bucket-derived: the p99 lands in 3's enclosing (2, 4] bucket
        p99 = m.snapshot()["emission"]["burst_tokens"]["p99"]
        assert 2.0 < p99 <= 4.0
        assert m.burst_tokens.max == 3.0


class TestMetricsEndpoint:
    def test_metrics_requires_local_engine(self, tmp_path, monkeypatch):
        from tests.test_server import make_client

        monkeypatch.delenv("KAFKA_TPU_PROFILING", raising=False)
        built, _, _ = make_client(tmp_path, [[{"content": "hi"}]])

        async def go():
            client = await built
            try:
                r = await client.get("/metrics")
                # FakeLLM has no engine -> 404 with a clean error body
                assert r.status == 404
                body = await r.json()
                assert "error" in body
                p = await client.post("/debug/profile", json={"seconds": 1})
                assert p.status == 403  # gated by KAFKA_TPU_PROFILING
            finally:
                await client.close()

        asyncio.run(go())

    def test_metrics_served_with_engine(self, tmp_path, engine):
        from kafka_tpu.llm import TPULLMProvider
        from kafka_tpu.models.tokenizer import ByteTokenizer
        from kafka_tpu.server.app import create_app
        from kafka_tpu.server.config import ServingConfig
        from kafka_tpu.db.local import LocalDBClient
        from aiohttp.test_utils import TestClient, TestServer

        # note: engine vocab (128) < ByteTokenizer's, but /metrics only
        # reads counters — no generation happens here
        provider = TPULLMProvider(engine, ByteTokenizer(), model_name="m")

        async def go():
            app = await create_app(
                cfg=ServingConfig(db_path=str(tmp_path / "m.db")),
                llm_provider=provider,
                db=LocalDBClient(str(tmp_path / "m.db")),
                tools=[],
            )
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                r = await client.get("/metrics")
                assert r.status == 200
                snap = await r.json()
                assert "ttft_ms" in snap and "engine" in snap
                assert snap["engine"]["pages_total"] == 64
                # the JSON snapshot carries the full telemetry plane
                assert "slo" in snap and "utilization" in snap
                assert "histograms" in snap

                # /admin/signals: the autoscaler input contract (ISSUE 10)
                s = await client.get("/admin/signals")
                assert s.status == 200
                sig = await s.json()
                assert sig["version"] == 9
                assert sig["dp"] == 1
                # version 4 (ISSUE 13): the autoscaler echo (null when
                # KAFKA_TPU_AUTOSCALE is off — the default here) and
                # the 1m-window verdict count behind the attainment
                # gauge
                assert sig["autoscaler"] is None
                assert isinstance(sig["slo"]["window_1m_requests"], int)
                assert set(sig["queue"]) >= {"depth", "peak",
                                             "trend_per_s"}
                # version 2 (ISSUE 11): flight-recorder anomaly state is
                # part of the contract — the "don't scale on stale math"
                # guard input
                assert sig["anomalies"]["anomalies_active"] == 0
                assert sig["anomalies"]["active"] == []
                for key in ("anomaly_queue_stall",
                            "anomaly_fetch_starvation",
                            "anomaly_mfu_collapse",
                            "anomaly_prefill_convoy"):
                    assert sig["anomalies"][key] == 0, key
                assert set(sig["batch"]) >= {"occupancy", "active",
                                             "max_batch", "slots_total"}
                for key in ("slo_attainment_1m", "slo_attainment_5m",
                            "goodput_tok_s", "slo_ttft_target_ms"):
                    assert key in sig["slo"], key
                # raw window SECTIONS stay internal to /metrics (the
                # version-4 window_1m_requests scalar is the one
                # deliberate exception)
                assert not any(isinstance(v, dict)
                               for v in sig["slo"].values())
                assert "window_1m" not in sig["slo"]
                assert set(sig["utilization"]) >= {"prefill", "decode",
                                                   "verify"}
                rep = sig["replicas"][0]
                assert rep["replica"] == 0
                assert rep["state"] == "healthy"
                for key in ("active", "waiting", "pages_free",
                            "pages_total", "utilization"):
                    assert key in rep, key
                assert set(rep["utilization"]["decode"]) == {
                    "mfu", "mfu_1m", "hbm_bw_util", "hbm_bw_util_1m",
                    "model_skew",
                }
                assert rep["anomalies_active"] == 0
                # version 3 (ISSUE 12): per-pool section — one
                # "colocated" pool when KAFKA_TPU_DP_ROLES is unset, so
                # the contract shape is role-independent
                assert sig["disagg"] is None
                (pool,) = sig["pools"]
                assert pool["role"] == "colocated"
                assert pool["replicas"] == [0]
                for key in ("queue_depth", "active", "batch_occupancy"):
                    assert key in pool, key
                assert set(pool["utilization"]) == {"prefill", "decode",
                                                    "verify"}
                assert set(pool["utilization"]["decode"]) == {
                    "mfu", "mfu_1m", "hbm_bw_util", "hbm_bw_util_1m",
                }
                assert sig["draining"] is False
                assert sig["admission"]["max_queue_depth"] == 256
            finally:
                await client.close()
                provider.worker.stop()

        asyncio.run(go())


# ----------------------------------------------------------------------
# decode_steps_walked / decode_steps_run (PR 53): the Pallas decode walk's
# whole softmax steps, and those fetched as one run copy a pool
# ----------------------------------------------------------------------

RUN_READER = os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "benchmarks", "layer_metrics",
    "decode_run_step_share.py")


def _layer_metric_reader(path):
    """`read` of a benchmarks/layer_metrics file, loaded with `benchmarks/`
    on the path for the time of the tests that use it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(os.path.dirname(os.path.dirname(path)))
        spec = importlib.util.spec_from_file_location(
            os.path.basename(path)[:-len(".py")], path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        yield module.read


@pytest.fixture(scope="module")
def run_share():
    yield from _layer_metric_reader(RUN_READER)


@pytest.fixture(scope="module")
def walk_model():
    cfg = ModelConfig(name="run-count", vocab_size=128, hidden_size=32,
                      intermediate_size=64, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


def _walk_engine(model, backend, **kw):
    cfg, params = model
    kw = {"num_pages": 160, "max_pages_per_seq": 64, **kw}
    return InferenceEngine(
        cfg, params,
        EngineConfig(max_batch=2, page_size=16, prefill_buckets=(16, 512),
                     attention_backend=backend, **kw),
        kv_dtype=jnp.float32)


@pytest.mark.parametrize("multi_step", [1, 4])
def test_decode_step_counters_are_the_kernels_arithmetic(
        walk_model, run_share, monkeypatch, multi_step):
    """Over two threads that share 300 tokens of prompt (the second attaches
    the first's 18 pages and goes on with pages of its own: its first step is
    no run), the counters equal `decode_step_runs` redone from every
    dispatch's page lists, pass by pass, although the engine never scans a
    list (SequencePages.run_steps)."""
    eng = _walk_engine(walk_model, "pallas", multi_step=multi_step)
    assert eng.cfg.attention_backend == "pallas"
    seen, book = [], eng._book_dispatch

    def spy(toks, members, steps):
        seen.append((steps, [(list(m.seq.pages), m.seq.length)
                             for m in members if m is not None]))
        return book(toks, members, steps)

    monkeypatch.setattr(eng, "_book_dispatch", spy)
    rng = np.random.RandomState(53)
    shared = list(rng.randint(1, 128, size=300))
    shares = []
    for i, n in enumerate((230, 235)):
        before = eng.metrics.snapshot(eng)
        eng.submit(GenRequest(
            request_id=f"r{i}", max_new_tokens=6, prefix_key=f"t{i}",
            prompt_ids=shared + list(rng.randint(1, 128, size=n))))
        eng.run_to_completion()
        shares.append(run_share(
            {"before": before, "after": eng.metrics.snapshot(eng)}))
    walked = run = 0
    for steps, lanes in seen:
        for pages, length in lanes:
            for i in range(steps):
                w, r = decode_step_runs(pages, length + i, None, 16, 64)
                walked, run = walked + w, run + r
    assert walked > 0 and 0 < run < walked
    assert (eng.decode_steps_walked, eng.decode_steps_run) == (walked, run)
    snap = eng.metrics.snapshot(eng)["engine"]
    assert (snap["decode_steps_walked"], snap["decode_steps_run"]) == (
        walked, run)
    # a fresh pool hands out 1, 2, 3, ...: the first thread's step is a run
    assert shares == [100.0, 0.0]


def test_the_first_prompt_after_boots_warm_up_is_one_run(
        walk_model, run_share):
    """server/app.py `_warm_engine` at this size (a prompt a prefill bucket,
    two at once after it, then short decoders), which takes pages and gives
    them back, then what `benchmarks/run.py::fill` sends first: one long
    prompt, reserved in one go.  Its whole steps are runs, every one, because
    the pool hands out its lowest free page first; last-released-first (a
    stack) scrambled the pages the warm-up had held.  And after threads of
    many lengths have come and gone, pages still come lowest first."""
    eng = _walk_engine(walk_model, "pallas", num_pages=400,
                       max_pages_per_seq=128)
    for j, n in enumerate((16, 512)):
        for burst in (1, 2):
            for i in range(burst):
                eng.submit(GenRequest(request_id=f"wb{j}_{burst}_{i}",
                                      prompt_ids=[3] * n, max_new_tokens=1))
            eng.run_to_completion()
    for i in range(3):
        eng.submit(GenRequest(request_id=f"w{i}", prompt_ids=[3] * 8,
                              max_new_tokens=eng.ecfg.multi_step + 2))
    eng.run_to_completion()
    rng = np.random.RandomState(531)
    before = eng.metrics.snapshot(eng)
    eng.submit(GenRequest(
        request_id="p0", max_new_tokens=4, prefix_key="p0",
        prompt_ids=list(rng.randint(1, 128, size=1100))))
    eng.run_to_completion()
    after = eng.metrics.snapshot(eng)
    assert after["engine"]["decode_steps_walked"] > (
        before["engine"]["decode_steps_walked"])
    assert run_share({"before": before, "after": after}) == 100.0
    for i, n in enumerate((40, 300, 90, 700, 20, 530)):
        eng.submit(GenRequest(
            request_id=f"c{i}", max_new_tokens=3 + i,
            prompt_ids=list(rng.randint(1, 128, size=n))))
    eng.run_to_completion()
    pages = eng.pool.alloc(60)
    assert pages == sorted(pages)
    eng.pool.release(pages)
    assert eng.pool.check_consistency() == []


@pytest.mark.parametrize("backend, kw", [
    ("xla", {}), ("pallas", {"kv_quantize": "int8"})])
def test_decode_step_counters_stay_zero_where_no_kernel_walks(
        walk_model, run_share, backend, kw):
    eng = _walk_engine(walk_model, backend, **kw)
    before = eng.metrics.snapshot(eng)
    eng.submit(GenRequest(request_id="a", max_new_tokens=4,
                          prompt_ids=list(range(1, 521))))
    eng.run_to_completion()
    after = eng.metrics.snapshot(eng)
    assert (eng.decode_steps_walked, eng.decode_steps_run) == (0, 0)
    assert after["engine"]["decode_steps_walked"] == 0
    assert after["engine"]["decode_steps_run"] == 0
    assert run_share({"before": before, "after": after}) is None
    assert run_share({"before": {"engine": {}}, "after": after}) is None


# ----------------------------------------------------------------------
# decode_steps_all / decode_steps_ahead (PR 62): every softmax step of the
# Pallas decode walk, and those started before their lane's program began
# ----------------------------------------------------------------------

AHEAD_READER = os.path.join(
    os.path.dirname(RUN_READER), "decode_ahead_step_share.py")


@pytest.fixture(scope="module")
def ahead_share():
    yield from _layer_metric_reader(AHEAD_READER)


@pytest.mark.parametrize("multi_step", [1, 4])
def test_ahead_step_counters_are_the_kernels_arithmetic(
        walk_model, ahead_share, monkeypatch, multi_step):
    """Two threads decoding side by side, 530 and 40 tokens of prompt: the
    counters equal the kernel's walk redone from every dispatch's lanes,
    slot by slot and pass by pass (`decode_step_runs`: the whole steps and a
    last one; a lane in any slot but the call's first finds its first
    RING - 1 steps started by the lane before it)."""
    from kafka_tpu.ops.pallas.paged_attention import RING

    eng = _walk_engine(walk_model, "pallas", multi_step=multi_step)
    seen, book = [], eng._book_dispatch

    def spy(toks, members, steps):
        seen.append((steps, [m and (list(m.seq.pages), m.seq.length)
                             for m in members]))
        return book(toks, members, steps)

    monkeypatch.setattr(eng, "_book_dispatch", spy)
    rng = np.random.RandomState(62)
    before = eng.metrics.snapshot(eng)
    for i, n in enumerate((530, 40)):
        eng.submit(GenRequest(
            request_id=f"r{i}", max_new_tokens=9,
            prompt_ids=list(rng.randint(1, 128, size=n))))
    eng.run_to_completion()
    every = ahead = 0
    for steps, lanes in seen:
        for slot, lane in enumerate(lanes):
            for i in range(steps if lane else 0):
                pages, length = lane
                n = decode_step_runs(pages, length + i, None, 16, 64)[0] + 1
                every += n
                ahead += min(n, RING - 1) if slot else 0
    assert any(lanes[0] and lanes[1] for _, lanes in seen)
    assert 0 < ahead < every
    assert (eng.decode_steps_all, eng.decode_steps_ahead) == (every, ahead)
    after = eng.metrics.snapshot(eng)
    assert (after["engine"]["decode_steps_all"],
            after["engine"]["decode_steps_ahead"]) == (every, ahead)
    assert ahead_share({"before": before, "after": after}) == pytest.approx(
        100.0 * ahead / every)


@pytest.mark.parametrize("backend, kw", [
    ("xla", {}), ("pallas", {"kv_quantize": "int8"})])
def test_ahead_step_counters_stay_zero_where_no_kernel_walks(
        walk_model, ahead_share, backend, kw):
    eng = _walk_engine(walk_model, backend, **kw)
    before = eng.metrics.snapshot(eng)
    eng.submit(GenRequest(request_id="a", max_new_tokens=4,
                          prompt_ids=list(range(1, 521))))
    eng.run_to_completion()
    after = eng.metrics.snapshot(eng)
    assert (eng.decode_steps_all, eng.decode_steps_ahead) == (0, 0)
    assert ahead_share({"before": before, "after": after}) is None
    # the parent: /metrics without the counters
    assert ahead_share({"before": {"engine": {}}, "after": after}) is None


@pytest.mark.parametrize("before, after, want", [
    ((1000, 100), (18000, 2100), 100.0 * 2000 / 17000),  # 2 of 17, 15 of 16
    ((0, 0), (340, 0), 0.0),                             # one lane a call
    ((50, 6), (50, 6), None),                            # no decode step
])
def test_the_ahead_reader_reads_the_window(ahead_share, before, after, want):
    ctx = {k: {"engine": {"decode_steps_all": v[0],
                          "decode_steps_ahead": v[1]}}
           for k, v in (("before", before), ("after", after))}
    got = ahead_share(ctx)
    assert got == (want if want is None else pytest.approx(want))
