"""Scheduler flight recorder (ISSUE 11).

The load-bearing claims:
  * one record per scheduler iteration lands in the ring — dispatch
    kinds, batch composition, cause codes, pressure gauges — and the
    ring wraps allocation-free at the configured size,
  * with KAFKA_TPU_FLIGHT_RING=0 no recorder is built and the dispatch
    paths produce BIT-IDENTICAL outputs to a recorder-on engine (the
    hooks are pure observation),
  * measured dispatch latency is derived from fetch-maturation timing
    and, against an env-overridden roofline, feeds the per-kind
    modeled-vs-measured skew gauge (kafka_tpu_dispatch_model_skew),
  * the anomaly detectors fire edge-triggered on queue stall / fetch
    starvation / MFU collapse / prefill convoy, increment the
    ANOMALY_METRIC_KEYS counters, and surface in /admin/signals,
  * a failpoint-killed engine and a quarantined DP replica each leave a
    readable postmortem JSON (schema asserted, file names sanitized like
    the persisted traces) whose last records explain the failing step,
  * the anomalies section carries exactly ANOMALY_METRIC_KEYS, the
    metric table's view of it,
  * the bench recorder-overhead A/B phase runs.
"""

import dataclasses
import glob
import json
import os
import re
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime import failpoints
from kafka_tpu.runtime.flight_recorder import (
    ANOMALY_KINDS,
    CAUSES,
    FlightRecorder,
    list_postmortems,
    postmortem_dir,
    ring_default,
    sanitize_name,
)
from kafka_tpu.runtime.metrics import (
    ANOMALY_METRIC_KEYS,
    FLIGHT_METRIC_KEYS,
    EngineMetrics,
)


def tiny_cfg():
    return ModelConfig(
        name="flight-test", vocab_size=300, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, dtype="float32",
    )


def make_engine(params=None, cfg=None, **ecfg_kw):
    cfg = cfg or tiny_cfg()
    if params is None:
        params = init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(max_batch=2, page_size=8, num_pages=64, max_pages_per_seq=8,
              prefill_buckets=(8, 16, 32), flight_ring=64)
    kw.update(ecfg_kw)
    return InferenceEngine(cfg, params, EngineConfig(**kw),
                           kv_dtype=jnp.float32)


@pytest.fixture(scope="module")
def shared():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def run_requests(engine, n=3, prompt_len=15, gen=8, seed_base=0):
    for i in range(n):
        engine.submit(GenRequest(
            request_id=f"r{seed_base}-{i}",
            prompt_ids=list(range(5, 5 + prompt_len)),
            max_new_tokens=gen,
        ))
    return engine.run_to_completion()


# ---------------------------------------------------------------------------
# recorder unit behavior
# ---------------------------------------------------------------------------


class _StubEngine:
    """Duck-typed engine for detector unit tests (injected clock)."""

    def __init__(self):
        self.waiting = []
        self.parked = []
        self.metrics = EngineMetrics()
        self._pending = []
        self._pending_steps = 0
        self.pool = SimpleNamespace(free_pages=10, num_pages=16)
        self.prefix_cache = None
        self.kv_tier = None
        self._requests = {}
        self._active = 0

    @property
    def num_active(self):
        return self._active


class TestRecorderUnit:
    def test_ring_wraps_at_size(self):
        fl = FlightRecorder(4)
        eng = _StubEngine()
        for i in range(11):
            fl.note_dispatch(2, 1, 1)
            fl.finish_step(eng, now=float(i))
        recs = fl.records()
        assert fl.next_seq == 11
        assert len(recs) == 4
        assert [r["seq"] for r in recs] == [7, 8, 9, 10]

    def test_stage_resets_between_steps(self):
        fl = FlightRecorder(8)
        eng = _StubEngine()
        fl.note_dispatch(2, 2, 2)
        fl.note_cause("admit", 2)
        fl.finish_step(eng, now=0.0)
        fl.finish_step(eng, now=1.0)
        recs = fl.records()
        assert recs[0]["lanes"] == 2 and recs[0]["causes"] == {"admit": 2}
        assert recs[1]["lanes"] == 0 and recs[1]["causes"] == {}
        assert recs[1]["gap_ms"] == pytest.approx(1000.0)

    def test_every_cause_code_round_trips(self):
        fl = FlightRecorder(4)
        eng = _StubEngine()
        for name in CAUSES:
            fl.note_cause(name)
        fl.finish_step(eng, now=0.0)
        assert fl.records()[-1]["causes"] == {name: 1 for name in CAUSES}

    def test_ring_default_env(self, monkeypatch):
        monkeypatch.setenv("KAFKA_TPU_FLIGHT_RING", "17")
        assert ring_default() == 17
        monkeypatch.setenv("KAFKA_TPU_FLIGHT_RING", "-3")
        assert ring_default() == 0
        monkeypatch.setenv("KAFKA_TPU_FLIGHT_RING", "junk")
        assert ring_default() == 256
        monkeypatch.delenv("KAFKA_TPU_FLIGHT_RING")
        assert ring_default() == 256

    def test_sanitize_name_defangs_traversal(self):
        stem = sanitize_name("../../etc/passwd")
        assert "/" not in stem and ".." not in stem.split(".")[0]
        assert re.fullmatch(r"[A-Za-z0-9._-]+\.[0-9a-f]{12}", stem)
        # distinct hostile inputs stay distinct via the digest
        assert stem != sanitize_name("../../etc/shadow")

    def test_postmortem_dir_resolution(self, monkeypatch, tmp_path):
        monkeypatch.setenv("KAFKA_TPU_FLIGHT_DIR", str(tmp_path))
        assert postmortem_dir() == str(tmp_path)
        monkeypatch.setenv("KAFKA_TPU_FLIGHT_DIR", "")  # explicit off
        assert postmortem_dir() is None


class TestDetectorsUnit:
    def _recorder(self, monkeypatch, stall="0.5"):
        monkeypatch.setenv("KAFKA_TPU_ANOMALY_STALL_S", stall)
        return FlightRecorder(16)

    def test_queue_stall_fires_and_clears(self, monkeypatch):
        fl = self._recorder(monkeypatch)
        eng = _StubEngine()
        # arm: one dispatch-bearing iteration
        fl.note_dispatch(2, 1, 1)
        fl.finish_step(eng, now=0.0)
        # queue sits undisipatched past the stall bound
        eng.waiting = [object()]
        fl.finish_step(eng, now=1.0)
        assert eng.metrics.anomaly_queue_stall == 1
        active = fl.active_anomalies()
        assert [a["kind"] for a in active] == ["queue_stall"]
        # level-holds: no double count
        fl.finish_step(eng, now=2.0)
        assert eng.metrics.anomaly_queue_stall == 1
        # a dispatch arriving AFTER a >stall gap is still part of the
        # same episode (chronic slow cadence): one edge, stays active
        fl.note_dispatch(2, 1, 1)
        fl.finish_step(eng, now=2.6)
        assert eng.metrics.anomaly_queue_stall == 1
        assert [a["kind"] for a in fl.active_anomalies()] == ["queue_stall"]
        # normal cadence resumes: the episode ends
        fl.note_dispatch(2, 1, 1)
        fl.finish_step(eng, now=2.7)
        assert fl.active_anomalies() == []
        # re-fires on the next stall (edge re-arm)
        fl.finish_step(eng, now=5.0)
        assert eng.metrics.anomaly_queue_stall == 2

    def test_chronic_slow_cadence_is_one_episode(self, monkeypatch):
        """A queue stepping every 2x the stall bound — each iteration
        dispatching — must count ONE firing and stay continuously
        active (the autoscaler's poll must see it), not fire+clear per
        iteration."""
        fl = self._recorder(monkeypatch)  # stall_s = 0.5
        eng = _StubEngine()
        eng.waiting = [object()]
        fl.note_dispatch(2, 1, 1)
        fl.finish_step(eng, now=0.0)
        for i in range(1, 6):
            fl.note_dispatch(2, 1, 1)
            fl.finish_step(eng, now=i * 1.0)
            assert [a["kind"] for a in fl.active_anomalies()] == \
                ["queue_stall"], i
        assert eng.metrics.anomaly_queue_stall == 1

    def test_gate_rejects_drain_into_ring(self, monkeypatch):
        """Gate-level 429s (event-loop thread) land in the next
        committed record's reject cause — an overload burst's ring must
        show the shed traffic the serving gate absorbed."""
        fl = self._recorder(monkeypatch)
        eng = _StubEngine()
        for _ in range(3):
            fl.note_gate_reject()
        fl.finish_step(eng, now=0.0)
        assert fl.records()[-1]["causes"] == {"reject": 3}
        fl.finish_step(eng, now=0.1)
        assert fl.records()[-1]["causes"] == {}  # drained, not re-counted

    def test_queue_stall_not_armed_before_first_dispatch(self, monkeypatch):
        fl = self._recorder(monkeypatch)
        eng = _StubEngine()
        eng.waiting = [object()]
        fl.finish_step(eng, now=100.0)  # cold start: admission, not stall
        assert eng.metrics.anomaly_queue_stall == 0

    def test_fetch_starvation(self, monkeypatch):
        fl = self._recorder(monkeypatch)
        eng = _StubEngine()
        eng._pending = [SimpleNamespace(t0=0.0)]
        fl.finish_step(eng, now=1.0)
        assert eng.metrics.anomaly_fetch_starvation == 1
        eng._pending = []
        fl.finish_step(eng, now=1.1)
        assert fl.active_anomalies() == []

    def test_prefill_convoy(self, monkeypatch):
        monkeypatch.setenv("KAFKA_TPU_ANOMALY_CONVOY_S", "0.5")
        fl = FlightRecorder(16)
        eng = _StubEngine()
        eng.waiting = [object()]
        for i, t in enumerate((0.0, 0.3, 0.6)):
            fl.note_prefill(1, 8)
            fl.finish_step(eng, now=t)
        assert eng.metrics.anomaly_prefill_convoy == 1
        # a decode dispatch breaks the convoy
        fl.note_prefill(1, 8)
        fl.note_dispatch(2, 1, 1)
        fl.finish_step(eng, now=0.9)
        assert fl.active_anomalies() == []

    def test_mfu_collapse(self, monkeypatch):
        fl = FlightRecorder(16)
        eng = _StubEngine()
        m = eng.metrics
        m.set_roofline(1e12, 1e12, "env")
        u = m.util["decode"]
        u.busy_s = 100.0
        u.flops = 50.0 * 1e12  # since-boot mfu = 0.5
        now = time.monotonic()
        # last minute: busy but nearly no flops -> mfu_1m ~ 0.005
        m._util_window["decode"].add((5e9, 0.0, 2.0), now=now)
        fl._mfu_check_t = now - 2.0  # bypass the 1 Hz throttle
        fl.finish_step(eng, now=now)
        assert m.anomaly_mfu_collapse == 1
        assert [a["kind"] for a in fl.active_anomalies()] == ["mfu_collapse"]


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


class TestEngineIntegration:
    def test_ring_records_dispatches_and_causes(self, shared):
        cfg, params = shared
        eng = make_engine(params, cfg)
        run_requests(eng, n=3)
        recs = eng.flight.records()
        assert recs, "no flight records after a full run"
        assert recs == sorted(recs, key=lambda r: r["seq"])
        kinds = {k for r in recs for k in r["kinds"]}
        assert {"prefill", "decode"} <= kinds
        causes = {}
        for r in recs:
            for c, n in r["causes"].items():
                causes[c] = causes.get(c, 0) + n
        # 3 requests over a 2-slot batch: two slot admissions, one park
        assert causes.get("admit", 0) >= 2
        assert causes.get("park", 0) >= 1
        assert causes.get("admit_parked", 0) >= 1
        # pressure gauges are live
        assert all(r["pages_total"] == 64 for r in recs)
        # measured fetch-maturation timing landed on some records
        assert any(r["measured_ms"] > 0 for r in recs)
        # the metrics snapshot exports the ring state
        snap = eng.metrics.snapshot(eng, reset_peak=False)
        assert snap["flight"]["flight_ring_size"] == 64
        assert snap["flight"]["flight_records"] == eng.flight.next_seq > 0

    def test_preempt_cause_recorded(self, shared):
        cfg, params = shared
        # starve the pool so decode growth must preempt: 2 lanes, pages
        # for barely one window.  Ring sized to hold the WHOLE run — the
        # preempt happens early and must not wrap away before the assert.
        eng = make_engine(params, cfg, num_pages=17, max_pages_per_seq=16,
                          prefix_cache_entries=0, max_parked=0,
                          flight_ring=4096)
        for i in range(2):
            eng.submit(GenRequest(
                request_id=f"p{i}", prompt_ids=list(range(5, 60)),
                max_new_tokens=80,
            ))
        eng.run_to_completion()
        assert eng.metrics.requests_preempted > 0, "scenario lost pressure"
        causes = {}
        for r in eng.flight.records():
            for c, n in r["causes"].items():
                causes[c] = causes.get(c, 0) + n
        assert causes.get("preempt", 0) >= 1

    def test_measured_skew_with_roofline(self, shared, monkeypatch):
        cfg, params = shared
        monkeypatch.setenv("KAFKA_TPU_PEAK_TFLOPS", "0.001")
        monkeypatch.setenv("KAFKA_TPU_PEAK_HBM_GBPS", "1")
        eng = make_engine(params, cfg)
        assert eng.metrics.peak_source == "env"
        run_requests(eng, n=2, gen=12)
        # a second, longer run on compiled programs: deeper than
        # fetch_lag, so the drain blocks on the head fetch and the next
        # poll sees completed decodes (a cold 12-step run can end before
        # the first decode is observed ready)
        run_requests(eng, n=2, gen=40, seed_base=1)
        util = eng.metrics.utilization_snapshot()
        dec = util["decode"]
        assert dec["measured_dispatches"] > 0
        assert dec["measured_busy_s"] > 0
        assert dec["modeled_busy_s"] > 0
        assert dec["model_skew"] > 0
        from kafka_tpu.server.prometheus import render_prometheus

        text = render_prometheus(eng.metrics.snapshot(eng))
        assert 'kafka_tpu_dispatch_model_skew{kind="decode"}' in text
        assert 'kafka_tpu_measured_dispatches_total{kind="decode"}' in text

    def test_ring_off_is_bit_identical(self, shared):
        cfg, params = shared
        outs = {}
        for ring in (0, 32):
            eng = make_engine(params, cfg, flight_ring=ring)
            if ring == 0:
                assert eng.flight is None
            done = run_requests(eng, n=3, gen=10)
            outs[ring] = {k: v.output_ids for k, v in done.items()}
        assert outs[0] == outs[32]

    def test_flight_section_absent_when_off(self, shared):
        cfg, params = shared
        eng = make_engine(params, cfg, flight_ring=0)
        snap = eng.metrics.snapshot(eng, reset_peak=False)
        assert "flight" not in snap
        # anomaly counters still export (zeros) — the registry holds
        assert snap["anomalies"]["anomalies_active"] == 0

    def test_negative_ring_rejected(self, shared):
        cfg, params = shared
        with pytest.raises(ValueError, match="flight_ring"):
            make_engine(params, cfg, flight_ring=-1)


class TestQueueStallEndToEnd:
    def test_delay_failpoint_trips_detector_and_counter(
        self, shared, monkeypatch
    ):
        """Acceptance (ISSUE 11): a synthetic queue stall — the engine
        stepping slowly while a request waits — trips the queue_stall
        detector and the kafka_tpu_anomalies_total counter."""
        cfg, params = shared
        monkeypatch.setenv("KAFKA_TPU_ANOMALY_STALL_S", "0.05")
        eng = make_engine(params, cfg, max_batch=1, max_parked=0)
        eng.submit(GenRequest(request_id="fg", prompt_ids=list(range(5, 20)),
                              max_new_tokens=60))
        # warm the decode path so the delayed iterations below measure
        # scheduling, not XLA compiles
        for _ in range(6):
            eng.step()
        eng.submit(GenRequest(request_id="queued",
                              prompt_ids=list(range(5, 20)),
                              max_new_tokens=4))
        with failpoints.armed("engine.step", "delay", "0.1", count=4):
            for _ in range(6):
                eng.step()
                if eng.metrics.anomaly_queue_stall:
                    break
        assert eng.metrics.anomaly_queue_stall >= 1
        from kafka_tpu.server.prometheus import render_prometheus

        text = render_prometheus(eng.metrics.snapshot(eng))
        m = re.search(
            r'kafka_tpu_anomalies_total\{kind="queue_stall"\} (\d+)', text
        )
        assert m and int(m.group(1)) >= 1
        # /admin/signals carries the anomaly section (version 2 contract)
        run_requests(eng, n=0)  # drain helper no-op; finish the run
        eng.run_to_completion()


# ---------------------------------------------------------------------------
# postmortem capture
# ---------------------------------------------------------------------------


POSTMORTEM_NAME_RE = re.compile(
    r"postmortem\.[A-Za-z0-9._-]+\.[0-9a-f]{12}\.flight\.json"
)


def _assert_postmortem_schema(pm):
    assert pm["version"] == 1
    assert pm["kind"] == "flight_postmortem"
    assert isinstance(pm["records"], list) and pm["records"]
    for rec in pm["records"]:
        for key in ("seq", "t", "kinds", "lanes", "toks", "queue_depth",
                    "pages_free", "causes", "measured_ms", "modeled_ms"):
            assert key in rec, key
    assert isinstance(pm["lanes"], list)
    for lane in pm["lanes"]:
        for key in ("request_id", "state", "slot", "dispatched",
                    "drained", "output_tokens"):
            assert key in lane, key
    assert set(pm["anomalies"]) == set(ANOMALY_KINDS)
    assert "requests" in pm["metrics"]


class TestPostmortem:
    def test_step_error_storm_leaves_readable_dump(
        self, shared, tmp_path, monkeypatch
    ):
        """Acceptance: a failpoint-killed engine leaves a postmortem
        whose last records explain the failing step, retrievable after
        restart (read back from disk alone)."""
        cfg, params = shared
        monkeypatch.setenv("KAFKA_TPU_FLIGHT_DIR", str(tmp_path))
        eng = make_engine(params, cfg)
        eng.submit(GenRequest(request_id="victim",
                              prompt_ids=list(range(5, 25)),
                              max_new_tokens=30))
        for _ in range(3):
            eng.step()
        with failpoints.armed("engine.step", "error", count=2):
            for _ in range(2):
                with pytest.raises(failpoints.FailpointError):
                    eng.step()
                eng.recover_from_failure()
        files = glob.glob(str(tmp_path / "*.flight.json"))
        assert files, "no postmortem written"
        for f in files:
            assert POSTMORTEM_NAME_RE.fullmatch(os.path.basename(f))
        # "after restart": nothing but the file — fresh parse from disk
        pm = json.loads(open(sorted(files)[0]).read())
        _assert_postmortem_schema(pm)
        assert pm["reason"] == "engine_failure"
        # the dump explains the pre-failure scheduling: the victim lane
        # is present and the records carry its dispatch history
        lanes = {ln["request_id"]: ln for ln in pm["lanes"]}
        assert "victim" in lanes
        assert lanes["victim"]["dispatched"] > 0
        assert any(r["kinds"] for r in pm["records"])
        assert list_postmortems(str(tmp_path))
        # the engine keeps serving afterwards and counts the dumps
        assert eng.flight.postmortems == len(files)
        snap = eng.metrics.snapshot(eng, reset_peak=False)
        assert snap["flight"]["flight_postmortems"] == len(files)

    def test_quarantine_dumps_postmortem(
        self, shared, tmp_path, monkeypatch
    ):
        from kafka_tpu.runtime.dp_router import DataParallelEngines

        cfg, params = shared
        monkeypatch.setenv("KAFKA_TPU_FLIGHT_DIR", str(tmp_path))
        dp = DataParallelEngines(
            cfg, params,
            EngineConfig(max_batch=2, page_size=8, num_pages=64,
                         max_pages_per_seq=8, prefill_buckets=(8, 16, 32),
                         flight_ring=32),
            dp=1, tp=1, quarantine_threshold=2, kv_dtype=jnp.float32,
        )
        assert dp.engines[0].flight.replica == 0
        dp.submit(GenRequest(request_id="q-victim",
                             prompt_ids=list(range(5, 20)),
                             max_new_tokens=20))
        dp.step()
        with failpoints.armed("engine.step", "error", count=2):
            for _ in range(2):
                with pytest.raises(failpoints.FailpointError):
                    dp.step()
        assert dp.health[0].state == "quarantined"
        files = glob.glob(str(tmp_path / "*.flight.json"))
        assert files
        pms = [json.loads(open(f).read()) for f in files]
        reasons = {pm["reason"] for pm in pms}
        assert "quarantine" in reasons
        pm = next(p for p in pms if p["reason"] == "quarantine")
        _assert_postmortem_schema(pm)
        assert pm["replica"] == 0

    def test_dump_skipped_without_dir(self, shared, monkeypatch):
        cfg, params = shared
        monkeypatch.setenv("KAFKA_TPU_FLIGHT_DIR", "")
        monkeypatch.delenv("KAFKA_TPU_TRACE_PERSIST_DIR", raising=False)
        monkeypatch.delenv("KAFKA_TPU_KV_DISK_TIER_DIR", raising=False)
        eng = make_engine(params, cfg)
        assert eng.dump_postmortem("test") is None


# ---------------------------------------------------------------------------
# registries + bench smoke
# ---------------------------------------------------------------------------


class TestFlightRegistry:
    """The anomalies section against the metric table's view of it (the
    both-directions check is tests/test_prometheus.py::TestMetricTable)."""

    def test_anomaly_snapshot_matches_registry(self):
        snap = EngineMetrics().anomalies_snapshot()
        flat = {k for k in snap if k != "active"}
        assert flat == set(ANOMALY_METRIC_KEYS)

    def test_anomaly_fields_in_engine_export_lint(self):
        from kafka_tpu.runtime.metrics import ENGINE_METRIC_EXPORTS

        fields = {f.name for f in dataclasses.fields(EngineMetrics)}
        for key in ANOMALY_METRIC_KEYS:
            if key == "anomalies_active":
                continue  # gauge derived from the recorder, not a field
            assert key in fields
            assert ENGINE_METRIC_EXPORTS[key] == ("anomalies", key)

    def test_flight_keys_render(self, shared):
        cfg, params = shared
        eng = make_engine(params, cfg)
        from kafka_tpu.server.prometheus import render_prometheus

        text = render_prometheus(eng.metrics.snapshot(eng))
        assert "kafka_tpu_flight_ring_size 64" in text
        assert "kafka_tpu_flight_records_total" in text
        assert "kafka_tpu_flight_postmortems_total" in text
        assert "kafka_tpu_anomalies_active 0" in text


class TestServerEndpoints:
    def _app_client(self, provider, tmp_path, **cfg_kw):
        from aiohttp.test_utils import TestClient, TestServer
        from kafka_tpu.db.local import LocalDBClient
        from kafka_tpu.server.app import create_app
        from kafka_tpu.server.config import ServingConfig

        async def build():
            app = await create_app(
                cfg=ServingConfig(db_path=str(tmp_path / "f.db"), **cfg_kw),
                llm_provider=provider,
                db=LocalDBClient(str(tmp_path / "f.db")),
                tools=[],
            )
            client = TestClient(TestServer(app))
            await client.start_server()
            return client

        return build

    def test_debug_flight_serves_live_ring(self, shared, tmp_path):
        import asyncio

        from kafka_tpu.llm import TPULLMProvider
        from kafka_tpu.models.tokenizer import ByteTokenizer

        cfg, params = shared
        eng = make_engine(params, cfg)
        run_requests(eng, n=2, gen=6)
        provider = TPULLMProvider(eng, ByteTokenizer(), model_name="m")
        build = self._app_client(provider, tmp_path)

        async def go():
            client = await build()
            try:
                r = await client.get("/debug/flight/0")
                assert r.status == 200
                payload = await r.json()
                assert payload["ring_size"] == 64
                assert payload["records"]
                assert set(payload["records"][-1]) >= {
                    "seq", "t", "kinds", "causes", "measured_ms",
                }
                assert payload["causes"] == list(CAUSES)
                # out-of-range and non-integer replicas answer cleanly
                assert (await client.get("/debug/flight/9")).status == 404
                assert (await client.get("/debug/flight/x")).status == 400
            finally:
                await client.close()
                provider.worker.stop()

        asyncio.run(go())

    def test_debug_flight_404_when_disabled(self, shared, tmp_path):
        import asyncio

        from kafka_tpu.llm import TPULLMProvider
        from kafka_tpu.models.tokenizer import ByteTokenizer

        cfg, params = shared
        eng = make_engine(params, cfg, flight_ring=0)
        provider = TPULLMProvider(eng, ByteTokenizer(), model_name="m")
        build = self._app_client(provider, tmp_path)

        async def go():
            client = await build()
            try:
                r = await client.get("/debug/flight/0")
                assert r.status == 404
                assert "disabled" in (await r.json())["error"]
            finally:
                await client.close()
                provider.worker.stop()

        asyncio.run(go())

    def test_profile_requires_machine_token(self, shared, tmp_path,
                                            monkeypatch):
        """ISSUE 11 satellite: with an api_token configured,
        POST /debug/profile demands exactly that token — and a granted
        capture reports the flight window covering it."""
        import asyncio

        from kafka_tpu.llm import TPULLMProvider
        from kafka_tpu.models.tokenizer import ByteTokenizer

        monkeypatch.setenv("KAFKA_TPU_PROFILING", "1")
        cfg, params = shared
        eng = make_engine(params, cfg)
        provider = TPULLMProvider(eng, ByteTokenizer(), model_name="m")
        build = self._app_client(provider, tmp_path, api_token="sekrit")

        async def go():
            client = await build()
            hdr = {"Authorization": "Bearer sekrit"}
            try:
                # wrong/missing token: 401 even though the middleware
                # would have been satisfied by a session token
                r = await client.post("/debug/profile",
                                      json={"seconds": 0.1})
                assert r.status == 401
                r = await client.post(
                    "/debug/profile", json={"seconds": 0.1},
                    headers={"Authorization": "Bearer wrong"},
                )
                assert r.status == 401
                r = await client.post("/debug/profile",
                                      json={"seconds": 0.1}, headers=hdr)
                assert r.status == 200
                body = await r.json()
                fw = body["flight_window"]
                assert fw is not None
                assert fw["t_end"] >= fw["t_start"]
                # the traced interval itself, inside the bracket that
                # also holds start_trace / stop_trace
                assert (fw["t_start"] <= fw["t_trace_on"]
                        <= fw["t_trace_off"] <= fw["t_end"])
                assert fw["t_trace_off"] - fw["t_trace_on"] >= 0.09
                reps = {w["replica"]: w for w in fw["replicas"]}
                assert 0 in reps
                assert reps[0]["end_seq"] >= reps[0]["start_seq"]
            finally:
                await client.close()
                provider.worker.stop()

        asyncio.run(go())

    def test_profile_guard_outlives_a_cancelled_handler(self, monkeypatch):
        """stop_trace runs in the executor: a handler cancelled while it
        waits there must leave the capture guard set until the thread
        has returned, or a second capture would call start_trace beside
        a running stop_trace."""
        import asyncio
        import threading
        import types

        import jax

        from kafka_tpu.server import app as app_mod

        monkeypatch.setenv("KAFKA_TPU_PROFILING", "1")
        stop_entered, release = threading.Event(), threading.Event()
        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: calls.append("start"))

        def slow_stop():
            calls.append("stop")
            stop_entered.set()
            assert release.wait(10.0)

        monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)

        class Req:
            headers = {}
            app = {app_mod.STATE_KEY: {
                "cfg": types.SimpleNamespace(api_token=None),
                "llm": object(),
            }}

            async def json(self):
                return {"seconds": 0.1}

        async def go():
            first = asyncio.ensure_future(app_mod.capture_profile(Req()))
            while not stop_entered.is_set():
                await asyncio.sleep(0.01)
            first.cancel()
            with pytest.raises(asyncio.CancelledError):
                await first
            # the thread is still inside stop_trace: still busy
            assert app_mod._PROFILE_BUSY is True
            second = await app_mod.capture_profile(Req())
            assert second.status == 409
            release.set()
            for _ in range(500):
                if not app_mod._PROFILE_BUSY:
                    break
                await asyncio.sleep(0.01)
            assert app_mod._PROFILE_BUSY is False
            assert calls == ["start", "stop"]
            # and an uncancelled capture still clears it on its way out
            third = await app_mod.capture_profile(Req())
            assert third.status == 200
            assert app_mod._PROFILE_BUSY is False

        try:
            asyncio.run(go())
        finally:
            release.set()


class TestBenchSmoke:
    def test_flight_overhead_phase_runs(self, shared):
        import random
        import sys

        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from bench import flight_overhead_phase

        cfg, params = shared
        eng = make_engine(params, cfg)
        args = SimpleNamespace(quick=True, batch=2, prompt_len=16)
        out = flight_overhead_phase(eng, cfg, args, random.Random(0))
        assert out["tok_s_on"] > 0 and out["tok_s_off"] > 0
        assert 0.0 <= out["regression_frac"] < 1.0
        # the phase restores the engine's recorder
        assert eng.flight is not None
