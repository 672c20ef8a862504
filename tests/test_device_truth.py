"""Device-truth telemetry (ISSUE 18).

The load-bearing claims:
  * every XLA compilation lands in the compile observatory's bounded
    ring — label, wall seconds, cache disposition, serving phase — and
    the storm detector holds only for first_traffic-phase churn,
  * with KAFKA_TPU_COMPILE_RING=0 nothing is constructed: instrument()
    returns the function object unchanged and engine outputs are
    BIT-IDENTICAL to an observed build,
  * the MemoryMonitor reconciles measured device bytes against the
    boot MemoryPlan (worst-device aggregation, plan_skew, watermark
    pressure) and synthesizes plan-sourced samples on chips without
    memory_stats so CPU CI runs the same export path,
  * the compiles / memory sections carry their views of the metric
    table and render,
  * GET /debug/compiles answers 404-when-off and serves the live
    payload when on; /admin/signals is version 7 with the
    compiles/memory sections,
  * the bench device_truth phase (warm-vs-cold rebuild outage) runs.

The every-Nth-step kernel sampler (/debug/kernels, kernel_skew) that
shared this file was removed in PR 24: /debug/profile is the one device
trace and benchmarks/scope_reduce.py reads it by component.
"""

import os
import time
from types import SimpleNamespace

import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime import compile_log
from kafka_tpu.runtime.compile_log import CompileObservatory
from kafka_tpu.runtime.metrics import (
    COMPILE_METRIC_KEYS,
    EngineMetrics,
)
from kafka_tpu.runtime.planner import MemoryMonitor


def tiny_cfg():
    # dims deliberately distinct from every other test module so this
    # module's first dispatches MISS step_programs' process cache and really
    # compile (the observatory integration tests depend on that)
    return ModelConfig(
        name="device-truth-test", vocab_size=322, hidden_size=64,
        intermediate_size=144, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, dtype="float32",
    )


def make_engine(params=None, cfg=None, **ecfg_kw):
    cfg = cfg or tiny_cfg()
    if params is None:
        params = init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(max_batch=2, page_size=8, num_pages=64, max_pages_per_seq=8,
              prefill_buckets=(8, 16, 32))
    kw.update(ecfg_kw)
    return InferenceEngine(cfg, params, EngineConfig(**kw),
                           kv_dtype=jnp.float32)


@pytest.fixture(scope="module")
def shared():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(autouse=True)
def _reset_observatory():
    """The observatory is a process singleton; never leak one into
    other tests (its listeners are no-ops while the singleton is
    None)."""
    compile_log.reset_for_tests()
    yield
    compile_log.reset_for_tests()


def run_requests(engine, n=3, prompt_len=15, gen=8, seed_base=0):
    for i in range(n):
        engine.submit(GenRequest(
            request_id=f"dt{seed_base}-{i}",
            prompt_ids=list(range(5, 5 + prompt_len)),
            max_new_tokens=gen,
        ))
    return engine.run_to_completion()


# ---------------------------------------------------------------------------
# compile observatory unit behavior
# ---------------------------------------------------------------------------


class TestObservatoryUnit:
    def test_ring_wraps_at_size(self):
        obs = CompileObservatory(4)
        for i in range(7):
            obs.record(f"fn{i}", 0.1, now=100.0 + i)
        recs = obs.records()
        assert len(recs) == 4
        assert [r["seq"] for r in recs] == [3, 4, 5, 6]
        assert obs.compiles_total == 7
        assert obs.next_seq == 7

    def test_zero_size_rejected(self):
        with pytest.raises(ValueError, match="size"):
            CompileObservatory(0)

    def test_ring_default_env(self, monkeypatch):
        monkeypatch.delenv(compile_log.RING_ENV, raising=False)
        assert compile_log.ring_default() == 256
        monkeypatch.setenv(compile_log.RING_ENV, "0")
        assert compile_log.ring_default() == 0
        monkeypatch.setenv(compile_log.RING_ENV, "-5")
        assert compile_log.ring_default() == 0
        monkeypatch.setenv(compile_log.RING_ENV, "banana")
        assert compile_log.ring_default() == 256
        monkeypatch.setenv(compile_log.RING_ENV, "17")
        assert compile_log.ring_default() == 17

    def test_cache_disposition_defaults(self):
        obs = CompileObservatory(8)
        obs.record("a", 0.2)
        assert obs.records()[-1]["cache"] == "off"
        obs.cache_dir = "/tmp/cache"
        obs.record("b", 0.2)
        assert obs.records()[-1]["cache"] == "miss"
        # jax fires the cache-hit event BEFORE the compile-duration event
        # closes: the mark applies to the NEXT record on this thread only
        obs.mark_cache_hit()
        assert obs.records()[-1]["cache"] == "miss"
        compile_log._OBS, prior = obs, compile_log._OBS
        try:
            compile_log._on_duration_event(compile_log._COMPILE_EVENT, 0.2)
            compile_log._on_duration_event(compile_log._COMPILE_EVENT, 0.2)
        finally:
            compile_log._OBS = prior
        assert [r["cache"] for r in obs.records()[-2:]] == ["hit", "miss"]
        # (`store`: loads by runtime/program_store.py, in place of a compile)
        assert obs.by_cache == {"hit": 1, "miss": 2, "off": 1, "store": 0}

    def test_phase_attribution(self):
        obs = CompileObservatory(8)
        assert obs.phase == "boot"
        obs.record("boot_fn", 0.1)
        obs.phase = "warmup"
        obs.record("warm_fn", 0.1)
        obs.phase = "rebuild"
        obs.record("rebuild_fn", 0.1)
        assert obs.by_phase["boot"] == 1
        assert obs.by_phase["warmup"] == 1
        assert obs.by_phase["rebuild"] == 1
        assert obs.by_phase["first_traffic"] == 0

    def test_storm_only_in_first_traffic(self, monkeypatch):
        monkeypatch.setenv(compile_log.STORM_N_ENV, "3")
        monkeypatch.setenv(compile_log.STORM_S_ENV, "60")
        obs = CompileObservatory(16)
        # boot/warmup/rebuild compiles never count toward a storm
        for phase in ("boot", "warmup", "rebuild"):
            obs.phase = phase
            for i in range(4):
                obs.record("x", 0.1, now=100.0 + i)
        assert not obs.storm_active(now=105.0)
        assert obs.storms_total == 0
        # three first_traffic compiles inside the window = a storm,
        # counted ONCE per episode (edge semantics on storms_total)
        obs.phase = "first_traffic"
        for i in range(3):
            obs.record("leak", 0.1, now=200.0 + i)
        assert obs.storm_active(now=203.0)
        assert obs.storms_total == 1
        obs.record("leak", 0.1, now=204.0)
        assert obs.storms_total == 1
        # the level clears once the window slides past the churn
        assert not obs.storm_active(now=500.0)
        # ...and a fresh burst is a SECOND counted episode
        for i in range(3):
            obs.record("leak2", 0.1, now=600.0 + i)
        assert obs.storm_active(now=603.0)
        assert obs.storms_total == 2

    def test_snapshot_and_sections_shape(self):
        obs = CompileObservatory(8)
        obs.record("fn", 0.5, now=100.0)
        snap = obs.snapshot()
        assert snap["ring_size"] == 8
        assert snap["totals"]["compiles"] == 1
        assert snap["totals"]["seconds"] == pytest.approx(0.5)
        assert set(snap["totals"]["by_phase"]) == set(compile_log.PHASES)
        assert set(snap["records"][0]) == {
            "seq", "t", "label", "seconds", "cache", "phase",
        }
        msec = obs.metrics_section()
        assert set(msec) == set(COMPILE_METRIC_KEYS) | {
            "by_cache", "by_phase",
            "trace_seconds_by_phase", "lower_seconds_by_phase",
        }
        ssec = obs.signals_section()
        assert ssec["storm_active"] is False
        assert ssec["recent"][-1]["label"] == "fn"
        assert {"ring_size", "phase", "cache_dir", "storm_n",
                "storm_window_s"} <= set(ssec)

    def test_module_singleton_lifecycle(self):
        assert compile_log.get() is None
        assert compile_log.init(0) is None  # 0 = off builds nothing
        obs = compile_log.init(4)
        assert obs is not None and compile_log.get() is obs
        assert compile_log.init(8) is obs  # idempotent
        compile_log.set_phase("warmup")
        assert compile_log.get_phase() == "warmup"
        compile_log.configure_cache("/tmp/x")
        assert obs.cache_dir == "/tmp/x"
        compile_log.configure_cache("")
        assert obs.cache_dir is None

    def test_instrument_off_returns_fn_unchanged(self):
        # the byte-identical-off contract at its sharpest: the SAME
        # function object, not a transparent wrapper
        def fn():
            return 41

        assert compile_log.get() is None
        assert compile_log.instrument("x", fn) is fn

    def test_instrument_fallback_records_first_call(self):
        compile_log.init(8)
        obs = compile_log.get()
        calls = []

        def fn(v):
            calls.append(v)
            return v + 1

        wrapped = compile_log.instrument("unit_fn", fn)
        assert wrapped is not fn and wrapped.__wrapped__ is fn
        before = obs.compiles_total
        assert wrapped(1) == 2
        # a plain python fn emits no monitoring event, so the
        # wall-clock fallback records exactly the first call
        assert obs.compiles_total == before + 1
        assert obs.records()[-1]["label"] == "unit_fn"
        assert wrapped(2) == 3
        assert obs.compiles_total == before + 1


# ---------------------------------------------------------------------------
# compile observatory against a real engine
# ---------------------------------------------------------------------------


class TestObservatoryEngine:
    def test_engine_compiles_land_in_ring(self, shared):
        cfg, params = shared
        compile_log.init(64)
        compile_log.set_phase("warmup")
        eng = make_engine(params, cfg)
        done = run_requests(eng, n=2, gen=6)
        assert len(done) == 2
        obs = compile_log.get()
        assert obs.compiles_total > 0
        labels = {r["label"] for r in obs.records()}
        # the instrumented step_programs cache misses attribute their labels
        assert any(lbl != "?" for lbl in labels)
        assert all(r["phase"] == "warmup" for r in obs.records())
        assert obs.by_phase["warmup"] == obs.compiles_total
        # no storm: warmup compiles are the expected cost of the phase
        assert not obs.storm_active()

    def test_off_is_bit_identical(self, shared):
        cfg, params = shared
        outs = {}
        for ring in (0, 32):
            compile_log.reset_for_tests()
            if ring:
                compile_log.init(ring)
            eng = make_engine(params, cfg)
            done = run_requests(eng, n=3, gen=10, seed_base=ring)
            outs[ring] = [done[f"dt{ring}-{i}"].output_ids
                          for i in range(3)]
        assert outs[0] == outs[32]


# ---------------------------------------------------------------------------
# live HBM accounting (MemoryMonitor)
# ---------------------------------------------------------------------------


class _Dev:
    def __init__(self, i, in_use, peak, limit):
        self.id = i
        self._stats = {"bytes_in_use": in_use, "peak_bytes_in_use": peak,
                       "bytes_limit": limit}

    def memory_stats(self):
        return dict(self._stats)


def _plan(total=100, usable=120):
    return SimpleNamespace(
        total_bytes=total, usable_bytes=usable, weight_bytes=60,
        kv_pool_bytes=25, activation_bytes=10, grammar_table_bytes=0,
    )


class TestMemoryMonitor:
    def test_worst_device_aggregation(self):
        mm = MemoryMonitor(
            [_Dev(0, 80, 90, 120), _Dev(1, 70, 95, 110)],
            plan=_plan(total=100), poll_s=0.0,
        )
        assert mm.section() is None  # no sample before the first poll
        sec = mm.poll(now=0.0)
        assert sec["source"] == "device"
        assert sec["hbm_bytes_in_use"] == 80    # max across devices
        assert sec["hbm_bytes_peak"] == 95      # max across devices
        assert sec["hbm_bytes_limit"] == 110    # min across devices
        assert sec["hbm_headroom_bytes"] == 30
        assert sec["hbm_plan_skew"] == pytest.approx(0.8)
        assert len(sec["devices"]) == 2
        assert mm.headroom_frac() == pytest.approx(30 / 110)
        # attribution: plan line items + the measured residual
        comp = sec["hbm_component_bytes"]
        assert comp["weights"] == 60 and comp["kv_pool"] == 25
        assert comp["unattributed"] == 80 - 100
        # default device watermark (3%): 30 >= 0.03 * 110, no pressure
        assert sec["hbm_pressure"] == 0 and not mm.pressure()

    def test_explicit_watermark_pressure(self, monkeypatch):
        monkeypatch.setenv("KAFKA_TPU_HBM_WATERMARK", "0.5")
        mm = MemoryMonitor([_Dev(0, 80, 80, 110)],
                           plan=_plan(), poll_s=0.0)
        sec = mm.poll(now=0.0)
        assert sec["hbm_pressure"] == 1 and mm.pressure()

    def test_plan_source_on_cpu(self, monkeypatch):
        # devices without memory_stats (CPU): the sample synthesizes
        # from the plan with skew pinned 1.0, and the watermark stays
        # DISABLED unless explicitly set — a barely-fitting plan must
        # not hold hbm_pressure forever on predicted numbers
        mm = MemoryMonitor([object()], plan=_plan(total=100, usable=101),
                           poll_s=0.0)
        sec = mm.poll(now=0.0)
        assert sec["source"] == "plan"
        assert sec["hbm_plan_skew"] == pytest.approx(1.0)
        assert sec["hbm_headroom_bytes"] == 1
        assert sec["hbm_pressure"] == 0
        monkeypatch.setenv("KAFKA_TPU_HBM_WATERMARK", "0.1")
        mm2 = MemoryMonitor([object()], plan=_plan(total=100, usable=101),
                            poll_s=0.0)
        assert mm2.poll(now=0.0)["hbm_pressure"] == 1

    def test_no_devices_no_plan(self):
        mm = MemoryMonitor([], plan=None, poll_s=0.0)
        sec = mm.poll(now=0.0)
        assert sec["source"] == "none"
        assert mm.headroom_frac() is None and not mm.pressure()

    def test_poll_throttle(self):
        dev = _Dev(0, 50, 50, 100)
        mm = MemoryMonitor([dev], plan=None, poll_s=1.0)
        s1 = mm.poll(now=0.0)
        dev._stats["bytes_in_use"] = 90
        assert mm.poll(now=0.5) is s1          # throttled
        assert mm.poll(now=0.5, force=True) is not s1
        assert mm.section()["hbm_bytes_in_use"] == 90
        assert mm.polls == 2

    def test_engine_snapshot_carries_memory_section(self, shared):
        cfg, params = shared
        eng = make_engine(params, cfg)
        assert eng.memory_monitor is not None
        eng.memory_monitor.plan = _plan(total=100, usable=120)
        run_requests(eng, n=1, gen=4, seed_base=7)
        snap = eng.metrics.snapshot(eng, reset_peak=False)
        assert "memory" in snap
        assert snap["memory"]["source"] == "plan"
        from kafka_tpu.server.prometheus import render_prometheus

        text = render_prometheus(snap)
        assert "kafka_tpu_hbm_headroom_bytes" in text
        assert "kafka_tpu_hbm_plan_skew 1\n" in text
        assert 'kafka_tpu_hbm_component_bytes{component="unattributed"}' \
            in text


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------


class TestDeviceTruthRegistry:
    """The compiles / memory sections against the metric table (the
    both-directions check is tests/test_prometheus.py::TestMetricTable)."""

    def test_anomaly_kinds_cover_device_truth(self):
        from kafka_tpu.runtime.flight_recorder import ANOMALY_KINDS
        from kafka_tpu.runtime.metrics import ANOMALY_METRIC_KEYS

        assert "compile_storm" in ANOMALY_KINDS
        assert "hbm_pressure" in ANOMALY_KINDS
        assert "anomaly_compile_storm" in ANOMALY_METRIC_KEYS
        assert "anomaly_hbm_pressure" in ANOMALY_METRIC_KEYS

    def test_compile_section_renders(self):
        # the compiles section is process-wide: server/app.py merges it
        # into the snapshot; prometheus renders whatever snapshot
        # carries, so feed it a merged-shape snapshot directly
        from kafka_tpu.server.prometheus import render_prometheus

        obs = CompileObservatory(8)
        obs.record("fn", 0.5, now=100.0)
        snap = EngineMetrics().snapshot()
        snap["compiles"] = obs.metrics_section()
        text = render_prometheus(snap)
        assert 'kafka_tpu_compiles_total{cache="off"} 1' in text
        assert "kafka_tpu_compile_seconds_total 0.5" in text
        assert "kafka_tpu_compile_storm_active 0" in text
        assert 'kafka_tpu_compiles_total{phase="boot"} 1' in text


# ---------------------------------------------------------------------------
# server endpoints + signals contract
# ---------------------------------------------------------------------------


class TestServerEndpoints:
    def _app_client(self, provider, tmp_path, **cfg_kw):
        from aiohttp.test_utils import TestClient, TestServer
        from kafka_tpu.db.local import LocalDBClient
        from kafka_tpu.server.app import create_app
        from kafka_tpu.server.config import ServingConfig

        async def build():
            app = await create_app(
                cfg=ServingConfig(db_path=str(tmp_path / "d.db"), **cfg_kw),
                llm_provider=provider,
                db=LocalDBClient(str(tmp_path / "d.db")),
                tools=[],
            )
            client = TestClient(TestServer(app))
            await client.start_server()
            return client

        return build

    def _provider(self, eng):
        from kafka_tpu.llm import TPULLMProvider
        from kafka_tpu.models.tokenizer import ByteTokenizer

        return TPULLMProvider(eng, ByteTokenizer(), model_name="m")

    def test_debug_compiles_endpoint(self, shared, tmp_path):
        import asyncio

        cfg, params = shared
        eng = make_engine(params, cfg)
        provider = self._provider(eng)
        build = self._app_client(provider, tmp_path)

        async def go():
            client = await build()
            try:
                # off: create_app never calls compile_log.init (that is
                # serve()'s job) and the fixture reset the singleton
                r = await client.get("/debug/compiles")
                assert r.status == 404
                assert "disabled" in (await r.json())["error"]
                # on: records show up with phase + cache disposition
                obs = compile_log.init(16)
                compile_log.set_phase("first_traffic")
                obs.record("live_fn", 1.25)
                r = await client.get("/debug/compiles")
                assert r.status == 200
                payload = await r.json()
                assert payload["totals"]["compiles"] >= 1
                rec = next(r for r in payload["records"]
                           if r["label"] == "live_fn")
                assert rec["phase"] == "first_traffic"
                assert rec["cache"] == "off"
                assert payload["storm"]["active"] is False
                # the /metrics snapshot merges the same section (the
                # Prometheus exposition is content-negotiated; the JSON
                # default carries the merged dict)
                m = await client.get("/metrics")
                msnap = await m.json()
                assert msnap["compiles"]["compiles_total"] >= 1
                assert msnap["compiles"]["by_phase"]["first_traffic"] >= 1
            finally:
                await client.close()
                provider.worker.stop()

        asyncio.run(go())

    def test_signals_v7_device_truth_sections(self, shared):
        cfg, params = shared
        eng = make_engine(params, cfg)
        eng.memory_monitor.plan = _plan(total=100, usable=120)
        run_requests(eng, n=1, gen=4, seed_base=11)
        compile_log.init(16)
        compile_log.get().record("sig_fn", 0.2)
        provider = self._provider(eng)
        try:
            sig = provider.signals()
            assert sig["version"] == 9
            assert sig["compiles"]["compiles_total"] >= 1
            assert sig["compiles"]["storm_active"] is False
            mem = sig["memory"]
            assert mem is not None
            assert mem["plan_skew"] == pytest.approx(1.0)
            assert mem["pressure"] == 0
            assert mem["replicas"][0]["replica"] == 0
            assert mem["replicas"][0]["source"] == "plan"
            assert mem["headroom_bytes"] == \
                mem["replicas"][0]["hbm_headroom_bytes"]
        finally:
            provider.worker.stop()

    def test_signals_sections_null_when_off(self, shared):
        cfg, params = shared
        eng = make_engine(params, cfg)
        # no poll has happened and no observatory exists: both device-
        # truth sections are null rather than fabricated
        provider = self._provider(eng)
        try:
            sig = provider.signals()
            assert sig["version"] == 9
            assert sig["compiles"] is None
            assert sig["memory"] is None
        finally:
            provider.worker.stop()


# ---------------------------------------------------------------------------
# bench phase smoke
# ---------------------------------------------------------------------------


class TestBenchSmoke:
    def test_device_truth_phase_runs(self, shared, monkeypatch):
        import random
        import sys

        # conftest forces the observatory off suite-wide; the bench phase
        # boots it via a bare init() (env-sized) and the rebuild-leg
        # assertions need a live ring.
        monkeypatch.setenv(compile_log.RING_ENV, "256")

        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from bench import device_truth_phase

        cfg, params = shared
        eng = make_engine(params, cfg)
        args = SimpleNamespace(quick=True, batch=2, prompt_len=16)
        out = device_truth_phase(eng, cfg, args, random.Random(0))
        assert set(out) == {"rebuild_outage"}
        reb = out["rebuild_outage"]
        assert reb["warm_first_token_s"] > 0
        assert reb["cold_first_token_s"] > 0
        # the cold leg really compiled; the warm leg really did not
        assert reb["compiles_cold_leg"] > reb["compiles_warm_leg"]
