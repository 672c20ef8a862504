"""Autoscaler control loop (ISSUE 13): decision-table unit matrix,
degradation-ladder actuation, chaos e2e (error storm -> quarantine ->
hold-then-act, token-exact streams across controller rebuilds),
KAFKA_TPU_AUTOSCALE=0 bit-identity, metric registry, sim + bench smoke."""

import dataclasses
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime import failpoints
from kafka_tpu.runtime.autoscaler import (
    DEGRADE,
    HOLD,
    LADDER_MAX,
    LADDER_RUNGS,
    RECOVER,
    SCALE_IN,
    SCALE_OUT,
    AutoscalerConfig,
    AutoscalerController,
    ControllerState,
    DegradationLadder,
    background_deferred,
    decide,
    parse_mode,
    set_background_deferred,
)
from kafka_tpu.runtime.dp_router import DataParallelEngines
from kafka_tpu.runtime.metrics import (
    AUTOSCALER_METRIC_KEYS,
    EngineMetrics,
    configure_slo,
)


# ---------------------------------------------------------------------------
# synthetic signals snapshots (the /admin/signals v4 shape)
# ---------------------------------------------------------------------------


def sig(dp=1, attain=1.0, wr=10, depth=0, trend=0.0, occ=0.5, mfu=0.3,
        anomalies=0, states=None, pools=None, draining=False):
    # defaults describe a HEALTHY BUSY fleet (occupancy/MFU above the
    # idle thresholds) so "steady" means steady, not idle-pending
    states = states if states is not None else ["healthy"] * dp
    snap = {
        "version": 4,
        "dp": dp,
        "slo": {"slo_attainment_1m": attain, "window_1m_requests": wr},
        "queue": {"depth": depth, "trend_per_s": trend, "peak": depth},
        "batch": {"occupancy_frac": occ, "active": 0, "max_batch": 8},
        "utilization": {
            "decode": {"mfu_1m": mfu, "hbm_bw_util_1m": mfu},
        },
        "anomalies": {"anomalies_active": anomalies},
        "replicas": [
            {"replica": i, "state": s} for i, s in enumerate(states)
        ],
        "pools": pools or [],
    }
    if draining:
        snap["draining"] = True
    return snap


def cfg_(**over):
    base = AutoscalerConfig(
        mode="recommend", interval_s=1.0, min_dp=1, max_dp=4,
        attain_out=0.9, attain_in=0.98, trend_out=0.5,
        idle_occupancy=0.25, idle_mfu=0.05,
        sustain_out=2, sustain_in=3, sustain_recover=2,
        cooldown_out_s=10.0, cooldown_in_s=20.0, ladder_cooldown_s=5.0,
        min_window_requests=3,
    )
    return dataclasses.replace(base, **over)


class TestDecisionTable:
    """The pure matrix: synthetic snapshots -> expected action/veto, no
    engine needed (the chaos e2e below exercises the same function
    against live signals)."""

    def test_steady_holds(self):
        st = ControllerState()
        d = decide(sig(), st, cfg_(), 0.0)
        assert d.action == HOLD and d.cause == "steady"
        assert not d.vetoes

    def test_attainment_collapse_scales_out_after_sustain(self):
        st, c = ControllerState(), cfg_()
        d1 = decide(sig(attain=0.5, depth=4), st, c, 0.0)
        assert d1.action == HOLD and d1.cause == "overload_pending"
        d2 = decide(sig(attain=0.5, depth=4), st, c, 1.0)
        assert d2.action == SCALE_OUT
        assert d2.cause == "attainment_collapse"
        assert d2.dp_target == 2 and d2.roles_target is None

    def test_low_attainment_needs_window_samples(self):
        st, c = ControllerState(), cfg_()
        for t in range(4):
            d = decide(sig(attain=0.0, wr=2), st, c, float(t))
            assert d.action == HOLD, "2 verdicts must not trigger a resize"
        # a v3 feed without the field is trusted (None = unknown)
        st2 = ControllerState()
        snap = sig(attain=0.5)
        del snap["slo"]["window_1m_requests"]
        decide(snap, st2, c, 0.0)
        d = decide(snap, st2, c, 1.0)
        assert d.action == SCALE_OUT

    def test_queue_growth_scales_out(self):
        st, c = ControllerState(), cfg_()
        decide(sig(depth=8, trend=2.0), st, c, 0.0)
        d = decide(sig(depth=12, trend=2.0), st, c, 1.0)
        assert d.action == SCALE_OUT and d.cause == "queue_growth"

    def test_anomaly_vetoes_every_action_then_acts(self):
        st, c = ControllerState(), cfg_()
        decide(sig(attain=0.2, anomalies=1), st, c, 0.0)
        d = decide(sig(attain=0.2, anomalies=1), st, c, 1.0)
        assert d.action == HOLD
        assert d.intended == SCALE_OUT
        assert "anomaly_active" in d.vetoes
        # evidence survives the veto: the first clean poll acts
        d = decide(sig(attain=0.2, anomalies=0), st, c, 2.0)
        assert d.action == SCALE_OUT

    def test_probation_vetoes_resizes_only(self):
        st, c = ControllerState(), cfg_()
        states = ["healthy", "probation"]
        decide(sig(dp=2, attain=0.2, states=states), st, c, 0.0)
        d = decide(sig(dp=2, attain=0.2, states=states), st, c, 1.0)
        assert d.action == HOLD and "replica_probation" in d.vetoes
        # ladder moves are NOT probation-vetoed (all-quarantined storms
        # force-probate — the ladder must still be reachable)
        st2, c2 = ControllerState(), cfg_(max_dp=2)
        decide(sig(dp=2, attain=0.2, states=states), st2, c2, 0.0)
        d = decide(sig(dp=2, attain=0.2, states=states), st2, c2, 1.0)
        assert d.action == DEGRADE and d.ladder_target == 1

    def test_draining_vetoes(self):
        st, c = ControllerState(), cfg_()
        decide(sig(attain=0.2, draining=True), st, c, 0.0)
        d = decide(sig(attain=0.2, draining=True), st, c, 1.0)
        assert d.action == HOLD and "draining" in d.vetoes

    def test_capped_descends_ladder_in_order_then_saturates(self):
        """At max dp the overload response is the ladder, one rung per
        cooldown window, in the documented order."""
        c = cfg_(max_dp=1, ladder_cooldown_s=5.0)
        ctl = AutoscalerController(provider=None, cfg=c)
        now = 0.0
        rungs = []
        for _ in range(40):
            d = ctl.poll_once(now=now, snap=sig(attain=0.2, depth=4))
            if d.action == DEGRADE:
                rungs.append(d.ladder_target)
            now += 2.0
            if ctl.state.ladder == LADDER_MAX and d.cause == "saturated":
                break
        assert rungs == [1, 2, 3]
        assert ctl.state.ladder == LADDER_MAX
        # at the floor: no further action, cause says so
        d = ctl.poll_once(now=now + 10, snap=sig(attain=0.2, depth=4))
        assert d.action == HOLD and d.cause == "saturated"

    def test_ladder_climbs_back_in_reverse_on_recovery(self):
        c = cfg_(max_dp=1, ladder_cooldown_s=1.0, sustain_recover=2)
        ctl = AutoscalerController(provider=None, cfg=c)
        now = 0.0
        while ctl.state.ladder < LADDER_MAX:
            ctl.poll_once(now=now, snap=sig(attain=0.2, depth=4))
            now += 2.0
        climbs = []
        for _ in range(40):
            d = ctl.poll_once(now=now, snap=sig(attain=1.0))
            if d.action == RECOVER:
                climbs.append(d.ladder_target)
            now += 2.0
            if ctl.state.ladder == 0:
                break
        assert climbs == [2, 1, 0]
        assert ctl.counters["autoscaler_recovers"] == 3

    def test_all_quarantined_goes_to_ladder_not_resize(self):
        st, c = ControllerState(), cfg_()  # dp < max_dp: room to grow
        states = ["quarantined", "quarantined"]
        decide(sig(dp=2, attain=0.2, states=states), st, c, 0.0)
        d = decide(sig(dp=2, attain=0.2, states=states), st, c, 1.0)
        assert d.action == DEGRADE
        assert "all_quarantined" in d.cause

    def test_idle_scale_in_after_long_sustain(self):
        st, c = ControllerState(), cfg_()
        idle = sig(dp=3, attain=1.0, occ=0.05, mfu=0.01)
        d = None
        for t in range(3):
            d = decide(idle, st, c, float(t))
        assert d.action == SCALE_IN and d.dp_target == 2
        assert d.cause == "idle"

    def test_scale_in_not_below_min_dp(self):
        st, c = ControllerState(), cfg_(min_dp=1)
        for t in range(6):
            d = decide(sig(dp=1, attain=1.0), st, c, float(t))
            assert d.action == HOLD

    def test_busy_device_blocks_scale_in(self):
        st, c = ControllerState(), cfg_()
        for t in range(6):
            d = decide(sig(dp=2, attain=1.0, mfu=0.6), st, c, float(t))
            assert d.action == HOLD, "high MFU is not idle"

    def test_cooldown_allows_one_resize_per_window(self):
        c = cfg_(cooldown_out_s=10.0)
        ctl = AutoscalerController(provider=None, cfg=c)
        overload = lambda: sig(attain=0.2, depth=6)  # noqa: E731
        ctl.poll_once(now=0.0, snap=overload())
        d = ctl.poll_once(now=1.0, snap=overload())
        assert d.action == SCALE_OUT
        vetoed = 0
        for t in range(2, 10):
            d = ctl.poll_once(now=float(t), snap=overload())
            assert d.action == HOLD
            if "cooldown" in d.vetoes:
                vetoed += 1
                assert d.intended == SCALE_OUT
        assert vetoed > 0
        # window expired: the next sustained overload may act again
        d = ctl.poll_once(now=12.0, snap=overload())
        assert d.action == SCALE_OUT

    def test_pools_grow_the_pressured_pool(self):
        st, c = ControllerState(), cfg_()
        pools = [
            {"role": "prefill", "replicas": [0], "queue_depth": 6},
            {"role": "decode", "replicas": [1], "queue_depth": 0},
        ]
        decide(sig(dp=2, attain=0.2, pools=pools), st, c, 0.0)
        d = decide(sig(dp=2, attain=0.2, pools=pools), st, c, 1.0)
        assert d.action == SCALE_OUT
        assert d.dp_target == 3
        assert d.roles_target == "prefill:2,decode:1"

    def test_pools_scale_in_shrinks_cooler_pool_and_floors(self):
        st, c = ControllerState(), cfg_()
        pools = [
            {"role": "prefill", "replicas": [0, 1], "queue_depth": 0},
            {"role": "decode", "replicas": [2], "queue_depth": 1},
        ]
        d = None
        for t in range(3):
            d = decide(sig(dp=3, attain=1.0, occ=0.05, mfu=0.01,
                           pools=pools), st, c, float(t))
        assert d.action == SCALE_IN
        assert d.roles_target == "prefill:1,decode:1"
        # both pools at one replica: dp=2 is the pool floor
        st2 = ControllerState()
        floor = [
            {"role": "prefill", "replicas": [0], "queue_depth": 0},
            {"role": "decode", "replicas": [1], "queue_depth": 0},
        ]
        for t in range(6):
            d = decide(sig(dp=2, attain=1.0, occ=0.05, mfu=0.01,
                           pools=floor), st2, c, float(t))
            assert d.action == HOLD

    def test_decision_log_collapses_steady_holds(self):
        ctl = AutoscalerController(provider=None, cfg=cfg_())
        for t in range(20):
            ctl.poll_once(now=float(t), snap=sig())
        assert len(ctl.decisions) == 1
        entry = ctl.decisions[0]
        assert entry["action"] == HOLD and entry["count"] == 20

    def test_parse_mode(self):
        assert parse_mode(None) == "off"
        assert parse_mode("0") == "off"
        assert parse_mode("nonsense") == "off"
        assert parse_mode("1") == "act"
        assert parse_mode("act") == "act"
        assert parse_mode("recommend") == "recommend"
        assert parse_mode("dry-run") == "recommend"


# ---------------------------------------------------------------------------
# degradation-ladder actuation
# ---------------------------------------------------------------------------


class _FakeProvider:
    def __init__(self, engines):
        self.engines = engines

    def _replicas(self):
        return self.engines


def _fake_engines(n=2, max_waiting=40):
    ecfg = EngineConfig(max_batch=4, page_size=8, num_pages=32,
                       max_pages_per_seq=4, max_waiting=max_waiting)
    return [SimpleNamespace(ecfg=ecfg, spec_k_cap=None) for _ in range(n)]


class TestDegradationLadder:
    def teardown_method(self):
        set_background_deferred(False)

    def test_rungs_apply_and_revert_in_order(self):
        engines = _fake_engines(max_waiting=40)
        ladder = DegradationLadder(_FakeProvider(engines))
        ecfg = engines[0].ecfg
        ladder.apply(1)
        assert ecfg.max_waiting == 10
        assert engines[0].spec_k_cap is None
        assert not background_deferred()
        ladder.apply(3)
        assert all(e.spec_k_cap == 0 for e in engines)
        assert background_deferred()
        ladder.apply(0)
        assert ecfg.max_waiting == 40
        assert all(e.spec_k_cap is None for e in engines)
        assert not background_deferred()

    def test_unbounded_admission_gets_a_bound(self):
        engines = _fake_engines(n=3, max_waiting=0)
        ladder = DegradationLadder(_FakeProvider(engines))
        ladder.apply(1)
        assert engines[0].ecfg.max_waiting == 2 * 4 * 3
        ladder.apply(0)
        assert engines[0].ecfg.max_waiting == 0

    def test_reassert_restamps_fresh_engines(self):
        provider = _FakeProvider(_fake_engines())
        ladder = DegradationLadder(provider)
        ladder.apply(2)
        provider.engines = _fake_engines()  # "rebuild" swapped objects
        assert provider.engines[0].spec_k_cap is None
        ladder.reassert()
        assert all(e.spec_k_cap == 0 for e in provider.engines)
        ladder.apply(0)

    def test_kv_tier_demote_refused_while_deferred(self):
        from kafka_tpu.runtime.kv_tier import KVTierManager

        class _Shipper:
            def bytes_per_page(self):
                return 64

        mgr = KVTierManager(_Shipper(), host_budget_bytes=1 << 20,
                            page_size=8)
        set_background_deferred(True)
        assert mgr.demote([1, 2]) is None
        set_background_deferred(False)


# ---------------------------------------------------------------------------
# live-engine fixtures (chaos e2e + bit-identity)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="as-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


ECFG = dict(max_batch=2, page_size=8, num_pages=64, max_pages_per_seq=8,
            prefill_buckets=(8, 16, 32))


def _shim(router_or_engine):
    """The provider's signals surface over a bare router/engine — the
    controller consumes the REAL /admin/signals contract while the test
    drives the engines directly (single-writer: the test thread)."""
    from kafka_tpu.llm.tpu_provider import TPULLMProvider

    class _SignalShim:
        autoscaler = None

        def __init__(self, engine):
            self.engine = engine

        _replicas = TPULLMProvider._replicas
        signals = TPULLMProvider.signals

    return _SignalShim(router_or_engine)


def _prompts(n, length=9, seed=5):
    return [list(np.random.RandomState(seed + i).randint(1, 128, length))
            for i in range(n)]


@pytest.fixture
def slo_restore():
    yield
    configure_slo(None, None)


class TestControllerChaosE2E:
    def test_attainment_collapse_scales_out_token_exact(
        self, model, slo_restore
    ):
        """Acceptance core: under an attainment collapse the controller
        scales out within 3 poll intervals through the real rebuild
        seam, queued streams ride through the rebuild TOKEN-EXACT, and
        at most one resize lands per cooldown window."""
        cfg, params = model
        # an impossible TTFT target: every finished request is an SLO
        # miss, which is exactly a window-attainment collapse
        configure_slo(ttft_ms=0.0001)
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                 dp=1, tp=1, kv_dtype=jnp.float32)
        resize_calls = []

        def resize_fn(dp_target, roles):
            assert roles is None
            dp.rebuild(dp=dp_target)
            resize_calls.append(dp_target)
            return True

        ctl = AutoscalerController(
            _shim(dp),
            cfg_(mode="act", max_dp=2, min_window_requests=1,
                 cooldown_out_s=60.0),
            resize_fn=resize_fn,
        )
        # two finished requests = two window misses -> collapse
        for i, p in enumerate(_prompts(2)):
            dp.submit(GenRequest(request_id=f"m{i}", prompt_ids=p,
                                 max_new_tokens=3))
        dp.run_to_completion()
        # queue work WITHOUT stepping: these must survive the rebuild
        queued = _prompts(4, seed=40)
        for i, p in enumerate(queued):
            dp.submit(GenRequest(request_id=f"q{i}", prompt_ids=list(p),
                                 max_new_tokens=5))
        d1 = ctl.poll_once(now=0.0)
        assert d1.action == HOLD and d1.cause == "overload_pending"
        d2 = ctl.poll_once(now=2.0)
        assert d2.action == SCALE_OUT and resize_calls == [2]
        assert len(dp.engines) == 2
        # further overload polls inside the cooldown: no second resize
        for t in (3.0, 4.0, 5.0):
            ctl.poll_once(now=t)
        assert resize_calls == [2]
        assert ctl.counters["autoscaler_scale_outs"] == 1
        # queued requests complete on the new topology, token-exact
        done = dp.run_to_completion()
        ref = InferenceEngine(cfg, params, EngineConfig(**ECFG),
                              kv_dtype=jnp.float32)
        for i, p in enumerate(queued):
            assert done[f"q{i}"].output_ids == ref.generate(
                list(p), max_new_tokens=5
            ).output_ids, f"q{i} diverged across the controller rebuild"

    def test_error_storm_quarantine_hold_then_act(
        self, model, monkeypatch, slo_restore
    ):
        """engine.step error storm -> quarantine -> the controller holds
        while a flight-recorder anomaly is active, acts once it clears
        and the replicas are healthy again, and never exceeds one resize
        per cooldown window."""
        cfg, params = model
        monkeypatch.setenv("KAFKA_TPU_ANOMALY_STALL_S", "0.05")
        ecfg = EngineConfig(**{**ECFG, "max_batch": 1, "max_parked": 0})
        dp = DataParallelEngines(
            cfg, params, ecfg, dp=2, tp=1, kv_dtype=jnp.float32,
            quarantine_threshold=2, quarantine_window_s=0.2,
            probation_steps=3, rebuild_threshold=0,
        )
        resize_calls = []

        def resize_fn(dp_target, roles):
            dp.rebuild(dp=dp_target)
            resize_calls.append(dp_target)
            return True

        ctl = AutoscalerController(
            _shim(dp),
            cfg_(mode="act", max_dp=3, min_window_requests=1,
                 sustain_out=1, cooldown_out_s=60.0,
                 sustain_in=10 ** 6),  # scale-in is not under test here
            resize_fn=resize_fn,
        )
        # error storm: both replicas trip the breaker; their requests
        # fail (= SLO misses, the attainment collapse)
        for i, p in enumerate(_prompts(4, seed=60)):
            dp.submit(GenRequest(request_id=f"s{i}", prompt_ids=p,
                                 max_new_tokens=4))
        with failpoints.armed("engine.step", "error", "storm", count=4):
            for _ in range(40):
                if not dp.has_work:
                    break
                try:
                    dp.step()
                except Exception:
                    dp.recover_from_failure()
        assert dp.supervisor.quarantines >= 1
        snap = dp.metrics.snapshot(reset_peak=False)
        assert snap["slo"]["slo_missed_requests"] >= 1

        # engineer an ACTIVE anomaly (queue stall) on replica 0: one
        # active lane, one waiting, a >stall_s gap between steps
        e = dp.engines[0]
        for i, p in enumerate(_prompts(2, seed=80)):
            e.submit(GenRequest(request_id=f"a{i}", prompt_ids=p,
                                 max_new_tokens=30))
        e.step()
        time.sleep(0.08)
        e.step()
        assert e.flight is not None
        assert e.flight.active_anomalies(), "stall detector did not fire"

        d = ctl.poll_once(now=0.0)
        assert d.action == HOLD
        assert "anomaly_active" in d.vetoes
        assert d.intended in (SCALE_OUT, DEGRADE)
        assert resize_calls == []

        # clear the anomaly (fast steps drain the queue) and finish the
        # stall lanes; then rehabilitate the replicas: quarantine windows
        # expire into probation, clean steps promote back to healthy
        while e.has_work:
            e.step()
        assert not e.flight.active_anomalies()
        time.sleep(0.45)  # both quarantine windows expire
        for i, p in enumerate(_prompts(4, seed=90)):
            dp.submit(GenRequest(request_id=f"h{i}", prompt_ids=p,
                                 max_new_tokens=6))
        for _ in range(200):
            if not dp.has_work:
                break
            dp.step()
        states = {h.state for h in dp.health}
        assert states == {"healthy"}, states

        # anomaly cleared, replicas healthy, attainment still collapsed
        # (the storm's misses sit in the 1m window): the controller acts
        d = ctl.poll_once(now=1.0)
        assert d.action == SCALE_OUT, (d.action, d.cause, d.vetoes)
        assert resize_calls == [3]
        # and holds through the rest of the cooldown window
        for t in (2.0, 3.0, 10.0, 30.0):
            ctl.poll_once(now=t)
        assert resize_calls == [3]
        assert ctl.counters["autoscaler_scale_outs"] == 1
        dp.run_to_completion()

    def test_roles_resize_through_rebuild(self, model):
        """/admin/resize roles plumbing (satellite): rebuild(roles=...)
        re-shapes the pools, validates the spec, and "" dissolves."""
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        assert dp._prefill_pool == []
        dp.rebuild(dp=2, roles="prefill:1,decode:1")
        assert dp._prefill_pool == [0] and dp._decode_pool == [1]
        with pytest.raises(ValueError, match="names 3 replicas"):
            dp.rebuild(dp=2, roles="prefill:1,decode:2")
        with pytest.raises(ValueError, match="unknown pool role"):
            dp.rebuild(dp=2, roles="bogus:2")
        # bad spec refused up front: pools unchanged
        assert dp._prefill_pool == [0] and dp._decode_pool == [1]
        dp.rebuild(dp=3, roles="prefill:1,decode:2")
        assert dp._prefill_pool == [0] and dp._decode_pool == [1, 2]
        dp.rebuild(dp=2, roles="")
        assert dp._prefill_pool == [] and dp._decode_pool == []
        # omitting roles keeps the current spec (colocated here)
        dp.rebuild(dp=1)
        assert dp._prefill_pool == []


class TestBitIdentity:
    def test_autoscale_off_paths_byte_identical(self, model):
        """KAFKA_TPU_AUTOSCALE=0 contract: with no controller (and with
        a recommend-mode controller polling mid-serve) every dispatch
        and admission path produces byte-identical streams, and no
        engine/config knob moves."""
        cfg, params = model
        prompts = _prompts(3, length=12, seed=7)

        def run(with_controller):
            eng = InferenceEngine(cfg, params, EngineConfig(**ECFG),
                                  kv_dtype=jnp.float32)
            ctl = None
            if with_controller:
                ctl = AutoscalerController(_shim(eng),
                                           cfg_(mode="recommend"))
            reqs = [
                GenRequest(request_id=f"r{i}", prompt_ids=list(p),
                           max_new_tokens=8)
                for i, p in enumerate(prompts)
            ]
            for r in reqs:
                eng.submit(r)
            steps = 0
            while eng.has_work:
                eng.step()
                steps += 1
                if ctl is not None and steps % 3 == 0:
                    ctl.poll_once()
            return eng, ctl, {r.request_id: r.output_ids for r in reqs}

        eng_a, _, outs_a = run(False)
        eng_b, ctl, outs_b = run(True)
        assert outs_a == outs_b
        # no knob moved: the off/recommend paths left everything alone
        assert eng_b.spec_k_cap is None
        assert eng_b.ecfg.max_waiting == eng_a.ecfg.max_waiting
        assert not background_deferred()
        assert ctl is not None and ctl._seq > 0  # the loop really ran

    def test_default_config_builds_no_controller(self, monkeypatch):
        from kafka_tpu.server.config import ServingConfig

        monkeypatch.delenv("KAFKA_TPU_AUTOSCALE", raising=False)
        cfg = ServingConfig.from_env()
        assert parse_mode(cfg.autoscale) == "off"


# ---------------------------------------------------------------------------
# metric registry + prometheus exposition
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_metrics_section_matches_registry(self):
        ctl = AutoscalerController(provider=None, cfg=cfg_())
        ctl.poll_once(now=0.0, snap=sig())
        section = ctl.metrics_section()
        assert set(section) == set(AUTOSCALER_METRIC_KEYS)

    def test_exposition_parses(self, model):
        from kafka_tpu.server.prometheus import render_prometheus

        cfg, params = model
        eng = InferenceEngine(cfg, params, EngineConfig(**ECFG),
                              kv_dtype=jnp.float32)
        eng.generate([5, 6, 7], max_new_tokens=3)
        ctl = AutoscalerController(_shim(eng), cfg_(mode="recommend"))
        ctl.poll_once(now=0.0)
        snap = eng.metrics.snapshot(eng, reset_peak=False)
        snap["autoscaler"] = ctl.metrics_section()
        text = render_prometheus(snap)
        assert 'kafka_tpu_autoscaler_events_total{event="poll"} 1' in text
        assert "kafka_tpu_autoscaler_ladder_level 0" in text
        assert "kafka_tpu_autoscaler_dp 1" in text
        from test_prometheus import parse_exposition

        parse_exposition(text)

    def test_signals_v4_shape(self, model):
        cfg, params = model
        eng = InferenceEngine(cfg, params, EngineConfig(**ECFG),
                              kv_dtype=jnp.float32)
        shim = _shim(eng)
        snap = shim.signals()
        assert snap["version"] == 9
        assert snap["autoscaler"] is None
        assert "window_1m_requests" in snap["slo"]
        ctl = AutoscalerController(shim, cfg_(mode="recommend"))
        ctl.poll_once(now=0.0)
        snap = shim.signals()
        sec = snap["autoscaler"]
        assert sec["mode"] == "recommend"
        assert sec["ladder_rung"] == LADDER_RUNGS[0]
        assert sec["decisions_logged"] == 1
        assert set(sec["cooldown"]) == {"scale_out_remaining_s",
                                        "scale_in_remaining_s"}


# ---------------------------------------------------------------------------
# autoscale_sim smoke (satellite: decision-table drift caught in tier-1)
# ---------------------------------------------------------------------------


class TestSimSmoke:
    def test_replay_prints_decision_trace(self, tmp_path):
        snaps = [sig()] + [sig(attain=0.3, depth=6, trend=1.0)] * 4 + [
            sig(attain=1.0)
        ] * 3
        path = tmp_path / "signals.json"
        path.write_text(json.dumps(snaps))
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, os.path.join(root, "scripts",
                                          "autoscale_sim.py"),
             str(path)],
            capture_output=True, text=True, timeout=180, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "scale_out" in out.stdout
        assert "attainment_collapse" in out.stdout
        assert "decision(s)" in out.stdout

    def test_replay_api_traces_ladder(self):
        cfg = cfg_(mode="recommend", max_dp=1, ladder_cooldown_s=0.5)
        ctl = AutoscalerController(provider=None, cfg=cfg)
        decisions = ctl.replay(
            [sig(attain=0.2, depth=5)] * 8, interval_s=1.0
        )
        assert any(d.action == DEGRADE for d in decisions)
        assert ctl.counters["autoscaler_degrades"] >= 1


# ---------------------------------------------------------------------------
# bench traffic-ramp smoke (acceptance: CPU smoke in tier-1)
# ---------------------------------------------------------------------------


class TestBenchSmoke:
    def test_traffic_ramp_phase_quick(self, model, slo_restore):
        import importlib.util

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "bench", os.path.join(root, "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        sys.modules["bench"] = bench
        spec.loader.exec_module(bench)
        cfg, params = model
        out = bench.traffic_ramp_phase(
            cfg, params, n_warm=2, n_ramp=10, n_post=4,
            prompt_len=16, gen_len=16, page_size=8,
            poll_every_steps=4,
        )
        assert out["acted"] is True
        assert out["dp"] == {"before": 1, "after": 2}
        assert out["resizes"] == 1
        seg = out["attainment_by_segment"]
        assert seg["post_action"]["requests"] >= 1
        # recovery proof: post-action arrivals meet the target the ramp
        # blew through (asserted inside the phase too)
        assert seg["post_action"]["attainment"] > \
            seg["ramp_overload"]["attainment"]
