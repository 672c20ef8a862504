"""Replica data parallelism: routing, thread affinity, correctness,
replica supervision (quarantine/probation/re-admit), and topology
rebuilds (drain/restart at a different dp count)."""

import asyncio
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.dp_router import (
    HEALTHY,
    PROBATION,
    QUARANTINED,
    DataParallelEngines,
)


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="dp-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(17))
    return cfg, params


ECFG = dict(max_batch=2, page_size=8, num_pages=32, max_pages_per_seq=8,
            prefill_buckets=(8, 16, 32))


class TestDPRouting:
    def test_outputs_match_single_engine(self, model):
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        ref = InferenceEngine(cfg, params, EngineConfig(**ECFG),
                              kv_dtype=jnp.float32)
        prompts = {f"r{i}": list(np.random.RandomState(i).randint(1, 128, 9))
                   for i in range(4)}
        for rid, p in prompts.items():
            dp.submit(GenRequest(request_id=rid, prompt_ids=list(p),
                                 max_new_tokens=5))
        done = dp.run_to_completion()
        assert set(done) == set(prompts)
        for rid, p in prompts.items():
            solo = ref.generate(list(p), max_new_tokens=5)
            assert done[rid].output_ids == solo.output_ids, rid

    def test_load_spreads_across_replicas(self, model):
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        for i in range(4):
            dp.submit(GenRequest(request_id=f"x{i}", prompt_ids=[1 + i, 2, 3],
                                 max_new_tokens=3))
        per_replica = [e.num_active + len(e.waiting) for e in dp.engines]
        assert per_replica == [2, 2]
        dp.run_to_completion()

    def test_thread_affinity_keeps_prefix_cache_hot(self, model):
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        p1 = list(np.random.RandomState(9).randint(1, 128, 10))
        r1 = GenRequest(request_id="t1", prompt_ids=p1, max_new_tokens=4,
                        prefix_key="thread-A")
        dp.submit(r1)
        dp.run_to_completion()
        replica = dp._affinity["thread-A"]
        # turn 2 must land on the same replica and hit its cache
        r2 = GenRequest(request_id="t2",
                        prompt_ids=p1 + r1.output_ids + [5],
                        max_new_tokens=4, prefix_key="thread-A")
        dp.submit(r2)
        dp.run_to_completion()
        assert dp._affinity["thread-A"] == replica
        assert dp.engines[replica].prefix_cache.hits == 1

    def test_cold_thread_routes_to_warm_prefix_replica(self, model):
        """ISSUE 4: prefix-aware routing — a COLD thread (no affinity pin)
        whose prompt begins with an already-cached shared prefix must land
        on the replica holding it (cross-thread radix hit), even when a
        less-loaded replica exists."""
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        common = list(np.random.RandomState(21).randint(1, 128, 16))
        seed = GenRequest(request_id="warm", prompt_ids=common + [3, 5],
                          max_new_tokens=4, prefix_key="thread-warm")
        dp.submit(seed)
        dp.run_to_completion()
        warm = dp._affinity["thread-warm"]
        # skew load AWAY from the warm replica: an unkeyed filler parks on
        # it, so pure least-loaded routing would now pick the other one
        filler = GenRequest(request_id="filler", prompt_ids=[9] * 8,
                            max_new_tokens=32)
        dp.engines[warm].submit(filler)
        cold = GenRequest(request_id="cold", prompt_ids=common + [7, 11, 13],
                          max_new_tokens=4, prefix_key="thread-cold")
        dp.submit(cold)
        assert dp._route["cold"] == warm  # prefix gravity beat load
        dp.run_to_completion()
        assert dp.engines[warm].prefix_cache.cross_thread_hits >= 1
        assert cold.cached_tokens == 16 and cold.cache_source == "cross"
        # correctness: identical tokens to an unrouted reference
        ref = InferenceEngine(cfg, params, EngineConfig(**ECFG),
                              kv_dtype=jnp.float32).generate(
            common + [7, 11, 13], max_new_tokens=4)
        assert cold.output_ids == ref.output_ids

    def test_prefix_gravity_spills_under_load_skew(self, model):
        """The balance guard: when the warm replica is more than a full
        batch deeper than the least-loaded one, load wins — the cold
        replica prefills the prefix once and becomes a second warm home."""
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        common = list(np.random.RandomState(22).randint(1, 128, 16))
        dp.submit(GenRequest(request_id="w", prompt_ids=common + [2],
                             max_new_tokens=4, prefix_key="t-w"))
        dp.run_to_completion()
        warm = dp._affinity["t-w"]
        # pile max_batch+1 requests onto the warm replica (> the guard)
        for i in range(dp.ecfg.max_batch + 1):
            dp.engines[warm].submit(GenRequest(
                request_id=f"pile{i}", prompt_ids=[9] * 8, max_new_tokens=32))
        cold = GenRequest(request_id="spill", prompt_ids=common + [4, 6],
                          max_new_tokens=2, prefix_key="t-spill")
        dp.submit(cold)
        assert dp._route["spill"] == 1 - warm  # spilled to the cold replica
        dp.run_to_completion()

    def test_cancel_routes_to_owner(self, model):
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        req = GenRequest(request_id="c1", prompt_ids=[1, 2, 3],
                         max_new_tokens=50)
        dp.submit(req)
        assert dp.cancel("c1") is True
        assert dp.cancel("ghost") is False

    def test_dp_times_tp_needs_enough_devices(self, model):
        cfg, params = model
        with pytest.raises(ValueError, match="devices"):
            DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                dp=8, tp=2)

    def test_supervision_metrics_in_snapshot(self, model):
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        snap = dp.metrics.snapshot()
        sup = snap["replica_supervisor"]
        assert sup["health"] == [1.0, 1.0]
        assert sup["states"] == [HEALTHY, HEALTHY]
        assert sup["quarantines"] == 0 and sup["readmits"] == 0

    def test_merged_snapshot_holds_the_fetch_stage_ledger(self, model):
        """The dp aggregate merges the four stage histograms that tile
        ttft_fetch_ms (they are in HISTOGRAM_NAMES) and sums the fetch
        pipeline's counters over the replicas."""
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        for i in range(4):
            dp.submit(GenRequest(request_id=f"m{i}", prompt_ids=[1 + i, 2, 3],
                                 max_new_tokens=3))
        dp.run_to_completion()
        snap = dp.metrics.snapshot()
        hists = snap["histograms"]
        for name in ("ttft_dev_wait_ms", "ttft_dev_exec_ms", "ttft_hold_ms",
                     "ttft_emit_ms"):
            assert hists[name]["count"] == hists["ttft_fetch_ms"]["count"] == 4
            assert hists[name]["count"] == sum(
                r["histograms"][name]["count"] for r in snap["replicas"])
        parts = sum(hists[n]["sum"] for n in (
            "ttft_dev_wait_ms", "ttft_dev_exec_ms", "ttft_hold_ms",
            "ttft_emit_ms"))
        # snapshot sums are rounded to the microsecond, per histogram
        assert abs(parts - hists["ttft_fetch_ms"]["sum"]) < 0.01
        eng = snap["engine"]
        for key in ("fetch_depth_steps_sum", "fetch_depth_samples",
                    "fetch_blocked_s"):
            assert eng[key] == pytest.approx(
                sum(r["engine"][key] for r in snap["replicas"]))
        assert eng["fetch_depth_samples"] > 0
        assert sum(eng["fetch_pops"].values()) == sum(
            sum(r["engine"]["fetch_pops"].values())
            for r in snap["replicas"]) > 0

    def test_dp_composes_with_tp(self, model):
        """dp=2 replicas each running tp=2 SPMD — batch spread across
        TP groups, token-exact vs single device."""
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                 dp=2, tp=2, kv_dtype=jnp.float32)
        ref = InferenceEngine(cfg, params, EngineConfig(**ECFG),
                              kv_dtype=jnp.float32)
        p = list(np.random.RandomState(3).randint(1, 128, 8))
        dp.submit(GenRequest(request_id="a", prompt_ids=list(p),
                             max_new_tokens=4))
        dp.submit(GenRequest(request_id="b", prompt_ids=list(p),
                             max_new_tokens=4))
        done = dp.run_to_completion()
        solo = ref.generate(list(p), max_new_tokens=4)
        assert done["a"].output_ids == solo.output_ids
        assert done["b"].output_ids == solo.output_ids


def make_dp(model, dp=2, threshold=2, window=0.15, **ecfg_kw):
    cfg, params = model
    e = dict(ECFG)
    e.update(ecfg_kw)
    return DataParallelEngines(
        cfg, params, EngineConfig(**e), dp=dp, tp=1,
        kv_dtype=jnp.float32, quarantine_threshold=threshold,
        quarantine_window_s=window,
    )


def drive(dp, step_cap=500):
    """Drive the router the way EngineWorker does (step, recover on
    exception); returns {request_id: finish_reason} asserting the
    exactly-one-terminal-event invariant inline."""
    terminal = {}
    steps = 0
    while dp.has_work and steps < step_cap:
        steps += 1
        try:
            events = dp.step()
        except Exception:
            events = dp.recover_from_failure()
        for ev in events:
            if ev.finished:
                assert ev.request_id not in terminal, (
                    f"{ev.request_id} got TWO terminal events"
                )
                terminal[ev.request_id] = ev.finish_reason
    return terminal


def kill_replica(dp, idx):
    """Make one replica's step raise (a dead device/process stand-in);
    returns a callable restoring the original step."""
    orig = dp.engines[idx].step

    def dead_step():
        raise RuntimeError(f"replica {idx} device lost")

    dp.engines[idx].step = dead_step
    return lambda: setattr(dp.engines[idx], "step", orig)


class TestReplicaSupervision:
    def test_quarantine_after_threshold_and_reroute(self, model):
        """Killing one replica's engine: circuit breaker trips after the
        threshold, every affected request still gets exactly one terminal
        event, zero pages leak, and NEW requests route to the survivor."""
        # a window no run outlasts: the default 0.15 s expired, under a
        # loaded host, while the survivor was still compiling its first
        # programs, and replica 0 was found on probation (re-admission
        # after the window is test_probation_and_warm_readmit's subject)
        dp = make_dp(model, threshold=2, window=60.0)
        restore = kill_replica(dp, 0)
        for i in range(4):  # spreads 2/2 across replicas
            dp.submit(GenRequest(request_id=f"r{i}", prompt_ids=[1, 2, 3],
                                 max_new_tokens=3))
        terminal = drive(dp)
        assert len(terminal) == 4, terminal
        assert dp.health[0].state == QUARANTINED
        assert dp.health[1].state == HEALTHY
        assert dp.supervisor.quarantines == 1
        # the router serves new requests from the survivor immediately
        dp.submit(GenRequest(request_id="post", prompt_ids=[7, 8, 9],
                             max_new_tokens=2))
        assert dp._route["post"] == 1
        assert drive(dp) == {"post": "length"}
        # zero leaked KV pages on BOTH replicas
        assert not dp.self_check(), dp.self_check()
        restore()

    def test_started_work_fails_waiting_migrates(self, model):
        """A replica that dies mid-decode: its STARTED request gets one
        terminal error, its QUEUED requests migrate to the survivor and
        finish normally, and the survivor's in-flight work is
        untouched."""
        dp = make_dp(model, threshold=1, max_batch=1, max_parked=0)
        # pin three requests to replica 0 via thread affinity (batch of 1:
        # one starts, two queue behind it) and one to replica 1
        dp.submit(GenRequest(request_id="a0", prompt_ids=[1, 2, 3],
                             max_new_tokens=20, prefix_key="t0"))
        dp.submit(GenRequest(request_id="a1", prompt_ids=[1, 2, 4],
                             max_new_tokens=3, prefix_key="t0"))
        dp.submit(GenRequest(request_id="a2", prompt_ids=[1, 2, 5],
                             max_new_tokens=3, prefix_key="t0"))
        dp.submit(GenRequest(request_id="b0", prompt_ids=[2, 2, 2],
                             max_new_tokens=3, prefix_key="t1"))
        assert dp._route["a0"] == dp._route["a1"] == dp._route["a2"]
        victim = dp._route["a0"]
        survivor = 1 - victim
        assert dp._route["b0"] == survivor
        # one clean step so a0 starts compute on the victim
        dp.step()
        restore = kill_replica(dp, victim)
        terminal = drive(dp)
        restore()
        assert len(terminal) == 4, terminal
        # started request on the dead replica: terminal error
        assert terminal["a0"] == "error:engine"
        # queued requests migrated and finished normally on the survivor
        assert terminal["a1"] == "length" and terminal["a2"] == "length"
        assert terminal["b0"] == "length"
        assert dp.supervisor.waiting_migrated >= 2
        assert not dp.self_check(), dp.self_check()

    def test_affinity_resteers_off_quarantined_replica(self, model):
        dp = make_dp(model, threshold=1)
        dp.submit(GenRequest(request_id="warm", prompt_ids=[1, 2, 3],
                             max_new_tokens=2, prefix_key="thread-X"))
        drive(dp)
        pinned = dp._affinity["thread-X"]
        restore = kill_replica(dp, pinned)
        dp.submit(GenRequest(request_id="w2", prompt_ids=[1, 2, 3],
                             max_new_tokens=2, prefix_key="thread-X"))
        # first submit may still land on the pinned replica (not yet
        # quarantined); drive until the breaker trips
        drive(dp)
        assert dp.health[pinned].state == QUARANTINED
        dp.submit(GenRequest(request_id="w3", prompt_ids=[1, 2, 3],
                             max_new_tokens=2, prefix_key="thread-X"))
        assert dp._route["w3"] != pinned
        assert dp._affinity["thread-X"] != pinned
        assert dp.supervisor.affinity_resteered >= 1
        drive(dp)
        restore()

    def test_probation_and_warm_readmit(self, model):
        dp = make_dp(model, threshold=1, window=0.1)
        restore = kill_replica(dp, 0)
        dp.submit(GenRequest(request_id="x", prompt_ids=[1, 2, 3],
                             max_new_tokens=2, prefix_key="t0"))
        dp.submit(GenRequest(request_id="y", prompt_ids=[2, 2, 3],
                             max_new_tokens=2, prefix_key="t1"))
        drive(dp)
        if dp.health[0].state != QUARANTINED:
            # routing put both on replica 1; force the trip deterministically
            dp.submit(GenRequest(request_id="z", prompt_ids=[3, 2, 3],
                                 max_new_tokens=2, prefix_key="t0"))
            dp._route["z"] = 0
            dp._affinity["t0"] = 0
            drive(dp)
        restore()
        assert dp.health[0].state == QUARANTINED
        time.sleep(0.12)  # quarantine window expires
        # long generation gives probation enough clean steps to promote
        dp.submit(GenRequest(request_id="long", prompt_ids=[1, 1, 1],
                             max_new_tokens=30))
        # probation replica is routable again (warm re-admit path)
        terminal = drive(dp)
        assert terminal["long"] == "length"
        states = {dp.health[0].state, dp.health[1].state}
        assert QUARANTINED not in states
        if dp._route.get("long") == 0 or dp.supervisor.readmits:
            assert dp.health[0].state in (HEALTHY, PROBATION)

    def test_probation_failure_retrips_immediately(self, model):
        dp = make_dp(model, threshold=3)
        dp.health[0].state = PROBATION
        restore = kill_replica(dp, 0)
        dp.submit(GenRequest(request_id="p", prompt_ids=[1, 2, 3],
                             max_new_tokens=2, prefix_key="t"))
        dp._route["p"] = 0
        dp._affinity["t"] = 0
        dp.engines[1 - 0].adopt  # noqa: B018 — silence lint on unused attr
        terminal = drive(dp)
        restore()
        # ONE failure on probation trips the breaker (not threshold=3)
        assert dp.health[0].state == QUARANTINED
        assert len(terminal) == 1
        assert not dp.self_check(), dp.self_check()

    def test_all_replicas_quarantined_degrades_not_refuses(self, model):
        dp = make_dp(model, threshold=1, window=30.0)
        for h in dp.health:
            h.state = QUARANTINED
            h.quarantined_until = time.monotonic() + 30.0
        # submit must still find a replica (force-probated), not crash
        dp.submit(GenRequest(request_id="s", prompt_ids=[1, 2, 3],
                             max_new_tokens=2))
        terminal = drive(dp)
        assert terminal == {"s": "length"}
        assert any(h.state != QUARANTINED for h in dp.health)


class TestTopologyRebuild:
    def test_rebuild_carries_waiting_requests(self, model):
        """Scale-down drain/restart: queued requests survive a dp=2 ->
        dp=1 rebuild and serve from the new replica set."""
        dp = make_dp(model)
        dp.submit(GenRequest(request_id="k1", prompt_ids=[1, 2, 3],
                             max_new_tokens=2))
        dp.submit(GenRequest(request_id="k2", prompt_ids=[4, 5, 6],
                             max_new_tokens=2, prefix_key="th"))
        dp.rebuild(dp=1)
        assert len(dp.engines) == 1
        assert dp.supervisor.rebuilds == 1
        assert {r.request_id for r in dp.waiting} == {"k1", "k2"}
        terminal = drive(dp)
        assert terminal == {"k1": "length", "k2": "length"}
        # routes/affinity rewritten for the new replica set
        assert dp._affinity["th"] == 0
        # scale back up works too
        dp.rebuild(dp=2)
        assert len(dp.engines) == 2
        assert not dp.self_check(), dp.self_check()

    def test_rebuild_refuses_started_work(self, model):
        dp = make_dp(model)
        dp.submit(GenRequest(request_id="busy", prompt_ids=[1, 2, 3],
                             max_new_tokens=50))
        dp.step()  # starts compute
        with pytest.raises(RuntimeError, match="started"):
            dp.rebuild(dp=1)
        drive(dp)

    def test_rebuild_validates_device_budget(self, model):
        dp = make_dp(model)
        with pytest.raises(ValueError, match="devices"):
            dp.rebuild(dp=64)

    def test_provider_resize_dp_waiting_survives(self, model):
        """The full drain/restart story through the serving stack: the
        worker pauses, the topology rebuilds at a new dp count, and a
        request sitting in the queue rides through the rebuild to a
        normal completion."""
        from kafka_tpu.llm import TPULLMProvider
        from kafka_tpu.models.tokenizer import ByteTokenizer

        cfg, params = model
        tok = ByteTokenizer()
        cfg = cfg.replace(vocab_size=tok.vocab_size)
        params = init_params(cfg, jax.random.PRNGKey(5))
        dp = DataParallelEngines(
            cfg, params, EngineConfig(**ECFG), dp=2, tp=1,
            kv_dtype=jnp.float32,
        )
        provider = TPULLMProvider(dp, tok, model_name="resize-test")

        async def go():
            chunks = []
            async for c in provider.stream_completion(
                [{"role": "user", "content": "hi"}], max_tokens=4
            ):
                chunks.append(c)
            assert chunks[-1].finish_reason in ("stop", "length")
            clean = await provider.resize_dp(1, drain_timeout_s=30)
            assert clean is True
            assert len(provider.engine.engines) == 1
            # serving continues on the rebuilt topology
            chunks2 = []
            async for c in provider.stream_completion(
                [{"role": "user", "content": "after"}], max_tokens=4
            ):
                chunks2.append(c)
            assert chunks2[-1].finish_reason in ("stop", "length")
            await provider.aclose()

        asyncio.run(go())


class TestProbeMemoization:
    """PR 4 follow-up (ISSUE 5 satellite): the per-replica radix probe in
    _pick is memoized for the shared system-prompt head — O(1) per replica
    per keyed submit while the caches' generations are unchanged, with one
    O(match) head verification per submit."""

    def _dp(self, model, dp=2):
        cfg, params = model
        return DataParallelEngines(cfg, params, EngineConfig(**ECFG),
                                   dp=dp, tp=1, kv_dtype=jnp.float32)

    def test_warm_head_probes_once_per_submit(self, model):
        cfg, params = model
        dp = self._dp(model)
        common = list(np.random.RandomState(31).randint(1, 128, 16))
        # seed one replica's cache with the shared head
        dp.submit(GenRequest(request_id="seed", prompt_ids=common + [3],
                             max_new_tokens=2, prefix_key="t-seed"))
        dp.run_to_completion()
        probes0 = sum(e.prefix_cache.probes for e in dp.engines)
        # submit several cold threads sharing the head BEFORE any of them
        # finishes (no store -> no generation bump between submits)
        for i in range(4):
            dp.submit(GenRequest(request_id=f"cold{i}",
                                 prompt_ids=common + [7 + i],
                                 max_new_tokens=2,
                                 prefix_key=f"t-cold-{i}"))
        probed = sum(e.prefix_cache.probes for e in dp.engines) - probes0
        # Soundness requires the DEEPEST-match replica to re-probe every
        # submit (its memoized walk ended at the run boundary, so a deeper
        # match for a new continuation can't be ruled out); every OTHER
        # replica (match strictly inside the run, or 0) is O(1) via the
        # memo.  4 submits -> at most 4 warm-replica probes + one initial
        # walk per cold replica.
        assert probed <= 4 + (len(dp.engines) - 1), (
            f"{probed} probes for 4 same-head submits across "
            f"{len(dp.engines)} replicas — memoization not engaged"
        )
        dp.run_to_completion()

    def test_generation_bump_invalidates_memo(self, model):
        cfg, params = model
        dp = self._dp(model)
        common = list(np.random.RandomState(32).randint(1, 128, 16))
        dp.submit(GenRequest(request_id="s", prompt_ids=common + [3],
                             max_new_tokens=2, prefix_key="t-a"))
        dp.run_to_completion()
        # c1's prompt extends one FULL page past the shared head so its
        # store inserts a new node (a same-content store would leave the
        # tree — and the generation — untouched, and memo reuse would be
        # sound)
        dp.submit(GenRequest(request_id="c1", prompt_ids=common + [9] * 8,
                             max_new_tokens=2, prefix_key="t-b"))
        dp.run_to_completion()  # finish -> store new node -> generation bump
        # routes retire with their requests (run_to_completion drives the
        # router's own step loop since ISSUE 12); the affinity pin is the
        # durable record of where the thread landed
        warm = dp._affinity["t-b"]
        probes0 = dp.engines[warm].prefix_cache.probes
        dp.submit(GenRequest(request_id="c2", prompt_ids=common + [11],
                             max_new_tokens=2, prefix_key="t-c"))
        # the mutated replica must be re-probed (stale match would
        # mis-route), and routing still steers to the warm replica
        assert dp.engines[warm].prefix_cache.probes > probes0
        assert dp._route["c2"] == warm
        dp.run_to_completion()

    def test_full_run_match_reprobes_for_deeper_continuation(self, model):
        """A memoized match that consumed the WHOLE run must re-probe on
        the next submit: the warm tree continues past the run where the
        OLD prompt diverged, and a new prompt whose continuation follows
        the tree would match deeper — stale reuse would under-score the
        warmest replica."""
        cfg, params = model
        dp = self._dp(model)
        common = list(np.random.RandomState(36).randint(1, 128, 16))
        deep = [9] * 8  # page 3 of the stored path
        dp.submit(GenRequest(request_id="s", prompt_ids=common + deep + [3],
                             max_new_tokens=2, prefix_key="t-s"))
        dp.run_to_completion()  # warm tree: [common p0, common p1, deep]
        warm = dp._affinity["t-s"]
        # diverges at page 3 -> memo records match == run length (16)
        dp.submit(GenRequest(request_id="x",
                             prompt_ids=common + [7] * 8 + [4],
                             max_new_tokens=2, prefix_key="t-x"))
        probes0 = dp.engines[warm].prefix_cache.probes
        # same head, but the continuation FOLLOWS the stored path: the
        # true match is 24 tokens, knowable only by re-probing (the warm
        # generation is unchanged since the memo refresh, so a stale
        # reuse would score 16)
        dp.submit(GenRequest(request_id="y",
                             prompt_ids=common + deep + [5],
                             max_new_tokens=2, prefix_key="t-y"))
        assert dp.engines[warm].prefix_cache.probes > probes0
        assert dp._route["y"] == warm
        dp.run_to_completion()

    def test_divergent_head_reprobes(self, model):
        """A prompt with a DIFFERENT head must not reuse another head's
        memo entry (keyed on the first page of tokens)."""
        cfg, params = model
        dp = self._dp(model)
        a = list(np.random.RandomState(33).randint(1, 128, 16))
        b = list(np.random.RandomState(34).randint(1, 128, 16))
        dp.submit(GenRequest(request_id="a", prompt_ids=a + [2],
                             max_new_tokens=2, prefix_key="t-a"))
        dp.run_to_completion()
        warm = dp._affinity["t-a"]
        probes0 = sum(e.prefix_cache.probes for e in dp.engines)
        dp.submit(GenRequest(request_id="b", prompt_ids=b + [2],
                             max_new_tokens=2, prefix_key="t-b"))
        assert sum(e.prefix_cache.probes for e in dp.engines) > probes0
        dp.run_to_completion()

    def test_rebuild_clears_memo(self, model):
        cfg, params = model
        dp = self._dp(model)
        common = list(np.random.RandomState(35).randint(1, 128, 16))
        dp.submit(GenRequest(request_id="s", prompt_ids=common + [3],
                             max_new_tokens=2, prefix_key="t-s"))
        dp.run_to_completion()
        assert dp._probe_memo
        dp.rebuild(dp=1)
        assert not dp._probe_memo
