"""Disaggregated prefill/decode (ISSUE 12): cross-replica KV page
shipping with role-specialized pools.

The load-bearing claims:
  * page runs round-trip byte-exact through the CrossReplicaPageShipper
    (float32 + bf16, single- and multi-chunk, host-staged),
  * with KAFKA_TPU_DP_ROLES unset the router is byte-identical to the
    colocated behavior (no pools, no ship counters, outputs match the
    single engine),
  * with roles set, long keyed prompts route to the prefill pool as
    prefill-and-hand-offs, ship to a decode replica, and resume with
    cache_source="shipped" and zero prompt re-prefill beyond the
    mandatory boundary token — greedy outputs token-exact vs both the
    colocated router and a single engine,
  * short prompts below KAFKA_TPU_DISAGG_MIN_PREFILL_TOKENS prefill in
    place on the decode pool (shipping must never cost more than it
    saves),
  * a torn ship (kv.ship failpoint, incl. mid-run nth=2) never yields
    partial KV: destination pages free in full, the thread re-prefills,
    the failure counts in disagg_ship_failures, and outputs stay exact,
  * quarantine escalation: after KAFKA_TPU_REPLICA_REBUILD_THRESHOLD
    trips the supervisor rebuilds the replica's engine instead of
    re-admitting it forever,
  * the disagg section carries exactly DISAGG_METRIC_KEYS, the metric
    table's view of it, and its families render as parseable exposition,
  * the bench disagg phase smoke-runs on CPU.
"""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu import tracing
from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime import failpoints
from kafka_tpu.runtime.dp_router import (
    PROBATION,
    DataParallelEngines,
    parse_dp_roles,
)
from kafka_tpu.runtime.kv_tier import CrossReplicaPageShipper


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="disagg-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(17))
    return cfg, params


ECFG = dict(max_batch=2, page_size=8, num_pages=64, max_pages_per_seq=16,
            prefill_buckets=(8, 16, 32, 64, 128))


def make_dp(cfg, params, roles="prefill:1,decode:1", min_tokens=16, **kw):
    return DataParallelEngines(
        cfg, params, EngineConfig(**ECFG), dp=2, tp=1,
        kv_dtype=jnp.float32, dp_roles=roles,
        disagg_min_prefill_tokens=min_tokens, **kw,
    )


def prompt_of(seed, n):
    return [int(x) for x in np.random.RandomState(seed).randint(1, 128, n)]


class _Owner:
    """Minimal pool-array holder standing in for a replica engine (the
    shipper only needs mutable k_pool/v_pool)."""

    def __init__(self, num_pages, page_size, layers=2, width=8, seed=0,
                 dtype=np.float32):
        rng = np.random.default_rng(seed)
        shape = (layers, num_pages * page_size, width)
        self.k_pool = jnp.asarray(
            rng.normal(size=shape).astype(np.float32)
        ).astype(dtype)
        self.v_pool = jnp.asarray(
            rng.normal(size=shape).astype(np.float32)
        ).astype(dtype)


def _rows(owner, pages, page_size, pool="k"):
    arr = np.asarray(owner.k_pool if pool == "k" else owner.v_pool)
    return np.concatenate(
        [arr[:, p * page_size:(p + 1) * page_size] for p in pages], axis=1
    )


class TestCrossReplicaShipper:
    @pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
    def test_round_trip_byte_exact(self, dtype):
        if dtype == "bfloat16":
            import ml_dtypes

            dtype = ml_dtypes.bfloat16
        ps = 4
        src = _Owner(16, ps, seed=1, dtype=dtype)
        dst = _Owner(16, ps, seed=2, dtype=dtype)
        ship = CrossReplicaPageShipper(src, dst, ps)
        src_pages, dst_pages = [3, 7, 5], [9, 2, 11]
        want_k = _rows(src, src_pages, ps, "k")
        want_v = _rows(src, src_pages, ps, "v")
        nbytes = ship.ship(src_pages, dst_pages)
        assert nbytes == len(src_pages) * ship.bytes_per_page()
        got_k = _rows(dst, dst_pages, ps, "k")
        got_v = _rows(dst, dst_pages, ps, "v")
        np.testing.assert_array_equal(
            got_k.view(np.uint8), want_k.view(np.uint8)
        )
        np.testing.assert_array_equal(
            got_v.view(np.uint8), want_v.view(np.uint8)
        )

    def test_multi_chunk_round_trip(self):
        # 65+ pages exceed the largest SHIP_BUCKET (64): two chunks
        ps = 2
        src = _Owner(80, ps, layers=1, width=4, seed=3)
        dst = _Owner(80, ps, layers=1, width=4, seed=4)
        ship = CrossReplicaPageShipper(src, dst, ps)
        src_pages = list(range(1, 68))
        dst_pages = list(range(10, 77))
        want = _rows(src, src_pages, ps, "k")
        ship.ship(src_pages, dst_pages)
        np.testing.assert_array_equal(
            _rows(dst, dst_pages, ps, "k"), want
        )

    def test_length_mismatch_raises(self):
        from kafka_tpu.runtime.kv_tier import ShipError

        ps = 2
        src, dst = _Owner(8, ps), _Owner(8, ps)
        with pytest.raises(ShipError):
            CrossReplicaPageShipper(src, dst, ps).ship([1, 2], [3])

    def test_torn_chunk_raises(self):
        ps = 2
        src = _Owner(80, ps, layers=1, width=4, seed=5)
        dst = _Owner(80, ps, layers=1, width=4, seed=6)
        ship = CrossReplicaPageShipper(src, dst, ps)
        with failpoints.armed("kv.ship", "error", "torn", nth=2):
            with pytest.raises(failpoints.FailpointError):
                ship.ship(list(range(1, 68)), list(range(10, 77)))


class TestRoleParsing:
    def test_parse(self):
        assert parse_dp_roles(None) is None
        assert parse_dp_roles("") is None
        assert parse_dp_roles("prefill:2,decode:6") == (2, 6)
        assert parse_dp_roles(" decode:1 , prefill:1 ") == (1, 1)

    def test_parse_rejects(self):
        with pytest.raises(ValueError, match="unknown pool role"):
            parse_dp_roles("verify:2,decode:1")
        with pytest.raises(ValueError, match="at least one"):
            parse_dp_roles("prefill:2,decode:0")
        with pytest.raises(ValueError, match="bad replica count"):
            parse_dp_roles("prefill:x,decode:1")

    def test_construction_validates_dp(self, model):
        cfg, params = model
        with pytest.raises(ValueError, match="dp=2"):
            make_dp(cfg, params, roles="prefill:1,decode:2")

    def test_unset_roles_build_no_pools(self, model):
        cfg, params = model
        dp = make_dp(cfg, params, roles=None)
        assert dp._prefill_pool == [] and dp._decode_pool == []
        assert "disagg" not in dp.metrics.snapshot()


class TestRoleSteering:
    def test_long_prompt_hands_off_short_stays(self, model):
        cfg, params = model
        dp = make_dp(cfg, params, min_tokens=16)
        long_req = GenRequest(request_id="L", prompt_ids=prompt_of(1, 41),
                              max_new_tokens=2, prefix_key="T-long")
        dp.submit(long_req)
        assert long_req.handoff and dp._route["L"] == 0  # prefill pool
        short = GenRequest(request_id="S", prompt_ids=prompt_of(2, 9),
                           max_new_tokens=2, prefix_key="T-short")
        dp.submit(short)
        assert not short.handoff and dp._route["S"] == 1  # decode pool
        dp.run_to_completion()
        assert dp.disagg.prefill_in_place == 1
        assert dp.disagg.handoffs == 1

    def test_min_token_knob_keeps_everything_in_place(self, model):
        cfg, params = model
        dp = make_dp(cfg, params, min_tokens=10_000)
        r = GenRequest(request_id="L", prompt_ids=prompt_of(3, 41),
                       max_new_tokens=2, prefix_key="T")
        dp.submit(r)
        assert not r.handoff and dp._route["L"] == 1
        dp.run_to_completion()
        assert dp.disagg.handoffs == 0
        assert dp.disagg.prefill_in_place == 1

    def test_unkeyed_requests_serve_on_decode_pool(self, model):
        cfg, params = model
        dp = make_dp(cfg, params, min_tokens=16)
        r = GenRequest(request_id="U", prompt_ids=prompt_of(4, 41),
                       max_new_tokens=2)
        dp.submit(r)
        assert not r.handoff and dp._route["U"] == 1
        dp.run_to_completion()

    def test_min_token_measures_uncached_span(self, model):
        """A long prompt whose head is already cached on the decode home
        prefills in place: only the UNCACHED span counts against the
        knob."""
        cfg, params = model
        dp = make_dp(cfg, params, min_tokens=16)
        head = prompt_of(5, 41)
        a = GenRequest(request_id="A", prompt_ids=list(head),
                       max_new_tokens=2, prefix_key="T-A")
        dp.submit(a)
        dp.run_to_completion()
        assert a.cache_source == "shipped"
        # same head, short new tail: uncached span is under the knob
        b = GenRequest(request_id="B",
                       prompt_ids=head[:40] + prompt_of(6, 8),
                       max_new_tokens=2, prefix_key="T-B")
        dp.submit(b)
        assert not b.handoff and dp._route["B"] == 1
        dp.run_to_completion()


class TestDisaggParity:
    def test_token_exact_vs_colocated_and_single(self, model):
        """Greedy outputs are token-exact across single engine, colocated
        dp=2, and prefill:1,decode:1 — two turns per thread, so the
        second turn also exercises the shipped-run reuse path."""
        cfg, params = model
        single = InferenceEngine(cfg, params, EngineConfig(**ECFG),
                                 kv_dtype=jnp.float32)
        colo = make_dp(cfg, params, roles=None)
        disagg = make_dp(cfg, params, min_tokens=16)

        prompts = {f"t{i}": prompt_of(10 + i, 33 + 8 * i)
                   for i in range(3)}
        outs = {}
        for name, eng in (("single", single), ("colo", colo),
                          ("disagg", disagg)):
            outs[name] = {}
            for tid, p in prompts.items():
                r1 = GenRequest(request_id=f"{name}-{tid}-1",
                                prompt_ids=list(p), max_new_tokens=5,
                                prefix_key=tid)
                eng.submit(r1)
                eng.run_to_completion()
                r2 = GenRequest(request_id=f"{name}-{tid}-2",
                                prompt_ids=list(p) + r1.output_ids + [7],
                                max_new_tokens=4, prefix_key=tid)
                eng.submit(r2)
                eng.run_to_completion()
                outs[name][tid] = (list(r1.output_ids),
                                   list(r2.output_ids))
        assert outs["colo"] == outs["single"]
        assert outs["disagg"] == outs["single"]
        assert disagg.disagg.shipped_runs >= 1
        assert disagg.disagg.ship_failures == 0
        for e in disagg.engines + colo.engines + [single]:
            assert not e.self_check()

    def test_shipped_resume_zero_reprefill_and_trace(self, model):
        """The acceptance proof: a k*ps+1-token prompt hands off, ships,
        and resumes with every prompt token but the mandatory boundary
        token served from shipped pages — cache_source="shipped" on the
        request, the resume trace event, and the handoff event."""
        cfg, params = model
        dp = make_dp(cfg, params, min_tokens=16)
        ps = dp.ecfg.page_size
        prompt = prompt_of(20, 5 * ps + 1)

        tracing.reset()
        root = tracing.start_trace(request_id="ship-A")
        r = GenRequest(request_id="A", prompt_ids=list(prompt),
                       max_new_tokens=4, prefix_key="T-ship",
                       trace=tracing.current())
        dp.submit(r)
        assert r.handoff
        done = dp.run_to_completion()
        tracing.finish_trace(root)

        assert done["A"] is r
        assert r.cache_source == "shipped"
        # zero prompt re-prefill: everything but the boundary token
        # (whose prefill regenerates the already-emitted first token)
        assert r.cached_tokens == len(prompt) - 1
        # ...but the CLIENT-visible share stays the first admission's: a
        # cold thread's prompt was computed (on the prefill pool), so the
        # hand-off re-attach must not bill it as cached compute
        assert r.usage_cached_tokens == 0
        assert dp.disagg.shipped_runs == 1
        assert dp.disagg.shipped_pages == 5
        assert dp.disagg.shipped_bytes > 0
        dst = dp.engines[1]
        assert dst.prefix_cache.shipped_hits == 1
        tr = tracing.get_trace("ship-A")
        hand = [e for e in tr.events if e["name"] == "handoff"]
        assert len(hand) == 1
        assert hand[0]["attrs"]["from_replica"] == 0
        assert hand[0]["attrs"]["to_replica"] == 1
        assert hand[0]["attrs"]["shipped"] is True
        assert hand[0]["attrs"]["shipped_pages"] == 5
        resume = [e for e in tr.events if e["name"] == "resume"]
        assert len(resume) == 1
        assert resume[0]["attrs"]["cache_source"] == "shipped"
        assert resume[0]["attrs"]["cached_tokens"] == len(prompt) - 1
        # exactly one first token: the prefill replica's emission, the
        # decode replica's duplicate dropped
        assert len(r.output_ids) == 4
        for e in dp.engines:
            assert not e.self_check()

    def test_colocated_roles_unset_no_disagg_machinery(self, model):
        """With roles unset the dispatch paths are the pre-ISSUE-12 ones:
        no handoffs, no ship counters, prefix-aware routing as before."""
        cfg, params = model
        dp = make_dp(cfg, params, roles=None)
        r = GenRequest(request_id="x", prompt_ids=prompt_of(30, 41),
                       max_new_tokens=4, prefix_key="T")
        dp.submit(r)
        assert not r.handoff
        dp.run_to_completion()
        snap = dp.disagg.snapshot()
        assert snap["disagg_handoffs"] == 0
        assert snap["disagg_shipped_runs"] == 0
        assert all(not e.handoffs for e in dp.engines)


class TestTieredDestinationDelta:
    def test_delta_ship_onto_tiered_decode_replica(self, model):
        """PR 12 follow-up (ISSUE 14): with content-keyed skips the
        delta-ship path is enabled on destinations running a KV tier.
        Thread B's hand-off skips the shared head the decode replica
        already holds — even with that head DEMOTED to the host tier,
        where the old dummy-id adopt hazard lived: store()'s adoption
        now requires real page ids, so the host run keeps its tier copy
        and B's resume promotes it (zero re-prefill, token-exact)."""
        cfg, params = model
        ecfg = EngineConfig(**ECFG, kv_host_tier_mb=64)
        dp = DataParallelEngines(
            cfg, params, ecfg, dp=2, tp=1, kv_dtype=jnp.float32,
            dp_roles="prefill:1,decode:1", disagg_min_prefill_tokens=8,
        )
        ps = dp.ecfg.page_size
        head = prompt_of(91, 4 * ps)
        tail_a = prompt_of(92, ps)
        tail_b = prompt_of(93, ps)

        # thread A: full 5-page ship seeds the decode replica's cache
        ra = GenRequest(request_id="A", prompt_ids=head + tail_a + [3],
                        max_new_tokens=4, prefix_key="T-a")
        dp.submit(ra)
        assert ra.handoff
        dp.run_to_completion()
        assert dp.disagg.shipped_pages == 5
        dst = dp.engines[1]
        assert dst.kv_tier is not None

        # demote A's run into the decode replica's HOST tier — the
        # configuration the delta path used to be gated off for
        assert dst.prefix_cache.reclaim(
            dst.pool.free_pages + dst.prefix_cache.total_pages
        )
        assert dst.prefix_cache.host_nodes >= 1

        rb = GenRequest(request_id="B", prompt_ids=head + tail_b + [5],
                        max_new_tokens=4, prefix_key="T-b")
        dp.submit(rb)
        assert rb.handoff
        dp.run_to_completion()
        # delta: only B's 1-page tail crossed the wire (the 4-page head
        # was counted as matched even though it sat in the HOST tier)
        assert dp.disagg.shipped_pages == 6
        # the host-resident head did NOT adopt the dummy entries — B's
        # resume PROMOTED it from the tier (real H2D traffic, not
        # captured garbage ids) and decoded with zero prompt re-prefill
        assert dst.kv_tier.snapshot()["promotions"] >= 1
        assert rb.cache_source == "shipped"
        assert rb.cached_tokens == 5 * ps
        for e in dp.engines:
            assert not e.self_check()

        # B's second turn stays warm on the tiered destination
        rb2 = GenRequest(request_id="B2",
                         prompt_ids=head + tail_b + [5] + rb.output_ids,
                         max_new_tokens=4, prefix_key="T-b")
        dp.submit(rb2)
        dp.run_to_completion()
        assert rb2.cached_tokens >= 5 * ps

        # token-exactness vs a single engine serving the same threads
        single = InferenceEngine(cfg, params, EngineConfig(**ECFG),
                                 kv_dtype=jnp.float32)
        outs = {}
        for tid, p in (("a", head + tail_a + [3]), ("b", head + tail_b + [5])):
            r1 = GenRequest(request_id=f"s-{tid}", prompt_ids=list(p),
                            max_new_tokens=4, prefix_key=f"s-{tid}")
            single.submit(r1)
            single.run_to_completion()
            outs[tid] = list(r1.output_ids)
        assert outs["a"] == list(ra.output_ids)
        assert outs["b"] == list(rb.output_ids)
        s2 = GenRequest(request_id="s-b2",
                        prompt_ids=head + tail_b + [5] + outs["b"],
                        max_new_tokens=4, prefix_key="s-b")
        single.submit(s2)
        single.run_to_completion()
        assert list(s2.output_ids) == list(rb2.output_ids)
        for e in dp.engines:
            assert not e.self_check()


class TestTornShip:
    def test_torn_first_chunk_degrades_to_reprefill(self, model):
        """kv.ship error on the first chunk: nothing lands, the thread
        re-prefills on the decode replica, outputs stay token-exact, the
        failure is counted, and the destination accounting stays
        clean."""
        cfg, params = model
        dp = make_dp(cfg, params, min_tokens=16)
        prompt = prompt_of(40, 41)
        ref = InferenceEngine(cfg, params, EngineConfig(**ECFG),
                              kv_dtype=jnp.float32)
        want = ref.generate(list(prompt), max_new_tokens=5).output_ids

        r = GenRequest(request_id="T", prompt_ids=list(prompt),
                       max_new_tokens=5, prefix_key="T-torn")
        with failpoints.armed("kv.ship", "error", "torn", nth=1):
            dp.submit(r)
            assert r.handoff
            done = dp.run_to_completion()
        assert done["T"].output_ids == want
        assert r.cache_source != "shipped"
        assert dp.disagg.ship_failures == 1
        dst = dp.engines[1]
        assert not dst.pool.check_consistency()
        for e in dp.engines:
            assert not e.self_check()

    def test_torn_mid_run_never_partial_kv(self, model):
        """A MULTI-chunk ship (> 64 pages = > one SHIP_BUCKET) torn at
        chunk 2: the first chunk already scattered into the destination,
        and the cleanup must free every destination page — the thread
        re-prefills from token zero rather than ever decoding from
        half-imported KV (token-exact vs an untouched engine)."""
        cfg, params = model
        ecfg = dict(max_batch=2, page_size=4, num_pages=256,
                    max_pages_per_seq=96,
                    prefill_buckets=(16, 64, 128, 256, 512))
        dp = DataParallelEngines(
            cfg, params, EngineConfig(**ecfg), dp=2, tp=1,
            kv_dtype=jnp.float32, dp_roles="prefill:1,decode:1",
            disagg_min_prefill_tokens=16,
        )
        prompt = prompt_of(42, 281)  # 70 pages -> chunks of 64 + 6
        ref = InferenceEngine(cfg, params, EngineConfig(**ecfg),
                              kv_dtype=jnp.float32)
        want = ref.generate(list(prompt), max_new_tokens=4).output_ids

        dst = dp.engines[1]
        free_before = dst.pool.free_pages
        r = GenRequest(request_id="T2", prompt_ids=list(prompt),
                       max_new_tokens=4, prefix_key="T-torn2")
        with failpoints.armed("kv.ship", "error", "torn", nth=2):
            dp.submit(r)
            assert r.handoff
            done = dp.run_to_completion()
        assert done["T2"].output_ids == want
        assert r.cache_source != "shipped"
        assert dp.disagg.ship_failures == 1
        # every destination page freed, then re-consumed by the
        # re-prefill whose pages the radix store retains at finish
        pc = dst.prefix_cache
        assert dst.pool.free_pages == free_before - pc.total_pages
        assert not dst.pool.check_consistency()
        for e in dp.engines:
            assert not e.self_check()

    def test_ship_delay_only_slows(self, model):
        cfg, params = model
        dp = make_dp(cfg, params, min_tokens=16)
        prompt = prompt_of(41, 41)
        r = GenRequest(request_id="D", prompt_ids=list(prompt),
                       max_new_tokens=4, prefix_key="T-slow")
        with failpoints.armed("kv.ship", "delay", "0.02"):
            dp.submit(r)
            dp.run_to_completion()
        assert r.cache_source == "shipped"
        assert dp.disagg.ship_failures == 0
        assert dp.disagg.ship_ms.sum >= 20.0  # the delay is in the span

    def test_ship_site_documented(self):
        assert "kv.ship" in failpoints.SITES

    def test_cancel_retires_pending_handoff(self, model):
        """A cancel landing in the window where the hand-off sits parked
        on engine.handoffs (prefill done, ship pending) must retire it —
        not let the next drain resurrect a cancelled stream as an orphan
        decoding into the void."""
        cfg, params = model
        dp = make_dp(cfg, params, min_tokens=16)
        r = GenRequest(request_id="C", prompt_ids=prompt_of(60, 41),
                       max_new_tokens=4, prefix_key="T-c")
        dp.submit(r)
        assert r.handoff
        e0 = dp.engines[0]
        # drive ONLY the prefill engine (the router's drain never runs),
        # reproducing a hand-off that survives a step boundary
        for _ in range(500):
            if e0.handoffs:
                break
            e0.step()
        assert e0.handoffs
        assert dp.cancel("C") is True
        assert not e0.handoffs
        assert r.seq is None and r.finish_reason == "cancelled"
        dp.run_to_completion()  # nothing resurrects
        assert "C" not in dp._route
        assert dp.engines[1].num_active == 0
        for e in dp.engines:
            assert not e.self_check()


class TestQuarantineEscalation:
    def test_rebuild_after_repeated_trips(self, model):
        """PR 2 follow-up: after rebuild_threshold quarantine trips the
        supervisor rebuilds the replica's engine at window expiry instead
        of re-admitting it forever; waiting requests carry over and the
        fresh engine serves."""
        cfg, params = model
        dp = DataParallelEngines(
            cfg, params, EngineConfig(**ECFG), dp=2, tp=1,
            kv_dtype=jnp.float32, quarantine_threshold=1,
            quarantine_window_s=0.02, rebuild_threshold=2,
        )
        old = dp.engines[0]

        class Boom(Exception):
            pass

        def bad_step():
            raise Boom("injected")

        for trip in range(2):
            dp.engines[0].step = bad_step
            r = GenRequest(request_id=f"q{trip}", prompt_ids=[1, 2, 3],
                           max_new_tokens=2)
            dp.engines[0].submit(r)
            dp._route[r.request_id] = 0
            with pytest.raises(Boom):
                dp.step()
            dp.recover_from_failure()
            assert dp.health[0].state == "quarantined"
            deadline = time.monotonic() + 5.0
            while (dp.health[0].state == "quarantined"
                   and time.monotonic() < deadline):
                time.sleep(0.01)
                dp._refresh_health()
        assert dp.engines[0] is not old
        assert dp.health[0].state == PROBATION
        assert dp.supervisor.replica_rebuilds == 1
        # the fresh engine serves (the injected bad step died with the
        # old engine object)
        r = GenRequest(request_id="ok", prompt_ids=[5, 6, 7],
                       max_new_tokens=3)
        dp.submit(r)
        done = dp.run_to_completion()
        assert done["ok"].finish_reason in ("length", "stop")

    def test_rebuild_disabled_at_zero(self, model):
        cfg, params = model
        dp = DataParallelEngines(
            cfg, params, EngineConfig(**ECFG), dp=2, tp=1,
            kv_dtype=jnp.float32, quarantine_threshold=1,
            quarantine_window_s=0.01, rebuild_threshold=0,
        )
        old = dp.engines[0]
        h = dp.health[0]
        h.state = "quarantined"
        h.quarantine_count = 99
        h.quarantined_until = time.monotonic() - 1.0
        dp._refresh_health()
        assert dp.engines[0] is old
        assert dp.health[0].state == PROBATION


class TestDisaggMetricsRegistry:
    def test_snapshot_matches_registry_exactly(self):
        from kafka_tpu.runtime.metrics import (
            DISAGG_METRIC_KEYS,
            DisaggMetrics,
        )

        snap = DisaggMetrics().snapshot()
        assert set(snap) - {"ship_ms"} == set(DISAGG_METRIC_KEYS)

    def test_aggregate_snapshot_and_prometheus(self, model):
        from kafka_tpu.runtime.metrics import DISAGG_METRIC_KEYS
        from kafka_tpu.server.prometheus import render_prometheus

        cfg, params = model
        dp = make_dp(cfg, params, min_tokens=16)
        r = GenRequest(request_id="m", prompt_ids=prompt_of(50, 41),
                       max_new_tokens=3, prefix_key="T-m")
        dp.submit(r)
        dp.run_to_completion()
        snap = dp.metrics.snapshot()
        assert set(snap["disagg"]) - {"ship_ms", "pools"} == set(
            DISAGG_METRIC_KEYS
        )
        roles = [p["role"] for p in snap["disagg"]["pools"]]
        assert roles == ["prefill", "decode"]
        for pool in snap["disagg"]["pools"]:
            assert set(pool["utilization"]) == {"prefill", "decode",
                                                "verify"}
        text = render_prometheus(snap)
        for family in (
            "kafka_tpu_disagg_shipped_runs_total",
            "kafka_tpu_disagg_shipped_pages_total",
            "kafka_tpu_disagg_shipped_bytes_total",
            "kafka_tpu_disagg_ship_failures_total",
            "kafka_tpu_disagg_handoffs_total",
            "kafka_tpu_disagg_ship_milliseconds_bucket",
            'kafka_tpu_disagg_pool_occupancy{role="decode"}',
            'kafka_tpu_prefix_cache_total{kind="shipped_hits"}',
        ):
            assert family in text, family
        # the in-tree exposition checker accepts the new families
        import sys

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from test_prometheus import parse_exposition

        parse_exposition(text)

    def test_trace_registry_has_disagg_events(self):
        assert "handoff" in tracing.EVENTS
        assert "resume" in tracing.EVENTS


class TestBenchSmoke:
    def test_disagg_phase_quick(self, model):
        import importlib.util
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "bench", os.path.join(root, "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        sys.modules["bench"] = bench
        spec.loader.exec_module(bench)
        cfg, params = model
        out = bench.disagg_phase(
            cfg, params, n_chatty=3, n_long=2, chatty_prompt=24,
            chatty_gen=24, long_prompt=129, long_gen=3, page_size=8,
            min_prefill_tokens=32, stagger_steps=4,
        )
        assert out["shipped_runs"] >= 1
        assert out["prefill_tokens_recomputed"] == 0
        assert out["ship_failures"] == 0
        # the stall on the scheduler's clock: lane-iterations a decoding
        # chatty lane shared with a long prompt's prefill chunk.  The
        # wall-clock decode_tpot_p99_ms beside it is reported, not held:
        # two ~10 ms CPU legs next to five other xdist workers ordered
        # either way in four driver runs of six.
        stall = out["decode_stall_steps"]
        assert stall["disaggregated"] == 0 < stall["colocated"], stall
        assert set(out["decode_tpot_p99_ms"]) >= {"colocated",
                                                  "disaggregated"}
