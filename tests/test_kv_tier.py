"""Tiered KV cache (ISSUE 9): host-RAM page tier under the PagePool.

The load-bearing claims:
  * page runs round-trip byte-exact through the host tier AND the disk
    tier (demote -> overwrite the source pages -> promote -> compare),
  * a thread whose KV was evicted under page pressure resumes with its
    prefill starting at the promoted page boundary
    (cache_source="host_tier"), token-identical to an untiered engine,
  * randomized store/demote/promote/evict/invalidate interleavings keep
    PagePool.check_consistency + reconcile clean and every promoted page
    byte-exact,
  * a failed/torn promote degrades to re-prefill (never corrupt KV), a
    failed demote falls back to plain eviction — both via the kv.demote /
    kv.promote failpoints,
  * with the tier knobs unset nothing is built and dispatch/eviction
    behavior is unchanged,
  * the tier's snapshot carries exactly KV_TIER_METRIC_KEYS, the metric
    table's view of the section,
  * the span ring persists alongside the disk tier and survives reset,
  * large-vocab grammar compiles defer to the background worker
    (constrained_compile_pending gauge) instead of stalling the first
    call.
"""

import os
import random
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu import tracing
from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import (
    EngineConfig,
    GenRequest,
    InferenceEngine,
    PagePool,
)
from kafka_tpu.runtime import failpoints
from kafka_tpu.runtime.kv_tier import (
    SHIP_BUCKETS,
    KVTierManager,
    LocalPageShipper,
    _bucketize,
)
from kafka_tpu.runtime.prefix_cache import PrefixCache


class _Owner:
    """Minimal pool-array holder standing in for the engine (the shipper
    only needs mutable k_pool/v_pool)."""

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


def _write_rows(owner, pages, page_size, k_rows, v_rows):
    for i, p in enumerate(pages):
        sl = slice(p * page_size, (p + 1) * page_size)
        src = slice(i * page_size, (i + 1) * page_size)
        owner.k_pool = owner.k_pool.at[:, sl].set(k_rows[:, src])
        owner.v_pool = owner.v_pool.at[:, sl].set(v_rows[:, src])


class TestShipper:
    def test_bucketize(self):
        assert _bucketize(1) == [1]
        assert _bucketize(3) == [4]
        assert _bucketize(64) == [64]
        assert _bucketize(65) == [64, 1]
        assert _bucketize(200) == [64, 64, 64, 8]
        assert sum(_bucketize(37)) >= 37

    def test_host_round_trip_exact(self):
        ps = 4
        o = _Owner(16, ps, seed=1)
        ship = LocalPageShipper(o, ps)
        mgr = KVTierManager(ship, host_budget_bytes=1 << 30, page_size=ps)
        pages = [3, 7, 5]
        want_k = _rows(o, pages, ps, "k")
        want_v = _rows(o, pages, ps, "v")
        rid = mgr.demote(pages)
        assert rid is not None
        mgr.drain(force=True)
        # clobber the source pages: promote must restore from the copy
        for p in pages:
            o.k_pool = o.k_pool.at[:, p * ps:(p + 1) * ps].set(0.0)
        dest = [1, 2, 9]
        assert mgr.promote(rid, dest)
        assert np.array_equal(_rows(o, dest, ps, "k"), want_k)
        assert np.array_equal(_rows(o, dest, ps, "v"), want_v)

    def test_multi_chunk_run_round_trips(self):
        # a run longer than the largest ship bucket crosses chunks
        ps, n = 2, SHIP_BUCKETS[-1] + 3
        o = _Owner(n + 10, ps, layers=1, width=4, seed=2)
        ship = LocalPageShipper(o, ps)
        mgr = KVTierManager(ship, host_budget_bytes=1 << 30, page_size=ps)
        pages = list(range(2, 2 + n))
        want = _rows(o, pages, ps, "k")
        rid = mgr.demote(pages)
        assert rid is not None
        dest = list(range(2, 2 + n))  # reuse the same slots
        o.k_pool = jnp.zeros_like(o.k_pool)
        assert mgr.promote(rid, dest)
        assert np.array_equal(_rows(o, dest, ps, "k"), want)

    def test_disk_round_trip_exact_bf16(self, tmp_path):
        ps = 4
        o = _Owner(16, ps, seed=3, dtype=jnp.bfloat16)
        ship = LocalPageShipper(o, ps)
        mgr = KVTierManager(ship, host_budget_bytes=0, page_size=ps,
                            disk_dir=str(tmp_path))
        mgr.host_budget_bytes = ship.bytes_per_page() * 2  # one 2-page run
        pages = [6, 7]
        want = _rows(o, pages, ps, "k")
        rid = mgr.demote(pages)
        assert rid is not None
        mgr.drain(force=True)
        rid2 = mgr.demote([1, 2])  # overflows the budget: rid spills
        assert rid2 is not None
        mgr.flush()
        snap = mgr.snapshot()
        # at least the over-budget run spilled; drain()'s budget
        # re-enforcement may also spill the second while the first's
        # write is still charged as host bytes (honest accounting —
        # both stay promotable either way)
        assert snap["disk_spills"] >= 1
        assert snap["disk_runs"] == snap["disk_spills"]
        assert os.listdir(tmp_path)
        o.k_pool = jnp.zeros_like(o.k_pool)
        assert mgr.promote(rid, [10, 11])
        assert np.array_equal(_rows(o, [10, 11], ps, "k"), want)
        assert mgr.snapshot()["disk_loads"] == 1

    def test_second_chance_keeps_touched_run(self, tmp_path):
        ps = 2
        o = _Owner(32, ps, layers=1, width=4, seed=4)
        ship = LocalPageShipper(o, ps)
        mgr = KVTierManager(ship, host_budget_bytes=0, page_size=ps)
        mgr.host_budget_bytes = ship.bytes_per_page() * 4  # two 2-page runs
        r1 = mgr.demote([1, 2])
        r2 = mgr.demote([3, 4])
        mgr.drain(force=True)
        mgr.touch(r1)  # reference bit: r1 gets a second chance
        r3 = mgr.demote([5, 6])  # overflow: victim should be r2, not r1
        assert r3 is not None
        assert mgr.snapshot()["host_evictions"] == 1
        assert mgr.promote(r1, [10, 11])  # survived
        assert not mgr.promote(r2, [12, 13])  # dropped -> promote fails

    def test_split_preserves_bytes(self):
        ps = 2
        o = _Owner(32, ps, layers=1, width=4, seed=5)
        ship = LocalPageShipper(o, ps)
        mgr = KVTierManager(ship, host_budget_bytes=1 << 30, page_size=ps)
        pages = [4, 5, 6]
        want = _rows(o, pages, ps, "k")
        rid = mgr.demote(pages)
        parts = mgr.split(rid, 1)
        assert parts is not None
        front, back = parts
        assert mgr.promote(front, [10])
        assert mgr.promote(back, [11, 12])
        got = _rows(o, [10, 11, 12], ps, "k")
        assert np.array_equal(got, want)

    def test_oversized_run_refused(self):
        ps = 2
        o = _Owner(16, ps, layers=1, width=4)
        ship = LocalPageShipper(o, ps)
        mgr = KVTierManager(ship, host_budget_bytes=1, page_size=ps)
        assert mgr.demote([1, 2, 3]) is None  # never fits: refused


class TestPrefixCacheTier:
    def _setup(self, num_pages=32, ps=4, budget=1 << 30, disk=None):
        o = _Owner(num_pages, ps, seed=11)
        pool = PagePool(num_pages=num_pages, page_size=ps)
        mgr = KVTierManager(LocalPageShipper(o, ps),
                            host_budget_bytes=budget, page_size=ps,
                            disk_dir=disk)
        cache = PrefixCache(pool, tier=mgr)
        return o, pool, mgr, cache

    def _store(self, o, pool, cache, key, tokens, rng):
        """Alloc pages, stamp them with a token-derived pattern (stand-in
        for real KV writes), store, release the sequence's holds."""
        ps = pool.page_size
        n = len(tokens) // ps
        pages = pool.alloc(n)
        k = np.empty((2, n * ps, 8), np.float32)
        v = np.empty((2, n * ps, 8), np.float32)
        for i in range(n):
            k[:, i * ps:(i + 1) * ps] = float(tokens[i * ps]) + 0.25
            v[:, i * ps:(i + 1) * ps] = float(tokens[i * ps]) + 0.5
        _write_rows(o, pages, ps, k, v)
        cache.store(key, tokens, pages)
        pool.release(pages)

    def _verify_hit(self, o, ps, prompt, hit):
        """Every returned page must carry the pattern of its token page."""
        for i, p in enumerate(hit.pages):
            tok = float(prompt[i * ps])
            k = np.asarray(o.k_pool)[:, p * ps:(p + 1) * ps]
            v = np.asarray(o.v_pool)[:, p * ps:(p + 1) * ps]
            assert np.all(k == tok + 0.25), f"K page {i} corrupt"
            assert np.all(v == tok + 0.5), f"V page {i} corrupt"

    def test_demote_then_promote_hit(self):
        o, pool, mgr, cache = self._setup()
        rng = random.Random(0)
        tokens = [rng.randrange(100) for _ in range(12)]
        self._store(o, pool, cache, "t1", tokens, rng)
        assert cache.reclaim(pool.free_pages + 3)
        assert cache.host_nodes == 1 and cache.total_pages == 0
        # still matchable: the router counts host runs as affinity
        assert cache.match_tokens(tokens + [1]) == 12
        hit = cache.lookup("t1", tokens + [1])
        assert hit is not None and hit.source == "host_tier"
        assert hit.promoted_tokens == 12 and hit.tokens == 12
        self._verify_hit(o, pool.page_size, tokens, hit)
        pool.release(hit.pages)
        assert not pool.check_consistency()
        assert not pool.reconcile(cache.page_owners())

    def test_promotion_reclaims_other_leaves(self):
        # pool too small to hold the promoted run AND the other cached
        # run: promotion must demote the cold one, never truncate
        o, pool, mgr, cache = self._setup(num_pages=12, ps=4)
        rng = random.Random(1)
        hot = [rng.randrange(50) for _ in range(24)]       # 6 pages
        cold = [50 + rng.randrange(50) for _ in range(24)]  # 6 pages
        self._store(o, pool, cache, "hot", hot, rng)
        assert cache.reclaim(pool.free_pages + 6)  # demote hot
        self._store(o, pool, cache, "cold", cold, rng)
        assert pool.free_pages < 6  # cold's pages crowd the pool
        hit = cache.lookup("hot", hot + [1])
        assert hit is not None and hit.promoted_tokens == 24
        self._verify_hit(o, 4, hot, hit)
        assert cache.host_nodes == 1  # cold got demoted to make room
        pool.release(hit.pages)
        assert not pool.check_consistency()

    def test_store_adopts_host_run(self):
        o, pool, mgr, cache = self._setup()
        rng = random.Random(2)
        tokens = [rng.randrange(100) for _ in range(8)]
        self._store(o, pool, cache, "a", tokens, rng)
        assert cache.reclaim(pool.free_pages + 2)
        assert cache.host_nodes == 1
        # a sibling stores the same prefix with freshly-computed pages
        self._store(o, pool, cache, "b", tokens, rng)
        assert cache.host_nodes == 0 and cache.total_pages == 2
        assert mgr.snapshot()["host_runs"] == 0  # run discarded (adopted)
        hit = cache.lookup("b", tokens + [1])
        assert hit.source == "own" and hit.promoted_tokens == 0
        pool.release(hit.pages)

    def test_invalidate_discards_host_runs(self):
        o, pool, mgr, cache = self._setup()
        rng = random.Random(3)
        tokens = [rng.randrange(100) for _ in range(8)]
        self._store(o, pool, cache, "a", tokens, rng)
        assert cache.reclaim(pool.free_pages + 2)
        cache.invalidate("a")
        assert len(cache) == 0 and cache.host_nodes == 0
        assert mgr.snapshot()["host_runs"] == 0
        assert not pool.check_consistency()

    def test_lost_run_degrades_to_miss_and_removes_node(self):
        o, pool, mgr, cache = self._setup()
        rng = random.Random(4)
        tokens = [rng.randrange(100) for _ in range(8)]
        self._store(o, pool, cache, "a", tokens, rng)
        assert cache.reclaim(pool.free_pages + 2)
        # simulate the tier losing the run (budget drop on a dir-less tier)
        run_id = next(iter(mgr._runs))
        mgr.discard(run_id)
        hit = cache.lookup("a", tokens + [1])
        assert hit is None  # degrade to re-prefill
        assert len(cache) == 0  # node removed
        assert mgr.promote_failures >= 1
        assert not pool.check_consistency()

    def test_randomized_tier_chaos(self):
        """store/demote/promote/evict/invalidate interleavings: allocator
        invariants hold after EVERY op and every hit's pages are
        byte-exact against the token-derived pattern."""
        o, pool, mgr, cache = self._setup(num_pages=48, ps=4, budget=0)
        mgr.host_budget_bytes = (
            mgr.shipper.bytes_per_page() * 20
        )  # tight: forces drops too
        rng = random.Random(1234)
        ps = 4
        threads = {}
        live_holds = []  # (pages,) retained by "live requests"

        def owners():
            own = dict(cache.page_owners())
            for pages in live_holds:
                for p in pages:
                    own[p] = own.get(p, 0) + 1
            return own

        for step in range(300):
            op = rng.randrange(7)
            if op <= 2 or not threads:  # store a (possibly shared) run
                if threads and rng.random() < 0.4:
                    base = list(rng.choice(list(threads.values())))
                    base = base[: ps * rng.randrange(
                        1, max(2, len(base) // ps + 1))]
                else:
                    base = []
                tail_pages = rng.randrange(1, 4)
                tokens = base + [rng.randrange(90)
                                 for _ in range(tail_pages * ps)]
                tokens = tokens[: (len(tokens) // ps) * ps]
                key = f"t{rng.randrange(8)}"
                if len(tokens) // ps > pool.free_pages:
                    cache.reclaim(len(tokens) // ps)
                if len(tokens) // ps <= pool.free_pages:
                    self._store(o, pool, cache, key, tokens, rng)
                    threads[key] = tokens
            elif op == 3:  # lookup (may promote) + verify + hold a bit
                key = rng.choice(list(threads))
                prompt = threads[key] + [rng.randrange(90)]
                hit = cache.lookup(key, prompt)
                if hit is not None:
                    self._verify_hit(o, ps, prompt, hit)
                    if rng.random() < 0.5 and len(live_holds) < 3:
                        live_holds.append(hit.pages)
                    else:
                        pool.release(hit.pages)
            elif op == 4:  # pressure reclaim (demotes or drops)
                cache.reclaim(pool.free_pages + rng.randrange(1, 6))
            elif op == 5:  # invalidate a thread
                key = rng.choice(list(threads))
                cache.invalidate(key)
                threads.pop(key, None)
            else:  # a live request retires
                if live_holds:
                    pool.release(live_holds.pop(
                        rng.randrange(len(live_holds))))
            if rng.random() < 0.3:
                mgr.drain(force=True)
            problems = pool.check_consistency()
            assert not problems, f"step {step}: {problems}"
            reports = pool.reconcile(owners())
            assert not reports, f"step {step}: {reports}"
        for pages in live_holds:
            pool.release(pages)
        cache.clear()
        mgr.flush()
        assert not pool.check_consistency()
        assert pool.free_pages == pool.num_pages - 1


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="tier-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def make_engine(cfg, params, **kw):
    defaults = dict(max_batch=2, page_size=8, num_pages=24,
                    max_pages_per_seq=16,
                    prefill_buckets=(8, 16, 32, 64, 128),
                    kv_host_tier_mb=64)
    defaults.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**defaults),
                           kv_dtype=jnp.float32)


def _churn(eng, rng, n=3, prompt_len=96):
    for i in range(n):
        r = GenRequest(
            request_id=f"churn-{i}-{int(rng.integers(1 << 30))}",
            prompt_ids=[int(x) for x in rng.integers(1, 120, prompt_len)],
            max_new_tokens=4, prefix_key=f"churn-{i}",
        )
        eng.submit(r)
        eng.run_to_completion()


class TestEngineTierResume:
    def test_resume_starts_at_promoted_boundary(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        assert eng.kv_tier is not None
        rng = np.random.default_rng(3)
        prompt = [int(x) for x in rng.integers(1, 120, 64)]
        a = GenRequest(request_id="A", prompt_ids=prompt,
                       max_new_tokens=8, prefix_key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        _churn(eng, rng)
        pc = eng.prefix_cache
        assert pc.host_nodes > 0, "pressure must demote, not drop"

        tracing.reset()
        root = tracing.start_trace(request_id="resume-A")
        resume = prompt + list(a.output_ids) + [
            int(x) for x in rng.integers(1, 120, 12)
        ]
        a2 = GenRequest(request_id="A2", prompt_ids=resume,
                        max_new_tokens=8, prefix_key="thread-A",
                        trace=tracing.current())
        eng.submit(a2)
        eng.run_to_completion()
        tracing.finish_trace(root)

        assert a2.cache_source == "host_tier"
        assert a2.promoted_tokens > 0
        assert a2.cached_tokens >= a2.promoted_tokens
        # prefill began at the promoted boundary, not token zero
        assert a2.cached_tokens % eng.ecfg.page_size == 0
        assert pc.host_tier_hits == 1
        tr = tracing.get_trace("resume-A")
        names = [s.name for s in tr.spans]
        assert "kv.promote" in names
        pf = next(s for s in tr.spans if s.name == "engine.prefill")
        assert pf.attrs["cache_source"] == "host_tier"
        assert pf.attrs["promoted_tokens"] == a2.promoted_tokens
        assert pf.attrs["cached_tokens"] == a2.cached_tokens
        assert not eng.self_check()

        # token-identical to an untiered engine on the same sequence
        base = make_engine(cfg, params, kv_host_tier_mb=0)
        assert base.kv_tier is None
        b1 = GenRequest(request_id="b1", prompt_ids=prompt,
                        max_new_tokens=8, prefix_key="t")
        base.submit(b1)
        base.run_to_completion()
        assert b1.output_ids == a.output_ids
        b2 = GenRequest(request_id="b2", prompt_ids=resume,
                        max_new_tokens=8, prefix_key="t")
        base.submit(b2)
        base.run_to_completion()
        assert b2.output_ids == a2.output_ids

    def test_tier_off_is_default_and_builds_nothing(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, kv_host_tier_mb=0)
        assert eng.kv_tier is None
        assert eng.prefix_cache.tier is None
        # default EngineConfig: off
        assert EngineConfig().kv_host_tier_mb == 0

    def test_negative_budget_rejected(self, model):
        cfg, params = model
        with pytest.raises(ValueError, match="kv_host_tier_mb"):
            make_engine(cfg, params, kv_host_tier_mb=-1)

    def test_warmup_kv_tier_compiles_without_state_change(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        free0 = eng.pool.free_pages
        eng.warmup_kv_tier()
        assert eng.pool.free_pages == free0
        assert not eng.self_check()
        # untiered engine: strict no-op
        base = make_engine(cfg, params, kv_host_tier_mb=0)
        base.warmup_kv_tier()

    def test_disk_tier_spill_and_resume(self, model, tmp_path):
        cfg, params = model
        eng = make_engine(cfg, params, kv_host_tier_mb=1,
                          kv_disk_tier_dir=str(tmp_path))
        # force the budget down to ~one-and-a-half runs so the second
        # demotion overflows the host tier and spills the first to disk
        eng.kv_tier.host_budget_bytes = (
            eng.kv_tier.shipper.bytes_per_page() * 14
        )
        rng = np.random.default_rng(5)
        prompt = [int(x) for x in rng.integers(1, 120, 64)]
        a = GenRequest(request_id="A", prompt_ids=prompt,
                       max_new_tokens=8, prefix_key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        out_a = list(a.output_ids)
        _churn(eng, rng, n=4)
        eng.kv_tier.flush()
        snap = eng.kv_tier.snapshot()
        assert snap["disk_spills"] > 0, snap
        resume = prompt + out_a + [int(x) for x in rng.integers(1, 120, 8)]
        a2 = GenRequest(request_id="A2", prompt_ids=resume,
                        max_new_tokens=8, prefix_key="thread-A")
        eng.submit(a2)
        eng.run_to_completion()
        # the resume either promoted (from host or disk) or re-prefilled
        # cleanly; either way the engine stays consistent and the output
        # matches the untiered engine
        assert not eng.self_check()
        base = make_engine(cfg, params, kv_host_tier_mb=0)
        r1 = GenRequest(request_id="r1", prompt_ids=prompt,
                        max_new_tokens=8, prefix_key="t")
        base.submit(r1)
        base.run_to_completion()
        r2 = GenRequest(request_id="r2", prompt_ids=resume,
                        max_new_tokens=8, prefix_key="t")
        base.submit(r2)
        base.run_to_completion()
        assert a2.output_ids == r2.output_ids


class TestTierFailpoints:
    def test_demote_fault_falls_back_to_plain_eviction(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        rng = np.random.default_rng(7)
        prompt = [int(x) for x in rng.integers(1, 120, 64)]
        a = GenRequest(request_id="A", prompt_ids=prompt,
                       max_new_tokens=8, prefix_key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        with failpoints.armed("kv.demote", "error", "torn demote"):
            _churn(eng, rng)
        assert eng.prefix_cache.host_nodes == 0  # demotes all failed
        assert eng.kv_tier.demote_failures > 0
        assert not eng.self_check()
        # resume still works — it just re-prefills
        resume = prompt + list(a.output_ids) + [3, 4, 5]
        a2 = GenRequest(request_id="A2", prompt_ids=resume,
                        max_new_tokens=4, prefix_key="thread-A")
        eng.submit(a2)
        eng.run_to_completion()
        assert a2.cache_source != "host_tier"
        assert not eng.self_check()

    def test_torn_promote_degrades_to_reprefill(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        rng = np.random.default_rng(9)
        prompt = [int(x) for x in rng.integers(1, 120, 64)]
        a = GenRequest(request_id="A", prompt_ids=prompt,
                       max_new_tokens=8, prefix_key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        _churn(eng, rng)
        assert eng.prefix_cache.host_nodes > 0
        resume = prompt + list(a.output_ids) + [3, 4, 5]
        # the error fires INSIDE the promote's chunk loop: destination
        # pages are freed, the node removed, the request re-prefills
        with failpoints.armed("kv.promote", "error", "torn promote"):
            a2 = GenRequest(request_id="A2", prompt_ids=resume,
                            max_new_tokens=8, prefix_key="thread-A")
            eng.submit(a2)
            eng.run_to_completion()
        assert a2.cache_source != "host_tier"
        assert eng.kv_tier.promote_failures > 0
        assert not eng.self_check(), eng.self_check()
        # output equals the clean-path output: degraded, never corrupted
        base = make_engine(cfg, params, kv_host_tier_mb=0)
        r1 = GenRequest(request_id="r1", prompt_ids=prompt,
                        max_new_tokens=8, prefix_key="t")
        base.submit(r1)
        base.run_to_completion()
        r2 = GenRequest(request_id="r2", prompt_ids=resume,
                        max_new_tokens=8, prefix_key="t")
        base.submit(r2)
        base.run_to_completion()
        assert a2.output_ids == r2.output_ids

    def test_torn_multichunk_copy_unit(self):
        """nth=2 error on a multi-chunk promote: chunk 1 lands, chunk 2
        faults — the manager reports failure and the caller's pages are
        safe to free (nothing shared)."""
        ps, n = 2, SHIP_BUCKETS[-1] + 3  # 2 chunks
        o = _Owner(2 * n + 10, ps, layers=1, width=4, seed=13)
        ship = LocalPageShipper(o, ps)
        mgr = KVTierManager(ship, host_budget_bytes=1 << 30, page_size=ps)
        pages = list(range(1, 1 + n))
        rid = mgr.demote(pages)
        assert rid is not None
        dest = list(range(1 + n, 1 + 2 * n))
        with failpoints.armed("kv.promote", "error", "torn", nth=2):
            assert not mgr.promote(rid, dest)
        assert mgr.promote_failures == 1

    def test_sites_registered(self):
        assert "kv.demote" in failpoints.SITES
        assert "kv.promote" in failpoints.SITES


class TestTierMetricsRegistry:
    def test_snapshot_matches_registry_exactly(self):
        from kafka_tpu.runtime.metrics import KV_TIER_METRIC_KEYS

        o = _Owner(8, 2, layers=1, width=4)
        mgr = KVTierManager(LocalPageShipper(o, 2),
                            host_budget_bytes=1024, page_size=2)
        assert set(mgr.snapshot()) == set(KV_TIER_METRIC_KEYS)

    def test_engine_snapshot_and_prometheus_families(self, model):
        from kafka_tpu.server.prometheus import render_prometheus

        cfg, params = model
        eng = make_engine(cfg, params)
        rng = np.random.default_rng(15)
        prompt = [int(x) for x in rng.integers(1, 120, 64)]
        a = GenRequest(request_id="A", prompt_ids=prompt,
                       max_new_tokens=8, prefix_key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        _churn(eng, rng)
        a2 = GenRequest(
            request_id="A2",
            prompt_ids=prompt + list(a.output_ids) + [3, 4],
            max_new_tokens=4, prefix_key="thread-A",
        )
        eng.submit(a2)
        eng.run_to_completion()
        snap = eng.metrics.snapshot(eng)
        assert "kv_tier" in snap
        assert snap["kv_tier"]["demotions"] > 0
        assert snap["kv_tier"]["promotions"] > 0
        assert snap["prefix_cache"]["host_tier_hits"] == 1
        text = render_prometheus(snap)
        for family in ("kafka_tpu_kv_tier_bytes", "kafka_tpu_kv_tier_runs",
                       "kafka_tpu_kv_tier_total",
                       "kafka_tpu_kv_tier_pages_total",
                       "kafka_tpu_kv_tier_bytes_total",
                       "kafka_tpu_prefix_cache_host_resident"):
            assert f"# TYPE {family}" in text, family
        assert 'kind="host_tier_hits"' in text
        assert 'event="demotions"' in text
        # untiered engines export NO kv_tier family at all
        base = make_engine(cfg, params, kv_host_tier_mb=0)
        text0 = render_prometheus(base.metrics.snapshot(base))
        assert "kv_tier" not in text0

    def test_span_registry_carries_tier_spans(self):
        assert "kv.demote" in tracing.SPANS
        assert "kv.promote" in tracing.SPANS


class TestRingPersistence:
    def test_trace_survives_reset_via_disk(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KAFKA_TPU_TRACE_PERSIST_DIR", str(tmp_path))
        tracing.reset()
        root = tracing.start_trace(request_id="persist-req")
        with tracing.span("agent.turn"):
            pass
        tracing.finish_trace(root)
        files = [f for f in os.listdir(tmp_path)
                 if f.endswith(".trace.json")]
        assert len(files) == 1
        tid = tracing.get_trace("persist-req").trace_id
        # a fresh process: ring empty, disk still there
        tracing.reset()
        tr = tracing.get_trace("persist-req")
        assert tr is not None and tr.trace_id == tid and tr.done
        assert tracing.chrome_trace("persist-req") is not None
        assert tracing.get_trace(tid) is not None  # by trace id too
        monkeypatch.delenv("KAFKA_TPU_TRACE_PERSIST_DIR")
        tracing.reset()

    def test_defaults_alongside_disk_tier(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KAFKA_TPU_KV_DISK_TIER_DIR", str(tmp_path))
        monkeypatch.delenv("KAFKA_TPU_TRACE_PERSIST_DIR", raising=False)
        tracing.reset()
        root = tracing.start_trace(request_id="alongside")
        tracing.finish_trace(root)
        assert os.path.isdir(os.path.join(str(tmp_path), "traces"))
        assert os.listdir(os.path.join(str(tmp_path), "traces"))
        # explicit "" is the hard off switch even with a disk tier
        monkeypatch.setenv("KAFKA_TPU_TRACE_PERSIST_DIR", "")
        tracing.reset()
        root = tracing.start_trace(request_id="off")
        tracing.finish_trace(root)
        traces_dir = os.path.join(str(tmp_path), "traces")
        assert len(os.listdir(traces_dir)) == 1  # nothing new landed
        monkeypatch.delenv("KAFKA_TPU_KV_DISK_TIER_DIR")
        monkeypatch.delenv("KAFKA_TPU_TRACE_PERSIST_DIR")
        tracing.reset()


class TestDeferredGrammarCompile:
    def test_large_vocab_defers_and_lands(self, monkeypatch):
        from kafka_tpu.llm.constrained import (
            build_tool_call_mask_fn,
            compile_grammar_for_mask_fn,
            compile_pending,
        )
        from kafka_tpu.models import ByteTokenizer

        tok = ByteTokenizer()
        tools = [{"type": "function", "function": {
            "name": "defer_probe",
            "parameters": {"type": "object",
                           "properties": {"q": {"type": "string"}}}}}]
        mf = build_tool_call_mask_fn(tok, tools, "required")
        # every vocab counts as "large": the threshold is the env knob
        monkeypatch.setenv("KAFKA_TPU_GRAMMAR_SYNC_VOCAB", "1")
        g = compile_grammar_for_mask_fn(mf, tok.vocab_size)
        assert g is None  # first call: host-mask path, no stall
        deadline = time.monotonic() + 30
        while compile_pending() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert compile_pending() == 0
        g2 = compile_grammar_for_mask_fn(mf, tok.vocab_size)
        assert g2 is not None  # flipped to on-device once the table landed

    def test_small_vocab_stays_synchronous(self):
        from kafka_tpu.llm.constrained import (
            build_tool_call_mask_fn,
            compile_grammar_for_mask_fn,
        )
        from kafka_tpu.models import ByteTokenizer

        tok = ByteTokenizer()
        tools = [{"type": "function", "function": {
            "name": "sync_probe",
            "parameters": {"type": "object",
                           "properties": {"n": {"type": "number"}}}}}]
        mf = build_tool_call_mask_fn(tok, tools, "required")
        g = compile_grammar_for_mask_fn(mf, tok.vocab_size)
        assert g is not None  # byte vocab < default threshold: inline

    def test_gauge_exported(self):
        from kafka_tpu.runtime.metrics import (
            CONSTRAINED_METRIC_KEYS,
            EngineMetrics,
        )

        assert "constrained_compile_pending" in CONSTRAINED_METRIC_KEYS
        snap = EngineMetrics().snapshot()
        assert "constrained_compile_pending" in snap["constrained"]


class TestPlannerHostTier:
    def test_plan_charges_host_tier_as_host_ram(self):
        from kafka_tpu.runtime.planner import plan_for_serving
        from kafka_tpu.server.config import ServingConfig

        scfg = ServingConfig(tiny_model=True, kv_host_tier_mb=512)
        plan = plan_for_serving(scfg, hbm_bytes=16 << 30,
                                model_cfg=_tiny_model_cfg())
        assert plan.kv_host_tier_bytes == 512 << 20
        assert plan.summary()["kv_host_tier_mib"] == 512.0
        # host RAM, not HBM: the tier must not change the fit verdict
        base = plan_for_serving(ServingConfig(tiny_model=True),
                                hbm_bytes=16 << 30,
                                model_cfg=_tiny_model_cfg())
        assert plan.total_bytes == base.total_bytes

    def test_config_env_round_trip(self, monkeypatch):
        from kafka_tpu.server.config import ServingConfig

        monkeypatch.setenv("KAFKA_TPU_KV_HOST_TIER_MB", "128")
        monkeypatch.setenv("KAFKA_TPU_KV_DISK_TIER_DIR", "/tmp/kvtier")
        cfg = ServingConfig.from_env()
        assert cfg.kv_host_tier_mb == 128
        assert cfg.kv_disk_tier_dir == "/tmp/kvtier"
        monkeypatch.setenv("KAFKA_TPU_KV_HOST_TIER_MB", "-5")
        assert ServingConfig.from_env().kv_host_tier_mb == 0


def _tiny_model_cfg():
    from kafka_tpu.models.config import get_config

    return get_config("tiny")


class TestBenchSmoke:
    def test_kv_tier_phase_counters_move_on_cpu(self, model):
        import importlib.util
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "bench", os.path.join(root, "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        sys.modules["bench"] = bench
        spec.loader.exec_module(bench)
        cfg, params = model
        # 480 tokens: re-prefill is ~4x a promote on a CPU, so the
        # ordering below holds on a loaded machine (at 96 it is ~1:1)
        out = bench.kv_tier_phase(cfg, params, n_churn=2, prompt_len=480,
                                  gen_len=8, page_size=8)
        assert out["resume_cached_tokens"] > 0
        assert out["cache_source"] == "host_tier"
        assert out["baseline_cached_tokens"] == 0  # untiered: evicted
        tier = out["tier_counters"]
        assert tier["demotions"] > 0 and tier["promotions"] > 0
        assert out["resume_ttft_ms"]["promote"] < \
            out["resume_ttft_ms"]["reprefill"], out["resume_ttft_ms"]
