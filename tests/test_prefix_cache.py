"""Cross-thread radix-tree KV prefix cache (BASELINE configs 2 + 3).

The load-bearing claims:
  * turn N+1 of a thread re-prefills only the suffix past the shared pages
    (engine counters prove the reuse; outputs prove correctness),
  * a DIFFERENT thread sharing the same prompt prefix (the fan-out system-
    prompt shape) reuses it too — prefill starts at the shared boundary,
  * shared pages are never re-written by the reusing sequence,
  * radix refcounts reconcile with the pool under randomized
    store/lookup/evict/invalidate interleavings (no leaks, no double frees),
  * cache entries are evicted (leaf-LRU) under page pressure before
    requests suffer.
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import (
    EngineConfig,
    GenRequest,
    InferenceEngine,
    OutOfPagesError,
    PagePool,
)
from kafka_tpu import tracing
from kafka_tpu.runtime.prefix_cache import PrefixCache


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="prefix-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def make_engine(cfg, params, **kw):
    defaults = dict(max_batch=4, page_size=8, num_pages=64, max_pages_per_seq=8,
                    prefill_buckets=(8, 16, 32, 64))
    defaults.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**defaults), kv_dtype=jnp.float32)


class TestRadixUnit:
    def test_store_lookup_roundtrip(self):
        pool = PagePool(num_pages=32, page_size=4)
        cache = PrefixCache(pool)
        pages = pool.alloc(3)
        tokens = list(range(10))  # 10 tokens -> 2 FULL pages (partial dropped)
        cache.store("t1", tokens, pages)
        hit = cache.lookup("t1", tokens + [99, 98])
        assert hit is not None
        assert hit.tokens == 8  # 2 full pages of 4
        assert hit.pages == pages[:2]
        assert hit.source == "own"
        # cache + our lookup retain: freeing the original keeps them alive;
        # the partial third page was never retained by the cache
        pool.release(pages)
        assert pool.refcount[pages[0]] == 2  # cache + lookup
        assert pool.refcount[pages[2]] == 0

    def test_lookup_respects_divergence(self):
        pool = PagePool(num_pages=32, page_size=4)
        cache = PrefixCache(pool)
        pages = pool.alloc(3)
        cache.store("t", list(range(12)), pages)
        # diverges at token 5 -> only 1 full page (4 tokens) shareable
        hit = cache.lookup("t", [0, 1, 2, 3, 4, 77, 78, 79])
        assert hit is not None and hit.tokens == 4
        # diverges at token 2 -> no full page
        assert cache.lookup("t", [0, 1, 99, 98]) is None

    def test_always_leaves_one_token_to_prefill(self):
        pool = PagePool(num_pages=32, page_size=4)
        cache = PrefixCache(pool)
        pages = pool.alloc(2)
        tokens = list(range(8))
        cache.store("t", tokens, pages)
        # prompt identical to cached tokens: at most (8-1)//4 = 1 page
        hit = cache.lookup("t", tokens)
        assert hit is not None and hit.tokens == 4

    def test_cross_thread_lookup_shares_content(self):
        """Content addressing: thread B hits thread A's pages — the whole
        point of the radix tree over the exact-key LRU."""
        pool = PagePool(num_pages=32, page_size=4)
        cache = PrefixCache(pool)
        pages = pool.alloc(3)
        tokens = list(range(12))
        cache.store("thread-A", tokens, pages)
        hit = cache.lookup("thread-B", tokens + [50, 51])
        assert hit is not None
        assert hit.tokens == 12 and hit.pages == pages
        assert hit.source == "cross"
        # counters commit only when the engine starts the prefill
        # (Prometheus counter monotonicity — see commit_hit)
        assert cache.cross_thread_hits == 0
        cache.commit_hit(hit.tokens, hit.source)
        assert cache.cross_thread_hits == 1 and cache.tokens_reused == 12
        pool.release(hit.pages)

    def test_divergent_stores_split_and_share_the_common_run(self):
        """Two threads sharing a prefix then diverging: the common pages
        live in ONE node (counted once by page_owners), each suffix in its
        own child, and both full paths remain hittable."""
        pool = PagePool(num_pages=32, page_size=4)
        cache = PrefixCache(pool)
        common = list(range(8))
        a_pages = pool.alloc(4)
        cache.store("A", common + [20, 21, 22, 23, 24, 25, 26, 27], a_pages)
        # B shares the first 8 tokens; its own pages for them are redundant
        b_pages = pool.alloc(4)
        cache.store("B", common + [30, 31, 32, 33, 34, 35, 36, 37], b_pages)
        owners = cache.page_owners()
        # A's common pages held once; B's duplicate common pages NOT kept
        assert owners.get(a_pages[0]) == 1 and owners.get(a_pages[1]) == 1
        assert b_pages[0] not in owners and b_pages[1] not in owners
        # both suffixes cached
        assert owners.get(a_pages[2]) == 1 and owners.get(b_pages[2]) == 1
        hit_a = cache.lookup("A", common + [20, 21, 22, 23, 24, 25, 26, 27, 1])
        hit_b = cache.lookup("B", common + [30, 31, 32, 33, 34, 35, 36, 37, 1])
        assert hit_a.tokens == 16 and hit_b.tokens == 16
        assert hit_b.pages[:2] == a_pages[:2]  # shared run = A's pages
        pool.release(hit_a.pages)
        pool.release(hit_b.pages)
        assert len(cache) == 3  # common node + two suffix children

    def test_store_ending_mid_node_splits_ownership(self):
        """Regression: a store whose tokens END partway through an existing
        run must split before claiming, or the short thread's key extends
        over the long thread's tail — mislabelling own/cross hits and
        pinning the tail against invalidate()."""
        pool = PagePool(num_pages=32, page_size=4)
        cache = PrefixCache(pool)
        a = pool.alloc(4)
        toks = list(range(16))
        cache.store("A", toks, a)
        pool.release(a)
        b = pool.alloc(2)
        cache.store("B", toks[:8], b)  # ends mid-run: must split at page 2
        pool.release(b)
        # B's lookup past its own stored depth is a CROSS hit on A's tail
        hit = cache.lookup("B", toks + [99])
        assert hit.tokens == 16 and hit.source == "cross"
        pool.release(hit.pages)
        # invalidating A frees A's unique tail; B's shared prefix survives
        cache.invalidate("A")
        hit = cache.lookup("B", toks + [99])
        assert hit.tokens == 8 and hit.source == "own"
        pool.release(hit.pages)
        assert pool.check_consistency() == []

    def test_page_budget_trims_lru_leaf_tail(self):
        pool = PagePool(num_pages=32, page_size=4)
        cache = PrefixCache(pool, max_pages=4)
        a = pool.alloc(3)
        cache.store("a", list(range(12)), a)
        pool.release(a)
        b = pool.alloc(3)
        cache.store("b", list(range(100, 112)), b)
        pool.release(b)
        # budget 4 < 6 stored: the LRU leaf ("a") was trimmed from its
        # TAIL to fit — its head page (the reusable prefix start) survives
        assert cache.total_pages == 4
        assert cache.pages_evicted == 2
        hit_a = cache.lookup("a", list(range(12)) + [1])
        assert hit_a is not None and hit_a.tokens == 4  # head page kept
        pool.release(hit_a.pages)
        hit_b = cache.lookup("b", list(range(100, 112)) + [1])
        assert hit_b is not None and hit_b.tokens == 12
        pool.release(hit_b.pages)

    def test_budget_smaller_than_one_run_keeps_prefix_head(self):
        """A budget below a single stored run must keep the run's HEAD —
        the shared-system-prompt span every thread reuses — not zero the
        cache by dropping the whole node."""
        pool = PagePool(num_pages=32, page_size=4)
        cache = PrefixCache(pool, max_pages=2)
        a = pool.alloc(5)
        toks = list(range(20))
        cache.store("A", toks, a)
        pool.release(a)
        assert cache.total_pages == 2
        hit = cache.lookup("B", toks)
        assert hit is not None and hit.tokens == 8 and hit.source == "cross"
        pool.release(hit.pages)
        assert pool.check_consistency() == []

    def test_reclaim_evicts_lru(self):
        pool = PagePool(num_pages=9, page_size=4)
        cache = PrefixCache(pool)
        a, b = pool.alloc(4), pool.alloc(4)
        cache.store("a", list(range(16)), a)
        cache.store("b", list(range(100, 116)), b)
        pool.release(a)
        pool.release(b)
        assert pool.free_pages == 0
        assert cache.reclaim(4)
        assert pool.free_pages >= 4
        assert cache.lookup("a", list(range(16)) + [1]) is None  # LRU evicted
        assert cache.lookup("b", list(range(100, 116)) + [1]) is not None

    def test_leaf_lru_keeps_shared_prefix_over_cold_suffix(self):
        """Eviction is LEAF-first: a shared prefix near the root survives
        the eviction of its coldest consumer's suffix."""
        pool = PagePool(num_pages=9, page_size=4)
        cache = PrefixCache(pool)
        common = list(range(8))
        a = pool.alloc(4)
        cache.store("A", common + [20, 21, 22, 23, 24, 25, 26, 27], a)
        pool.release(a)
        b = pool.alloc(4)
        cache.store("B", common + [30, 31, 32, 33, 34, 35, 36, 37], b)
        pool.release(b)
        # tree holds 6 pages (2 common + 2 + 2); pool of 8 usable is full
        # except the 2 duplicates B released.  Force one eviction:
        assert cache.reclaim(3)
        # the common run must still be hittable (a leaf went, not the root)
        hit = cache.lookup("C", common + [99])
        assert hit is not None and hit.tokens == 8
        pool.release(hit.pages)

    def test_invalidate_keeps_shared_nodes(self):
        pool = PagePool(num_pages=32, page_size=4)
        cache = PrefixCache(pool)
        common = list(range(8))
        a = pool.alloc(4)
        cache.store("A", common + [20, 21, 22, 23, 24, 25, 26, 27], a)
        pool.release(a)
        b = pool.alloc(4)
        cache.store("B", common + [30, 31, 32, 33, 34, 35, 36, 37], b)
        pool.release(b)
        cache.invalidate("A")
        # A's unique suffix is gone; the shared common run survives for B
        assert cache.lookup("A", common + [20, 21, 22, 23, 24]).tokens == 8
        hit_b = cache.lookup("B", common + [30, 31, 32, 33, 34, 35, 36, 37, 1])
        assert hit_b is not None and hit_b.tokens == 16
        cache.invalidate("B")
        assert len(cache) == 0
        assert pool.check_consistency() == []

    def test_invalidate_after_claim_cap_still_frees_stranded_tail(self, monkeypatch):
        """Once a node's claim list hits the cap and drops a key, the
        root-anchored claim invariant is broken — invalidate must fall
        back to the full-tree sweep and still free that key's private
        tail nodes."""
        import kafka_tpu.runtime.prefix_cache as pc_mod

        monkeypatch.setattr(pc_mod, "_KEYS_CAP", 2)
        pool = PagePool(num_pages=64, page_size=4)
        cache = PrefixCache(pool)
        common = list(range(8))
        k = pool.alloc(4)
        cache.store("K", common + [20, 21, 22, 23, 24, 25, 26, 27], k)
        pool.release(k)
        # flood the shared head node with more claimants than the cap,
        # evicting K's claim from it (but not from K's private tail)
        for i in range(3):
            p = pool.alloc(4)
            cache.store(f"flood-{i}",
                        common + [40 + 8 * i + j for j in range(8)], p)
            pool.release(p)
        head = cache._root.children[tuple(common[:4])]
        assert "K" not in head.keys  # invariant genuinely broken
        pages_before = cache.total_pages
        cache.invalidate("K")
        # K's private 2-page tail was found and freed despite the broken
        # ancestor claim; the shared head survives for the flood threads
        assert cache.total_pages == pages_before - 2
        hit = cache.lookup("other", common + [99])
        assert hit is not None and hit.tokens == 8
        pool.release(hit.pages)
        assert pool.check_consistency() == []

    def test_match_tokens_probe_is_read_only(self):
        pool = PagePool(num_pages=32, page_size=4)
        cache = PrefixCache(pool)
        pages = pool.alloc(2)
        cache.store("t", list(range(8)), pages)
        before = (cache.hits, cache.misses, pool.refcount.copy())
        assert cache.match_tokens(list(range(8)) + [9]) == 8
        assert cache.match_tokens([7, 7, 7, 7, 7]) == 0
        assert (cache.hits, cache.misses) == before[:2]
        assert (pool.refcount == before[2]).all()

    def test_randomized_ops_reconcile_with_pool(self):
        """Chaos sweep over store/lookup/evict/invalidate/reclaim with
        live lookup-holds in flight: after EVERY operation the allocator's
        internal invariants hold and the refcounts equal exactly the
        enumerable owners (radix retains + live holds) — no leaks, no
        double frees."""
        rng = random.Random(0)
        pool = PagePool(num_pages=48, page_size=4)
        cache = PrefixCache(pool, max_pages=28)
        bases = [[rng.randrange(100) for _ in range(12)] for _ in range(3)]
        keys = [f"k{i}" for i in range(6)]
        holds = []  # retained page lists from lookups (live "sequences")

        def reconcile():
            assert pool.check_consistency() == []
            expected = cache.page_owners()
            for pages in holds:
                for p in pages:
                    expected[p] = expected.get(p, 0) + 1
            problems = pool.reconcile(expected)
            assert problems == [], problems

        for _ in range(400):
            op = rng.random()
            if op < 0.45:
                # finish a "sequence": shared base + random suffix, pages
                # part-shared through a lookup (the engine's exact shape)
                tokens = rng.choice(bases) + [
                    rng.randrange(100) for _ in range(rng.randrange(0, 13))
                ]
                hit = cache.lookup(rng.choice(keys), tokens)
                shared = hit.pages if hit else []
                n_total = -(-len(tokens) // 4)
                try:
                    own = pool.alloc(n_total - len(shared))
                except OutOfPagesError:
                    if shared:
                        pool.release(shared)
                    cache.reclaim(n_total)
                    reconcile()
                    continue
                pages = shared + own
                cache.store(rng.choice(keys), tokens, pages)
                pool.release(pages)  # the sequence retires
            elif op < 0.6:
                hit = cache.lookup(
                    rng.choice(keys),
                    rng.choice(bases) + [rng.randrange(100)],
                )
                if hit is not None:
                    holds.append(hit.pages)
            elif op < 0.7 and holds:
                pool.release(holds.pop(rng.randrange(len(holds))))
            elif op < 0.8:
                cache.invalidate(rng.choice(keys))
            elif op < 0.9:
                cache.reclaim(rng.randrange(1, 8))
            else:
                cache._evict_leaf()
            reconcile()
        cache.clear()
        while holds:
            pool.release(holds.pop())
        assert pool.check_consistency() == []
        assert pool.free_pages == pool.num_pages - 1


class TestEnginePrefixReuse:
    def test_turn_two_prefills_only_suffix(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        p1 = list(np.random.RandomState(0).randint(1, 128, size=20))
        r1 = GenRequest(request_id="turn1", prompt_ids=p1, max_new_tokens=6,
                        prefix_key="thread-A")
        eng.submit(r1)
        eng.run_to_completion()
        assert len(eng.prefix_cache) == 1

        # turn 2: conversation grew by turn-1 output + new user tokens
        p2 = p1 + r1.output_ids + [5, 9, 2]
        r2 = GenRequest(request_id="turn2", prompt_ids=p2, max_new_tokens=6,
                        prefix_key="thread-A")
        eng.submit(r2)
        eng.run_to_completion()
        assert eng.prefix_cache.hits == 1
        # 20 prompt + 6 output = 25 materialized -> 3 full pages of 8 shared
        assert eng.prefix_cache.tokens_reused == 24
        assert r2.cached_tokens == 24 and r2.cache_source == "own"

        # correctness: same tokens as a cache-less engine
        eng2 = make_engine(cfg, params, prefix_cache_entries=0)
        ref = eng2.generate(p2, max_new_tokens=6)
        assert r2.output_ids == ref.output_ids

    def test_cross_thread_hit_prefills_only_suffix(self, model):
        """ISSUE 4 acceptance: thread B's prefill starts at thread A's
        shared system-prompt boundary — the reuse the exact-key cache
        could never give (B never ran before)."""
        cfg, params = model
        eng = make_engine(cfg, params)
        common = list(np.random.RandomState(2).randint(1, 128, size=16))
        sfx_a = [3, 7, 11, 13, 17, 19]
        sfx_b = [23, 29, 31, 37, 41, 43]
        ra = GenRequest(request_id="A", prompt_ids=common + sfx_a,
                        max_new_tokens=4, prefix_key="thread-A")
        eng.submit(ra)
        eng.run_to_completion()
        rb = GenRequest(request_id="B", prompt_ids=common + sfx_b,
                        max_new_tokens=4, prefix_key="thread-B")
        eng.submit(rb)
        eng.run_to_completion()
        # B never stored anything, yet its prefill resumed past the common
        # 2 full pages (16 tokens) of A's KV
        assert rb.cached_tokens == 16
        assert rb.cache_source == "cross"
        assert eng.prefix_cache.cross_thread_hits == 1
        # correctness: identical tokens to a cache-less prefill
        ref = make_engine(cfg, params, prefix_cache_entries=0).generate(
            common + sfx_b, max_new_tokens=4)
        assert rb.output_ids == ref.output_ids
        assert not eng.self_check()

    def test_prefill_span_carries_cache_attrs(self, model):
        """The engine.prefill span reports cached_tokens + cache_source so
        a trace shows exactly how much prefill the radix tree saved."""
        cfg, params = model
        eng = make_engine(cfg, params)
        common = list(np.random.RandomState(4).randint(1, 128, size=16))
        eng.submit(GenRequest(request_id="seed", prompt_ids=common + [1, 2],
                              max_new_tokens=4, prefix_key="t-seed"))
        eng.run_to_completion()
        tracing.reset()
        root = tracing.start_trace(request_id="pfx1")
        eng.submit(GenRequest(request_id="hit", prompt_ids=common + [9, 8, 7],
                              max_new_tokens=2, prefix_key="t-other",
                              trace=tracing.current()))
        eng.run_to_completion()
        tracing.finish_trace(root)
        tr = tracing.get_trace("pfx1")
        prefill = next(s for s in tr.spans if s.name == "engine.prefill")
        assert prefill.attrs["cached_tokens"] == 16
        assert prefill.attrs["cache_source"] == "cross"

    def test_page_aligned_turn_boundary_not_corrupted(self, model):
        """Regression: the final sampled token's KV is never written; if the
        materialized count lands exactly on a page boundary the stored entry
        must not claim that token, or turn 2 shares a page with an unwritten
        slot and silently generates wrong tokens."""
        cfg, params = model
        eng = make_engine(cfg, params)
        # 20 prompt + 4 output = 24 tokens = exactly 3 pages of 8, but only
        # 23 KV slots are materialized (length-finish drops the last write)
        p1 = list(np.random.RandomState(5).randint(1, 128, size=20))
        r1 = GenRequest(request_id="t1", prompt_ids=p1, max_new_tokens=4,
                        prefix_key="aligned")
        eng.submit(r1)
        eng.run_to_completion()
        p2 = p1 + r1.output_ids + [11, 12]
        r2 = GenRequest(request_id="t2", prompt_ids=p2, max_new_tokens=6,
                        prefix_key="aligned")
        eng.submit(r2)
        eng.run_to_completion()
        ref = make_engine(cfg, params, prefix_cache_entries=0).generate(
            p2, max_new_tokens=6)
        assert r2.output_ids == ref.output_ids

    def test_no_key_no_cache(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        eng.generate([1, 2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=4)
        assert len(eng.prefix_cache) == 0

    def test_divergent_second_turn_still_correct(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        p1 = list(np.random.RandomState(1).randint(1, 128, size=17))
        r1 = GenRequest(request_id="a", prompt_ids=p1, max_new_tokens=4,
                        prefix_key="t")
        eng.submit(r1)
        eng.run_to_completion()
        # second turn shares only part of the prompt then diverges mid-page
        p2 = p1[:10] + [100, 101, 102, 103, 104]
        r2 = GenRequest(request_id="b", prompt_ids=p2, max_new_tokens=5,
                        prefix_key="t")
        eng.submit(r2)
        eng.run_to_completion()
        ref = make_engine(cfg, params, prefix_cache_entries=0).generate(
            p2, max_new_tokens=5)
        assert r2.output_ids == ref.output_ids

    def test_pages_released_after_cache_clear(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        r = GenRequest(request_id="x", prompt_ids=list(range(1, 20)),
                       max_new_tokens=4, prefix_key="t")
        eng.submit(r)
        eng.run_to_completion()
        held = 64 - 1 - eng.pool.free_pages
        assert held > 0  # cache holds the thread's pages
        eng.prefix_cache.clear()
        assert eng.pool.free_pages == 63  # everything back

    def test_pressure_evicts_cache_not_requests(self, model):
        cfg, params = model
        # pool sized so a cached thread + a new long request can't coexist
        eng = make_engine(cfg, params, max_batch=2, num_pages=9,
                          max_pages_per_seq=8)
        r1 = GenRequest(request_id="t1", prompt_ids=list(range(1, 25)),
                        max_new_tokens=4, prefix_key="thread-A")
        eng.submit(r1)
        eng.run_to_completion()
        assert len(eng.prefix_cache) == 1
        # a fat unrelated request must displace the cache, not deadlock
        r2 = GenRequest(request_id="big", prompt_ids=list(range(1, 40)),
                        max_new_tokens=8)
        eng.submit(r2)
        done = eng.run_to_completion()
        assert "big" in done and len(done["big"].output_ids) == 8

    def test_multi_turn_chain_keeps_reusing(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, num_pages=64)
        prompt = list(np.random.RandomState(3).randint(1, 128, size=12))
        for turn in range(3):
            r = GenRequest(request_id=f"turn{turn}", prompt_ids=list(prompt),
                           max_new_tokens=4, prefix_key="chain")
            eng.submit(r)
            eng.run_to_completion()
            prompt = prompt + r.output_ids + [7, 3]
        assert eng.prefix_cache.hits == 2
        assert eng.prefix_cache.tokens_reused > 0


class TestSharedPrefixBench:
    def test_bench_shared_prefix_counters_move_on_cpu(self, model):
        """Tier-1 smoke for the bench.py shared_prefix scenario: the radix
        counters (hits, tokens_reused, cross-thread hits) move and the
        prefill-tokens-saved figure is positive under the CPU backend."""
        import os
        import sys

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from bench import shared_prefix_phase

        cfg, params = model
        out = shared_prefix_phase(cfg, params, n_threads=3, common_len=24,
                                  suffix_len=8, gen_len=3, page_size=8)
        assert out["cache_hits"] >= 2
        assert out["cross_thread_hits"] >= 2  # threads 2..3 reuse thread 1
        assert out["prefill_tokens_saved"] >= 2 * 16  # >= 2 full shared pages
        assert out["radix_ttft_ms"]["p50"] > 0
        assert out["baseline_ttft_ms"]["p50"] > 0
