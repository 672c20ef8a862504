"""Object-store KV tier + portable thread state (ISSUE 14).

The load-bearing claims:
  * run payloads round-trip byte-exact through the store (f32 + bf16 +
    multi-run paths),
  * content addressing dedupes identical prefixes across TWO tier
    managers sharing one store directory (one object, a dedupe counter
    increment, per-owner refcounting with last-ref deletion),
  * a thread drained to the store by replica A wakes on replica B — a
    FRESH engine that never served it — with cache_source="object_tier",
    token-exact output vs a never-slept reference, and 0 coverable
    prompt tokens re-prefilled,
  * randomized sleep/wake chaos keeps PagePool.check_consistency +
    reconcile clean after every op and every woken page byte-exact,
  * a torn manifest write leaves the previous manifest intact (atomic
    rename), a get miss aborts the WHOLE wake with all its pages freed
    (kv.object_get failpoint), a torn put degrades the archive
    (kv.object_put failpoint) — serving continues via re-prefill,
  * the tier's snapshot carries exactly OBJECT_TIER_METRIC_KEYS, the
    metric table's view of the section; SITES/SPANS carry the new
    failpoints/spans,
  * with KAFKA_TPU_KV_OBJECT_DIR unset nothing is built and every
    dispatch/eviction path is byte-identical.
"""

import os
import random

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
from kafka_tpu.runtime.kv_tier import KVTierManager, LocalPageShipper
from kafka_tpu.runtime.object_tier import (
    LocalFSObjectStore,
    ObjectTier,
    _decode_run,
    _encode_run,
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


class TestObjectStore:
    def test_put_get_head_delete_list(self, tmp_path):
        st = LocalFSObjectStore(str(tmp_path))
        assert st.get("objects/x.npz") is None
        assert st.head("objects/x.npz") is None
        st.put("objects/x.npz", b"abc")
        assert st.get("objects/x.npz") == b"abc"
        assert st.head("objects/x.npz")[0] == 3
        st.put("refs/x/a", b"")
        st.put("refs/x/b", b"")
        assert sorted(st.list("refs/x/")) == ["refs/x/a", "refs/x/b"]
        st.delete("refs/x/a")
        assert st.list("refs/x/") == ["refs/x/b"]
        st.delete("objects/x.npz")
        assert st.get("objects/x.npz") is None
        st.delete("objects/x.npz")  # idempotent
        # no tmp litter: every put cleaned its staging file
        assert os.listdir(tmp_path / ".tmp") == []

    def test_traversal_keys_stay_inside_root(self, tmp_path):
        st = LocalFSObjectStore(str(tmp_path))
        st.put("objects/../escape", b"x")
        # ".." segments are dropped: the write lands INSIDE the root
        assert not (tmp_path.parent / "escape").exists()
        assert st.get("objects/../escape") == b"x"

    def test_usage_counts_objects(self, tmp_path):
        st = LocalFSObjectStore(str(tmp_path))
        st.put("objects/a.npz", b"1234")
        st.put("objects/b.npz", b"12")
        st._usage_cache = (0.0, (0, 0))  # bust the TTL cache
        count, total = st.usage()
        assert count == 2 and total == 6


class TestRunPayloads:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_round_trip_byte_exact(self, dtype):
        if dtype == "bfloat16":
            import ml_dtypes

            npdt = ml_dtypes.bfloat16
        else:
            npdt = np.float32
        rng = np.random.default_rng(3)
        k = [rng.normal(size=(2, 12, 4)).astype(npdt),
             rng.normal(size=(2, 12, 2)).astype(npdt)]
        v = [rng.normal(size=(2, 12, 4)).astype(npdt),
             rng.normal(size=(2, 12, 2)).astype(npdt)]
        data = _encode_run(k, v, 3)
        k2, v2, n = _decode_run(data)
        assert n == 3
        for a, b in zip(k + v, k2 + v2):
            assert a.dtype == b.dtype
            assert np.array_equal(a.view(np.uint8), b.view(np.uint8))

    def test_put_get_run_and_spans(self, tmp_path):
        obj = ObjectTier(LocalFSObjectStore(str(tmp_path)),
                         fingerprint="f1", page_size=4)
        rng = np.random.default_rng(5)
        k = [rng.normal(size=(2, 8, 4)).astype(np.float32)]
        v = [rng.normal(size=(2, 8, 4)).astype(np.float32)]
        key = obj.put_run([1, 2, 3, 4, 5, 6, 7, 8], k, v, 2)
        assert key is not None
        got = obj.get_run(key)
        assert got is not None
        k2, v2, n, nbytes = got
        assert n == 2 and nbytes > 0
        assert np.array_equal(k[0], k2[0])
        assert np.array_equal(v[0], v2[0])
        assert obj.object_puts == 1 and obj.object_gets == 1

    def test_content_key_covers_prefix_and_fingerprint(self, tmp_path):
        obj = ObjectTier(LocalFSObjectStore(str(tmp_path)),
                         fingerprint="f1", page_size=4)
        other = ObjectTier(LocalFSObjectStore(str(tmp_path)),
                           fingerprint="f2", page_size=4)
        toks = list(range(8))  # 2 pages at page_size=4
        assert obj.run_key(toks, 2) == obj.run_key(toks, 2)
        assert obj.run_key(toks, 2) != obj.run_key(toks[:-1] + [99], 2)
        # same tokens, different pool geometry: different object space
        assert obj.run_key(toks, 2) != other.run_key(toks, 2)
        # same full path, different run span (a SPLIT's back half): a
        # collision here would let a 1-page node dedupe onto a 2-page
        # object and a later promote import the wrong half's KV
        assert obj.run_key(toks, 2) != obj.run_key(toks, 1)


class TestDedupeAndRefs:
    def _leaves(self, seed=7):
        rng = np.random.default_rng(seed)
        return ([rng.normal(size=(2, 8, 4)).astype(np.float32)],
                [rng.normal(size=(2, 8, 4)).astype(np.float32)])

    def test_two_owners_one_object(self, tmp_path):
        st_a = LocalFSObjectStore(str(tmp_path))
        st_b = LocalFSObjectStore(str(tmp_path))
        a = ObjectTier(st_a, fingerprint="f", page_size=4)
        b = ObjectTier(st_b, fingerprint="f", page_size=4)
        k, v = self._leaves()
        toks = list(range(8))
        key = a.put_run(toks, k, v, 2)
        assert key is not None and a.dedupe_hits == 0
        # owner B archives the IDENTICAL prefix: no payload moves
        key_b = b.put_run(toks, k, v, 2)
        assert key_b == key
        assert b.dedupe_hits == 1 and b.object_puts == 0
        st_a._usage_cache = (0.0, (0, 0))
        assert st_a.usage()[0] == 1  # ONE object fleet-wide
        assert len(st_a.list(f"refs/{key}/")) == 2
        # last-reference deletion: A's release keeps it, B's removes it
        a.release(key)
        assert st_a.head(f"objects/{key}.npz") is not None
        b.release(key)
        assert st_a.head(f"objects/{key}.npz") is None

    def test_budget_second_chance(self, tmp_path):
        obj = ObjectTier(LocalFSObjectStore(str(tmp_path)),
                         fingerprint="f", page_size=4)
        k, v = self._leaves()
        k1 = obj.put_run([1] * 8, k, v, 2)
        size = obj.owned_bytes
        obj.budget_bytes = 2 * size + size // 2  # fits two runs
        k2 = obj.put_run([2] * 8, k, v, 2)
        # touch k1 (ref bit) so the third put's eviction skips it once
        assert obj.get_run(k1) is not None
        k3 = obj.put_run([3] * 8, k, v, 2)
        assert obj.owned_bytes <= obj.budget_bytes
        assert obj.objects_released >= 1
        # k2 (unreferenced) was the victim; k1 survived its second chance
        assert obj.has_run(k1) and obj.has_run(k3)
        assert not obj.has_run(k2)


class TestManifests:
    def _put_path(self, obj, path_runs):
        rng = np.random.default_rng(1)
        acc = []
        for seg in path_runs:
            acc.extend(seg)
            n = len(seg) // obj.page_size
            k = [rng.normal(size=(1, len(seg), 2)).astype(np.float32)]
            v = [rng.normal(size=(1, len(seg), 2)).astype(np.float32)]
            assert obj.put_run(list(acc), k, v, n) is not None

    def test_write_read_match(self, tmp_path):
        obj = ObjectTier(LocalFSObjectStore(str(tmp_path)),
                         fingerprint="f", page_size=4)
        toks = list(range(12))
        runs = obj.manifest_runs([toks[:8], toks[8:]])
        assert obj.write_manifest("thread/1", toks, runs)
        man = obj.read_manifest("thread/1")
        assert man["tokens"] == toks and len(man["runs"]) == 2
        # runs not archived yet: the probe counts ONLY wakeable depth
        assert obj.manifest_match_tokens("thread/1", toks + [99]) == 0
        self._put_path(obj, [toks[:8], toks[8:]])
        obj._manifest_cache.clear()  # drop the memoized 0 depth
        # page-aligned match, >= 1 token always left to prefill
        assert obj.manifest_match_tokens("thread/1", toks + [99]) == 12
        assert obj.manifest_match_tokens("thread/1", toks) == 8
        assert obj.manifest_match_tokens("thread/1", [5] + toks) == 0
        assert obj.manifest_match_tokens("missing", toks) == 0

    def test_shallower_write_keeps_deeper_manifest(self, tmp_path):
        obj = ObjectTier(LocalFSObjectStore(str(tmp_path)),
                         fingerprint="f", page_size=4)
        toks = list(range(16))
        obj.write_manifest("t", toks, obj.manifest_runs([toks]))
        # an ancestor's organic archive writes a PREFIX of it: kept
        obj.write_manifest("t", toks[:8], obj.manifest_runs([toks[:8]]))
        assert obj.read_manifest("t")["tokens"] == toks
        # a DIVERGENT write replaces it (the thread's path changed)
        other = [99] * 8
        obj.write_manifest("t", other, obj.manifest_runs([other]))
        assert obj.read_manifest("t")["tokens"] == other

    def test_torn_manifest_write_keeps_previous(self, tmp_path):
        obj = ObjectTier(LocalFSObjectStore(str(tmp_path)),
                         fingerprint="f", page_size=4)
        v1 = list(range(8))
        assert obj.write_manifest("t", v1, obj.manifest_runs([v1]))
        v2 = [7] * 8
        with failpoints.armed("kv.object_put", "error", "torn"):
            assert not obj.write_manifest("t", v2, obj.manifest_runs([v2]))
        assert obj.object_put_failures == 1
        assert obj.read_manifest("t")["tokens"] == v1  # intact

    def test_fingerprint_mismatch_reads_none(self, tmp_path):
        a = ObjectTier(LocalFSObjectStore(str(tmp_path)),
                       fingerprint="fa", page_size=4)
        b = ObjectTier(LocalFSObjectStore(str(tmp_path)),
                       fingerprint="fb", page_size=4)
        toks = list(range(8))
        a.write_manifest("t", toks, a.manifest_runs([toks]))
        assert b.read_manifest("t") is None
        assert b.manifest_match_tokens("t", toks + [1]) == 0


class TestCacheSleepWake:
    """Stub-pool sleep/wake: two (pool, tier, cache) stacks — replica A
    and replica B — sharing one store directory."""

    def _stack(self, tmp_path, num_pages=32, ps=4, seed=11, name="r"):
        o = _Owner(num_pages, ps, seed=seed)
        pool = PagePool(num_pages=num_pages, page_size=ps)
        mgr = KVTierManager(LocalPageShipper(o, ps),
                            host_budget_bytes=1 << 30, page_size=ps)
        mgr.attach_object(ObjectTier(
            LocalFSObjectStore(str(tmp_path)), fingerprint="shared",
            page_size=ps,
        ))
        cache = PrefixCache(pool, tier=mgr)
        return o, pool, mgr, cache

    def _store(self, o, pool, cache, key, tokens, pattern_from=None):
        ps = pool.page_size
        n = len(tokens) // ps
        pages = pool.alloc(n)
        k = np.empty((2, n * ps, 8), np.float32)
        v = np.empty((2, n * ps, 8), np.float32)
        src = pattern_from if pattern_from is not None else tokens
        for i in range(n):
            k[:, i * ps:(i + 1) * ps] = float(src[i * ps]) + 0.25
            v[:, i * ps:(i + 1) * ps] = float(src[i * ps]) + 0.5
        _write_rows(o, pages, ps, k, v)
        cache.store(key, tokens, pages)
        pool.release(pages)

    def _verify_hit(self, o, ps, prompt, hit):
        for i, p in enumerate(hit.pages):
            tok = float(prompt[i * ps])
            k = np.asarray(o.k_pool)[:, p * ps:(p + 1) * ps]
            v = np.asarray(o.v_pool)[:, p * ps:(p + 1) * ps]
            assert np.all(k == tok + 0.25), f"K page {i} corrupt"
            assert np.all(v == tok + 0.5), f"V page {i} corrupt"

    def test_sleep_then_wake_on_second_stack(self, tmp_path):
        a_o, a_pool, a_mgr, a_cache = self._stack(tmp_path, seed=1)
        rng = random.Random(0)
        tokens = [rng.randrange(90) for _ in range(12)]
        self._store(a_o, a_pool, a_cache, "t1", tokens)
        stats = a_cache.sleep_to_object()
        assert stats["enabled"] and stats["runs_archived"] == 1
        assert stats["manifests"] == 1

        b_o, b_pool, b_mgr, b_cache = self._stack(tmp_path, seed=2)
        hit = b_cache.lookup("t1", tokens + [1])
        assert hit is not None
        assert hit.source == "object_tier"
        assert hit.tokens == 12 and hit.object_tokens == 12
        self._verify_hit(b_o, 4, tokens, hit)
        b_pool.release(hit.pages)
        assert b_mgr.object.wake_threads == 1
        assert b_mgr.object.wake_tokens == 12
        assert not b_pool.check_consistency()
        assert not b_pool.reconcile(b_cache.page_owners())
        # the woken run is ordinary content after the thread stores
        # through it: source flips back to "own"
        self._store(b_o, b_pool, b_cache, "t1", tokens + [1, 2, 3, 4][:4])
        hit2 = b_cache.lookup("t1", tokens + [1])
        assert hit2.source == "own"
        b_pool.release(hit2.pages)

    def test_sleep_dedupes_across_replicas(self, tmp_path):
        a = self._stack(tmp_path, seed=3)
        b = self._stack(tmp_path, seed=4)
        rng = random.Random(7)
        shared = [rng.randrange(90) for _ in range(8)]
        self._store(a[0], a[1], a[3], "ta", shared)
        self._store(b[0], b[1], b[3], "tb", shared)
        s1 = a[3].sleep_to_object()
        assert s1["runs_archived"] == 1 and s1["dedupe_hits"] == 0
        s2 = b[3].sleep_to_object()
        # identical prefix: ONE object, reference-only second archive
        assert s2["dedupe_hits"] == 1
        store = a[2].object.store
        store._usage_cache = (0.0, (0, 0))
        assert store.usage()[0] == 1

    def test_get_miss_aborts_wake_and_frees_everything(self, tmp_path):
        a = self._stack(tmp_path, seed=5)
        rng = random.Random(9)
        tokens = [rng.randrange(90) for _ in range(16)]
        self._store(a[0], a[1], a[3], "t", tokens)
        a[3].sleep_to_object()
        b_o, b_pool, b_mgr, b_cache = self._stack(tmp_path, seed=6)
        free0 = b_pool.free_pages
        with failpoints.armed("kv.object_get", "error", "lost"):
            hit = b_cache.lookup("t", tokens + [1])
        # whole wake aborted: no partial pages, no tree entries
        assert hit is None
        assert b_pool.free_pages == free0
        assert len(b_cache) == 0
        assert b_mgr.object.object_get_failures >= 1
        assert not b_pool.check_consistency()
        # store healthy again: the same lookup wakes
        hit = b_cache.lookup("t", tokens + [1])
        assert hit is not None and hit.source == "object_tier"
        b_pool.release(hit.pages)

    def test_delay_injection_slow_store_still_serves(self, tmp_path):
        """`delay` on both sites = a slow store link: everything still
        works, just slower (the chaos matrix's liveness leg)."""
        import time as _time

        a = self._stack(tmp_path, seed=31)
        rng = random.Random(41)
        tokens = [rng.randrange(90) for _ in range(8)]
        self._store(a[0], a[1], a[3], "t", tokens)
        with failpoints.armed("kv.object_put", "delay", "0.05"):
            t0 = _time.monotonic()
            stats = a[3].sleep_to_object()
            assert _time.monotonic() - t0 >= 0.05
        assert stats["runs_archived"] == 1
        b = self._stack(tmp_path, seed=32)
        with failpoints.armed("kv.object_get", "delay", "0.05"):
            t0 = _time.monotonic()
            hit = b[3].lookup("t", tokens + [1])
            assert _time.monotonic() - t0 >= 0.05
        assert hit is not None and hit.source == "object_tier"
        self._verify_hit(b[0], 4, tokens, hit)
        b[1].release(hit.pages)

    def test_torn_put_during_sleep_degrades(self, tmp_path):
        a = self._stack(tmp_path, seed=8)
        rng = random.Random(11)
        tokens = [rng.randrange(90) for _ in range(8)]
        self._store(a[0], a[1], a[3], "t", tokens)
        with failpoints.armed("kv.object_put", "error", "torn"):
            stats = a[3].sleep_to_object()
        assert stats["runs_failed"] == 1 and stats["runs_archived"] == 0
        assert a[2].object.object_put_failures >= 1
        # nothing landed: a fresh replica has nothing to wake
        b = self._stack(tmp_path, seed=9)
        assert b[3].lookup("t", tokens + [1]) is None
        # the local replica is untouched — its own hit still serves
        hit = a[3].lookup("t", tokens + [1])
        assert hit is not None
        a[1].release(hit.pages)

    def test_randomized_sleep_wake_chaos(self, tmp_path):
        """store/lookup/reclaim/invalidate/sleep/clear-then-wake
        interleavings on one stack sharing a store with periodic fresh
        stacks: allocator invariants hold after EVERY op and every hit's
        pages are byte-exact against the token-derived pattern."""
        ps = 4
        o, pool, mgr, cache = self._stack(tmp_path, num_pages=48, seed=21)
        rng = random.Random(4321)
        threads = {}
        live_holds = []

        def owners():
            own = dict(cache.page_owners())
            for pages in live_holds:
                for p in pages:
                    own[p] = own.get(p, 0) + 1
            return own

        for step in range(250):
            op = rng.randrange(8)
            if op <= 2 or not threads:
                if threads and rng.random() < 0.4:
                    base = list(rng.choice(list(threads.values())))
                    base = base[: ps * rng.randrange(
                        1, max(2, len(base) // ps + 1))]
                else:
                    base = []
                tail = rng.randrange(1, 4)
                tokens = base + [rng.randrange(90)
                                 for _ in range(tail * ps)]
                tokens = tokens[: (len(tokens) // ps) * ps]
                key = f"t{rng.randrange(6)}"
                if len(tokens) // ps > pool.free_pages:
                    cache.reclaim(len(tokens) // ps)
                if len(tokens) // ps <= pool.free_pages:
                    self._store(o, pool, cache, key, tokens)
                    threads[key] = tokens
            elif op == 3:
                key = rng.choice(list(threads))
                prompt = threads[key] + [rng.randrange(90)]
                hit = cache.lookup(key, prompt)
                if hit is not None:
                    self._verify_hit(o, ps, prompt, hit)
                    if rng.random() < 0.5 and len(live_holds) < 3:
                        live_holds.append(hit.pages)
                    else:
                        pool.release(hit.pages)
            elif op == 4:
                cache.reclaim(pool.free_pages + rng.randrange(1, 6))
            elif op == 5:
                key = rng.choice(list(threads))
                cache.invalidate(key)
                threads.pop(key, None)
            elif op == 6:
                cache.sleep_to_object()
            else:
                if live_holds:
                    pool.release(live_holds.pop(
                        rng.randrange(len(live_holds))))
                elif threads and rng.random() < 0.5:
                    # clear-then-wake: the store is the only copy left
                    cache.sleep_to_object()
                    for pages in live_holds:
                        pool.release(pages)
                    live_holds.clear()
                    cache.clear()
                    key = rng.choice(list(threads))
                    prompt = threads[key] + [rng.randrange(90)]
                    hit = cache.lookup(key, prompt)
                    if hit is not None:
                        assert hit.source == "object_tier"
                        self._verify_hit(o, ps, prompt, hit)
                        pool.release(hit.pages)
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
    cfg = ModelConfig(name="object-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def make_engine(cfg, params, obj_dir=None, **kw):
    defaults = dict(max_batch=2, page_size=8, num_pages=24,
                    max_pages_per_seq=16,
                    prefill_buckets=(8, 16, 32, 64, 128),
                    kv_host_tier_mb=64,
                    kv_object_dir=str(obj_dir) if obj_dir else None)
    defaults.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**defaults),
                           kv_dtype=jnp.float32)


class TestEngineCrossReplicaWake:
    def test_drained_thread_wakes_on_fresh_engine_token_exact(
        self, model, tmp_path
    ):
        """THE acceptance criterion: a thread demoted to the object
        store by replica A wakes on replica B (fresh engine, A gone)
        with cache_source="object_tier", token-exact output vs the
        never-slept reference, and 0 coverable prompt tokens
        re-prefilled — with the full span evidence."""
        cfg, params = model
        rng = np.random.default_rng(3)
        prompt = [int(x) for x in rng.integers(1, 120, 64)]
        a_eng = make_engine(cfg, params, tmp_path)
        a = GenRequest(request_id="A", prompt_ids=prompt,
                       max_new_tokens=8, prefix_key="thread-A")
        a_eng.submit(a)
        a_eng.run_to_completion()
        stats = a_eng.sleep_to_object()
        assert stats["enabled"] and stats["runs_archived"] >= 1
        assert stats["manifests"] == 1
        del a_eng  # replica A drained and torn down

        b_eng = make_engine(cfg, params, tmp_path)
        resume = prompt + list(a.output_ids) + [
            int(x) for x in rng.integers(1, 120, 12)
        ]
        tracing.reset()
        root = tracing.start_trace(request_id="wake-B")
        b = GenRequest(request_id="B", prompt_ids=resume,
                       max_new_tokens=8, prefix_key="thread-A",
                       trace=tracing.current())
        b_eng.submit(b)
        b_eng.run_to_completion()
        tracing.finish_trace(root)

        assert b.cache_source == "object_tier"
        ps = b_eng.ecfg.page_size
        stored = len(prompt) + len(a.output_ids) - 1
        coverable = (stored // ps) * ps
        assert b.cached_tokens == coverable  # 0 coverable re-prefilled
        assert b.object_tokens > 0
        obj = b_eng.kv_tier.object
        assert obj.wake_threads == 1
        assert b_eng.prefix_cache.object_tier_hits == 1
        assert not b_eng.self_check()

        tr = tracing.get_trace("wake-B")
        names = [s.name for s in tr.spans]
        assert "thread.wake" in names and "kv.object_get" in names
        wake = next(s for s in tr.spans if s.name == "thread.wake")
        assert wake.attrs["source"] == "object_tier"
        assert wake.attrs["tokens"] == b.object_tokens
        assert wake.attrs["bytes"] > 0
        pf = next(s for s in tr.spans if s.name == "engine.prefill")
        assert pf.attrs["cache_source"] == "object_tier"
        assert pf.attrs["object_tokens"] == b.object_tokens
        tracing.reset()

        # token-exact vs a never-slept engine serving both turns
        ref = make_engine(cfg, params, obj_dir=None)
        r1 = GenRequest(request_id="r1", prompt_ids=prompt,
                        max_new_tokens=8, prefix_key="t")
        ref.submit(r1)
        ref.run_to_completion()
        assert r1.output_ids == a.output_ids
        r2 = GenRequest(request_id="r2", prompt_ids=resume,
                        max_new_tokens=8, prefix_key="t")
        ref.submit(r2)
        ref.run_to_completion()
        assert r2.output_ids == b.output_ids

    def test_wake_composes_with_shared_prefix(self, model, tmp_path):
        """Fan-out shape: two threads share a system prefix.  After the
        first wakes, the second's wake imports ONLY its private tail
        (the shared head is already local) — and both are token-exact."""
        cfg, params = model
        rng = np.random.default_rng(5)
        common = [int(x) for x in rng.integers(1, 120, 32)]
        sfx = [[int(x) for x in rng.integers(1, 120, 16)]
               for _ in range(2)]
        a_eng = make_engine(cfg, params, tmp_path)
        firsts = []
        for i in range(2):
            r = GenRequest(request_id=f"A{i}", prompt_ids=common + sfx[i],
                           max_new_tokens=6, prefix_key=f"th-{i}")
            a_eng.submit(r)
            a_eng.run_to_completion()
            firsts.append(list(r.output_ids))
        a_eng.sleep_to_object()
        del a_eng

        b_eng = make_engine(cfg, params, tmp_path)
        woken = []
        for i in range(2):
            r = GenRequest(
                request_id=f"B{i}",
                prompt_ids=common + sfx[i] + firsts[i] + [3, 4, 5],
                max_new_tokens=6, prefix_key=f"th-{i}",
            )
            b_eng.submit(r)
            b_eng.run_to_completion()
            woken.append(r)
        assert [r.cache_source for r in woken] == ["object_tier"] * 2
        # the second thread woke fewer tokens: the shared head was local
        assert woken[1].object_tokens < woken[0].object_tokens
        assert not b_eng.self_check()

        ref = make_engine(cfg, params, obj_dir=None)
        for i in range(2):
            r1 = GenRequest(request_id=f"c{i}",
                            prompt_ids=common + sfx[i],
                            max_new_tokens=6, prefix_key=f"c-{i}")
            ref.submit(r1)
            ref.run_to_completion()
            assert list(r1.output_ids) == firsts[i]
            r2 = GenRequest(
                request_id=f"d{i}",
                prompt_ids=common + sfx[i] + firsts[i] + [3, 4, 5],
                max_new_tokens=6, prefix_key=f"c-{i}",
            )
            ref.submit(r2)
            ref.run_to_completion()
            assert list(r2.output_ids) == list(woken[i].output_ids)

    def test_organic_archive_past_disk(self, model, tmp_path):
        """Without a disk tier, host-budget overflow archives runs into
        the object store (demotion past disk) instead of dropping them —
        and the claimants' manifests follow."""
        cfg, params = model
        eng = make_engine(cfg, params, tmp_path)
        # shrink the host tier to ~one run so churn overflows it
        eng.kv_tier.host_budget_bytes = (
            eng.kv_tier.shipper.bytes_per_page() * 9
        )
        rng = np.random.default_rng(9)
        prompt = [int(x) for x in rng.integers(1, 120, 64)]
        a = GenRequest(request_id="A", prompt_ids=prompt,
                       max_new_tokens=8, prefix_key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        for i in range(3):
            r = GenRequest(
                request_id=f"c{i}",
                prompt_ids=[int(x) for x in rng.integers(1, 120, 64)],
                max_new_tokens=4, prefix_key=f"churn-{i}",
            )
            eng.submit(r)
            eng.run_to_completion()
        obj = eng.kv_tier.object
        assert obj.object_puts >= 1, "overflow must archive, not drop"
        assert obj.manifests_written >= 1
        assert not eng.self_check()

    def test_object_dir_unset_builds_nothing_bit_identical(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, obj_dir=None)
        assert eng.kv_tier is not None  # host tier still on
        assert eng.kv_tier.object is None
        assert EngineConfig().kv_object_dir is None
        snap = eng.metrics.snapshot(eng)
        assert "object_tier" not in snap
        # no tier at all when both knobs are off
        bare = make_engine(cfg, params, obj_dir=None, kv_host_tier_mb=0)
        assert bare.kv_tier is None

    def test_object_only_config_mounts_tier(self, model, tmp_path):
        """KAFKA_TPU_KV_OBJECT_DIR without a host tier still mounts the
        store (budget-0 manager = pure mount point): drain + wake work,
        ordinary eviction just drops as before."""
        cfg, params = model
        eng = make_engine(cfg, params, tmp_path, kv_host_tier_mb=0)
        assert eng.kv_tier is not None
        assert eng.kv_tier.object is not None
        rng = np.random.default_rng(13)
        prompt = [int(x) for x in rng.integers(1, 120, 48)]
        a = GenRequest(request_id="A", prompt_ids=prompt,
                       max_new_tokens=6, prefix_key="t")
        eng.submit(a)
        eng.run_to_completion()
        stats = eng.sleep_to_object()
        assert stats["enabled"] and stats["runs_archived"] >= 1
        b_eng = make_engine(cfg, params, tmp_path, kv_host_tier_mb=0)
        b = GenRequest(request_id="B",
                       prompt_ids=prompt + list(a.output_ids) + [3, 4],
                       max_new_tokens=6, prefix_key="t")
        b_eng.submit(b)
        b_eng.run_to_completion()
        assert b.cache_source == "object_tier"
        assert not b_eng.self_check()

    def test_negative_budget_rejected(self, model, tmp_path):
        cfg, params = model
        with pytest.raises(ValueError, match="kv_object_mb"):
            make_engine(cfg, params, tmp_path, kv_object_mb=-1)

    def test_config_env_round_trip(self, monkeypatch):
        from kafka_tpu.server.config import ServingConfig

        monkeypatch.setenv("KAFKA_TPU_KV_OBJECT_DIR", "/tmp/kvobj")
        monkeypatch.setenv("KAFKA_TPU_KV_OBJECT_MB", "128")
        cfg = ServingConfig.from_env()
        assert cfg.kv_object_dir == "/tmp/kvobj"
        assert cfg.kv_object_mb == 128
        monkeypatch.setenv("KAFKA_TPU_KV_OBJECT_MB", "-5")
        assert ServingConfig.from_env().kv_object_mb == 0


class TestRouterObjectAffinity:
    def test_manifest_hit_routes_by_load(self, model, tmp_path):
        """A thread known only to the shared store is routable ANYWHERE:
        with no local match, the router sends it to the least-loaded
        replica rather than forcing a cold pin — and the wake serves it
        there (affinity became a hint, ISSUE 14)."""
        from kafka_tpu.runtime.dp_router import DataParallelEngines

        cfg, params = model
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices for dp=2")
        ecfg = EngineConfig(max_batch=2, page_size=8, num_pages=24,
                            max_pages_per_seq=16,
                            prefill_buckets=(8, 16, 32, 64, 128),
                            kv_host_tier_mb=64,
                            kv_object_dir=str(tmp_path))
        # seed the store from a standalone engine (the "old host")
        old = make_engine(cfg, params, tmp_path)
        rng = np.random.default_rng(17)
        prompt = [int(x) for x in rng.integers(1, 120, 48)]
        a = GenRequest(request_id="A", prompt_ids=prompt,
                       max_new_tokens=6, prefix_key="portable")
        old.submit(a)
        old.run_to_completion()
        old.sleep_to_object()
        del old

        dp = DataParallelEngines(cfg, params, ecfg, dp=2, tp=1,
                                 kv_dtype=jnp.float32)
        # load replica 0 so the least-loaded choice is deterministic
        dp.engines[0].submit(GenRequest(
            request_id="busy", prompt_ids=prompt[:9], max_new_tokens=2,
        ))
        r = GenRequest(request_id="B",
                       prompt_ids=prompt + list(a.output_ids) + [3, 4],
                       max_new_tokens=6, prefix_key="portable")
        assert dp._object_match(r) > 0
        picked = dp._pick(r)
        assert picked == 1  # least-loaded, NOT the empty affinity table
        dp.submit(r)
        dp.run_to_completion()
        assert r.cache_source == "object_tier"
        for e in dp.engines:
            assert not e.self_check()


class TestDrainEndpoint:
    def _serve(self, engine, tmp_path, token="tok"):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from kafka_tpu.db.local import LocalDBClient
        from kafka_tpu.llm import TPULLMProvider
        from kafka_tpu.models.tokenizer import ByteTokenizer
        from kafka_tpu.server.app import create_app
        from kafka_tpu.server.config import ServingConfig

        provider = TPULLMProvider(engine, ByteTokenizer(), model_name="m")

        async def build():
            app = await create_app(
                cfg=ServingConfig(db_path=str(tmp_path / "d.db"),
                                  api_token=token),
                llm_provider=provider,
                db=LocalDBClient(str(tmp_path / "d.db")),
                tools=[],
            )
            client = TestClient(TestServer(app))
            await client.start_server()
            return client

        return asyncio, build, provider

    def test_drain_replica_endpoint(self, model, tmp_path):
        cfg, params = model
        store_dir = tmp_path / "store"
        eng = make_engine(cfg, params, store_dir)
        rng = np.random.default_rng(19)
        prompt = [int(x) for x in rng.integers(1, 120, 48)]
        a = GenRequest(request_id="A", prompt_ids=prompt,
                       max_new_tokens=6, prefix_key="t")
        eng.submit(a)
        eng.run_to_completion()
        asyncio, build, provider = self._serve(eng, tmp_path)

        async def go():
            client = await build()
            hdr = {"Authorization": "Bearer tok"}
            try:
                # token-gated like /admin/resize
                r = await client.post("/admin/drain/0")
                assert r.status == 401
                r = await client.post("/admin/drain/x", headers=hdr)
                assert r.status == 400
                r = await client.post("/admin/drain/7", headers=hdr)
                assert r.status == 400  # out of range
                r = await client.post("/admin/drain/0", headers=hdr)
                assert r.status == 200
                stats = await r.json()
                assert stats["enabled"] and stats["replica"] == 0
                assert stats["runs_archived"] >= 1
                assert stats["manifests"] >= 1
                # idempotent: the re-drain dedupes instead of re-writing
                r = await client.post("/admin/drain/0", headers=hdr)
                stats2 = await r.json()
                assert stats2["dedupe_hits"] >= stats2["runs_archived"] - \
                    stats2["runs_failed"] - 1 or stats2["dedupe_hits"] >= 1
                # signals v6 carries the object_tier section +
                # store health (ISSUE 17)
                s = await client.get("/admin/signals", headers=hdr)
                sig = await s.json()
                assert sig["version"] == 9
                assert sig["object_tier"]["store_objects"] >= 1
                assert "dedupe_ratio" in sig["object_tier"]
                assert sig["object_tier"]["breaker_state"] == "closed"
                assert sig["object_tier"]["store_available"] is True
            finally:
                await client.close()

        asyncio.run(go())
        # serving still works after the (non-destructive) drain
        b = GenRequest(request_id="B",
                       prompt_ids=prompt + list(a.output_ids) + [3],
                       max_new_tokens=4, prefix_key="t")
        eng.submit(b)
        eng.run_to_completion()
        assert not eng.self_check()

    def test_drain_without_store_409(self, model, tmp_path):
        cfg, params = model
        eng = make_engine(cfg, params, obj_dir=None)
        asyncio, build, provider = self._serve(eng, tmp_path)

        async def go():
            client = await build()
            try:
                r = await client.post(
                    "/admin/drain/0",
                    headers={"Authorization": "Bearer tok"},
                )
                assert r.status == 409
                body = await r.json()
                assert "KAFKA_TPU_KV_OBJECT_DIR" in body["error"]
            finally:
                await client.close()

        asyncio.run(go())


class TestRegistry:
    def test_snapshot_matches_registry_exactly(self, tmp_path):
        from kafka_tpu.runtime.metrics import OBJECT_TIER_METRIC_KEYS

        obj = ObjectTier(LocalFSObjectStore(str(tmp_path)),
                         fingerprint="f", page_size=4)
        assert set(obj.snapshot()) == set(OBJECT_TIER_METRIC_KEYS)

    def test_sites_and_spans_registered(self):
        assert "kv.object_put" in failpoints.SITES
        assert "kv.object_get" in failpoints.SITES
        assert "kv.object_put" in tracing.SPANS
        assert "kv.object_get" in tracing.SPANS
        assert "thread.wake" in tracing.SPANS

    def test_prometheus_families(self, model, tmp_path):
        from kafka_tpu.server.prometheus import render_prometheus

        cfg, params = model
        a_eng = make_engine(cfg, params, tmp_path)
        rng = np.random.default_rng(15)
        prompt = [int(x) for x in rng.integers(1, 120, 48)]
        a = GenRequest(request_id="A", prompt_ids=prompt,
                       max_new_tokens=6, prefix_key="t")
        a_eng.submit(a)
        a_eng.run_to_completion()
        a_eng.sleep_to_object()
        b_eng = make_engine(cfg, params, tmp_path)
        b = GenRequest(request_id="B",
                       prompt_ids=prompt + list(a.output_ids) + [3],
                       max_new_tokens=4, prefix_key="t")
        b_eng.submit(b)
        b_eng.run_to_completion()
        snap = b_eng.metrics.snapshot(b_eng)
        assert snap["object_tier"]["wake_threads"] == 1
        assert snap["prefix_cache"]["object_tier_hits"] == 1
        text = render_prometheus(snap)
        for family in (
            "kafka_tpu_object_tier_bytes",
            "kafka_tpu_object_tier_objects",
            "kafka_tpu_object_tier_puts_total",
            "kafka_tpu_object_tier_gets_total",
            "kafka_tpu_object_tier_bytes_total",
            "kafka_tpu_object_tier_dedupe_hits_total",
            "kafka_tpu_object_tier_wake_threads_total",
            "kafka_tpu_object_tier_wake_tokens_total",
            "kafka_tpu_object_tier_manifests_total",
        ):
            assert f"# TYPE {family}" in text, family
        assert 'kind="object_tier_hits"' in text
        # storeless engines export NO object_tier FAMILY (the prefix-
        # cache hit kind stays — it is an always-present counter label)
        bare = make_engine(cfg, params, obj_dir=None)
        assert "kafka_tpu_object_tier" not in render_prometheus(
            bare.metrics.snapshot(bare)
        )

    def test_autoscaler_drains_in_registries(self):
        from kafka_tpu.runtime.autoscaler import COUNTER_KEYS
        from kafka_tpu.runtime.metrics import AUTOSCALER_METRIC_KEYS

        assert "autoscaler_drains" in COUNTER_KEYS
        assert "autoscaler_drains" in AUTOSCALER_METRIC_KEYS
        from kafka_tpu.server.prometheus import render_prometheus

        text = render_prometheus({"autoscaler": {"autoscaler_drains": 3}})
        assert 'kafka_tpu_autoscaler_events_total{event="drain"} 3' in text


class TestBenchSmoke:
    def test_sleep_wake_phase_cpu(self, model):
        import importlib.util
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "bench", os.path.join(root, "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        sys.modules["bench"] = bench
        spec.loader.exec_module(bench)
        cfg, params = model
        out = bench.sleep_wake_phase(cfg, params, n_threads=3,
                                     common_len=496, suffix_len=16,
                                     gen_len=8, page_size=8)
        assert out["outputs_match"]
        assert out["cache_sources"] == ["object_tier"] * 3
        # the acceptance pair: wake beats re-prefill, and the woken span
        # re-prefills ZERO prompt tokens
        assert out["prompt_tokens_recomputed"] == 0
        cold = out["cold_resume_ttft_ms"]
        assert cold["object_wake"] < cold["reprefill"], out
        assert out["cross_host_dedupe_hits"] > 0
        assert out["wake_threads"] == 3
        assert out["store_objects"] >= 1
