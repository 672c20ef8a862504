"""Engine tests: paged attention correctness, continuous batching, sampling.

The load-bearing invariant: paged decode through the engine must produce the
same tokens as a plain full-context forward (greedy), for any batch mix.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, forward, init_params
from kafka_tpu.ops.sampling import SamplingParams, apply_top_k, apply_top_p, sample_tokens
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine, PagePool
from kafka_tpu.runtime.kv_cache import OutOfPagesError


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="engine-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def assert_greedy_consistent(cfg, params, prompt, out):
    """Check `out` is the greedy continuation of `prompt` with ONE forward.

    Runs the uncached model over prompt+out once; every position from the
    last prompt token onward must argmax-predict the next emitted token.
    Equivalent to comparing against step-by-step greedy generation (greedy
    is self-consistent), but ~n_new times faster.
    """
    seq = list(prompt) + list(out)
    x = jnp.asarray([seq], jnp.int32)
    pos = jnp.arange(len(seq), dtype=jnp.int32)[None, :]
    logits, _ = forward(params, cfg, x, pos)
    preds = np.asarray(jnp.argmax(logits[0], axis=-1))
    for i in range(len(prompt) - 1, len(seq) - 1):
        assert preds[i] == seq[i + 1], (
            f"divergence at position {i}: engine={seq[i + 1]} ref={preds[i]}"
        )


def make_engine(cfg, params, **kw):
    defaults = dict(max_batch=4, page_size=8, num_pages=64, max_pages_per_seq=8,
                    prefill_buckets=(8, 16, 32, 64))
    defaults.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**defaults), kv_dtype=jnp.float32)


class TestEngineCorrectness:
    def test_greedy_matches_uncached_forward(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        prompt = [1, 9, 23, 54, 3, 17, 88, 4, 61, 12, 7]  # crosses a page boundary
        req = eng.generate(prompt, max_new_tokens=12)
        assert_greedy_consistent(cfg, params, prompt, req.output_ids)
        assert len(req.output_ids) == 12
        assert req.finish_reason == "length"

    def test_chunked_prefill_matches(self, model):
        cfg, params = model
        # prompt longer than largest bucket forces multi-chunk prefill
        eng = make_engine(cfg, params, prefill_buckets=(8,), max_pages_per_seq=8)
        prompt = list(np.random.RandomState(0).randint(1, 128, size=21))
        req = eng.generate(prompt, max_new_tokens=6)
        assert_greedy_consistent(cfg, params, prompt, req.output_ids)
        assert len(req.output_ids) == 6

    def test_concurrent_requests_match_solo_runs(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        prompts = {
            "a": [5, 2, 9],
            "b": [88, 13, 54, 70, 21, 99, 6],
            "c": [1] * 17,
            "d": [42, 42, 7, 100],
        }
        for rid, p in prompts.items():
            eng.submit(GenRequest(request_id=rid, prompt_ids=p, max_new_tokens=8))
        done = eng.run_to_completion()
        assert set(done) == set(prompts)
        for rid, p in prompts.items():
            assert len(done[rid].output_ids) == 8, rid
            assert_greedy_consistent(cfg, params, p, done[rid].output_ids)

    def test_queueing_beyond_batch_size(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=2)
        for i in range(5):
            eng.submit(GenRequest(request_id=f"r{i}", prompt_ids=[i + 1, 3, 5],
                                  max_new_tokens=4))
        done = eng.run_to_completion()
        assert len(done) == 5
        for i in range(5):
            assert_greedy_consistent(cfg, params, [i + 1, 3, 5], done[f"r{i}"].output_ids)

    def test_stop_token_terminates(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        prompt = [1, 9, 23, 54]
        free = eng.generate(prompt, max_new_tokens=10)
        stop_tok = free.output_ids[2]
        first_idx = free.output_ids.index(stop_tok)  # may appear before idx 2
        req = eng.generate(prompt, max_new_tokens=10, stop_token_ids=(stop_tok,))
        assert req.output_ids == free.output_ids[: first_idx + 1]
        assert req.finish_reason == "stop"

    def test_preemption_resumes_correctly(self, model):
        cfg, params = model
        # tiny pool: 2 long-running requests must fight for pages
        eng = make_engine(cfg, params, max_batch=2, num_pages=9, max_pages_per_seq=8)
        p1, p2 = [3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8]
        eng.submit(GenRequest(request_id="x", prompt_ids=p1, max_new_tokens=20))
        eng.submit(GenRequest(request_id="y", prompt_ids=p2, max_new_tokens=20))
        done = eng.run_to_completion()
        assert len(done["x"].output_ids) == 20 and len(done["y"].output_ids) == 20
        assert_greedy_consistent(cfg, params, p1, done["x"].output_ids)
        assert_greedy_consistent(cfg, params, p2, done["y"].output_ids)
        # all pages back in the pool afterwards
        assert eng.pool.free_pages == 9 - 1

    def test_seeded_sampling_reproducible_across_batching(self, model):
        cfg, params = model
        kw = dict(max_new_tokens=10, temperature=0.9, top_p=0.95, seed=1234)
        eng1 = make_engine(cfg, params)
        solo = eng1.generate([7, 7, 7], **kw)
        eng2 = make_engine(cfg, params)
        eng2.submit(GenRequest(request_id="noise", prompt_ids=[9, 2], max_new_tokens=10,
                               temperature=1.3, seed=77))
        eng2.submit(GenRequest(request_id="probe", prompt_ids=[7, 7, 7], **kw))
        done = eng2.run_to_completion()
        assert done["probe"].output_ids == solo.output_ids

    def test_constrained_decoding_mask(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        allowed = [10, 11, 12]
        req = GenRequest(request_id="c", prompt_ids=[5, 2, 9], max_new_tokens=6,
                         logits_mask_fn=lambda out: allowed)
        eng.submit(req)
        done = eng.run_to_completion()
        assert all(t in allowed for t in done["c"].output_ids)


class TestSamplingOps:
    def test_top_k_masks(self):
        logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0]])
        out = apply_top_k(logits, jnp.asarray([2]))
        assert np.asarray(out[0, 0]) < -1e29 and np.asarray(out[0, 3]) < -1e29
        assert float(out[0, 1]) == 5.0 and float(out[0, 2]) == 3.0

    def test_top_k_zero_disables(self):
        logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0]])
        np.testing.assert_array_equal(np.asarray(apply_top_k(logits, jnp.asarray([0]))),
                                      np.asarray(logits))

    def test_top_p_keeps_head(self):
        logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
        out = apply_top_p(logits, jnp.asarray([0.7]))
        assert np.asarray(out[0, 0]) > -1e29 and np.asarray(out[0, 1]) > -1e29
        assert np.asarray(out[0, 2]) < -1e29 and np.asarray(out[0, 3]) < -1e29

    def test_greedy_is_argmax(self):
        logits = jnp.asarray([[0.1, 0.9, 0.2], [0.8, 0.1, 0.3]])
        sp = SamplingParams.make(2, temperature=0.0)
        toks = sample_tokens(logits, sp, jax.random.key(0))
        assert list(np.asarray(toks)) == [1, 0]

    def test_allowed_mask_restricts(self):
        logits = jnp.asarray([[0.1, 0.9, 0.2]])
        mask = jnp.asarray([[True, False, True]])
        sp = SamplingParams.make(1, temperature=0.0)
        toks = sample_tokens(logits, sp, jax.random.key(0), allowed_mask=mask)
        assert int(toks[0]) == 2

    def test_fully_masked_row_falls_back(self):
        logits = jnp.asarray([[0.1, 0.9, 0.2]])
        mask = jnp.zeros((1, 3), bool)
        sp = SamplingParams.make(1, temperature=0.0)
        toks = sample_tokens(logits, sp, jax.random.key(0), allowed_mask=mask)
        assert int(toks[0]) == 1  # unconstrained argmax


class TestPagePool:
    def test_alloc_release_refcount(self):
        pool = PagePool(num_pages=8, page_size=4)
        pages = pool.alloc(3)
        assert pool.free_pages == 4
        pool.retain(pages)
        pool.release(pages)
        assert pool.free_pages == 4  # still held once
        pool.release(pages)
        assert pool.free_pages == 7

    def test_exhaustion_raises(self):
        pool = PagePool(num_pages=4, page_size=4)
        pool.alloc(3)
        with pytest.raises(OutOfPagesError):
            pool.alloc(1)

    def test_trash_page_never_allocated(self):
        pool = PagePool(num_pages=4, page_size=4)
        assert 0 not in pool.alloc(3)

    @pytest.mark.parametrize("order", ["held", "reversed", "second_first"])
    def test_released_pages_are_handed_out_lowest_first(self, order):
        """Whatever was given back and in whatever order, the next prompt
        reserved in one go is one ascending run of pages (what boot's
        warm-up requests held comes back as 1, 2, 3, ...)."""
        pool = PagePool(num_pages=64, page_size=4)
        a, b = pool.alloc(5), pool.alloc(17)
        assert a + b == list(range(1, 23))
        kept = pool.alloc(2)    # still held: the run goes round it
        for pages in {"held": (a, b), "reversed": (b[::-1], a[::-1]),
                      "second_first": (b, a)}[order]:
            pool.release(pages)
        assert pool.alloc(30) == list(range(1, 23)) + list(range(25, 33))
        assert pool.check_consistency() == []
        pool.release(kept)
        assert pool.alloc(3) == kept + [33]


class TestReviewRegressions:
    def test_overlong_prompt_rejected_cleanly(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)  # window = 8 pages * 8 = 64
        with pytest.raises(ValueError, match="attention window"):
            eng.submit(GenRequest(request_id="big", prompt_ids=list(range(1, 80))))
        assert eng.pool.free_pages == 63  # nothing leaked

    def test_top_p_zero_is_argmax(self):
        logits = jnp.asarray([[0.1, 2.0, 0.3, 0.2]])
        sp = SamplingParams.make(1, temperature=1.0, top_p=0.0)
        toks = sample_tokens(logits, sp, jax.random.key(3))
        assert int(toks[0]) == 1

    def test_repeated_preemption_context_not_corrupted(self, model):
        cfg, params = model
        # 3 slots + 9 pages: constant page pressure -> multiple preemptions
        eng = make_engine(cfg, params, max_batch=3, num_pages=9, max_pages_per_seq=8)
        prompts = {"p0": [3, 1, 4, 1, 5], "p1": [2, 7, 1, 8], "p2": [9, 9, 8, 2, 6, 5]}
        for rid, p in prompts.items():
            eng.submit(GenRequest(request_id=rid, prompt_ids=p, max_new_tokens=24))
        done = eng.run_to_completion()
        for rid, p in prompts.items():
            assert len(done[rid].output_ids) == 24, rid
            assert_greedy_consistent(cfg, params, p, done[rid].output_ids)
            # prompt itself must be untouched by preemption bookkeeping
            assert done[rid].prompt_ids == p

    def test_registry_drained_after_completion(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        eng.generate([1, 2, 3], max_new_tokens=3)
        assert eng._requests == {}


class TestMultiStepDecode:
    """Fused k-step decode dispatches (EngineConfig.multi_step): engage
    only for busy stable batches and stay token-identical to single-step
    scheduling (position-keyed RNG makes fusion invisible to outputs)."""

    def _run_batch(self, cfg, params, multi_step, n_req=4, seeds=(0, 1, 2, 3)):
        eng = make_engine(cfg, params, max_batch=4, num_pages=96,
                          max_pages_per_seq=12, multi_step=multi_step)
        dispatched_multi = []
        orig = eng._dispatch_multi
        eng._dispatch_multi = lambda k: (dispatched_multi.append(k), orig(k))[1]
        reqs = []
        for i in range(n_req):
            r = GenRequest(
                request_id=f"ms-{i}", prompt_ids=[2 + i, 9, 23, 54, 7],
                max_new_tokens=24,
                temperature=0.0 if i % 2 == 0 else 0.9, seed=seeds[i],
            )
            eng.submit(r)
            reqs.append(r)
        eng.run_to_completion()
        return [r.output_ids for r in reqs], dispatched_multi

    def test_multi_step_token_exact_vs_single_step(self, model):
        cfg, params = model
        multi, ks = self._run_batch(cfg, params, multi_step=8)
        single, ks1 = self._run_batch(cfg, params, multi_step=1)
        assert multi == single
        assert ks and max(ks) >= 4, f"multi-step never engaged: {ks}"
        assert ks1 == []

    def test_stop_token_mid_burst_truncates(self, model):
        cfg, params = model
        # find each request's natural stop candidate from the single-step
        # run, then re-run WITH stop tokens under multi-step: the burst may
        # overshoot the stop on device, but emission must truncate exactly
        single, _ = self._run_batch(cfg, params, multi_step=1)
        stops = [out[5] for out in single]

        def with_stops(multi_step):
            eng = make_engine(cfg, params, max_batch=4, num_pages=96,
                              max_pages_per_seq=12, multi_step=multi_step)
            reqs = []
            for i in range(4):
                r = GenRequest(
                    request_id=f"st-{i}", prompt_ids=[2 + i, 9, 23, 54, 7],
                    max_new_tokens=24,
                    temperature=0.0 if i % 2 == 0 else 0.9, seed=i,
                    stop_token_ids=(stops[i],),
                )
                eng.submit(r)
                reqs.append(r)
            eng.run_to_completion()
            return [(r.output_ids, r.finish_reason) for r in reqs]

        assert with_stops(8) == with_stops(1)

    def test_multi_step_engages_under_queue_pressure(self, model):
        """Sustained load (queued requests, every slot busy) is exactly
        where fused dispatches matter: fusion must stay ON — admission can
        only happen at iteration boundaries anyway — and oversubscribed
        runs must still produce correct outputs."""
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=4, num_pages=96,
                          max_pages_per_seq=12, multi_step=8)
        # queued requests now prefill off-slot and PARK awaiting a decode
        # slot (EngineConfig.max_parked), so "queue pressure" = waiting OR
        # parked lanes at fused-dispatch time
        fused_while_waiting = []
        orig = eng._dispatch_multi
        eng._dispatch_multi = lambda k: (
            fused_while_waiting.append(bool(eng.waiting or eng.parked)),
            orig(k))[1]
        reqs = []
        for i in range(8):  # 8 requests > 4 slots -> sustained queue
            r = GenRequest(request_id=f"q-{i}",
                           prompt_ids=[3 + i, 9, 23], max_new_tokens=32)
            eng.submit(r)
            reqs.append(r)
        eng.run_to_completion()
        assert any(fused_while_waiting), (
            "fusion never engaged under queue pressure"
        )
        for r in reqs:
            assert len(r.output_ids) == 32
            assert_greedy_consistent(cfg, params, r.prompt_ids, r.output_ids)


class TestInterleavedPrefill:
    """Admitting a long prompt must not stall co-scheduled decode streams:
    prefill advances one chunk per scheduler iteration while active lanes
    keep decoding (continuous-batching prefill/decode interleave)."""

    def test_decode_continues_during_long_prefill(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=2, num_pages=96,
                          max_pages_per_seq=16, prefill_buckets=(8,))
        a = GenRequest(request_id="a", prompt_ids=[1, 2, 3, 4],
                       max_new_tokens=64)
        eng.submit(a)
        while a.state != "active":
            eng.step()
        base = a.dispatched
        # 40-token prompt through 8-token chunks = 5 prefill iterations
        b = GenRequest(request_id="b", prompt_ids=list(range(1, 41)),
                       max_new_tokens=4)
        eng.submit(b)
        saw_prefilling = False
        for _ in range(50):
            if b.state not in ("waiting", "prefilling"):
                break
            if b.state == "prefilling":
                saw_prefilling = True
            eng.step()
        assert saw_prefilling, "prefill never interleaved (inlined?)"
        # the co-scheduled stream kept decoding during b's prefill
        assert a.dispatched - base >= 3
        eng.run_to_completion()
        # and both outputs are still exactly right
        assert_greedy_consistent(cfg, params, a.prompt_ids, a.output_ids)
        assert_greedy_consistent(cfg, params, b.prompt_ids, b.output_ids)

    def test_solo_long_prompt_still_correct(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=2, num_pages=96,
                          max_pages_per_seq=16, prefill_buckets=(8, 16))
        prompt = list(np.random.RandomState(12).randint(1, 128, size=45))
        req = eng.generate(prompt, max_new_tokens=6)
        assert_greedy_consistent(cfg, params, prompt, req.output_ids)


class TestBatchedPrefill:
    """Same-bucket prefills fuse into one dispatch; outputs must be
    token-identical to solo runs (position-keyed sampling; f32 tests)."""

    def test_batched_admission_token_exact(self, model):
        cfg, params = model
        mk = lambda: make_engine(cfg, params, max_batch=4, num_pages=96,
                                 max_pages_per_seq=12)
        # solo baselines
        solo = []
        ref_eng = mk()
        for i in range(4):
            r = ref_eng.generate([5 + i, 9, 23, 54, 7, 2, 11, 3],
                                 max_new_tokens=12,
                                 temperature=0.0 if i % 2 == 0 else 1.1,
                                 seed=i)
            solo.append(r.output_ids)
        # batched admission: all 4 submitted before stepping -> the 4
        # same-bucket first chunks ride ONE dispatch
        eng = mk()
        batched_calls = []
        orig = eng._advance_prefill_batch
        eng._advance_prefill_batch = (
            lambda b, rs, w: (batched_calls.append(len(rs)), orig(b, rs, w))[1])
        reqs = []
        for i in range(4):
            r = GenRequest(request_id=f"bp-{i}",
                           prompt_ids=[5 + i, 9, 23, 54, 7, 2, 11, 3],
                           max_new_tokens=12,
                           temperature=0.0 if i % 2 == 0 else 1.1, seed=i)
            eng.submit(r)
            reqs.append(r)
        eng.run_to_completion()
        assert batched_calls and max(batched_calls) >= 2, batched_calls
        assert [r.output_ids for r in reqs] == solo

    def test_constrained_lane_never_fuses(self, model):
        """A constrained request admitted alongside same-bucket peers must
        take the single-sequence path (its final chunk pops the sampled
        token synchronously so the first decode mask sees complete
        output_ids) — fusing it reorders token visibility and breaks the
        mask contract."""
        cfg, params = model

        def run(with_peers):
            eng = make_engine(cfg, params, max_batch=4, num_pages=96,
                              max_pages_per_seq=12)
            mask = lambda out: None if not out else [out[0] + 1, out[0] + 2]
            c = GenRequest(request_id="c", prompt_ids=[5, 9, 23, 54],
                           max_new_tokens=6, logits_mask_fn=mask)
            eng.submit(c)
            if with_peers:
                for i in range(3):
                    eng.submit(GenRequest(request_id=f"p{i}",
                                          prompt_ids=[6 + i, 9, 23, 54],
                                          max_new_tokens=6))
            eng.run_to_completion()
            return c.output_ids

        solo = run(with_peers=False)
        assert run(with_peers=True) == solo

    def test_mixed_bucket_admissions_split_correctly(self, model):
        """Different prompt lengths land in different buckets: each group
        fuses, singletons go solo, everything stays correct."""
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=4, num_pages=96,
                          max_pages_per_seq=12, prefill_buckets=(8, 32))
        lens = [6, 7, 20, 25]  # two in bucket 8, two in bucket 32
        reqs = []
        for i, n in enumerate(lens):
            r = GenRequest(
                request_id=f"mix-{i}",
                prompt_ids=list(np.random.RandomState(i).randint(1, 128, n)),
                max_new_tokens=6)
            eng.submit(r)
            reqs.append(r)
        eng.run_to_completion()
        for r in reqs:
            assert_greedy_consistent(cfg, params, r.prompt_ids, r.output_ids)


class TestOffSlotAdmission:
    """Parking (EngineConfig.max_parked): when every decode slot is busy,
    waiting requests prefill off-slot and emit their FIRST token without
    waiting for a slot — TTFT under oversubscription is bounded by prefill
    latency, not queue wait (VERDICT r3 weak #2).  Parked pages must be
    reclaimed before any active lane is preempted, and outputs must stay
    token-exact through park/seat/rollback."""

    def test_first_tokens_precede_queue_drain(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=2, num_pages=96,
                          max_pages_per_seq=8)
        reqs = [GenRequest(request_id=f"p-{i}", prompt_ids=[5 + i, 9, 23],
                           max_new_tokens=24) for i in range(8)]
        for r in reqs:
            eng.submit(r)
        # step until every request has its first token
        finished_when_all_started = None
        for _ in range(3000):
            eng.step()
            if all(r.first_token_time is not None for r in reqs):
                finished_when_all_started = sum(
                    1 for r in reqs if r.state == "finished")
                break
        assert finished_when_all_started is not None, "first tokens missing"
        # 8 requests over 2 slots: first tokens must NOT have required the
        # queue to drain (without parking, request 8's first token arrives
        # after ~3 full turns retire)
        assert finished_when_all_started <= 4
        eng.run_to_completion()
        for r in reqs:
            assert len(r.output_ids) == 24, r.request_id
            assert_greedy_consistent(cfg, params, r.prompt_ids, r.output_ids)

    def test_parked_rollback_under_page_pressure(self, model):
        cfg, params = model
        # tight pool: 2 slots of long-ish generations + parked extras force
        # page-pressure rollback of parked lanes (never active preemption)
        eng = make_engine(cfg, params, max_batch=2, num_pages=14,
                          max_pages_per_seq=6, park_reserve_pages=2)
        reqs = [GenRequest(request_id=f"r-{i}", prompt_ids=[7 + i, 3],
                           max_new_tokens=30) for i in range(6)]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        for r in reqs:
            assert len(r.output_ids) == 30, r.request_id
            assert_greedy_consistent(cfg, params, r.prompt_ids, r.output_ids)

    def test_cancel_parked_request_frees_pages(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=2, num_pages=96,
                          max_pages_per_seq=8)
        reqs = [GenRequest(request_id=f"c-{i}", prompt_ids=[11 + i, 2, 9],
                           max_new_tokens=20) for i in range(5)]
        for r in reqs:
            eng.submit(r)
        # step until something parks, then cancel it
        for _ in range(500):
            eng.step()
            if eng.parked:
                break
        assert eng.parked, "nothing parked"
        victim = eng.parked[0]
        assert eng.cancel(victim.request_id)
        assert victim not in eng.parked and victim.seq is None
        eng.run_to_completion()
        for r in reqs:
            if r is victim:
                continue
            assert len(r.output_ids) == 20, r.request_id
            assert_greedy_consistent(cfg, params, r.prompt_ids, r.output_ids)

    def test_disabled_parking_keeps_fifo_waiting(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=2, num_pages=96,
                          max_pages_per_seq=8, max_parked=0)
        reqs = [GenRequest(request_id=f"d-{i}", prompt_ids=[4 + i, 8],
                           max_new_tokens=8) for i in range(5)]
        for r in reqs:
            eng.submit(r)
        eng.step()
        assert not eng.parked and len(eng.waiting) == 3
        eng.run_to_completion()
        for r in reqs:
            assert len(r.output_ids) == 8


class TestConstrainedChaining:
    """Singleton-mask chaining: grammar-forced tokens dispatch at
    scheduler cadence instead of one device->host round trip each (the
    dominant cost of constrained tool-call JSON on high-RTT links)."""

    def test_forced_sequence_chains_without_blocking_pops(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=2)
        seq = [9, 23, 54, 3, 17, 88, 4, 61, 12, 7, 33, 90]

        def mask_fn(out):
            return [seq[len(out)]] if len(out) < len(seq) else [2]

        pops = []
        orig = eng._pop_entry_now
        eng._pop_entry_now = lambda e: (pops.append(1), orig(e))[1]
        req = GenRequest(request_id="chain", prompt_ids=[5, 2, 9],
                         max_new_tokens=len(seq) + 1,
                         logits_mask_fn=mask_fn)
        eng.submit(req)
        done = eng.run_to_completion()
        assert done["chain"].output_ids == seq + [2]
        # the prefill's synchronous pop is expected; the forced decode run
        # must NOT have popped per token (13 tokens -> <= a few pops)
        assert len(pops) <= 3, f"{len(pops)} blocking pops for forced run"

    def test_mixed_forced_and_free_steps_still_correct(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=2)
        forced_prefix = [11, 45, 2]

        def mask_fn(out):
            if len(out) < len(forced_prefix):
                return [forced_prefix[len(out)]]
            return None  # free generation afterwards

        req = GenRequest(request_id="mix", prompt_ids=[7, 3],
                         max_new_tokens=8, logits_mask_fn=mask_fn)
        eng.submit(req)
        done = eng.run_to_completion()
        out = done["mix"].output_ids
        assert out[:3] == forced_prefix and len(out) == 8
        # the free tail must be the model's real greedy continuation
        assert_greedy_consistent(cfg, params, [7, 3] + forced_prefix,
                                 out[3:])

    def test_chained_alongside_unconstrained_lane(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=2)
        seq = [8, 19, 42, 5, 77, 1]

        def mask_fn(out):
            return [seq[len(out)]] if len(out) < len(seq) else [2]

        free = GenRequest(request_id="free", prompt_ids=[1, 9, 23],
                          max_new_tokens=12)
        conq = GenRequest(request_id="con", prompt_ids=[5, 2, 9],
                          max_new_tokens=len(seq) + 1,
                          logits_mask_fn=mask_fn)
        eng.submit(free)
        eng.submit(conq)
        done = eng.run_to_completion()
        assert done["con"].output_ids == seq + [2]
        assert_greedy_consistent(cfg, params, [1, 9, 23],
                                 done["free"].output_ids)

    def test_forced_stop_token_ends_chain_without_mask_overrun(self, model):
        """A grammar whose table ends at the stop token must not be called
        past its end (the chain stops at a predicted stop token), and a
        mask fn that DOES get called out of range must degrade the step,
        not kill the engine thread."""
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=2)
        seq = [9, 23, 54, 99]

        def mask_fn(out):
            return [seq[len(out)]]  # IndexError if called past the end

        req = GenRequest(request_id="stop-chain", prompt_ids=[5, 2],
                         max_new_tokens=20, stop_token_ids=(99,),
                         logits_mask_fn=mask_fn)
        eng.submit(req)
        done = eng.run_to_completion()
        assert done["stop-chain"].output_ids == seq
        assert done["stop-chain"].finish_reason == "stop"

    def test_exhausted_mask_table_degrades_not_crashes(self, model):
        """Grammar ends but generation continues: the raising mask fn
        degrades the lane to unconstrained instead of failing every
        in-flight request."""
        cfg, params = model
        eng = make_engine(cfg, params, max_batch=2)
        seq = [9, 23, 54]  # no stop token: generation outlives the table

        def mask_fn(out):
            return [seq[len(out)]]

        req = GenRequest(request_id="exhaust", prompt_ids=[5, 2],
                         max_new_tokens=8, logits_mask_fn=mask_fn)
        eng.submit(req)
        done = eng.run_to_completion()
        out = done["exhaust"].output_ids
        assert out[:3] == seq and len(out) == 8


class TestLifecycleHardening:
    """Deadlines + admission backpressure (ISSUE 1 request-lifecycle
    hardening): timeouts finish with finish_reason="timeout" and free slot
    + pages exactly like a cancel; a submit past the bounded queue raises
    AdmissionError with a Retry-After estimate."""

    def test_total_deadline_times_out_waiting_request(self, model):
        import time as _time

        cfg, params = model
        eng = make_engine(cfg, params, max_total_s=0.0)
        eng.submit(GenRequest(request_id="t1", prompt_ids=[1, 2, 3]))
        _time.sleep(0.005)
        events = eng.step()
        terminal = [e for e in events if e.finished]
        assert len(terminal) == 1
        assert terminal[0].finish_reason == "timeout"
        assert eng.pool.free_pages == eng.pool.num_pages - 1
        assert not eng.waiting and not eng._requests
        assert eng.metrics.requests_timeout == 1

    def test_deadline_frees_slot_and_pages_mid_decode(self, model):
        import time as _time

        from kafka_tpu.runtime import failpoints as _fp

        cfg, params = model
        eng = make_engine(cfg, params)
        req = GenRequest(request_id="mid", prompt_ids=[1, 2, 3],
                         max_new_tokens=500, deadline_s=0.05)
        eng.submit(req)
        reason = None
        t0 = _time.monotonic()
        # slow each scheduler iteration so the deadline ALWAYS expires
        # mid-decode — with warm compiled programs (XLA cache shared
        # across modules) 500 tokens can otherwise finish inside 50ms
        # and the finish reason races to "length"
        with _fp.armed("engine.step", "delay", "0.005"):
            while reason is None and _time.monotonic() - t0 < 30:
                for ev in eng.step():
                    if ev.finished:
                        reason = ev.finish_reason
        assert reason == "timeout"
        assert all(s is None for s in eng.slots)
        assert eng.pool.free_pages == eng.pool.num_pages - 1
        assert not eng.self_check(), eng.self_check()
        # the engine keeps serving afterwards
        ok = eng.generate([4, 5, 6], max_new_tokens=2)
        assert ok.finish_reason == "length"

    def test_ttft_deadline_spares_request_that_got_first_token(self, model):
        import time as _time

        cfg, params = model
        # generous TTFT bound: the first token arrives well inside it, so
        # the request must run to its full budget even after the bound
        eng = make_engine(cfg, params, max_ttft_s=30.0)
        req = eng.generate([1, 2, 3], max_new_tokens=4)
        assert req.finish_reason == "length"
        assert len(req.output_ids) == 4

    def test_per_request_deadline_overrides_config(self, model):
        import time as _time

        cfg, params = model
        eng = make_engine(cfg, params, max_total_s=300.0)
        eng.submit(GenRequest(request_id="o1", prompt_ids=[1, 2, 3],
                              deadline_s=0.0))
        _time.sleep(0.005)
        events = eng.step()
        assert any(e.finished and e.finish_reason == "timeout"
                   for e in events)

    def test_bounded_queue_rejects_with_retry_after(self, model):
        from kafka_tpu.runtime import AdmissionError

        cfg, params = model
        eng = make_engine(cfg, params, max_waiting=2)
        rejected = None
        for i in range(16):
            try:
                eng.submit(GenRequest(request_id=f"q{i}",
                                      prompt_ids=[1, 2], max_new_tokens=2))
            except AdmissionError as e:
                rejected = e
                break
        assert rejected is not None
        assert rejected.retry_after_s >= 1.0
        assert eng.metrics.requests_rejected == 1
        # everything admitted before the bound still completes
        done = eng.run_to_completion()
        assert len(done) == i
        assert eng.metrics.queue_depth_peak >= 1

    def test_unbounded_queue_by_default(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        for i in range(20):
            eng.submit(GenRequest(request_id=f"u{i}", prompt_ids=[1, 2],
                                  max_new_tokens=1))
        assert len(eng.run_to_completion()) == 20
