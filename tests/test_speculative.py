"""Draft-free speculative decoding (ISSUE 5): equivalence matrix,
distribution preservation, rollback under preemption, prefix-cache
write-span invariant, the default-off guarantee, and the speculation
metric registry.

The load-bearing property: speculation is a pure latency/throughput
optimization — greedy outputs are BIT-IDENTICAL to the non-speculative
path across any scheduler churn, and sampled outputs follow the target
distribution at any temperature (the verify step samples every position
with the sequential path's own per-(seed, position) keys and accepts
candidates exactly while sample == candidate).
"""

import math
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, forward, init_params
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.speculative import LaneSpeculator


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="spec-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def make_engine(cfg, params, spec_k=4, **kw):
    defaults = dict(max_batch=4, page_size=8, num_pages=64,
                    max_pages_per_seq=8, prefill_buckets=(8, 16, 32, 64),
                    speculative_k=spec_k)
    defaults.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**defaults),
                           kv_dtype=jnp.float32)


class ForcedSpeculator:
    """Test stand-in for LaneSpeculator with a scripted proposal fn —
    engagement becomes deterministic (the organic proposer depends on
    model-emitted repetition)."""

    def __init__(self, fn):
        self._fn = fn
        self.hist = []
        self.accept_ewma = 1.0
        self.observed = []

    def push(self, token):
        self.hist.append(token)

    def propose(self, k_max):
        return list(self._fn(k_max))[:max(0, k_max)]

    def observe(self, accepted, proposed):
        self.observed.append((accepted, proposed))


def assert_greedy_consistent(cfg, params, prompt, out):
    seq = list(prompt) + list(out)
    x = jnp.asarray([seq], jnp.int32)
    pos = jnp.arange(len(seq), dtype=jnp.int32)[None, :]
    logits, _ = forward(params, cfg, x, pos)
    preds = np.asarray(jnp.argmax(logits[0], axis=-1))
    for i in range(len(prompt) - 1, len(seq) - 1):
        assert preds[i] == seq[i + 1], (
            f"divergence at position {i}: engine={seq[i + 1]} ref={preds[i]}"
        )


class TestNgramProposer:
    def test_earliest_occurrence_anchors_long_runs(self):
        sp = LaneSpeculator([1, 2, 3, 4, 5, 1, 2])
        # suffix (1, 2) first occurred at position 0 -> continuation 3,4,5
        assert sp.propose(3) == [3, 4, 5]
        assert sp.propose(2) == [3, 4]

    def test_no_match_no_proposal(self):
        sp = LaneSpeculator([1, 2, 3, 4, 5, 6])
        assert sp.propose(4) == []

    def test_pushes_extend_history(self):
        sp = LaneSpeculator([9, 8, 9, 8])
        sp.push(9)
        sp.push(8)
        # longest anchor wins: suffix trigram (8, 9, 8) first occurred at
        # positions 1..3 -> continuation from index 4 = [9, 8]
        assert sp.propose(4) == [9, 8]

    def test_long_prompt_index_amortized(self):
        """Admitting a long prompt must not index it eagerly (that work
        runs on the single engine worker thread and would freeze token
        emission for every in-flight stream); the index catches up
        INDEX_BUDGET tokens per propose call and the lane rides plain
        decode until it covers the whole history."""
        from kafka_tpu.runtime import speculative as sd

        base = [1, 2, 3, 4, 5, 1, 2]
        prompt = list(range(6, 300)) * 40 + base  # ~11.8k tokens
        sp = LaneSpeculator(prompt)
        assert sp._indexed == 0  # construction defers all index work
        assert sp.propose(3) == []  # still warming: no anchor yet
        for _ in range(len(prompt) // sd.INDEX_BUDGET + 2):
            out = sp.propose(3)
            if out:
                break
        assert out == [3, 4, 5]  # same anchor an eager build finds
        assert sp._indexed == len(sp.hist)
        from kafka_tpu.runtime import speculative as sd

        sp = LaneSpeculator([1, 2, 1, 2])
        for _ in range(20):
            sp.observe(0, 4)  # total rejection
        assert sp.accept_ewma < sd.ACCEPT_FLOOR
        assert sp.propose(4) == []  # throttled despite a match
        for _ in range(sd.PROBE_TOKENS):
            sp.push(1)
            sp.push(2)
        assert sp.propose(4) != []  # periodic re-probe


class TestSpeculativeEquivalence:
    """Greedy bit-identity and seeded-sampling identity, spec on vs off,
    across admit/retire churn, parking, and mixed temperatures."""

    def test_solo_greedy_bit_identical(self, model):
        cfg, params = model
        prompt = [1, 9, 23, 54, 3, 17, 88, 4, 61, 12, 7]
        plain = make_engine(cfg, params, spec_k=0).generate(
            prompt, max_new_tokens=24)
        spec = make_engine(cfg, params, spec_k=4).generate(
            prompt, max_new_tokens=24)
        assert spec.output_ids == plain.output_ids
        assert spec.finish_reason == plain.finish_reason
        assert_greedy_consistent(cfg, params, prompt, spec.output_ids)

    def _batch(self, cfg, params, spec_k, n=6, gen=24, **kw):
        eng = make_engine(cfg, params, spec_k=spec_k, **kw)
        reqs = []
        for i in range(n):
            r = GenRequest(
                request_id=f"r{i}", prompt_ids=[2 + i, 9, 23, 54, 7],
                max_new_tokens=gen,
                temperature=0.0 if i % 2 == 0 else 0.9, seed=i,
            )
            eng.submit(r)
            reqs.append(r)
        eng.run_to_completion()
        return [(r.output_ids, r.finish_reason) for r in reqs], eng

    def test_churn_batch_identical_mixed_temperatures(self, model):
        """6 requests over 4 slots: admissions, retirements, parking, and
        sampled lanes alongside greedy ones — outputs must match the
        non-speculative engine token for token."""
        cfg, params = model
        plain, _ = self._batch(cfg, params, 0)
        spec, eng = self._batch(cfg, params, 4)
        assert spec == plain
        assert eng.metrics.speculation_verify_steps > 0, (
            "speculation never engaged — the equivalence was vacuous"
        )
        assert not eng.self_check()

    def test_oversubscribed_parking_identical(self, model):
        cfg, params = model

        def run(spec_k):
            eng = make_engine(cfg, params, spec_k=spec_k, max_batch=2,
                              num_pages=96, max_pages_per_seq=8)
            reqs = [GenRequest(request_id=f"p-{i}",
                               prompt_ids=[5 + i, 9, 23],
                               max_new_tokens=24) for i in range(8)]
            for r in reqs:
                eng.submit(r)
            eng.run_to_completion()
            return [r.output_ids for r in reqs], eng

        plain, _ = run(0)
        spec, eng = run(4)
        assert spec == plain
        assert eng.metrics.speculation_verify_steps > 0
        assert not eng.self_check()

    def test_stop_tokens_inside_accepted_run(self, model):
        """A stop token discovered inside an accepted speculative run must
        truncate exactly where sequential decoding would."""
        cfg, params = model
        free = make_engine(cfg, params, spec_k=0).generate(
            [1, 9, 23, 54], max_new_tokens=16)
        stop_tok = free.output_ids[5]
        first = free.output_ids.index(stop_tok)

        def with_stop(spec_k):
            r = make_engine(cfg, params, spec_k=spec_k).generate(
                [1, 9, 23, 54], max_new_tokens=16,
                stop_token_ids=(stop_tok,))
            return r.output_ids, r.finish_reason

        assert with_stop(4) == with_stop(0)
        out, reason = with_stop(4)
        assert out == free.output_ids[: first + 1]
        assert reason == "stop"

    def test_deadline_timeout_with_speculation(self, model):
        cfg, params = model
        # wide window so the budget outlives the deadline even with every
        # program pre-compiled by earlier tests (the timeout must land
        # MID-decode, with speculative dispatches in flight)
        eng = make_engine(cfg, params, spec_k=4, num_pages=96,
                          max_pages_per_seq=32)
        req = GenRequest(request_id="dl", prompt_ids=[1, 2, 3],
                         max_new_tokens=5000, deadline_s=0.02)
        eng.submit(req)
        reason = None
        t0 = time.monotonic()
        while reason is None and time.monotonic() - t0 < 60:
            for ev in eng.step():
                if ev.finished:
                    reason = ev.finish_reason
        assert reason == "timeout"
        assert all(s is None for s in eng.slots)
        assert eng.pool.free_pages == eng.pool.num_pages - 1
        assert not eng.self_check()
        # monotone counters survive the discard of in-flight verify work
        m = eng.metrics
        assert (m.speculation_accepted_tokens + m.speculation_rejected_tokens
                <= m.speculation_proposed_tokens)
        # the engine keeps serving afterwards
        ok = eng.generate([4, 5, 6], max_new_tokens=2)
        assert ok.finish_reason == "length"

    def test_constrained_lane_never_speculates(self, model):
        """Constrained lanes keep the mask contract (per-token host
        turnaround) and must coexist with speculating peers.  The peer is
        FORCED to propose (oracle speculator): verify dispatches really
        happen while the constrained lane is active, so a constrained
        lane riding a verify dispatch unmasked would fail the equality
        below (the organic proposer would not engage on this prompt and
        the coexistence would go untested)."""
        cfg, params = model
        free_truth = self._free_truth(cfg, params)

        def run(spec_k):
            eng = make_engine(cfg, params, spec_k=spec_k)
            allowed = [10, 11, 12]
            c = GenRequest(request_id="c", prompt_ids=[5, 2, 9],
                           max_new_tokens=6,
                           logits_mask_fn=lambda out: allowed)
            free = GenRequest(request_id="f", prompt_ids=[1, 9, 23],
                              max_new_tokens=12)
            eng.submit(c)
            eng.submit(free)
            assert c.spec is None  # constrained: no speculator
            if spec_k > 0:
                free.spec = ForcedSpeculator(
                    lambda k: free_truth[
                        len(free.output_ids):len(free.output_ids) + k])
            done = eng.run_to_completion()
            if spec_k > 0:
                # the coexistence was actually exercised
                assert eng.metrics.speculation_proposed_tokens > 0
            assert all(t in allowed for t in done["c"].output_ids)
            return done["c"].output_ids, done["f"].output_ids

        assert run(4) == run(0)

    def _free_truth(self, cfg, params):
        return make_engine(cfg, params, spec_k=0).generate(
            [1, 9, 23], max_new_tokens=12).output_ids


class TestAcceptancePath:
    """Deterministic exercise of full and partial acceptance via a
    patched proposer (the organic n-gram proposer's engagement depends on
    model-emitted repetition)."""

    def _true_continuation(self, cfg, params, prompt, gen):
        return make_engine(cfg, params, spec_k=0).generate(
            prompt, max_new_tokens=gen).output_ids

    def test_oracle_proposals_fully_accepted(self, model):
        cfg, params = model
        prompt = [4, 40, 77, 2]
        truth = self._true_continuation(cfg, params, prompt, 20)
        eng = make_engine(cfg, params, spec_k=4)
        req = GenRequest(request_id="o", prompt_ids=prompt,
                         max_new_tokens=20)
        eng.submit(req)
        # oracle: always propose the true greedy continuation
        req.spec = ForcedSpeculator(
            lambda k: truth[len(req.output_ids):len(req.output_ids) + k])
        eng.run_to_completion()
        assert req.output_ids == truth
        m = eng.metrics
        assert m.speculation_accepted_tokens > 0
        assert m.speculation_accepted_tokens == m.speculation_proposed_tokens
        # K+1 tokens per verify dispatch: far fewer steps than tokens
        assert m.decode_steps < len(truth)

    def test_adversarial_proposals_all_rejected_still_exact(self, model):
        cfg, params = model
        prompt = [4, 40, 77, 2]
        truth = self._true_continuation(cfg, params, prompt, 12)
        eng = make_engine(cfg, params, spec_k=4)
        req = GenRequest(request_id="j", prompt_ids=prompt,
                         max_new_tokens=12)
        eng.submit(req)
        # junk candidates never matching the model's argmax stream
        req.spec = ForcedSpeculator(lambda k: [
            (truth[min(len(req.output_ids), len(truth) - 1)] + 1) % 128
        ] * min(k, 3))
        eng.run_to_completion()
        assert req.output_ids == truth  # bonus tokens carry the stream
        m = eng.metrics
        assert m.speculation_rejected_tokens > 0
        assert m.speculation_accepted_tokens == 0

    def test_partial_acceptance_mid_run(self, model):
        cfg, params = model
        prompt = [4, 40, 77, 2]
        truth = self._true_continuation(cfg, params, prompt, 20)
        eng = make_engine(cfg, params, spec_k=4)
        req = GenRequest(request_id="h", prompt_ids=prompt,
                         max_new_tokens=20)
        eng.submit(req)

        def half_oracle(k):
            pos = len(req.output_ids)
            good = truth[pos:pos + max(1, k // 2)]
            return good + [(t + 1) % 128 for t in
                           truth[pos + len(good):pos + k]]

        req.spec = ForcedSpeculator(half_oracle)
        eng.run_to_completion()
        assert req.output_ids == truth
        m = eng.metrics
        assert m.speculation_accepted_tokens > 0
        assert m.speculation_rejected_tokens > 0


class TestDistributionPreservation:
    """The verify sampler must follow the target distribution at any
    temperature.  By construction it samples with the sequential path's
    per-(seed, position) keys, so (a) per-seed outputs are identical to
    the non-speculative engine, and (b) the empirical first-verify-token
    distribution chi-squares against the analytic softmax."""

    N_SEEDS = 400

    def _collect(self, cfg, params, spec_k, temp, seeds, force_junk):
        outs = {}
        eng = make_engine(cfg, params, spec_k=spec_k)
        for s in seeds:
            req = GenRequest(request_id=f"d{spec_k}-{temp}-{s}",
                             prompt_ids=[3, 71, 15, 8], max_new_tokens=2,
                             temperature=temp, seed=s)
            eng.submit(req)
            if force_junk and req.spec is not None:
                # always propose one junk candidate: every verify round
                # exercises the rejection/bonus sampler
                req.spec = ForcedSpeculator(lambda k: [0])
            eng.run_to_completion()
            outs[s] = list(req.output_ids)
        return outs

    @pytest.mark.parametrize("temp", [1.0, 1.5])
    def test_sampled_outputs_identical_high_temp(self, model, temp):
        """Exact per-seed identity with the non-speculative engine — the
        strongest preservation claim (the verify sampler IS the
        sequential sampler at every position)."""
        cfg, params = model
        seeds = list(range(120))
        spec = self._collect(cfg, params, 4, temp, seeds, force_junk=True)
        plain = self._collect(cfg, params, 0, temp, seeds, force_junk=False)
        assert spec == plain

    def test_sampled_outputs_identical_and_chi_square(self, model):
        """At temp 0.7 (modal first token frequent enough to condition
        on), additionally chi-square the verify-sampled SECOND token
        against the analytic conditional softmax — the end-to-end check
        that the rejection/bonus sampler preserves the target
        distribution, not just that two implementations agree."""
        temp = 0.7
        cfg, params = model
        seeds = list(range(self.N_SEEDS))
        spec = self._collect(cfg, params, 4, temp, seeds, force_junk=True)
        plain = self._collect(cfg, params, 0, temp, seeds, force_junk=False)
        assert spec == plain
        # the first token is prefill-sampled; the second is the verify
        # step's bonus sample (the junk candidate forces a verify round)
        firsts = [spec[s][0] for s in seeds]
        mode = max(set(firsts), key=firsts.count)
        cond = [spec[s][1] for s in seeds if spec[s][0] == mode]
        assert len(cond) >= 40, "modal first token too rare for the test"
        seq = jnp.asarray([[3, 71, 15, 8, mode]], jnp.int32)
        pos = jnp.arange(5, dtype=jnp.int32)[None, :]
        logits, _ = forward(params, cfg, seq, pos)
        probs = np.asarray(jax.nn.softmax(logits[0, -1] / temp))
        counts = np.bincount(cond, minlength=cfg.vocab_size).astype(float)
        n = counts.sum()
        # lump tokens with tiny expected counts into one bucket
        big = probs * n >= 5
        exp = np.concatenate([probs[big] * n, [probs[~big].sum() * n]])
        obs = np.concatenate([counts[big], [counts[~big].sum()]])
        keep = exp > 0
        chi2 = float(((obs[keep] - exp[keep]) ** 2 / exp[keep]).sum())
        df = int(keep.sum()) - 1
        # generous bound (~p > 1e-4): catches systematic bias, not noise
        limit = df + 4.0 * math.sqrt(2.0 * max(df, 1)) + 10.0
        assert chi2 < limit, (
            f"temp {temp}: chi2 {chi2:.1f} over df {df} (limit {limit:.1f})"
        )


class TestRollbackAndPreemption:
    def test_rollback_under_preemption_with_partial_acceptance(self, model):
        """Page pressure mid-speculation: the pipeline drains (reconciling
        partially accepted runs), the victim rolls back to the queue, and
        resumed outputs stay greedy-exact."""
        cfg, params = model

        def run(spec_k):
            # 6 usable pages against two lanes whose full trajectories
            # need 6 pages EACH (window-clamped budgets): page pressure
            # must preempt someone mid-generation in every scheduling,
            # however fast speculation retires tokens
            eng = make_engine(cfg, params, spec_k=spec_k, max_batch=2,
                              num_pages=7, max_pages_per_seq=5,
                              max_parked=0)
            p1 = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7]
            p2 = [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5, 9, 4]
            a = GenRequest(request_id="x", prompt_ids=p1, max_new_tokens=26)
            b = GenRequest(request_id="y", prompt_ids=p2, max_new_tokens=26)
            eng.submit(a)
            eng.submit(b)
            if spec_k and a.spec is not None:
                # half-oracle proposals keep partial acceptance happening
                # right up to the page-pressure preemption point
                truth = make_engine(cfg, params, spec_k=0).generate(
                    p1, max_new_tokens=26).output_ids

                def half(k):
                    pos = len(a.output_ids)
                    good = truth[pos:pos + max(1, k // 2)]
                    return good + [(t + 3) % 128 for t in
                                   truth[pos + len(good):pos + k]]

                a.spec = ForcedSpeculator(half)
            done = eng.run_to_completion()
            return ([done["x"].output_ids, done["y"].output_ids],
                    eng.metrics.requests_preempted, eng)

        plain, _, _ = run(0)
        spec, preempts, eng = run(4)
        assert spec == plain
        assert preempts > 0, "preemption never exercised"
        assert eng.metrics.speculation_accepted_tokens > 0
        assert eng.pool.free_pages == 7 - 1
        assert not eng.self_check()

    def test_window_limit_inside_speculative_run(self, model):
        """A lane whose window fills mid-run must finish with length at
        exactly the sequential boundary (the drain-side limit check)."""
        cfg, params = model

        def run(spec_k):
            eng = make_engine(cfg, params, spec_k=spec_k, max_batch=2,
                              num_pages=16, max_pages_per_seq=4)  # window 32
            r = eng.generate([5, 2, 9, 1], max_new_tokens=64)
            return r.output_ids, r.finish_reason

        assert run(4) == run(0)
        out, reason = run(4)
        assert reason == "length"


class TestPrefixCacheInteraction:
    def test_speculative_writes_never_touch_shared_pages(self, model):
        """Thread B reuses thread A's radix-cached prefix while
        speculating: every verify write span must be private (refcount 1,
        unknown to the cache) — asserted live by _assert_private_tail on
        every proposing dispatch."""
        cfg, params = model
        eng = make_engine(cfg, params, spec_k=4, num_pages=96)
        checks = []
        orig = eng._assert_private_tail
        eng._assert_private_tail = lambda req, cl: (
            checks.append((req.request_id, cl)), orig(req, cl))[1]
        a = GenRequest(request_id="a", prompt_ids=[7] * 20 + [3, 9],
                       max_new_tokens=16, prefix_key="tA")
        eng.submit(a)
        eng.run_to_completion()
        assert eng.prefix_cache.total_pages > 0
        b = GenRequest(request_id="b", prompt_ids=[7] * 20 + [3, 9, 4],
                       max_new_tokens=16, prefix_key="tB")
        eng.submit(b)
        eng.run_to_completion()
        assert b.cached_tokens > 0 and b.cache_source == "cross"
        assert checks, "no speculative dispatch exercised the invariant"
        assert not eng.self_check()
        # outputs still greedy-exact through cache reuse + speculation
        assert_greedy_consistent(cfg, params, b.prompt_ids, b.output_ids)

    def test_own_thread_rehit_with_speculation(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, spec_k=4, num_pages=96)
        p = [7] * 20 + [3, 9]
        a = GenRequest(request_id="a", prompt_ids=p, max_new_tokens=8,
                       prefix_key="tS")
        eng.submit(a)
        eng.run_to_completion()
        p2 = p + a.output_ids + [4, 4]
        b = GenRequest(request_id="b", prompt_ids=p2, max_new_tokens=8,
                       prefix_key="tS")
        eng.submit(b)
        eng.run_to_completion()
        assert b.cached_tokens > 0 and b.cache_source == "own"
        assert not eng.self_check()


class TestDefaultOff:
    def test_k0_compiles_no_verify_fn_and_matches(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, spec_k=0)
        reqs = [GenRequest(request_id=f"k0-{i}", prompt_ids=[2 + i, 9, 23],
                           max_new_tokens=12) for i in range(4)]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        assert eng._verify_fn is None, "K=0 must never build a verify fn"
        for r in reqs:
            assert r.spec is None and r.spec_ahead == 0
            assert_greedy_consistent(cfg, params, r.prompt_ids,
                                     r.output_ids)
        m = eng.metrics
        assert m.speculation_verify_steps == 0
        assert m.speculation_proposed_tokens == 0

    def test_negative_k_rejected(self, model):
        cfg, params = model
        with pytest.raises(ValueError, match="speculative_k"):
            make_engine(cfg, params, spec_k=-1)

    def test_oversized_k_rejected(self, model):
        cfg, params = model
        with pytest.raises(ValueError, match="speculative_k"):
            make_engine(cfg, params, spec_k=64, max_pages_per_seq=2)


class TestSpeculationMetricRegistry:
    """The speculation section carries SPECULATION_METRIC_KEYS, the
    metric table's view of it."""

    def test_snapshot_carries_registry_keys(self, model):
        from kafka_tpu.runtime.metrics import (
            EngineMetrics,
            SPECULATION_METRIC_KEYS,
        )

        snap = EngineMetrics().snapshot()
        for key in SPECULATION_METRIC_KEYS:
            assert key in snap["speculation"]

    def test_waste_rename_aliases_removed(self, model):
        """The speculative_wasted_* JSON aliases PR 5 kept 'one release'
        are gone — fetch_pipeline_wasted_* is the only spelling (README
        "Metrics rename")."""
        from kafka_tpu.runtime.metrics import EngineMetrics

        m = EngineMetrics()
        m.record_wasted_token(3)
        snap = m.snapshot()
        assert snap["tokens"]["fetch_pipeline_wasted"] == 3
        assert "speculative_wasted" not in snap["tokens"]
        assert "speculative_waste_frac" not in snap["tokens"]


class TestBenchSpeculativeSmoke:
    def test_bench_speculative_cpu_smoke(self, model):
        """bench.py speculative, tier-1 shape: acceptance > 0 and output
        equivalence must hold on the CPU backend."""
        import sys
        from pathlib import Path

        sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
        from bench import speculative_phase

        cfg, params = model
        out = speculative_phase(cfg, params, n_lanes=3, prompt_len=40,
                                gen_len=24, k=6, page_size=8)
        assert out["outputs_match"], "speculation changed greedy outputs"
        assert out["acceptance_rate"] > 0
        assert out["accepted_tokens"] > 0
        assert out["verify_steps"] > 0
        # speculation must actually shrink the dispatch count
        assert (out["decode_steps"]["speculative"]
                < out["decode_steps"]["baseline"])
