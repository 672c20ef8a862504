"""Agent-native scheduling (ISSUE 20): exploit the tool-call gap.

The load-bearing claims:
  * a thread that finishes a turn with a tool call demotes its KV down
    the tier ladder after the linger window, resumes token-identical to
    a never-demoted engine (cache_source="host_tier"), and the return
    hint cancels a still-lingering demote so sub-linger tools never pay
    the round trip,
  * the return hint kicks the wake prefetcher with the thread's
    locally-resident depth,
  * background-class requests (tool-result prefill, compaction
    summarization) yield to interactive work every scheduler iteration,
    admit only into idle capacity, and produce byte-identical outputs
    to a foreground run,
  * with KAFKA_TPU_AGENT_DEMOTE unset every hook is a no-op and
    scheduling is unchanged,
  * agent_section() carries exactly AGENT_METRIC_KEYS, the metric
    table's view of the section,
  * EngineWorker routes note_tool_gap/note_tool_return through its
    inbox (engine is single-writer), the DP router pins
    expected-return hints to the thread's affinity replica,
  * HTTPObjectStore signs requests (AWS SigV4 / GCS bearer) that a
    stub verifying by INDEPENDENT recomputation accepts — and rejects
    with 403/401 when the credentials are wrong.
"""

import asyncio
import hashlib
import os
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import (
    AdmissionError,
    EngineConfig,
    GenRequest,
    InferenceEngine,
)
from kafka_tpu.runtime.dp_router import DataParallelEngines
from kafka_tpu.runtime.engine import (
    AGENT_DEMOTE_ENV,
    AGENT_LINGER_ENV,
    agent_demote_default,
    agent_linger_default,
)
from kafka_tpu.runtime.flight_recorder import CAUSES
from kafka_tpu.runtime.metrics import AGENT_METRIC_KEYS
from kafka_tpu.runtime.object_tier import (
    ENV_OBJECT_AUTH,
    ENV_OBJECT_BEARER,
    HTTPObjectStore,
    _load_object_auth,
    _sigv4_headers,
)

from objstore_stub import StubS3Server


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="agent-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def make_engine(cfg, params, **kw):
    defaults = dict(max_batch=2, page_size=8, num_pages=24,
                    max_pages_per_seq=16,
                    prefill_buckets=(8, 16, 32, 64, 128),
                    kv_host_tier_mb=64,
                    agent_demote="host", agent_linger_s=0.0)
    defaults.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**defaults),
                           kv_dtype=jnp.float32)


def _req(rid, prompt, key=None, max_new=8, background=False):
    return GenRequest(request_id=rid, prompt_ids=list(prompt),
                      max_new_tokens=max_new, prefix_key=key,
                      background=background)


def _prompt(seed, n=64):
    return [int(x) for x in np.random.default_rng(seed).integers(1, 120, n)]


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------


class TestKnobs:
    def test_defaults_off(self, monkeypatch):
        monkeypatch.delenv(AGENT_DEMOTE_ENV, raising=False)
        monkeypatch.delenv(AGENT_LINGER_ENV, raising=False)
        assert agent_demote_default() == ""
        assert agent_linger_default() == pytest.approx(0.25)
        assert EngineConfig().agent_demote == ""

    def test_env_knobs(self, monkeypatch):
        monkeypatch.setenv(AGENT_DEMOTE_ENV, "on")
        monkeypatch.setenv(AGENT_LINGER_ENV, "100")
        assert agent_demote_default() == "host"
        assert agent_linger_default() == pytest.approx(0.1)
        monkeypatch.setenv(AGENT_DEMOTE_ENV, "object")
        assert agent_demote_default() == "object"
        monkeypatch.setenv(AGENT_DEMOTE_ENV, "bogus")
        assert agent_demote_default() == ""  # nonsense = off, not a crash
        monkeypatch.setenv(AGENT_LINGER_ENV, "not-a-number")
        assert agent_linger_default() == pytest.approx(0.25)

    def test_invalid_mode_rejected(self, model):
        cfg, params = model
        with pytest.raises(ValueError, match="agent_demote"):
            make_engine(cfg, params, agent_demote="bogus")


# ---------------------------------------------------------------------------
# gap lifecycle
# ---------------------------------------------------------------------------


class TestGapLifecycle:
    def test_demote_then_resume_token_exact(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, flight_ring=64)
        prompt = _prompt(3)
        a = _req("A", prompt, key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        pc = eng.prefix_cache
        assert pc.host_nodes == 0

        # the turn ended in a tool call; linger=0 -> next step demotes
        eng.note_tool_gap("thread-A")
        assert eng.agent_gaps == 1
        eng.step()
        assert eng.agent_gap_demotions == 1
        assert eng.agent_gap_pages_demoted > 0
        assert eng.agent_gap_bytes_demoted > 0
        assert pc.host_nodes > 0, "gap must demote the thread's KV"
        assert eng.awaiting_tool_keys() == ["thread-A"]
        sec = eng.agent_section()
        assert sec["agent_awaiting_threads"] == 1
        assert sec["agent_awaiting_bytes"] > 0
        assert any("agent_demote" in r.get("causes", {})
                   for r in eng.flight.records())
        assert not eng.self_check()

        # the tool finished: hint fires, awaiting state clears
        eng.note_tool_return("thread-A")
        assert eng.agent_hint_hits == 1
        assert eng.awaiting_tool_keys() == []
        assert eng.agent_section()["agent_awaiting_threads"] == 0

        # follow-up turn resumes from the host tier, token-identical
        resume = prompt + list(a.output_ids) + [7, 9, 11]
        a2 = _req("A2", resume, key="thread-A")
        eng.submit(a2)
        eng.run_to_completion()
        assert a2.cache_source == "host_tier"
        assert a2.promoted_tokens > 0

        base = make_engine(cfg, params, kv_host_tier_mb=0, agent_demote="")
        b1 = _req("b1", prompt, key="t")
        base.submit(b1)
        base.run_to_completion()
        assert b1.output_ids == a.output_ids
        b2 = _req("b2", resume, key="t")
        base.submit(b2)
        base.run_to_completion()
        assert b2.output_ids == a2.output_ids

    def test_sub_linger_return_cancels_demote(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, agent_linger_s=60.0)
        a = _req("A", _prompt(4), key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        eng.note_tool_gap("thread-A")
        eng.step()  # linger far in the future: nothing demotes
        assert eng.agent_gap_demotions == 0
        assert eng.prefix_cache.host_nodes == 0
        eng.note_tool_return("thread-A")  # quick tool: cancel in linger
        assert eng.agent_gap_cancelled == 1
        assert eng.agent_hint_hits == 1
        assert eng.prefix_cache.host_nodes == 0
        assert eng.awaiting_tool_keys() == []
        eng.step()
        assert eng.agent_gap_demotions == 0

    def test_resubmit_cancels_pending_gap(self, model):
        # the thread came back via a fresh submit (the return hint was
        # lost, or the client skipped it): admission must cancel the gap
        cfg, params = model
        eng = make_engine(cfg, params, agent_linger_s=60.0)
        prompt = _prompt(5)
        a = _req("A", prompt, key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        eng.note_tool_gap("thread-A")
        a2 = _req("A2", prompt + list(a.output_ids) + [3], key="thread-A")
        eng.submit(a2)
        assert "thread-A" not in eng._agent_gaps
        eng.run_to_completion()
        assert eng.agent_gap_demotions == 0

    def test_idle_engine_still_fires_linger(self, model):
        # has_work includes pending gaps: run_to_completion on an
        # otherwise-idle engine keeps stepping until the demote fires
        cfg, params = model
        eng = make_engine(cfg, params, agent_linger_s=0.05)
        a = _req("A", _prompt(6), key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        eng.note_tool_gap("thread-A")
        assert eng.has_work
        eng.run_to_completion()
        assert eng.agent_gap_demotions == 1
        assert eng.prefix_cache.host_nodes > 0

    def test_return_kicks_wake_prefetcher(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        a = _req("A", _prompt(8), key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        eng.note_tool_gap("thread-A")
        eng.step()
        assert eng.agent_gap_demotions == 1

        calls = []

        class _Pre:
            def prefetch_thread(self, key, min_depth=0):
                calls.append((key, min_depth))

            def staged_bytes_for(self, key):
                return 0

        class _Obj:
            prefetcher = _Pre()

        eng.kv_tier.object = _Obj()
        eng.note_tool_return("thread-A")
        assert calls and calls[0][0] == "thread-A"
        # host runs still hold the whole chain: min_depth covers it, so
        # the prefetcher won't issue object GETs below that depth
        assert calls[0][1] > 0

    def test_unknown_return_is_a_hint_miss(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        eng.note_tool_return("nobody")
        assert eng.agent_hint_misses == 1
        assert eng.agent_hint_hits == 0

    def test_knob_off_is_inert(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, agent_demote="")
        a = _req("A", _prompt(9), key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        eng.note_tool_gap("thread-A")
        eng.note_tool_return("thread-A")
        eng.step()
        sec = eng.agent_section()
        assert all(sec[k] == 0 for k in AGENT_METRIC_KEYS)
        assert eng.awaiting_tool_keys() == []
        assert eng.prefix_cache.host_nodes == 0

    def test_lane_table_flags_awaiting_thread(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        a = _req("A", _prompt(10), key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        eng.note_tool_gap("thread-A")
        eng.step()
        rows = [r for r in eng.lane_table() if r.get("awaiting_tool")]
        assert len(rows) == 1
        row = rows[0]
        assert row["state"] == "awaiting_tool"
        assert row["demoted_pages"] > 0
        assert not row["lingering"]

    def test_object_mode_drops_to_store_when_host_tier_refuses(
            self, model, tmp_path):
        """The ladder's first rung missing (kv_host_tier_mb=0): a durable
        archive licenses the direct-to-object drop — pages free at the
        gap, the follow-up wakes from the store, token-identical."""
        cfg, params = model
        eng = make_engine(cfg, params, num_pages=48, max_pages_per_seq=32,
                          kv_host_tier_mb=0,
                          kv_object_dir=str(tmp_path / "on"),
                          agent_demote="object")
        prompt = _prompt(3, n=160)
        a = _req("A", prompt, key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        free0 = eng.pool.free_pages
        eng.note_tool_gap("thread-A")
        eng.step()
        # host tier refused every run (budget 0) yet HBM freed anyway:
        # the chain dropped to the object rung, not to a host run
        assert eng.pool.free_pages > free0
        assert eng.agent_gap_pages_demoted > 0
        assert eng.prefix_cache._host_nodes == 0
        eng.note_tool_return("thread-A")
        assert eng.agent_hint_hits == 1
        follow = list(prompt) + list(a.output_ids) + [5, 6, 7, 8]
        time.sleep(0.1)  # prefetch staging window (sync wake also works)
        b = _req("B", follow, key="thread-A")
        eng.submit(b)
        eng.run_to_completion()
        assert b.cache_source == "object_tier"
        assert b.cached_tokens >= (len(prompt) // 8) * 8
        # token identity against a knobs-off untiered engine
        ref = make_engine(cfg, params, num_pages=48, max_pages_per_seq=32,
                          agent_demote="")
        ra = _req("A", prompt, key="thread-A")
        ref.submit(ra)
        ref.run_to_completion()
        rb = _req("B", follow, key="thread-A")
        ref.submit(rb)
        ref.run_to_completion()
        assert list(ra.output_ids) == list(a.output_ids)
        assert list(rb.output_ids) == list(b.output_ids)

    def test_object_mode_without_manifest_never_drops(self, model,
                                                      tmp_path, monkeypatch):
        """A failed archive (store write fault) must fall back to the
        never-drop rule: refused host demote + no durable manifest keeps
        the chain in HBM."""
        from kafka_tpu import failpoints as fp

        cfg, params = model
        eng = make_engine(cfg, params, num_pages=48, max_pages_per_seq=32,
                          kv_host_tier_mb=0,
                          kv_object_dir=str(tmp_path / "on"),
                          agent_demote="object")
        prompt = _prompt(4, n=160)
        a = _req("A", prompt, key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        free0 = eng.pool.free_pages
        eng.note_tool_gap("thread-A")
        with fp.armed("kv.object_put", "error"):
            eng.step()
        # archive torn -> no manifest -> refusal keeps the chain hot
        assert eng.pool.free_pages == free0
        assert eng.agent_gap_pages_demoted == 0
        follow = list(prompt) + list(a.output_ids) + [5, 6, 7, 8]
        b = _req("B", follow, key="thread-A")
        eng.submit(b)
        eng.run_to_completion()
        assert b.cached_tokens > 0  # still device-resident


# ---------------------------------------------------------------------------
# background priority class
# ---------------------------------------------------------------------------


class TestBackgroundClass:
    # both 96-token prompts must fit the pool TOGETHER (admission defers
    # on pages, not class, otherwise) and prefill must take several
    # 32-bucket chunks — one 128-bucket chunk leaves nothing to yield
    BG_ECFG = dict(num_pages=64, prefill_buckets=(8, 16, 32),
                   flight_ring=256)

    def test_bg_yields_to_interactive_and_output_identical(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, **self.BG_ECFG)
        bg_prompt = _prompt(11, 96)
        fg_prompt = _prompt(12, 96)
        bg = _req("bg", bg_prompt, background=True, max_new=6)
        fg = _req("fg", fg_prompt, max_new=6)
        eng.submit(bg)
        eng.submit(fg)
        assert eng.agent_section()["bg_queue_depth"] == 1
        eng.run_to_completion()
        assert fg.finish_reason and bg.finish_reason
        # the interactive lane's prefill never waited on the bg dump
        assert fg.first_token_time < bg.first_token_time
        assert eng.bg_admitted == 1
        assert eng.bg_yields > 0
        assert eng.bg_chunks > 0
        causes = set()
        for r in eng.flight.records():
            causes.update(r.get("causes", {}))
        assert {"bg_admit", "bg_yield", "bg_prefill"} <= causes

        # scheduling priority must not change bytes: same request run
        # FOREGROUND on a fresh engine produces identical tokens
        ref = make_engine(cfg, params, **self.BG_ECFG)
        ref_r = _req("ref", bg_prompt, max_new=6)
        ref.submit(ref_r)
        ref.run_to_completion()
        assert ref_r.output_ids == bg.output_ids

    def test_bg_admits_only_into_idle_capacity(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        fgs = [_req(f"fg{i}", _prompt(20 + i, 48), max_new=5)
               for i in range(3)]
        bg = _req("bg", _prompt(30, 48), background=True, max_new=5)
        eng.submit(bg)
        for r in fgs:
            eng.submit(r)
        eng.run_to_completion()
        assert eng.bg_admitted == 1
        assert all(r.finish_reason for r in fgs + [bg])
        # every interactive request got its first token before the
        # background dump (bg was submitted FIRST — class, not FIFO)
        assert bg.first_token_time > max(r.first_token_time for r in fgs)

    def test_bg_exempt_from_max_waiting(self, model):
        cfg, params = model
        eng = make_engine(cfg, params, max_waiting=1)
        eng.submit(_req("fg0", [1, 2, 3]))  # queue now full
        with pytest.raises(AdmissionError):
            eng.submit(_req("fg1", [1, 2, 4]))
        # background is deferred work — rejecting it with Retry-After
        # would just convert it into interactive retry pressure
        eng.submit(_req("bg", [1, 2, 7], background=True))
        eng.run_to_completion()

    def test_bg_reclaims_cold_cache_on_idle_engine(self, model):
        """A cache-saturated but otherwise idle engine must not starve
        its background queue: bg admission reclaims cold radix KV (the
        same eviction interactive admission runs) while honoring the
        park reserve."""
        cfg, params = model
        eng = make_engine(cfg, params, **self.BG_ECFG)
        # saturate the pool with cold cached KV
        for i in range(4):
            eng.submit(_req(f"w{i}", _prompt(40 + i, n=96),
                            key=f"w-t{i}", max_new=4))
            eng.run_to_completion()
        reserve = 2 * eng.ecfg.max_batch
        bg = _req("bg", _prompt(50, n=96), key="bg-t", background=True)
        needed = -(-(96 + 1) // eng.ecfg.page_size)  # no shared prefix
        assert needed > eng.pool.free_pages - reserve
        eng.submit(bg)
        eng.run_to_completion()
        assert eng.bg_admitted == 1
        assert len(bg.output_ids) == 8

    def test_cancel_waiting_background(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        bg = _req("bg", [1, 2, 3], background=True)
        eng.submit(bg)
        assert eng.cancel("bg")
        assert not eng.waiting_bg
        assert eng.agent_section()["bg_queue_depth"] == 0


# ---------------------------------------------------------------------------
# metric registry + exposition
# ---------------------------------------------------------------------------


class TestAgentMetricsRegistry:
    def test_agent_section_matches_registry_exactly(self, model):
        cfg, params = model
        eng = make_engine(cfg, params)
        assert set(eng.agent_section()) == set(AGENT_METRIC_KEYS)

    def test_new_flight_causes_registered(self):
        for cause in ("agent_demote", "bg_admit", "bg_prefill", "bg_yield"):
            assert cause in CAUSES, cause

    def test_snapshot_and_prometheus_families(self, model):
        from kafka_tpu.server.prometheus import render_prometheus

        cfg, params = model
        eng = make_engine(cfg, params)
        a = _req("A", _prompt(13), key="thread-A")
        eng.submit(a)
        eng.run_to_completion()
        eng.note_tool_gap("thread-A")
        eng.step()
        snap = eng.metrics.snapshot(eng)
        assert snap["agent"]["agent_gap_demotions"] == 1
        text = render_prometheus(snap)
        for family in ("kafka_tpu_agent_events_total",
                       "kafka_tpu_agent_gap_pages_demoted_total",
                       "kafka_tpu_agent_gap_bytes_demoted_total",
                       "kafka_tpu_agent_awaiting_threads",
                       "kafka_tpu_agent_awaiting_bytes",
                       "kafka_tpu_bg_queue_depth",
                       "kafka_tpu_bg_events_total"):
            assert f"# TYPE {family}" in text, family
        assert 'event="demote"' in text
        assert "kafka_tpu_agent_awaiting_threads 1" in text


# ---------------------------------------------------------------------------
# worker inbox routing (engine is single-writer)
# ---------------------------------------------------------------------------


class TestWorkerInbox:
    def test_gap_and_return_run_on_engine_thread(self, model):
        from kafka_tpu.llm.worker import EngineWorker

        cfg, params = model
        eng = make_engine(cfg, params)
        worker = EngineWorker(eng).start()
        try:
            async def go():
                loop = asyncio.get_running_loop()
                q = worker.submit(
                    _req("w1", _prompt(14), key="thread-A"), loop
                )
                while True:
                    ev = await asyncio.wait_for(q.get(), timeout=30)
                    if ev.finished:
                        return

            asyncio.run(go())
            worker.note_tool_gap("thread-A")
            deadline = time.monotonic() + 10
            while (eng.agent_gap_demotions < 1
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert eng.agent_gap_demotions == 1
            worker.note_tool_return("thread-A")
            deadline = time.monotonic() + 10
            while eng.agent_hint_hits < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert eng.agent_hint_hits == 1
        finally:
            worker.stop()


# ---------------------------------------------------------------------------
# DP router: expected-return hints ride thread affinity
# ---------------------------------------------------------------------------


class TestRouterHints:
    ECFG = dict(max_batch=2, page_size=8, num_pages=32, max_pages_per_seq=8,
                prefill_buckets=(8, 16, 32), kv_host_tier_mb=64,
                agent_demote="host", agent_linger_s=60.0)

    def test_hint_pinned_to_affinity_replica(self, model):
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**self.ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        p = list(np.random.RandomState(9).randint(1, 128, 10))
        dp.submit(_req("t1", p, key="thread-A", max_new=4))
        dp.run_to_completion()
        idx = dp._affinity["thread-A"]
        other = 1 - idx
        dp.note_tool_gap("thread-A")
        assert dp._expected_returns["thread-A"] == idx
        assert dp.engines[idx].agent_gaps == 1
        assert dp.engines[other].agent_gaps == 0
        dp.note_tool_return("thread-A")
        assert "thread-A" not in dp._expected_returns
        assert dp.engines[idx].agent_gap_cancelled == 1
        assert dp.engines[other].agent_gap_cancelled == 0
        # aggregate /metrics sums the per-replica agent sections
        agg = dp.metrics.snapshot()
        assert agg["agent"]["agent_gaps"] == 1
        assert agg["agent"]["agent_gap_cancelled"] == 1

    def test_unknown_thread_is_a_noop(self, model):
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**self.ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        dp.note_tool_gap("ghost")    # no affinity: nothing locatable
        dp.note_tool_return("ghost")
        assert not dp._expected_returns
        assert all(e.agent_gaps == 0 for e in dp.engines)

    def test_expected_returns_lru_capped(self, model):
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**self.ECFG),
                                 dp=2, tp=1, kv_dtype=jnp.float32)
        dp._expected_cap = 2
        for k in ("a", "b", "c"):
            dp._affinity[k] = 0
            dp.note_tool_gap(k)
        assert list(dp._expected_returns) == ["b", "c"]


# ---------------------------------------------------------------------------
# agent loop + compaction integration
# ---------------------------------------------------------------------------


class _Chunk:
    """Minimal StreamChunk stand-in for the agent loop."""

    def __init__(self, content=None, tool_calls=None, finish_reason=None):
        self.content = content
        self.tool_calls = tool_calls
        self.finish_reason = finish_reason
        self.usage = None
        self.id = "c1"

    def to_openai_dict(self):
        return {"id": self.id}


class _ScriptedLLM:
    """Two scripted turns: a tool call, then text. Records the return
    hint and every stream_completion kwarg set."""

    provider_name = "fake"
    supports_background = True

    def __init__(self):
        self.returned = []
        self.seen_kwargs = []

    def note_tool_return(self, prefix_key):
        self.returned.append(prefix_key)

    async def stream_completion(self, messages, **kw):
        self.seen_kwargs.append(kw)
        if len(self.seen_kwargs) == 1:
            yield _Chunk(tool_calls=[{
                "index": 0, "id": "call_1",
                "function": {"name": "add", "arguments": '{"a":1,"b":2}'},
            }])
            yield _Chunk(finish_reason="tool_calls")
        else:
            yield _Chunk(content="done")
            yield _Chunk(finish_reason="stop")


def _make_agent(llm, **kw):
    from kafka_tpu.agents.base import Agent
    from kafka_tpu.tools.provider import AgentToolProvider, Tool

    def add(a: int, b: int):
        return a + b

    tools = AgentToolProvider(tools=[
        Tool(name="add", description="add",
             parameters={"type": "object", "properties": {
                 "a": {"type": "integer"}, "b": {"type": "integer"}}},
             handler=add),
    ])
    return Agent(llm, tools, system_prompt="sys", **kw)


class TestAgentLoopIntegration:
    def test_return_hint_fires_after_tool_batch(self):
        llm = _ScriptedLLM()
        agent = _make_agent(llm)

        async def go():
            events = []
            async for ev in agent.run(
                [{"role": "user", "content": "hi"}], prefix_key="thread-A"
            ):
                events.append(ev)
            return events

        events = asyncio.run(go())
        assert events[-1]["type"] == "agent_done"
        # the hint fired exactly once, between the tool batch and the
        # follow-up turn, carrying the thread identity
        assert llm.returned == ["thread-A"]
        # not opted in: no turn rode the background class
        assert not any(kw.get("background") for kw in llm.seen_kwargs)

    def test_tool_result_turn_rides_background_class(self):
        llm = _ScriptedLLM()
        agent = _make_agent(llm, background_tool_turns=True)

        async def go():
            async for _ in agent.run([{"role": "user", "content": "hi"}]):
                pass

        asyncio.run(go())
        assert len(llm.seen_kwargs) == 2
        # turn 1 (the user prompt) is interactive; turn 2's prompt is
        # dominated by tool results — that one rides the bg class
        assert not llm.seen_kwargs[0].get("background")
        assert llm.seen_kwargs[1].get("background") is True

    def test_compaction_summarization_rides_background(self):
        from kafka_tpu.core.types import CompletionResponse
        from kafka_tpu.llm.base import LLMProvider
        from kafka_tpu.llm.compaction.v1 import (
            SummarizationCompactionProvider,
        )

        class _Summarizer(LLMProvider):
            provider_name = "fake"
            supports_background = True

            def __init__(self):
                self.kwargs = []

            async def stream_completion(self, messages, **kw):
                raise AssertionError("unused")
                yield  # pragma: no cover

            async def completion(self, messages, **kw):
                self.kwargs.append(kw)
                return CompletionResponse(content="SUMMARY",
                                          finish_reason="stop")

        llm = _Summarizer()
        prov = SummarizationCompactionProvider(llm, min_messages=2)
        msgs = [{"role": "user", "content": f"m{i}"} for i in range(12)]
        out = asyncio.run(prov.compact(msgs))
        assert llm.kwargs and llm.kwargs[0].get("background") is True
        assert any("SUMMARY" in str(m.get("content")) for m in out)
        # a provider without the capability never sees the kwarg
        llm2 = _Summarizer()
        llm2.supports_background = False
        prov2 = SummarizationCompactionProvider(llm2, min_messages=2)
        asyncio.run(prov2.compact(msgs))
        assert "background" not in llm2.kwargs[0]


# ---------------------------------------------------------------------------
# object-store auth: AWS SigV4 + bearer
# ---------------------------------------------------------------------------

AKID, SECRET = "AKIDEXAMPLE", "wJalrXUtnFEMI/K7MDENG+bPxRfiCYEXAMPLEKEY"


def _sigv4_env(monkeypatch, secret=SECRET, token=""):
    monkeypatch.setenv(ENV_OBJECT_AUTH, "sigv4")
    monkeypatch.setenv("AWS_ACCESS_KEY_ID", AKID)
    monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", secret)
    monkeypatch.setenv("AWS_REGION", "us-east-1")
    if token:
        monkeypatch.setenv("AWS_SESSION_TOKEN", token)
    else:
        monkeypatch.delenv("AWS_SESSION_TOKEN", raising=False)


class TestObjectAuth:
    def test_sigv4_round_trip_stub_verifies_signature(self, monkeypatch):
        _sigv4_env(monkeypatch)
        with StubS3Server() as srv:
            srv.auth_secret = (AKID, SECRET)
            st = HTTPObjectStore(srv.url)
            payload = os.urandom(2048)
            st.put("objects/x.npz", payload)
            assert st.get("objects/x.npz") == payload
            assert st.head("objects/x.npz")[0] == len(payload)
            st.put("refs/x/a", b"")
            st.put("refs/x/b", b"")
            # the listing query ('/' in the prefix) exercises query
            # canonicalization — loose encoding breaks the signature
            assert sorted(st.list("refs/x/")) == ["refs/x/a", "refs/x/b"]
            assert st.put_if_absent("objects/x.npz", payload) is False
            st.delete("objects/x.npz")
            assert st.get("objects/x.npz") is None

            hdrs = srv.captured_headers[0]
            auth = hdrs["authorization"]
            assert auth.startswith(
                f"AWS4-HMAC-SHA256 Credential={AKID}/"
            )
            assert "/us-east-1/s3/aws4_request" in auth
            assert "host;x-amz-content-sha256;x-amz-date" in auth
            assert re.fullmatch(r"\d{8}T\d{6}Z", hdrs["x-amz-date"])
            assert hdrs["x-amz-content-sha256"] == hashlib.sha256(
                payload
            ).hexdigest()

    def test_sigv4_wrong_secret_rejected(self, monkeypatch):
        _sigv4_env(monkeypatch, secret="the-wrong-secret")
        with StubS3Server() as srv:
            srv.auth_secret = (AKID, SECRET)
            st = HTTPObjectStore(srv.url)
            with pytest.raises(OSError, match="403"):
                st.put("objects/x.npz", b"payload")
            assert not srv.objects  # rejected writes never land

    def test_sigv4_session_token_is_signed(self, monkeypatch):
        _sigv4_env(monkeypatch, token="THE-SESSION-TOKEN")
        with StubS3Server() as srv:
            srv.auth_secret = (AKID, SECRET)
            st = HTTPObjectStore(srv.url)
            st.put("objects/t", b"tok")
            assert st.get("objects/t") == b"tok"
            hdrs = srv.captured_headers[0]
            assert hdrs["x-amz-security-token"] == "THE-SESSION-TOKEN"
            assert "x-amz-security-token" in hdrs["authorization"]

    def test_bearer_round_trip_and_rejection(self, monkeypatch):
        monkeypatch.setenv(ENV_OBJECT_AUTH, "bearer")
        monkeypatch.setenv(ENV_OBJECT_BEARER, "sesame")
        with StubS3Server() as srv:
            srv.bearer_token = "sesame"
            st = HTTPObjectStore(srv.url)
            st.put("objects/x", b"data")
            assert st.get("objects/x") == b"data"
            assert srv.captured_headers[0]["authorization"] == (
                "Bearer sesame"
            )
            monkeypatch.setenv(ENV_OBJECT_BEARER, "wrong")
            bad = HTTPObjectStore(srv.url)
            with pytest.raises(OSError, match="401"):
                bad.put("objects/y", b"data")

    def test_unauthed_request_rejected_when_stub_requires(self, monkeypatch):
        monkeypatch.delenv(ENV_OBJECT_AUTH, raising=False)
        with StubS3Server() as srv:
            srv.auth_secret = (AKID, SECRET)
            st = HTTPObjectStore(srv.url)
            with pytest.raises(OSError, match="403"):
                st.put("objects/x", b"data")

    def test_load_object_auth_validation(self, monkeypatch):
        monkeypatch.delenv(ENV_OBJECT_AUTH, raising=False)
        assert _load_object_auth() == ("", {})
        monkeypatch.setenv(ENV_OBJECT_AUTH, "sigv4")
        monkeypatch.delenv("AWS_ACCESS_KEY_ID", raising=False)
        monkeypatch.delenv("AWS_SECRET_ACCESS_KEY", raising=False)
        with pytest.raises(ValueError, match="AWS_ACCESS_KEY_ID"):
            _load_object_auth()
        monkeypatch.setenv(ENV_OBJECT_AUTH, "bearer")
        monkeypatch.delenv(ENV_OBJECT_BEARER, raising=False)
        with pytest.raises(ValueError, match="BEARER"):
            _load_object_auth()
        monkeypatch.setenv(ENV_OBJECT_AUTH, "kerberos")
        with pytest.raises(ValueError, match="kerberos"):
            _load_object_auth()

    def test_sigv4_headers_deterministic_with_pinned_clock(self):
        now = time.gmtime(1722816000)  # 2024-08-05T00:00:00Z
        kw = dict(method="PUT", host="bucket.example.com",
                  path="/objects/a%2Fb?list-type=2&prefix=refs/x/",
                  headers={"Content-Length": "3"}, body=b"abc",
                  access_key=AKID, secret_key=SECRET, region="eu-west-1")
        h1 = _sigv4_headers(now=now, **kw)
        h2 = _sigv4_headers(now=now, **kw)
        assert h1 == h2
        assert h1["x-amz-date"] == "20240805T000000Z"
        assert h1["Host"] == "bucket.example.com"
        assert h1["x-amz-content-sha256"] == hashlib.sha256(
            b"abc"
        ).hexdigest()
        assert "Credential=AKIDEXAMPLE/20240805/eu-west-1/s3/aws4_request" \
            in h1["Authorization"]
        sig = re.search(r"Signature=([0-9a-f]{64})$", h1["Authorization"])
        assert sig is not None
        # the signature covers the body: a different payload re-signs
        h3 = _sigv4_headers(now=now, **{**kw, "body": b"abd"})
        assert h3["Authorization"] != h1["Authorization"]


# ---------------------------------------------------------------------------
# tool-execution failpoint (agent.tool)
# ---------------------------------------------------------------------------


class TestToolFailpoint:
    def _provider(self):
        from kafka_tpu.tools.provider import AgentToolProvider
        from kafka_tpu.tools.types import Tool

        prov = AgentToolProvider()
        prov.register_tool(Tool(
            name="add",
            description="add two ints",
            parameters={"type": "object", "properties": {
                "a": {"type": "integer"}, "b": {"type": "integer"}},
                "required": ["a", "b"]},
            handler=lambda a, b: str(a + b),
        ))
        return prov

    def test_delay_injects_tool_latency(self):
        from kafka_tpu import failpoints as fp

        prov = self._provider()

        async def call():
            evs = []
            async for ev in prov.run_tool_stream("add", {"a": 1, "b": 2},
                                                 tool_call_id="c1"):
                evs.append(ev)
            return evs

        with fp.armed("agent.tool", "delay", arg=0.2):
            t0 = time.monotonic()
            evs = asyncio.run(call())
            took = time.monotonic() - t0
        assert took >= 0.2
        assert any(ev.kind != "error" for ev in evs)

    def test_error_surfaces_as_tool_error_event(self):
        from kafka_tpu import failpoints as fp

        prov = self._provider()

        async def call():
            return [ev async for ev in prov.run_tool_stream(
                "add", {"a": 1, "b": 2}, tool_call_id="c2")]

        with fp.armed("agent.tool", "error"):
            evs = asyncio.run(call())
        assert evs and evs[0].kind == "error"
        assert "injected" in evs[0].data


# ---------------------------------------------------------------------------
# bench smoke: the agent_gap A/B phase on CPU
# ---------------------------------------------------------------------------


class TestBenchSmoke:
    def test_agent_gap_phase_cpu(self, model):
        import importlib.util
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "bench", os.path.join(root, "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        sys.modules["bench"] = bench
        spec.loader.exec_module(bench)
        cfg, params = model
        out = bench.agent_gap_phase(cfg, params, n_agents=3,
                                    agent_len=448, churn_requests=6,
                                    churn_len=256, page_size=8)
        # the acceptance set (ISSUE 20): identical token streams, pages
        # measurably released mid-gap only with the knob on, the gap-on
        # follow-up strictly faster with ZERO recomputed prompt tokens
        assert out["outputs_match"]
        assert out["cache_sources_on"] == ["object_tier"] * 3
        assert out["prompt_tokens_recomputed"]["gap_on"] == 0
        assert out["prompt_tokens_recomputed"]["gap_off"] > 0
        assert out["hbm_pages_freed_mid_gap"]["gap_on"] > 0
        assert out["hbm_pages_freed_mid_gap"]["gap_off"] == 0
        on = out["followup_ttft_mean_ms"]["gap_on"]
        off = out["followup_ttft_mean_ms"]["gap_off"]
        assert on < off, out
        assert out["agent"]["agent_hint_hits"] == 3
        assert out["bg"]["admitted"] == 1
