"""End-to-end request tracing (ISSUE 3): span registry static checks,
engine span integration, cross-process stitching through a REAL sandbox
subprocess, supervisor span events, slow-request logs, structured JSON
logging, and the /debug/trace HTTP surface."""

import asyncio
import json
import logging
import os
import pathlib
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu import tracing
from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine


@pytest.fixture(autouse=True)
def _fresh_tracer(monkeypatch):
    """Every test starts with an empty ring and default config."""
    monkeypatch.delenv(tracing.ENV_SAMPLE, raising=False)
    monkeypatch.delenv(tracing.ENV_SLOW_TTFT, raising=False)
    monkeypatch.delenv(tracing.ENV_SLOW_TOTAL, raising=False)
    tracing.reset()
    yield
    tracing.reset()


# ---------------------------------------------------------------------------
# span registry static check (satellite: SITES-style schema enforcement)
# ---------------------------------------------------------------------------


class TestSpanRegistry:
    """Every span name emitted in kafka_tpu/ must appear in the documented
    SPANS registry (and vice versa); same for trace-level EVENTS — the
    trace schema cannot silently drift, mirroring failpoints.SITES."""

    SPAN_PATTERNS = (
        r"\.span\(\s*[\"']([\w.]+)[\"']",              # tracing/collector.span("x")
        r"\brecord_span\(\s*[^,]+,\s*[\"']([\w.]+)[\"']",  # engine hot path
        r"start_trace\([^)]*?name=[\"']([\w.]+)[\"']",     # root spans
    )
    EVENT_PATTERN = r"\badd_event\(\s*[^,]+,\s*[\"']([\w.]+)[\"']"

    def _scan(self, patterns):
        import kafka_tpu

        root = pathlib.Path(kafka_tpu.__file__).parent
        wired = set()
        for path in root.rglob("*.py"):
            if path.name == "tracing.py":
                continue  # the definition modules, not call sites
            text = path.read_text()
            for pat in patterns:
                wired.update(re.findall(pat, text))
        return wired

    def test_every_wired_span_is_documented(self):
        wired = self._scan(self.SPAN_PATTERNS)
        undocumented = wired - set(tracing.SPANS)
        assert not undocumented, (
            f"span names wired but missing from SPANS: {undocumented}"
        )

    def test_every_documented_span_is_wired(self):
        wired = self._scan(self.SPAN_PATTERNS)
        dead = set(tracing.SPANS) - wired
        assert not dead, f"SPANS documents unwired names: {dead}"

    def test_events_registry_both_directions(self):
        wired = self._scan((self.EVENT_PATTERN,))
        assert not wired - set(tracing.EVENTS), (
            f"event names wired but undocumented: "
            f"{wired - set(tracing.EVENTS)}"
        )
        assert not set(tracing.EVENTS) - wired, (
            f"EVENTS documents unwired names: "
            f"{set(tracing.EVENTS) - wired}"
        )

    SCOPE_PATTERN = r"jax\.named_scope\(\s*[\"'](\w+)[\"']"

    def test_device_scopes_registry_both_directions(self):
        """Every jax.named_scope("...") literal under kafka_tpu/ is in
        DEVICE_SCOPES and every registered scope is used: the device-time
        account (benchmarks/scope_reduce.py) reads these names out of the
        capture, so they cannot silently drift."""
        wired = self._scan((self.SCOPE_PATTERN,))
        assert not wired - set(tracing.DEVICE_SCOPES), (
            f"named scopes wired but missing from DEVICE_SCOPES: "
            f"{wired - set(tracing.DEVICE_SCOPES)}"
        )
        assert not set(tracing.DEVICE_SCOPES) - wired, (
            f"DEVICE_SCOPES documents unwired names: "
            f"{set(tracing.DEVICE_SCOPES) - wired}"
        )
        assert len(set(tracing.DEVICE_SCOPES)) == len(tracing.DEVICE_SCOPES)

    MARK_PATTERN = r"\bmark\(\s*[\"'](\w+)[\"']"

    def _marks(self, *rel):
        import kafka_tpu

        root = pathlib.Path(kafka_tpu.__file__).parent
        return set().union(*(
            re.findall(self.MARK_PATTERN, (root / r).read_text())
            for r in rel))

    def test_sched_phases_registry_both_directions(self):
        """Every `mark("...")` in the worker's loop, step() and the clock's
        own nap (for loops that drive step() themselves) names a phase of
        SCHED_PHASES, and every phase is
        marked somewhere: the phases tile the engine thread's time, and
        /metrics `sched`, the `kafka.sched.*` annotations and the
        benchmark's readers all go by these names."""
        wired = self._marks("llm/worker.py", "runtime/engine.py",
                            "runtime/phase_clock.py")
        assert not wired - set(tracing.SCHED_PHASES), (
            f"phases marked but missing from SCHED_PHASES: "
            f"{wired - set(tracing.SCHED_PHASES)}")
        assert not set(tracing.SCHED_PHASES) - wired, (
            f"SCHED_PHASES documents unmarked phases: "
            f"{set(tracing.SCHED_PHASES) - wired}")
        assert len(set(tracing.SCHED_PHASES)) == len(tracing.SCHED_PHASES)
        # step() alone goes through its nine, the worker's loop the rest
        assert self._marks("llm/worker.py") >= {
            "idle_wait", "hold_wait", "inbox", "paused", "deliver"}
        assert self._marks("runtime/engine.py") >= {
            "house", "drain", "admit", "prefill", "hold_check", "decode",
            "flush", "flight"}

    def test_boot_stages_registry_both_directions(self):
        wired = self._marks("server/app.py")
        assert wired == set(tracing.BOOT_STAGES), (
            wired ^ set(tracing.BOOT_STAGES))
        assert len(set(tracing.BOOT_STAGES)) == len(tracing.BOOT_STAGES)

    def test_benchmark_scopes_are_registered(self):
        """The benchmark keeps its own table of the scopes its metrics
        read (the yardstick does not move when the program adds a finer
        scope); every name in it must exist in the program."""
        import importlib.util

        path = (pathlib.Path(__file__).parent.parent / "benchmarks"
                / "scope_reduce.py")
        spec = importlib.util.spec_from_file_location("scope_reduce", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert set(mod.SCOPES) <= set(tracing.DEVICE_SCOPES)
        assert mod.SCAN_SCOPE in mod.SCOPES

    def test_readme_documents_every_span_and_event(self):
        readme = (pathlib.Path(__file__).parent.parent / "README.md"
                  ).read_text()
        missing = [n for n in (*tracing.SPANS, *tracing.EVENTS)
                   if f"`{n}`" not in readme]
        assert not missing, f"README missing span/event names: {missing}"


# ---------------------------------------------------------------------------
# tracer unit behavior
# ---------------------------------------------------------------------------


class TestTracerUnit:
    def test_trace_lifecycle_and_nesting(self):
        root = tracing.start_trace(request_id="u1", name="http.request")
        assert root is not None
        with tracing.span("agent.turn", attrs={"iteration": 1}) as turn:
            with tracing.span("tool.exec", attrs={"tool": "x"}) as tool:
                assert tool.parent_id == turn.span_id
        tracing.finish_trace(root, status=200)
        tr = tracing.get_trace("u1")
        assert tr.done
        assert [s.name for s in tr.spans] == [
            "http.request", "agent.turn", "tool.exec"]
        assert tr.spans[1].parent_id == root.span_id
        assert all(s.t1 is not None for s in tr.spans)
        assert root.attrs["status"] == 200

    def test_sampled_out_is_one_none(self):
        tracing.configure(sample=0.0)
        assert tracing.start_trace(request_id="nope") is None
        assert tracing.current() is None
        # explicit-context sites no-op on None (the engine's one branch)
        tracing.record_span(None, "engine.decode", 0.01)
        tracing.add_event(None, "preempt")
        # sample 0 is a HARD off switch: even an adopted id records
        # nothing (a proxy stamping X-Request-Id must not re-enable
        # tracing a deployment turned off)
        assert tracing.start_trace(request_id="want",
                                   trace_id="want") is None
        # between 0 and 1, an adopted id bypasses the coin flip
        tracing.configure(sample=1e-9)
        assert tracing.start_trace(request_id="named",
                                   trace_id="named") is not None

    def test_span_cap_bounds_trace_growth(self):
        tracing.configure(span_cap=3)
        root = tracing.start_trace(request_id="cap1")
        ctx = tracing.current()
        for _ in range(10):
            tracing.record_span(ctx, "engine.decode", 0.001)
        with tracing.span("agent.turn") as s:
            assert s is None  # cap reached: context spans refuse too
        assert tracing.stitch({
            "trace_id": ctx.trace_id,
            "spans": [{"name": "sandbox.exec", "span_id": "x",
                       "t0": 0.0, "t1": 1.0}],
        }) == 0
        tracing.finish_trace(root)
        tr = tracing.get_trace("cap1")
        assert len(tr.spans) == 3  # root + 2 admitted decode spans
        assert tr.dropped_spans == 10  # 8 decode + 1 span() + 1 stitched
        idx = next(t for t in tracing.recent_traces()
                   if t["request_id"] == "cap1")
        assert idx["dropped_spans"] == 10

    def test_ring_eviction_bounds_memory(self):
        tracing.configure(ring=4)
        for i in range(10):
            root = tracing.start_trace(request_id=f"r{i}")
            tracing.finish_trace(root)
        idx = tracing.recent_traces()
        assert len(idx) == 4
        assert tracing.get_trace("r0") is None
        assert tracing.get_trace("r9") is not None

    def test_chrome_export_is_perfetto_shaped(self):
        root = tracing.start_trace(request_id="c1")
        with tracing.span("agent.turn"):
            pass
        tracing.add_event(tracing.current(), "preempt", {"k": 1})
        tracing.finish_trace(root)
        data = tracing.chrome_trace("c1")
        # must round-trip as JSON (the HTTP endpoint serves it verbatim)
        data = json.loads(json.dumps(data))
        events = data["traceEvents"]
        complete = [e for e in events if e["ph"] == "X"]
        instants = [e for e in events if e["ph"] == "i"]
        metas = [e for e in events if e["ph"] == "M"]
        assert {e["name"] for e in complete} == {"http.request",
                                                "agent.turn"}
        assert all(
            set(e) >= {"name", "ts", "dur", "pid", "tid", "args"}
            for e in complete
        )
        assert instants and instants[0]["name"] == "preempt"
        assert metas  # named lanes for Perfetto
        assert data["otherData"]["request_id"] == "c1"

    def test_stitch_merges_child_spans_by_trace_id(self):
        root = tracing.start_trace(request_id="s1")
        ctx = tracing.current()
        child = tracing.ChildSpans(ctx.trace_id, ctx.span_id)
        with child.span("sandbox.exec", attrs={"tool": "shell_exec"}):
            time.sleep(0.001)
        n = tracing.stitch(child.export())
        assert n == 1
        tracing.finish_trace(root)
        tr = tracing.get_trace("s1")
        stitched = [s for s in tr.spans if s.name == "sandbox.exec"]
        assert stitched and stitched[0].parent_id == root.span_id
        assert tracing.counters()["stitched_spans"] == 1
        # unknown trace ids drop silently (ring rolled over)
        assert tracing.stitch({"trace_id": "gone", "spans": [{}]}) == 0

    def test_subprocess_env_carries_live_config(self):
        tracing.configure(sample=0.25)
        env = tracing.subprocess_env({"PATH": "/bin"})
        assert float(env[tracing.ENV_SAMPLE]) == 0.25

    def test_traceparent_shape_understood_by_server_helper(self):
        from kafka_tpu.server.app import _incoming_trace

        class Req:
            headers = {"traceparent":
                       f"00-{'a' * 32}-{'b' * 16}-01"}
        tid, parent = _incoming_trace(Req())
        assert tid == "a" * 32 and parent == "b" * 16

        class Req2:
            headers = {"X-Request-Id": "my-req"}
        assert _incoming_trace(Req2()) == ("my-req", None)


# ---------------------------------------------------------------------------
# engine integration: the span tree a served request produces
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    cfg = ModelConfig(name="trace-test", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7))
    return InferenceEngine(
        cfg, params,
        EngineConfig(max_batch=2, page_size=8, num_pages=64,
                     max_pages_per_seq=8, prefill_buckets=(8, 16, 32)),
        kv_dtype=jnp.float32,
    )


class TestEngineSpans:
    def test_request_produces_queue_prefill_decode_emit(self, engine):
        root = tracing.start_trace(request_id="e1")
        engine.submit(GenRequest(
            request_id="er1", prompt_ids=[5, 9, 23, 4], max_new_tokens=4,
            trace=tracing.current(),
        ))
        engine.run_to_completion()
        tracing.finish_trace(root)
        tr = tracing.get_trace("e1")
        names = [s.name for s in tr.spans]
        for expected in ("engine.queue", "engine.prefill",
                        "engine.decode", "emit"):
            assert expected in names, (expected, names)
        # decode spans carry burst annotations (fused-step count + batch
        # occupancy) and every engine span parents to the carried context
        decode = [s for s in tr.spans if s.name == "engine.decode"]
        assert all(s.attrs["steps"] >= 1 and s.attrs["busy"] >= 1
                   for s in decode)
        assert all(s.parent_id == root.span_id for s in tr.spans
                   if s.name.startswith("engine."))
        # the emit span is the last stage of the fetch phase (the entry's
        # pop -> first token on the host) and stamps TTFT
        emit = next(s for s in tr.spans if s.name == "emit")
        assert emit.attrs["ttft_ms"] > 0

    def test_fetch_phase_is_four_contiguous_spans(self, engine):
        """The first-fetch phase of TTFT (last prefill chunk dispatched ->
        first token on the host) on a traced request: engine.dev_wait,
        engine.dev_exec, engine.hold and emit, each starting where the one
        before ended, inside http.request, the last ending at the first
        token; the prefill span names the host's run-ahead."""
        root = tracing.start_trace(request_id="e2")
        req = GenRequest(request_id="er2", prompt_ids=[5, 9, 23, 4],
                         max_new_tokens=4, trace=tracing.current())
        engine.submit(req)
        engine.run_to_completion()
        tracing.finish_trace(root)
        tr = tracing.get_trace("e2")
        assert root.name == "http.request"
        stages = [next(s for s in tr.spans if s.name == n)
                  for n in ("engine.dev_wait", "engine.dev_exec",
                            "engine.hold", "emit")]
        for a, b in zip(stages, stages[1:]):
            assert abs(b.t0 - a.t1) < 1e-6, (a.name, b.name)
        for s in stages:
            assert s.parent_id == root.span_id
            assert root.t0 <= s.t0 <= s.t1 <= root.t1
        # together they are the phase: first dispatch -> first token
        whole = req.first_token_time - req.t_first_dispatch
        assert abs((stages[-1].t1 - stages[0].t0) - whole) < 1e-6
        assert "ttft_ms" not in stages[0].attrs
        prefill = next(s for s in tr.spans if s.name == "engine.prefill")
        assert prefill.attrs["backlog_steps"] >= 0
        # and the phase follows the prefill span (two clock reads apart)
        assert abs(stages[0].t0 - prefill.t1) < 0.05

    def test_fetch_reads_are_annotated_by_kind(self, engine, monkeypatch):
        """KAFKA_TPU_PROFILING=1: every read of the fetch pipeline sits in
        a `kafka.fetch[<kind of entry>]` annotation, so a capture shows
        when the scheduler thread was in a read; off, none is built."""
        import contextlib

        from kafka_tpu.runtime import engine as engine_mod

        names = []
        monkeypatch.setattr(
            engine_mod.jax.profiler, "TraceAnnotation",
            lambda name: names.append(name) or contextlib.nullcontext(),
        )
        engine.generate([5, 9, 23, 4], max_new_tokens=3)
        assert names == []
        tracing.configure(profiling=True)
        try:
            engine.generate([5, 9, 23, 4], max_new_tokens=3)
        finally:
            tracing.configure(profiling=False)
        fetches = [n for n in names if n.startswith("kafka.fetch[")]
        assert "kafka.fetch[prefill]" in fetches
        assert "kafka.fetch[decode]" in fetches or any(
            n.startswith("kafka.fetch[") and n != "kafka.fetch[prefill]"
            for n in fetches)

    def test_profiler_annotation_scope_keyed_by_trace_id(self, engine):
        """KAFKA_TPU_PROFILING=1: decode dispatches run inside a
        jax.profiler.TraceAnnotation scope named by the dispatched trace
        ids — the xplane/server-span correlation key.  Disabled (the
        default) the scope carries no annotation (it still books the
        device's starvation: engine._DispatchScope)."""
        req = GenRequest(request_id="prof-r", prompt_ids=[1, 2],
                         max_new_tokens=2)
        assert engine._dispatch_scope("decode", [req]).ann is None
        tracing.configure(profiling=True)
        try:
            root = tracing.start_trace(request_id="prof1")
            req.trace = tracing.current()
            scope = engine._dispatch_scope("decode", [req, None])
            assert scope.ann is not None
            with scope:
                pass  # TraceAnnotation is harmless without a live capture
            # a traced end-to-end generation still works under the flag
            engine.submit(req)
            engine.run_to_completion()
            tracing.finish_trace(root)
        finally:
            tracing.configure(profiling=False)
        tr = tracing.get_trace("prof1")
        assert any(s.name == "engine.decode" for s in tr.spans)

    @pytest.mark.parametrize("kind", ["prefill", "decode", "verify"])
    def test_dispatch_scope_names_the_kind(self, engine, kind, monkeypatch):
        """`kafka.<kind>[<trace ids>]` per dispatch kind (what
        benchmarks/trace_reduce.host_label strips to `kafka.<kind>`), and
        with profiling off one bool read and a scope without one: no
        annotation object is built, whatever the kind."""
        import contextlib

        from kafka_tpu.runtime import engine as engine_mod

        names = []
        monkeypatch.setattr(
            engine_mod.jax.profiler, "TraceAnnotation",
            lambda name: names.append(name) or contextlib.nullcontext(),
        )
        req = GenRequest(request_id="kind-r", prompt_ids=[1, 2],
                         max_new_tokens=2)
        assert engine._dispatch_scope(kind, [req]).ann is None
        assert names == []
        tracing.configure(profiling=True)
        try:
            root = tracing.start_trace(request_id="kind1")
            req.trace = tracing.current()
            engine._dispatch_scope(kind, [req, None])
            tracing.finish_trace(root)
        finally:
            tracing.configure(profiling=False)
        assert names == [f"kafka.{kind}[{req.trace.trace_id[:8]}]"]

    def test_every_dispatch_site_is_annotated(self, engine, monkeypatch):
        """A traced request that prefills and decodes passes through a
        `kafka.prefill` and a `kafka.decode` annotation."""
        import contextlib

        from kafka_tpu.runtime import engine as engine_mod

        names = []
        monkeypatch.setattr(
            engine_mod.jax.profiler, "TraceAnnotation",
            lambda name: names.append(name) or contextlib.nullcontext(),
        )
        tracing.configure(profiling=True)
        try:
            engine.submit(GenRequest(request_id="site-r",
                                     prompt_ids=[3, 1, 4, 1, 5],
                                     max_new_tokens=4))
            engine.run_to_completion()
        finally:
            tracing.configure(profiling=False)
        kinds = {n.split("[")[0] for n in names}
        assert {"kafka.prefill", "kafka.decode"} <= kinds, names

    def test_untraced_request_records_nothing(self, engine):
        before = len(tracing.recent_traces())
        engine.submit(GenRequest(
            request_id="plain", prompt_ids=[1, 2, 3], max_new_tokens=3,
        ))
        engine.run_to_completion()
        assert len(tracing.recent_traces()) == before

    def test_preempt_event_lands_on_victim_trace(self, engine):
        root = tracing.start_trace(request_id="pe1")
        req = GenRequest(request_id="victim", prompt_ids=[1, 2, 3],
                         max_new_tokens=2, trace=tracing.current())
        engine._preempt(req)  # synthetic victim: no device state needed
        engine.waiting.remove(req)  # undo _preempt's re-queue
        tracing.finish_trace(root)
        tr = tracing.get_trace("pe1")
        assert [e["name"] for e in tr.events] == ["preempt"]


class TestQuarantineEvents:
    def test_quarantine_and_migrate_punctuate_the_trace(self):
        """A quarantine mid-request appears as a span event carrying the
        replica id; a queued request migrated off the sick replica gets a
        migrate event naming both replicas (acceptance: satellite 4)."""
        from kafka_tpu.runtime.dp_router import DataParallelEngines

        cfg = ModelConfig(name="trace-dp", vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=4,
                          num_kv_heads=2, head_dim=16, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(8))
        dp = DataParallelEngines(
            cfg, params,
            EngineConfig(max_batch=1, page_size=8, num_pages=64,
                         max_pages_per_seq=8, prefill_buckets=(8, 16),
                         max_parked=0),
            dp=2, tp=1, kv_dtype=jnp.float32,
            quarantine_threshold=1, quarantine_window_s=5.0,
        )
        assert [e.replica for e in dp.engines] == [0, 1]
        # two requests pinned to one replica: one starts (batch of 1),
        # one queues behind it and will migrate on quarantine
        roots, ctxs = [], []
        for i in range(2):
            roots.append(tracing.start_trace(request_id=f"dp{i}"))
            ctxs.append(tracing.current())
            dp.submit(GenRequest(
                request_id=f"q{i}", prompt_ids=[1, 2, 3],
                max_new_tokens=20, prefix_key="thread-q",
                trace=ctxs[-1],
            ))
        victim = dp._route["q0"]
        dp.step()  # q0 starts compute
        orig = dp.engines[victim].step

        def dead_step():
            raise RuntimeError("device lost")

        dp.engines[victim].step = dead_step
        terminal = {}
        for _ in range(200):
            try:
                events = dp.step()
            except Exception:
                events = dp.recover_from_failure()
            for ev in events:
                if ev.finished:
                    terminal[ev.request_id] = ev.finish_reason
            if not dp.has_work:
                break
        dp.engines[victim].step = orig
        for r in roots:
            tracing.finish_trace(r)
        assert terminal["q0"] == "error:engine"
        t0 = tracing.get_trace("dp0")
        ev_names = {e["name"] for e in t0.events}
        assert "quarantine" in ev_names
        q_ev = next(e for e in t0.events if e["name"] == "quarantine")
        assert q_ev["attrs"]["replica"] == victim
        assert "engine.recover" in ev_names
        # the queued request migrated (and finished on the survivor)
        t1 = tracing.get_trace("dp1")
        mig = [e for e in t1.events if e["name"] == "migrate"]
        assert mig and mig[0]["attrs"]["from_replica"] == victim
        assert terminal["q1"] == "length"


# ---------------------------------------------------------------------------
# cross-process propagation through a REAL sandbox subprocess
# ---------------------------------------------------------------------------


class TestCrossProcessStitching:
    def test_sandbox_child_spans_stitch_under_tool_exec(self):
        """Acceptance: a traced tool call executing in a real sandbox
        subprocess yields ONE stitched trace whose sandbox.exec span was
        recorded on the far side of the PID boundary (its pid differs)
        and parents under the client-side tool.exec span."""
        from kafka_tpu.sandbox.process import ProcessSandboxFactory
        from kafka_tpu.tools.provider import AgentToolProvider
        from kafka_tpu.sandbox.tools import shell_tools

        async def go():
            factory = ProcessSandboxFactory(boot_timeout_s=30,
                                            supervise=False)
            try:
                sbx = await factory.create("t-trace")
                provider = AgentToolProvider(
                    tools=[t.bind(sbx) for t in shell_tools()]
                )
                root = tracing.start_trace(request_id="xp1")
                events = []
                async for ev in provider.run_tool_stream(
                    "shell_exec", {"command": "echo traced"}, "call-1"
                ):
                    events.append(ev)
                tracing.finish_trace(root)
                assert any(
                    ev.kind == "result" and "traced" in (ev.data or "")
                    for ev in events
                )
                await sbx.aclose()
            finally:
                await factory.aclose()

        asyncio.run(go())
        tr = tracing.get_trace("xp1")
        tool = next(s for s in tr.spans if s.name == "tool.exec")
        child = next(s for s in tr.spans if s.name == "sandbox.exec")
        # recorded inside the subprocess: a DIFFERENT pid, stitched by
        # trace id, parented under the client-side tool.exec span
        assert child.pid != 0 and child.pid != os.getpid()
        assert tool.pid == os.getpid()
        assert child.parent_id == tool.span_id
        assert child.attrs["tool"] == "shell_exec"
        assert child.t1 is not None and child.t1 >= child.t0
        # the spans frame never leaked into tool output (asserted above:
        # only delta/result events were yielded)
        # and the chrome export shows both processes
        data = tracing.chrome_trace("xp1")
        pids = {e["pid"] for e in data["traceEvents"] if e["ph"] == "X"}
        assert len(pids) == 2


# ---------------------------------------------------------------------------
# slow-request log + counter (satellite)
# ---------------------------------------------------------------------------


class TestSlowRequests:
    def test_slow_total_threshold_logs_breakdown_and_counts(self, caplog):
        tracing.configure(slow_total_ms=0.001)
        root = tracing.start_trace(request_id="slow1")
        with tracing.span("agent.turn"):
            time.sleep(0.005)
        with caplog.at_level(logging.WARNING, logger="kafka_tpu.tracing"):
            tracing.finish_trace(root)
        assert tracing.slow_count() == 1
        rec = next(r for r in caplog.records
                   if getattr(r, "slow_request", False))
        assert rec.trace_id == tracing.get_trace("slow1").trace_id
        assert rec.total_ms > 0
        names = [s["name"] for s in rec.spans]
        assert names == ["http.request", "agent.turn"]

    def test_fast_request_does_not_count(self):
        tracing.configure(slow_total_ms=60_000)
        root = tracing.start_trace(request_id="fast1")
        tracing.finish_trace(root)
        assert tracing.slow_count() == 0

    def test_ttft_threshold_uses_emit_span(self):
        # the END of `emit` is the first token on the host, wherever the
        # span starts (since PR 35 at the fetch entry's pop)
        tracing.configure(slow_ttft_ms=0.001)
        root = tracing.start_trace(request_id="ttft1")
        ctx = tracing.current()
        time.sleep(0.004)
        tracing.record_span(ctx, "emit", 0.002)  # first token late
        tracing.finish_trace(root)
        assert tracing.slow_count() == 1


# ---------------------------------------------------------------------------
# structured JSON logging
# ---------------------------------------------------------------------------


class TestJsonLogging:
    def test_json_lines_carry_trace_and_thread_ids(self):
        from kafka_tpu.logs import JsonFormatter

        root = tracing.start_trace(request_id="log1")
        record = logging.LogRecord(
            "kafka_tpu.test", logging.INFO, __file__, 1,
            "hello %s", ("world",), None,
        )
        line = JsonFormatter().format(record)
        tracing.finish_trace(root)
        payload = json.loads(line)
        assert payload["msg"] == "hello world"
        assert payload["trace_id"] == tracing.get_trace("log1").trace_id
        assert payload["span_id"]
        assert isinstance(payload["thread_id"], int)
        assert payload["pid"] == os.getpid()

    def test_extra_fields_ride_along_and_win(self):
        from kafka_tpu.logs import JsonFormatter

        record = logging.LogRecord(
            "kafka_tpu.test", logging.WARNING, __file__, 1, "slow", (),
            None,
        )
        record.trace_id = "explicit-id"
        record.spans = [{"name": "emit", "dur_ms": 3}]
        payload = json.loads(JsonFormatter().format(record))
        assert payload["trace_id"] == "explicit-id"
        assert payload["spans"][0]["name"] == "emit"

    def test_setup_logging_is_idempotent(self):
        from kafka_tpu.logs import JsonFormatter, setup_logging

        root = logging.getLogger()
        before = list(root.handlers)
        try:
            setup_logging("json")
            setup_logging("json")
            assert len(root.handlers) == max(1, len(before))
            assert all(isinstance(h.formatter, JsonFormatter)
                       for h in root.handlers)
        finally:
            setup_logging("text")


# ---------------------------------------------------------------------------
# HTTP surface: middleware + /debug/trace endpoints
# ---------------------------------------------------------------------------


class TestTraceHTTP:
    def test_request_id_adoption_and_debug_endpoints(self, tmp_path):
        from tests.test_server import make_client, text_turn

        built, _, _ = make_client(tmp_path, [text_turn("hello")])

        async def go():
            client = await built
            try:
                r = await client.post(
                    "/v1/chat/completions",
                    json={"model": "fake-model",
                          "messages": [{"role": "user", "content": "hi"}]},
                    headers={"X-Request-Id": "req-abc"},
                )
                assert r.status == 200
                assert r.headers.get("X-Request-Id") == "req-abc"

                idx = await (await client.get("/debug/traces")).json()
                assert any(t["request_id"] == "req-abc"
                           for t in idx["traces"])

                d = await client.get("/debug/trace/req-abc")
                assert d.status == 200
                data = await d.json()
                names = {e["name"] for e in data["traceEvents"]
                         if e["ph"] == "X"}
                assert {"http.request", "agent.turn"} <= names
                root = next(e for e in data["traceEvents"]
                            if e["ph"] == "X"
                            and e["name"] == "http.request")
                assert root["args"]["status"] == 200

                missing = await client.get("/debug/trace/ghost")
                assert missing.status == 404
            finally:
                await client.close()

        asyncio.run(go())

    def test_threads_agent_path_with_sandboxed_tool_one_stitched_trace(
        self, tmp_path
    ):
        """Acceptance: one traced request through the threads agent path
        whose tool call executes in a REAL sandbox subprocess yields one
        Perfetto-loadable trace from /debug/trace/{request_id} holding
        http.request, agent.turn, tool.exec AND the sandbox.exec child
        recorded on the far side of the PID boundary (engine spans are
        covered by TestEngineSpans against a real engine)."""
        from aiohttp.test_utils import TestClient, TestServer
        from kafka_tpu.db import LocalDBClient
        from kafka_tpu.sandbox.process import ProcessSandboxFactory
        from kafka_tpu.sandbox.tools import shell_tools
        from kafka_tpu.server import ServingConfig, create_app
        from tests.test_server import FakeLLM, text_turn, tool_turn

        llm = FakeLLM([
            tool_turn("shell_exec", {"command": "echo from-sandbox"}),
            text_turn("done", cid="chatcmpl-tr2"),
        ])

        async def go():
            factory = ProcessSandboxFactory(boot_timeout_s=30,
                                            supervise=False)
            sbx = await factory.create("t-accept")
            app = await create_app(
                cfg=ServingConfig(db_path=str(tmp_path / "tr.db")),
                llm_provider=llm,
                db=LocalDBClient(str(tmp_path / "tr.db")),
                tools=[t.bind(sbx) for t in shell_tools()],
            )
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                r = await client.post(
                    "/v1/threads/t-accept/chat/completions",
                    json={"model": "fake-model", "stream": True,
                          "messages": [{"role": "user",
                                        "content": "run it"}]},
                    headers={"X-Request-Id": "accept-1"},
                )
                assert r.status == 200
                body = await r.text()
                assert "from-sandbox" in body
                d = await client.get("/debug/trace/accept-1")
                assert d.status == 200
                return await d.json()
            finally:
                await client.close()
                await sbx.aclose()
                await factory.aclose()

        data = asyncio.run(go())
        spans = [e for e in data["traceEvents"] if e["ph"] == "X"]
        names = {e["name"] for e in spans}
        assert {"http.request", "agent.turn", "tool.exec",
                "sandbox.exec"} <= names
        child = next(e for e in spans if e["name"] == "sandbox.exec")
        tool = next(e for e in spans if e["name"] == "tool.exec")
        assert child["pid"] != os.getpid()  # recorded inside the sandbox
        assert child["args"]["parent_id"] == tool["args"]["span_id"]

    def test_sampled_out_requests_leave_no_trace(self, tmp_path):
        from tests.test_server import make_client, text_turn

        # build through make_client then dial sampling to 0 post-boot
        built, _, _ = make_client(tmp_path, [text_turn("ok")])

        async def go():
            client = await built
            try:
                tracing.configure(sample=0.0)
                r = await client.post(
                    "/v1/chat/completions",
                    json={"model": "fake-model",
                          "messages": [{"role": "user", "content": "hi"}]},
                )
                assert r.status == 200
                assert "X-Request-Id" not in r.headers
                idx = await (await client.get("/debug/traces")).json()
                assert idx["traces"] == []
            finally:
                await client.close()

        asyncio.run(go())
