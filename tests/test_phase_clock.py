"""The engine thread's time, tiled by phase (ISSUE 52): the clock itself under
a fake time source, the marks in the worker's loop and step(), the profiler
annotations, the iteration classes, the starvation rule, the older counters
held to the account, and the sections on /metrics and /debug/profile."""

import asyncio
import json
import time

import pytest

import jax
import jax.numpy as jnp

from kafka_tpu import tracing
from kafka_tpu.llm import TPULLMProvider
from kafka_tpu.llm.worker import EngineWorker
from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.models.tokenizer import ByteTokenizer
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime import compile_log
from kafka_tpu.runtime import metrics as M
from kafka_tpu.runtime.engine import _DispatchScope, _Fetch
from kafka_tpu.runtime.phase_clock import SchedClock
from kafka_tpu.server.prometheus import render_prometheus
from kafka_tpu.tracing import (
    BOOT_STAGES,
    SCHED_ITER_CLASSES,
    SCHED_PHASES,
    PhaseClock,
)


class FakeTime:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def step(self, dt):
        self.t += dt
        return self.t


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="clock-test", vocab_size=262, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(11))


def make_engine(model, **kw):
    cfg, params = model
    ecfg = dict(max_batch=4, page_size=8, num_pages=96, max_pages_per_seq=16,
                prefill_buckets=(8, 16, 32), multi_step=4)
    return InferenceEngine(cfg, params, EngineConfig(**dict(ecfg, **kw)),
                           kv_dtype=jnp.float32)


def submit(eng, n, new=12, start=0):
    reqs = [GenRequest(request_id=f"c{start + i}",
                       prompt_ids=[5 + start + i, 9, 23, 4, 7][: 3 + i % 3],
                       max_new_tokens=new) for i in range(n)]
    for r in reqs:
        eng.submit(r)
    return reqs


def phase_sum(section):
    return sum(section[p + "_s"] for p in SCHED_PHASES)


# ---------------------------------------------------------------------------
# the clock under a fake time source
# ---------------------------------------------------------------------------


class TestClockTiles:
    SCRIPT = [("idle_wait", 1.0), ("inbox", 0.001), ("house", 0.0002),
              ("drain", 0.0031), ("admit", 0.007), ("prefill", 0.0005),
              ("hold_check", 0.0001), ("decode", 0.0042), ("drain", 0.0),
              ("house", 0.00007), ("flight", 0.00011), ("inbox", 0.00001),
              ("deliver", 0.0013), ("inbox", 0.0), ("hold_wait", 0.0015),
              ("paused", 0.25), ("flush", 0.04)]

    @pytest.mark.parametrize("upto", range(1, len(SCRIPT) + 1))
    def test_sum_of_phases_is_elapsed_at_every_mark(self, upto):
        """Every instant belongs to exactly one phase: after any number of
        marks, and in between, the phases add up to the time since the
        clock was made, exactly (the fake clock steps in binary
        fractions' neighbours, so equality is held to 1e-12)."""
        now = FakeTime()
        clock = SchedClock(now=now)
        t0 = now.t
        want = {p: 0.0 for p in SCHED_PHASES}
        open_phase = "inbox"
        for phase, dt in self.SCRIPT[:upto]:
            now.step(dt)
            want[open_phase] += dt
            assert clock.mark(phase) == now.t
            open_phase = phase
            assert sum(clock.read()) == pytest.approx(now.t - t0, abs=1e-12)
        now.step(0.125)  # the open phase counts up to the read
        want[open_phase] += 0.125
        got = dict(zip(SCHED_PHASES, clock.read()))
        assert got == pytest.approx(want, abs=1e-12)
        assert sum(got.values()) == pytest.approx(now.t - t0, abs=1e-12)

    def test_stop_closes_the_account(self):
        now = FakeTime()
        clock = PhaseClock(BOOT_STAGES, "kafka.boot.", "rest", now=now)
        now.step(2.0)
        clock.mark("weights")
        now.step(3.0)
        clock.stop()
        now.step(50.0)  # after the boot: nobody's time
        assert clock.section() == {
            "import_s": 0.0, "weights_s": 3.0, "engine_build_s": 0.0,
            "grammar_s": 0.0, "warmup_s": 0.0, "rest_s": 2.0}

    def test_an_unregistered_phase_is_refused(self):
        with pytest.raises(KeyError):
            SchedClock().mark("lunch")

    def test_wait_over_counts_only_what_ran_past_the_timeout(self):
        clock = SchedClock(now=FakeTime())
        clock.wait_over(0.0021 - 0.0015)
        clock.wait_over(-0.0004)  # woken early by a submit: nothing lost
        assert clock.section()["wait_over_s"] == pytest.approx(0.0006)

    def test_a_reader_on_another_thread_sees_whole_marks(self):
        """The owner marks as fast as it can; every read in between adds
        up to the clock's age (the sequence counter refuses a copy taken
        mid-mark)."""
        import threading

        clock = SchedClock()
        t_made = time.monotonic()
        stop = threading.Event()

        def owner():
            i = 0
            while not stop.is_set():
                clock.mark(SCHED_PHASES[i % len(SCHED_PHASES)])
                i += 1

        th = threading.Thread(target=owner)
        th.start()
        try:
            for _ in range(200):
                before = time.monotonic()
                total = sum(clock.read())
                after = time.monotonic()
                assert before - t_made - 1e-4 <= total <= after - t_made + 1e-4
        finally:
            stop.set()
            th.join()


# ---------------------------------------------------------------------------
# the iteration classes
# ---------------------------------------------------------------------------


class TestIterationClasses:
    @pytest.mark.parametrize("did,want", [
        (("decode", "admit", "prefill"), "admit"),
        (("prefill", "decode"), "prefill"),
        (("decode", "multi"), "multi"),
        (("decode",), "decode"),
        ((), "held"),
    ])
    def test_class_is_the_first_in_order_of_what_was_dispatched(
            self, did, want):
        now = FakeTime()
        clock = SchedClock(now=now)
        clock.begin_iteration(clock.mark("inbox"))
        for cls in did:
            clock.did(cls)
        now.step(0.004)
        clock.end_iteration(clock.mark("inbox"))
        counts = {c: clock.iter_hists[c].count for c in SCHED_ITER_CLASSES}
        assert counts == {c: int(c == want) for c in SCHED_ITER_CLASSES}
        assert clock.iter_hists[want].sum == pytest.approx(4.0)
        # the next iteration starts clean, timed from the last one's end
        now.step(0.001)
        clock.end_iteration(clock.mark("inbox"))
        assert clock.iter_hists["held"].count == 1 + int(want == "held")

    def test_a_worker_run_files_every_iteration_under_what_it_did(
            self, model):
        """Scripted through a live worker: four requests at multi_step 4
        admit, prefill, fuse and finish; every class the run went through
        has samples, and their number is the steps the loop made."""
        eng = make_engine(model)
        w = EngineWorker(eng).start()
        try:
            async def go():
                loop = asyncio.get_running_loop()
                qs = [w.submit(r, loop) for r in [GenRequest(
                    request_id=f"it{i}", prompt_ids=[5 + i, 9, 23, 4],
                    max_new_tokens=24) for i in range(4)]]
                for q in qs:
                    while not (await q.get()).finished:
                        pass
            asyncio.run(go())
        finally:
            w.stop()
        snap = eng.metrics.snapshot(eng, reset_peak=False)
        n = {c: sum(snap["histograms"][f"sched_iter_{c}_ms"]["counts"])
             for c in SCHED_ITER_CLASSES}
        assert n["admit"] >= 1 and n["multi"] >= 1, n
        assert set(snap["sched_iter_ms"]) == set(SCHED_ITER_CLASSES)
        assert snap["sched"]["delivered"] >= 4 * 24
        assert snap["sched"]["deliver_s"] > 0.0


# ---------------------------------------------------------------------------
# the starvation rule, on scripted stamps
# ---------------------------------------------------------------------------


class _Arr:
    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready


def entry(t0, ready=False):
    return _Fetch(arr=_Arr(ready), items=[], final=[[]], t0=t0)


class TestStarvationRule:
    def setup_engine(self, model):
        eng = make_engine(model)
        now = FakeTime()
        eng.sched = SchedClock(now=now)
        return eng, now

    def dispatch(self, eng, now, took=0.0):
        scope = _DispatchScope(eng, None)
        scope.t_call = now.t
        now.step(took)
        return scope

    def queue(self, eng, now, ready=False):
        """One program dispatched and its fetch entry queued."""
        self.dispatch(eng, now).__exit__(None, None, None)
        e = entry(t0=now.t, ready=ready)
        eng._push_entry(e)
        return e

    def test_gap_from_the_emptying_stamp_to_the_next_dispatch(
            self, model, monkeypatch):
        """The queue's only entry is seen running at t=100.2 and done at
        t1=100.5; the thread then spends 2 ms in `house`, 5 ms in `admit`
        and 1 ms of `prefill` before the next dispatch call begins at t2,
        which returns 3 ms later: dev_starved_s += t2 - t1 = 8 ms, charged
        2 + 5 + 1 by phase; the upper bound runs from the last look that
        saw the program running to the call's return."""
        eng, now = self.setup_engine(model)
        monkeypatch.setattr("kafka_tpu.runtime.engine.time.monotonic", now)
        clock = eng.sched
        clock.mark("drain")
        e = self.queue(eng, now)
        now.step(0.2)
        eng._stamp_ready()                      # still running
        assert eng._seen_running[0] == now.t and eng._starve is None
        now.step(0.3)
        e.arr.ready = True
        eng._stamp_ready()                      # t1: the queue is empty
        t1 = now.t
        assert eng._starve is not None
        clock.mark("house")
        now.step(0.002)
        clock.mark("admit")
        now.step(0.005)
        clock.mark("prefill")
        now.step(0.001)
        t2 = now.t
        scope = self.dispatch(eng, now, took=0.003)
        scope.__exit__(None, None, None)
        s = clock.section()
        assert s["dev_starved_gaps"] == 1
        assert s["dev_starved_s"] == pytest.approx(t2 - t1)
        assert s["dev_starved_hi_s"] == pytest.approx(0.3 + 0.008 + 0.003)
        by_phase = {p: s[f"starved_{p}_s"] for p in SCHED_PHASES
                    if s[f"starved_{p}_s"]}
        assert by_phase == pytest.approx(
            {"house": 0.002, "admit": 0.005, "prefill": 0.001})
        assert sum(by_phase.values()) == pytest.approx(s["dev_starved_s"])
        # the upper bound's interval also holds the 0.3 s of `drain` in
        # which the completion went unseen, and the call itself
        hi_by_phase = {p: s[f"starved_hi_{p}_s"] for p in SCHED_PHASES
                       if s[f"starved_hi_{p}_s"]}
        assert hi_by_phase == pytest.approx(
            {"drain": 0.3, "house": 0.002, "admit": 0.005, "prefill": 0.004})
        assert sum(hi_by_phase.values()) == pytest.approx(
            s["dev_starved_hi_s"])
        assert eng._starve is None              # booked once

    @pytest.mark.parametrize("case", ["unstamped_pending", "unqueued_chunk",
                                      "no_stamp", "idle_engine",
                                      "dispatch_between"])
    def test_nothing_is_added_without_an_emptied_queue(
            self, model, monkeypatch, case):
        eng, now = self.setup_engine(model)
        monkeypatch.setattr("kafka_tpu.runtime.engine.time.monotonic", now)
        if case == "unstamped_pending":
            # the first of two programs finished, the second still runs
            first, second = self.queue(eng, now, True), self.queue(eng, now)
            eng._stamp_ready()
            assert first.t_ready is not None and second.t_ready is None
        elif case == "unqueued_chunk":
            # the program behind the finished one queued no fetch entry (a
            # prefill chunk that is not its prompt's last): still running
            first = self.queue(eng, now, True)
            self.dispatch(eng, now).__exit__(None, None, None)
            eng._stamp_ready()
            assert first.t_ready is not None
        elif case == "no_stamp":
            pass  # nothing was ever queued
        elif case == "idle_engine":
            # the queue emptied, then the last lane finished: step() drops
            # the stamp, time without work is not starvation
            self.queue(eng, now, True)
            eng._stamp_ready()
            eng._pending.clear()
            assert eng._starve is not None
            eng.step()
        else:
            # a dispatch since the stamp already ended that gap
            self.queue(eng, now, True)
            eng._stamp_ready()
            self.dispatch(eng, now).__exit__(None, None, None)
            booked = eng.sched.section()["dev_starved_gaps"]
            assert booked == 1
        assert eng._starve is None
        before = eng.sched.section()
        now.step(0.05)
        self.dispatch(eng, now, took=0.001).__exit__(None, None, None)
        after = eng.sched.section()
        for key in ("dev_starved_s", "dev_starved_hi_s", "dev_starved_gaps"):
            assert after[key] == before[key], key

    def test_a_failed_dispatch_books_nothing(self, model):
        eng, now = self.setup_engine(model)
        eng._starve = eng.sched.emptied(
            now.t, eng.sched.seen_running(now.t))
        with pytest.raises(RuntimeError):
            with _DispatchScope(eng, None):
                raise RuntimeError("the program died")
        assert eng._starve is None
        assert eng.sched.section()["dev_starved_gaps"] == 0

    def test_a_live_run_charges_every_starved_second_to_a_phase(self, model):
        eng = make_engine(model)
        submit(eng, 3, new=16)
        eng.run_to_completion()
        s = eng.sched.section()
        for hi in ("", "hi_"):
            starved = sum(s[f"starved_{hi}{p}_s"] for p in SCHED_PHASES)
            assert starved == pytest.approx(
                s[f"dev_starved_{hi}s"], abs=1e-4)
        assert s["dev_starved_hi_s"] >= s["dev_starved_s"]


# ---------------------------------------------------------------------------
# the marks in the worker's loop and step(), and their annotations
# ---------------------------------------------------------------------------


class TestMarksAndAnnotations:
    def test_profiling_off_builds_no_annotation(self, model, monkeypatch):
        """KAFKA_TPU_PROFILING unset: a worker-driven run of prefill,
        fused decode and delivery constructs no TraceAnnotation at all."""
        def boom(name):
            raise AssertionError(f"TraceAnnotation({name!r}) built")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
        assert not tracing.profiler_annotations_enabled()
        eng = make_engine(model)
        w = EngineWorker(eng).start()
        try:
            async def go():
                loop = asyncio.get_running_loop()
                q = w.submit(GenRequest(request_id="off", prompt_ids=[5, 9],
                                        max_new_tokens=6), loop)
                reason = None
                while reason is None:
                    ev = await q.get()
                    reason = ev.finish_reason if ev.finished else None
                return reason
            assert asyncio.run(go()) == "length"
        finally:
            w.stop()
        assert eng.sched.section()["decode_s"] > 0.0

    def test_profiling_on_emits_every_phase_it_went_through(
            self, model, monkeypatch):
        """Every phase that gained time was opened as
        `kafka.sched.<phase>`, each closed before the next opened."""
        opened, live = [], []

        class Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                if self.name.startswith("kafka.sched."):
                    assert not [a for a in live
                                if a.startswith("kafka.sched.")], live
                    opened.append(self.name[len("kafka.sched."):])
                live.append(self.name)

            def __exit__(self, *exc):
                live.remove(self.name)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
        eng = make_engine(model)
        tracing.configure(profiling=True)
        try:
            submit(eng, 4, new=20)
            eng.run_to_completion()
        finally:
            tracing.configure(profiling=False)
        eng.sched.mark("inbox")  # profiling off again: closes the last one
        assert not live
        went = {p for p in SCHED_PHASES if eng.sched.section()[p + "_s"] > 0}
        assert went <= set(opened) | {"inbox"}
        assert {"house", "drain", "admit", "prefill", "hold_check",
                "decode"} <= set(opened)
        assert set(opened) <= set(SCHED_PHASES)

    def test_hold_wait_is_inside_decode_hold_s(self, model):
        """engine.decode_hold_s runs from a held iteration to the next
        look at the backlog, the clock's hold_wait only while the loop
        waits in between: decode_hold_s >= hold_wait_s.  And a read that
        blocks is inside a drain: fetch_blocked_s <= drain_s + flush_s."""
        from test_decode_hold import Gate

        eng = make_engine(model)
        gate = Gate(eng)
        submit(eng, 4, new=40)
        i = 0
        while eng.has_work:
            gate.open = i % 5 == 0
            eng.step()
            if eng.decode_held:
                eng.sched.nap(0.0005)
            i += 1
            assert i < 20000
        s = eng.sched.section()
        assert eng.decode_holds > 0 and s["hold_wait_s"] > 0.0
        assert eng.decode_hold_s >= s["hold_wait_s"]
        assert eng.fetch_blocked_s <= s["drain_s"] + s["flush_s"] + 1e-6


# ---------------------------------------------------------------------------
# /metrics, the dp aggregate, the text
# ---------------------------------------------------------------------------


class TestSections:
    def test_sched_section_is_the_registered_key_set(self, model):
        eng = make_engine(model)
        submit(eng, 2)
        eng.run_to_completion()
        snap = eng.metrics.snapshot(eng, reset_peak=False)
        assert set(snap["sched"]) == set(M.SCHED_METRIC_KEYS)
        assert phase_sum(snap["sched"]) > 0.0
        text = render_prometheus(snap)
        assert 'kafka_tpu_sched_phase_seconds_total{phase="decode"}' in text
        assert ('kafka_tpu_sched_iteration_milliseconds_bucket{did="admit"'
                in text)
        assert ('kafka_tpu_sched_starved_seconds_total{bound="upper",'
                'phase="drain"}') in text

    def test_replicas_one_thread_steps_report_its_clock_once(self, model):
        """dp=2 behind one worker: both replicas carry the worker's clock,
        the aggregate's `sched` is that clock's, not twice it."""
        import types

        from kafka_tpu.runtime.dp_router import _AggregateMetrics

        a, b = make_engine(model), make_engine(model)
        clock = SchedClock()
        a.sched = b.sched = clock
        submit(a, 1)
        a.run_to_completion()
        router = types.SimpleNamespace(
            engines=[a, b], _prefill_pool=[], _decode_pool=[],
            health=[], supervisor=M.ReplicaSupervisorMetrics())
        agg = _AggregateMetrics(router).snapshot(reset_peak=False)
        assert agg["sched"]["threads"] == 1
        assert "sched" in agg["replicas"][0]
        assert "sched" not in agg["replicas"][1]
        n = sum(agg["histograms"]["sched_iter_held_ms"]["counts"])
        assert n == clock.iter_hists["held"].count
        # a thread a replica: the aggregate sums them
        b.sched = SchedClock()
        agg = _AggregateMetrics(router).snapshot(reset_peak=False)
        assert agg["sched"]["threads"] == 2
        assert phase_sum(agg["sched"]) == pytest.approx(
            phase_sum(agg["replicas"][0]["sched"])
            + phase_sum(agg["replicas"][1]["sched"]), abs=1e-4)

    def test_the_router_hands_the_workers_clock_to_every_replica(self):
        from kafka_tpu.runtime.dp_router import DataParallelEngines

        router = DataParallelEngines.__new__(DataParallelEngines)
        router._sched = None
        e0, e1 = (type("E", (), {"sched": SchedClock()})() for _ in range(2))
        router.engines = [e0, e1]
        assert router.sched is e0.sched  # before a worker drives it
        clock = SchedClock()
        router.sched = clock
        assert e0.sched is clock and e1.sched is clock
        assert router.sched is clock

    def test_app_serves_boot_metrics_and_the_capture_brackets(
            self, model, tmp_path, monkeypatch):
        from aiohttp.test_utils import TestClient, TestServer
        from kafka_tpu.db.local import LocalDBClient
        from kafka_tpu.server import app as app_mod
        from kafka_tpu.server.config import ServingConfig

        monkeypatch.setenv("KAFKA_TPU_PROFILING", "1")
        monkeypatch.setattr(app_mod, "_PROFILE_DIR", str(tmp_path / "trace"))
        eng = make_engine(model)
        provider = TPULLMProvider(eng, ByteTokenizer(), model_name="m")

        async def go():
            app = await app_mod.create_app(
                cfg=ServingConfig(db_path=str(tmp_path / "c.db")),
                llm_provider=provider,
                db=LocalDBClient(str(tmp_path / "c.db")), tools=[])
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                first = await (await client.get("/metrics")).json()
                again = await (await client.get("/metrics")).json()
                text = await (await client.get(
                    "/metrics?format=prometheus")).text()
                r = await client.post("/debug/profile",
                                      json={"seconds": 0.2})
                assert r.status == 200
                assert "sched_window" not in again  # no capture yet
                after = await (await client.get("/metrics")).json()
                return first, again, text, await r.json(), after
            finally:
                await client.close()
                provider.worker.stop()

        first, again, text, profile, after = asyncio.run(go())
        # (the stages, and what the program store spent loading programs)
        assert set(first["boot"]) == {s + "_s" for s in BOOT_STAGES} | {
            "store_load_s"}
        assert first["boot"] == again["boot"]  # closed when the app was built
        assert first["boot"]["rest_s"] > 0.0
        assert first["metrics"] == {"snapshot_s": 0.0, "snapshots": 0}
        assert again["metrics"]["snapshots"] == 1
        assert again["metrics"]["snapshot_s"] > 0.0
        assert first["sched"]["idle_wait_s"] >= 0.0
        assert 'kafka_tpu_boot_stage_seconds{stage="rest"}' in text
        sw = profile["sched_window"]
        assert list(sw) == ["at_start", "at_stop_call", "at_stop_return"]
        # /metrics carries the newest capture's edges too: a client that
        # stopped waiting for the reply finds them there
        assert after["sched_window"] == sw
        assert sw["at_start"]["t"] <= sw["at_stop_call"]["t"] \
            <= sw["at_stop_return"]["t"]
        fw = profile["flight_window"]
        # (the flight window's stamps are rounded to 0.1 ms)
        assert fw["t_trace_on"] <= sw["at_start"]["t"] + 1e-3
        assert sw["at_stop_call"]["t"] <= fw["t_trace_off"] + 1e-3
        for a, b in (("at_start", "at_stop_call"),
                     ("at_stop_call", "at_stop_return")):
            # the account over a bracket is the bracket's seconds
            gained = phase_sum(sw[b]["sched"]) - phase_sum(sw[a]["sched"])
            assert gained == pytest.approx(sw[b]["t"] - sw[a]["t"], abs=0.02)


class TestCompileStages:
    def test_trace_and_lower_seconds_are_summed_by_phase(self):
        compile_log.reset_for_tests()
        try:
            obs = compile_log.init(8)
            trace = "/jax/core/compile/jaxpr_trace_duration"
            lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
            compile_log._on_duration_event(trace, 0.25)
            compile_log.set_phase("warmup")
            compile_log._on_duration_event(trace, 0.5)
            compile_log._on_duration_event(lower, 1.5)
            compile_log._on_duration_event("/jax/other", 9.0)
            sec = obs.metrics_section()
            assert sec["trace_seconds_total"] == 0.75
            assert sec["lower_seconds_total"] == 1.5
            assert sec["trace_seconds_by_phase"]["boot"] == 0.25
            assert sec["trace_seconds_by_phase"]["warmup"] == 0.5
            assert sec["lower_seconds_by_phase"]["warmup"] == 1.5
            assert sec["compiles_total"] == 0  # a stage is not a compile
            assert obs.snapshot()["totals"]["lower_seconds"] == 1.5
        finally:
            compile_log.reset_for_tests()

    def test_a_real_jit_feeds_both_sums(self):
        compile_log.reset_for_tests()
        try:
            obs = compile_log.init(8)
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
            sec = obs.metrics_section()
            assert sec["trace_seconds_total"] > 0.0
            assert sec["lower_seconds_total"] > 0.0
        finally:
            compile_log.reset_for_tests()


def test_flightview_prints_the_sched_section(capsys):
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).parent.parent / "scripts" / "flightview.py"
    spec = importlib.util.spec_from_file_location("flightview", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    now = FakeTime()
    clock = SchedClock(now=now)
    clock.mark("decode")
    now.step(2.0)
    mod.print_sched(clock.section())
    out = capsys.readouterr().out
    assert "decode" in out and "100.00%" in out and "dev_starved" in out
    mod.print_sched({})  # a postmortem of a program without the account
    assert capsys.readouterr().out == ""
    assert json.dumps(clock.section())
