"""The bound on the host's run-ahead (InferenceEngine._hold_decode): decode
is dispatched only while at most one program's worth of steps is queued that
the device has not been seen to finish; prefill chunks are never held; the
driving loops wait a moment instead of spinning.

The device here is the CPU and finishes a tiny step at once, so the tests
stand a gate between the engine and `is_ready`: while it is shut no dispatch
is seen to finish, which is what a busy chip looks like to the scheduler.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.llm import worker as worker_mod
from kafka_tpu.llm.constrained import ToolCallMaskFn, compile_tool_call_grammar
from kafka_tpu.llm.worker import EngineWorker
from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.models.tokenizer import ByteTokenizer
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.dp_router import DataParallelEngines
from kafka_tpu.runtime.engine import _HOLD_FLOOR_STEPS
from kafka_tpu.server.prometheus import render_prometheus

TOOLS = [{"type": "function", "function": {
    "name": "get_time", "parameters": {"type": "object", "properties": {}}}}]


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="hold-test", vocab_size=262, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(11))


ECFG = dict(max_batch=4, page_size=8, num_pages=96, max_pages_per_seq=16,
            prefill_buckets=(8, 16, 32), multi_step=4)


def make_engine(model, **kw):
    cfg, params = model
    return InferenceEngine(cfg, params, EngineConfig(**dict(ECFG, **kw)),
                           kv_dtype=jnp.float32)


class Gated:
    """A fetch entry's array behind the gate: `is_ready` is what the
    scheduler polls, `__array__` what a pop reads."""

    def __init__(self, arr, gate):
        self._arr, self._gate = arr, gate

    def is_ready(self):
        return self._gate.open and self._arr.is_ready()

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._arr)


class Gate:
    """Shuts the device's completions away from one engine and logs every
    entry it queues: (kind, backlog before, backlog after, busy lanes)."""

    def __init__(self, eng):
        self.open = True
        self.log = []
        push = eng._push_entry

        def spy(entry):
            before = eng._backlog_steps()
            entry.arr = Gated(entry.arr, self)
            push(entry)
            self.log.append((entry.kind, before, eng._backlog_steps(),
                             eng.num_active))

        eng._push_entry = spy


def submit(eng, n, new=24, start=0, **kw):
    reqs = [GenRequest(request_id=f"r{start + i}",
                       prompt_ids=[5 + start + i, 9, 23, 4, 7][: 3 + i % 3],
                       max_new_tokens=new, **kw) for i in range(n)]
    for r in reqs:
        eng.submit(r)
    return reqs


def drive(eng, gate, period=5, cap=20000):
    """Step to the end with the gate shut four iterations in five."""
    i = 0
    while eng.has_work:
        gate.open = i % period == 0
        eng.step()
        i += 1
        assert i < cap, "the engine made no progress under the gate"
    gate.open = True


def bound(eng):
    return max(eng.ecfg.multi_step, _HOLD_FLOOR_STEPS)


class TestBacklogBound:
    @pytest.mark.parametrize("multi_step", [1, 4, 16])
    def test_backlog_at_every_decode_dispatch(self, model, multi_step):
        eng = make_engine(model, multi_step=multi_step)
        gate = Gate(eng)
        reqs = submit(eng, 4, new=3 * multi_step + 8)
        drive(eng, gate)
        b = bound(eng)
        busy = [(k, before, after) for k, before, after, lanes in gate.log
                if k != "prefill" and lanes > 2]
        assert busy and eng.decode_holds > 0
        assert all(before <= b for _, before, _ in busy), busy
        assert all(after <= 2 * b for _, _, after in busy), busy
        if multi_step > 1:  # the fused program was what queued
            assert any(after - before == multi_step for _, before, after in busy)
        assert all(len(r.output_ids) == r.max_new_tokens for r in reqs)
        # fetch_depth_steps_mean, the benchmark's reading, is that backlog
        assert eng.fetch_depth_steps_sum == sum(
            before for k, before, _, _ in gate.log if k != "prefill")

    def test_prefill_chunk_dispatched_while_decode_is_held(self, model):
        eng = make_engine(model)
        gate = Gate(eng)
        submit(eng, 3, new=40)
        gate.open = False
        for _ in range(50):
            eng.step()
            if eng.decode_held:
                break
        assert eng.decode_held
        late = submit(eng, 1, new=6, start=3)[0]
        seen = len(gate.log)
        eng.step()
        kinds = [k for k, *_ in gate.log[seen:]]
        assert kinds == ["prefill"] and eng.decode_held
        # and it blocked nowhere: the device has still finished nothing
        assert all(e.t_ready is None for e in eng._pending[-2:])
        drive(eng, gate)
        assert len(late.output_ids) == 6

    @pytest.mark.parametrize("lanes", [1, 2])
    def test_a_lone_stream_takes_no_hold(self, model, lanes):
        eng = make_engine(model)
        gate = Gate(eng)
        reqs = submit(eng, lanes, new=40)
        gate.open = False
        for _ in range(3 * bound(eng)):
            eng.step()
            assert not eng.decode_held
        # it ran ahead as it always has: nothing was withheld
        assert eng._backlog_steps() > bound(eng)
        drive(eng, gate)
        assert eng.decode_holds == 0 and eng.decode_hold_s == 0.0
        assert all(len(r.output_ids) == 40 for r in reqs)


def unheld(eng):
    """The parent's scheduler: decode is never withheld."""
    eng._hold_decode = lambda: False
    return eng


def free_lanes(eng):
    return submit(eng, 4, new=30)


def fsm_lanes(eng):
    tok = ByteTokenizer()
    grammar = compile_tool_call_grammar(tok, TOOLS, vocab_size=262)
    eng.warmup_grammar(grammar)
    reqs = [GenRequest(
        request_id=f"g{i}", prompt_ids=tok.encode("call a tool" + "!" * i),
        max_new_tokens=40, stop_token_ids=tuple(tok.stop_ids),
        logits_mask_fn=ToolCallMaskFn(tok, TOOLS), grammar=grammar)
        for i in range(4)]
    for r in reqs:
        eng.submit(r)
    return reqs


def speculative_lanes(eng):
    reqs = [GenRequest(request_id=f"s{i}",
                       prompt_ids=[3 + i, 4, 5, 6, 3 + i, 4, 5, 6, 3 + i, 4],
                       max_new_tokens=30) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    return reqs


class TestSameTokens:
    @pytest.mark.parametrize("lanes,ecfg", [
        (free_lanes, {}),
        (fsm_lanes, {"page_size": 16, "prefill_buckets": (16, 32, 64)}),
        (speculative_lanes, {"speculative_k": 3}),
    ], ids=["free", "device_fsm", "speculative"])
    def test_greedy_streams_equal_the_unheld_scheduler(self, model, lanes,
                                                       ecfg):
        outs = []
        for make in (make_engine, lambda m, **kw: unheld(make_engine(m, **kw))):
            eng = make(model, **ecfg)
            gate = Gate(eng)
            reqs = lanes(eng)
            drive(eng, gate)
            outs.append({r.request_id: (list(r.output_ids), r.finish_reason)
                         for r in reqs})
        assert outs[0] == outs[1]
        assert all(ids for ids, _ in outs[0].values())


class TestDataParallel:
    def test_a_held_replica_returns_and_the_other_dispatches(self, model):
        cfg, params = model
        dp = DataParallelEngines(cfg, params, EngineConfig(**ECFG), dp=2,
                                 tp=1, kv_dtype=jnp.float32)
        gates = [Gate(e) for e in dp.engines]
        for i, eng in enumerate(dp.engines):
            submit(eng, 3, new=100, start=10 * i)
        gates[0].open = False
        for _ in range(50):
            dp.step()
            if dp.engines[0].decode_held:
                break
        assert dp.engines[0].decode_held
        # replica 0 stays held; in one and the same pass over the replicas
        # it returns without a dispatch and replica 1 queues decode (the
        # CPU holds replica 1 of its own accord until its program is done)
        both = False
        for _ in range(2000):
            time.sleep(0.001)
            seen = [len(g.log) for g in gates]
            t0 = time.monotonic()
            dp.step()
            assert time.monotonic() - t0 < 1.0
            assert dp.engines[0].decode_held and len(gates[0].log) == seen[0]
            if gates[1].log[seen[1]:]:
                assert not dp.engines[1].decode_held
                assert not dp.decode_held  # one dispatched: no wait
                both = True
                break
        assert both and dp.engines[1].num_active == 3
        gates[1].open = False
        for _ in range(50):
            dp.step()
            if dp.decode_held:
                break
        assert dp.decode_held and all(e.decode_held for e in dp.engines)
        snap = dp.metrics.snapshot()
        eng = snap["engine"]
        assert eng["decode_holds"] == sum(
            r["engine"]["decode_holds"] for r in snap["replicas"]) > 0
        assert eng["decode_hold_s"] == pytest.approx(sum(
            r["engine"]["decode_hold_s"] for r in snap["replicas"]))
        for g in gates:
            g.open = True
        dp.run_to_completion()
        assert not dp.has_work


class TestCounters:
    def test_both_move_in_a_hold_and_reach_the_expositions(self, model):
        eng = make_engine(model)
        gate = Gate(eng)
        submit(eng, 3, new=40)
        before = eng.metrics.snapshot(eng)["engine"]
        assert before["decode_holds"] == 0 and before["decode_hold_s"] == 0.0
        gate.open = False
        for _ in range(40):
            eng.step()
        time.sleep(0.005)
        eng.step()
        snap = eng.metrics.snapshot(eng)
        after = snap["engine"]
        assert after["decode_holds"] > 0 and after["decode_hold_s"] >= 0.005
        text = render_prometheus(snap)
        assert (f"kafka_tpu_engine_decode_holds_total "
                f"{after['decode_holds']}") in text
        assert "kafka_tpu_engine_decode_hold_seconds_total " in text
        # iterations that only hold fill no flight ring, yet the recorder's
        # detectors keep running through a hold of any length
        if eng.flight is not None:
            from kafka_tpu.runtime.flight_recorder import QUIET_S

            seq, holds = eng.flight.next_seq, eng.decode_holds
            t0 = time.monotonic()
            for _ in range(300):
                eng.step()
            assert eng.decode_holds == holds + 300
            assert eng.flight.next_seq - seq <= 1 + (
                time.monotonic() - t0) / QUIET_S
            time.sleep(QUIET_S)
            seq = eng.flight.next_seq
            eng.step()
            assert eng.flight.next_seq == seq + 1
        drive(eng, gate)


class HeldEngine:
    """What the worker sees of an engine that withholds decode for as long
    as the test likes: work to do, nothing to dispatch."""

    has_work = True
    decode_held = True

    def __init__(self):
        self.steps, self.submitted, self.cancelled = [], [], []
        self._requests = {}

    def step(self):
        self.steps.append(time.monotonic())
        return []

    def submit(self, req):
        self.submitted.append(time.monotonic())

    def cancel(self, rid, reason="cancelled"):
        self.cancelled.append(time.monotonic())
        return False


class TestWorkerWaitsUnderAHold:
    def test_steps_once_a_timeout_and_wakes_for_the_inbox(self, monkeypatch):
        wait = 0.5
        monkeypatch.setattr(worker_mod, "_HOLD_WAIT_S", wait)
        eng = HeldEngine()
        w = EngineWorker(eng).start()
        loop = asyncio.new_event_loop()
        try:
            time.sleep(3.2 * wait)
            n = len(eng.steps)
            assert 2 <= n <= 5  # not a spin: one step() a timeout
            gaps = np.diff(eng.steps)
            assert gaps.min() >= 0.8 * wait
            # a submission ends the wait at once, not at the timeout
            time.sleep(0.1 * wait)
            t0 = time.monotonic()
            w.submit(GenRequest(request_id="late", prompt_ids=[1, 2],
                                max_new_tokens=2), loop)
            deadline = t0 + 2.0
            while not eng.submitted and time.monotonic() < deadline:
                time.sleep(0.001)
            assert eng.submitted and eng.submitted[0] - t0 < 0.4 * wait
            # and so does a cancel
            time.sleep(0.1 * wait)
            t0 = time.monotonic()
            w.cancel("late")
            while not eng.cancelled and time.monotonic() < deadline:
                time.sleep(0.001)
            assert eng.cancelled and eng.cancelled[0] - t0 < 0.4 * wait
        finally:
            w.stop(timeout=5.0)
            loop.close()
        assert not w.alive

    def test_an_engine_that_dispatches_is_stepped_without_waiting(self):
        eng = HeldEngine()
        eng.decode_held = False
        w = EngineWorker(eng).start()
        try:
            time.sleep(0.05)
        finally:
            w.stop(timeout=5.0)
        assert len(eng.steps) > 50

    def test_a_real_engine_under_the_worker_finishes(self, model):
        """End to end on the thread: four lanes through the worker, holds
        taken where the CPU lets the host run ahead, every stream whole."""
        eng = make_engine(model)
        w = EngineWorker(eng).start()
        loop = asyncio.new_event_loop()
        done = threading.Event()
        counts = {}

        async def consume(q, rid):
            n = 0
            while True:
                ev = await q.get()
                if ev.finished:
                    counts[rid] = (n + (ev.token_id is not None),
                                   ev.finish_reason)
                    return
                n += 1

        async def main():
            qs = [(w.submit(GenRequest(
                request_id=f"w{i}", prompt_ids=[7 + i, 3, 9],
                max_new_tokens=32), loop), f"w{i}") for i in range(4)]
            await asyncio.wait_for(
                asyncio.gather(*(consume(q, rid) for q, rid in qs)), 60)
            done.set()

        try:
            loop.run_until_complete(main())
        finally:
            w.stop(timeout=5.0)
            loop.close()
        assert done.is_set()
        assert counts == {f"w{i}": (32, "length") for i in range(4)}
