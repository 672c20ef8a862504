"""Engine dispatch thread: bridges the synchronous TPU step loop to asyncio.

The InferenceEngine (runtime/engine.py) is synchronous by design — one
thread owns the device and runs admit/decode/retire steps.  The serving
layer is asyncio (like the reference's uvicorn event loop).  This module is
the seam: a single daemon thread drives the engine continuously while
requests and token events cross thread boundaries through queues.

Design (SURVEY §2.2 "host-side dispatch thread feeding the device loop"):

* `submit()` (any asyncio loop) → thread-safe inbox queue → picked up at the
  top of each engine step.
* Engine `TokenEvent`s → `loop.call_soon_threadsafe(asyncio.Queue.put_nowait)`
  into the per-request event queue, so each request's consumer wakes on its
  own loop with no polling.
* When idle, the thread blocks on the inbox (zero busy-wait); when active it
  drains the inbox without blocking between decode steps; when the engine
  withheld decode because the device has enough queued, it waits on the
  inbox for a millisecond or two before it looks again.

The single-writer design means engine state needs no locks — the dispatch
thread is the only mutator (SURVEY §5.2: the reference's hand-rolled
concurrency gaps are removed by construction).
"""

from __future__ import annotations

import asyncio
import logging
import queue
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from ..runtime.engine import AdmissionError, GenRequest, InferenceEngine, TokenEvent
from ..runtime.failpoints import failpoint
from ..runtime.phase_clock import SchedClock
from ..tracing import add_event

logger = logging.getLogger("kafka_tpu.llm.worker")


# How long the engine thread waits on its inbox: with no work, and
# between two looks at the device's backlog while decode is held.
_IDLE_WAIT_S = 1.0
_HOLD_WAIT_S = 0.0015


@dataclass
class _Route:
    loop: asyncio.AbstractEventLoop
    events: "asyncio.Queue[TokenEvent]"
    # backpressure: tokens queued but not yet consumed (approximate)
    dropped: bool = field(default=False)


class EngineWorker:
    """Owns the engine thread; routes token events to per-request queues."""

    def __init__(self, engine: InferenceEngine):
        self.engine = engine
        self._inbox: "queue.Queue[Tuple[str, object]]" = queue.Queue()
        self._routes: Dict[str, _Route] = {}
        self._thread: Optional[threading.Thread] = None
        self._stopped = threading.Event()
        self._lock = threading.Lock()  # guards _routes (submit vs dispatch)
        # pause seam (topology rebuilds): while paused the worker thread
        # parks between steps — the engine's single-writer invariant then
        # lets ANOTHER thread mutate engine structure safely
        self._pause_req = threading.Event()
        self._pause_ack = threading.Event()
        self._resume_evt = threading.Event()
        # terminal events whose dispatch failed, awaiting a paced retry
        # (worker-thread only; see _dispatch_guarded/_retry_redispatches)
        self._redispatches: list = []
        # The engine thread's clock (runtime/phase_clock.py): every instant
        # of _run and of the step() it calls is charged to one phase
        # (tracing.SCHED_PHASES).  The thread owns it and hands it to the
        # engine it drives, in place of the engine's own.
        self.sched = SchedClock()
        engine.sched = self.sched

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "EngineWorker":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._run, name="kafka-tpu-engine", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        if self._thread is None:
            return
        self._stopped.set()
        self._inbox.put(("__wake__", None))
        self._thread.join(timeout=timeout)
        self._thread = None

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def pause(self, timeout: float = 30.0) -> bool:
        """Park the engine thread between steps; returns once it is parked
        (True) or the wait timed out (False).  While paused, no step()
        runs and no inbox command is processed — the caller owns the
        engine and may restructure it (DataParallelEngines.rebuild).
        Always pair with resume(), promptly: submits and cancels queue up
        behind the pause."""
        if not self.alive:
            return True  # no thread -> nothing can race the caller
        self._resume_evt.clear()
        self._pause_ack.clear()
        self._pause_req.set()
        self._inbox.put(("__wake__", None))
        return self._pause_ack.wait(timeout)

    def resume(self) -> None:
        self._pause_req.clear()
        self._resume_evt.set()

    # -- request API (called from asyncio) -----------------------------

    def submit(
        self, req: GenRequest, loop: asyncio.AbstractEventLoop
    ) -> "asyncio.Queue[TokenEvent]":
        """Enqueue a request; returns the asyncio queue its events land on."""
        if self._stopped.is_set():
            raise RuntimeError("engine worker is stopped")
        events: "asyncio.Queue[TokenEvent]" = asyncio.Queue()
        with self._lock:
            self._routes[req.request_id] = _Route(loop=loop, events=events)
        self._inbox.put(("submit", req))
        return events

    def cancel(self, request_id: str) -> None:
        """Abort a request from the serving side (client disconnect)."""
        self._inbox.put(("cancel", request_id))

    def note_tool_gap(self, prefix_key: str) -> None:
        """Agent-native scheduling (ISSUE 20): the provider saw a lane
        finish with finish_reason=tool_calls — route the gap signal onto
        the engine thread (single-writer: all gap state lives there)."""
        self._inbox.put(("agent", ("gap", prefix_key)))

    def note_tool_return(self, prefix_key: str) -> None:
        """The thread's tool completed (sandbox SSE terminal): cancel a
        lingering demote or kick the return-prefetch, on the engine
        thread."""
        self._inbox.put(("agent", ("return", prefix_key)))

    # -- engine thread -------------------------------------------------

    def _run(self) -> None:
        logger.info("engine worker started")
        clock = self.sched
        mark = clock.mark
        mark("inbox")
        while not self._stopped.is_set():
            # pause seam: park between steps until resumed (or stopped)
            if self._pause_req.is_set():
                mark("paused")
                while (self._pause_req.is_set()
                       and not self._stopped.is_set()):
                    self._pause_ack.set()
                    self._resume_evt.wait(timeout=0.1)
                mark("inbox")
            # Block when idle; drain without blocking when active.  When
            # the last step withheld decode (the device has its two
            # programs queued: engine._hold_decode) there is nothing to
            # dispatch until one finishes, so wait a moment on the inbox
            # instead of stepping again at once: a submission or a
            # cancel ends the wait, and the thread does not spin against
            # the event loop for the GIL.
            if not self.engine.has_work:
                wait: Optional[float] = _IDLE_WAIT_S
                t_wait = mark("idle_wait")
            elif getattr(self.engine, "decode_held", False):
                wait = _HOLD_WAIT_S
                t_wait = mark("hold_wait")
            else:
                wait = None
            try:
                kind, payload = self._inbox.get(
                    block=wait is not None, timeout=wait)
                if wait is not None:
                    clock.begin_iteration(mark("inbox"))
                self._handle(kind, payload)
                # drain any further queued commands
                while True:
                    try:
                        kind, payload = self._inbox.get_nowait()
                    except queue.Empty:
                        break
                    self._handle(kind, payload)
            except queue.Empty:
                if wait is not None:
                    # the wait ran out: what it took past its timeout is
                    # what the GIL and the OS kept from this thread
                    t_woke = mark("inbox")
                    clock.begin_iteration(t_woke)
                    clock.wait_over(t_woke - t_wait - wait)
            if self._stopped.is_set():
                break
            # paced retry of parked terminal events: one attempt per loop
            # iteration (the blocking inbox get above bounds idle-engine
            # pacing at ~1s/round), placed before the idle `continue` so
            # an idle engine still drains its redispatch backlog
            self._retry_redispatches()
            if not self.engine.has_work:
                continue
            try:
                events = self.engine.step()
            except Exception:
                # Recovery ladder: rebuild a servable engine (fail started
                # requests, keep waiting ones, repair page accounting); if
                # recovery ITSELF dies, fall back to failing everything —
                # "every request gets a terminal event" must hold even
                # when the engine is beyond repair.
                mark("inbox")  # recovery is not the phase that raised
                logger.exception("engine step failed; recovering")
                try:
                    events = self.engine.recover_from_failure()
                except Exception:
                    logger.exception(
                        "engine recovery failed; failing all requests"
                    )
                    events = self._fail_all()
            if events:
                mark("deliver")
                for ev in events:
                    self._dispatch_guarded(ev)
                clock.note_delivered(len(events))
            # one iteration, from the end of its wait to here, under what
            # it dispatched (tracing.SCHED_ITER_CLASSES)
            clock.end_iteration(mark("inbox"))
        clock.stop()
        logger.info("engine worker stopped")

    def _dispatch_guarded(self, ev: TokenEvent, attempts: int = 0) -> None:
        """Dispatch one event without letting a bad route (or an armed
        worker.dispatch failpoint) take down the worker loop or lose a
        terminal event.  Terminal events are load-bearing — a consumer
        awaits them forever — so a failed terminal dispatch is parked and
        retried once per loop iteration (_retry_redispatches paces the
        budget across real time, so bounded nth/count fault rules expire
        within it); when the budget is spent, a last-resort delivery runs
        with the failpoint bypassed — only a genuinely dead route loses
        its terminal event."""
        try:
            self._dispatch(ev)
        except Exception:
            logger.exception("event dispatch failed for %s", ev.request_id)
            if not ev.finished:
                return  # one lost token; the stream continues
            if attempts < 8:
                self._redispatches.append((ev, attempts + 1))
                return
            logger.error(
                "terminal event for %s still undeliverable after %d "
                "attempts; trying once more without fault injection",
                ev.request_id, attempts,
            )
            try:
                self._deliver(ev)
            except Exception:
                logger.exception(
                    "final delivery failed for %s; dropping its route",
                    ev.request_id,
                )
                with self._lock:
                    self._routes.pop(ev.request_id, None)

    def _retry_redispatches(self) -> None:
        """One retry round per loop iteration: each parked terminal event
        gets a single fresh attempt (re-parking itself on failure).  A
        list swap, not in-place iteration — _dispatch_guarded appends."""
        if not self._redispatches:
            return
        pending, self._redispatches = self._redispatches, []
        for ev, attempts in pending:
            self._dispatch_guarded(ev, attempts=attempts)

    def _handle(self, kind: str, payload: object) -> None:
        if kind == "submit":
            try:
                self.engine.submit(payload)  # type: ignore[arg-type]
            except AdmissionError as e:
                # queue-full backstop behind the server's admission gate
                # (the race where the queue fills between the gate's check
                # and this thread's submit): a distinct reason prefix so
                # the provider maps it to HTTP 429, not a 500
                req: GenRequest = payload  # type: ignore[assignment]
                logger.warning("submit rejected for %s: %s",
                               req.request_id, e)
                self._dispatch_guarded(
                    TokenEvent(
                        req.request_id, None, finished=True,
                        finish_reason=f"rejected:{e.retry_after_s:.0f}:{e}",
                    )
                )
            except Exception as e:  # surfaced to the consumer as an error event
                req = payload  # type: ignore[assignment]
                logger.warning("submit rejected for %s: %s", req.request_id, e)
                self._dispatch_guarded(
                    TokenEvent(
                        req.request_id, None, finished=True,
                        finish_reason=f"error:{e}",
                    )
                )
        elif kind == "agent":
            # ("gap"|"return", prefix_key) — the engine may be a single
            # InferenceEngine or a DataParallelEngines router (both
            # implement the note_tool_* pair); getattr keeps the worker
            # duck-typed against engine shims in tests
            verb, key = payload  # type: ignore[misc]
            fn = getattr(self.engine, f"note_tool_{verb}", None)
            if fn is not None:
                try:
                    fn(key)
                except Exception:  # an optimization must never kill steps
                    logger.exception("agent %s signal failed for %r",
                                     verb, key)
        elif kind == "cancel":
            rid: str = payload  # type: ignore[assignment]
            if self.engine.cancel(rid):
                self._dispatch_guarded(
                    TokenEvent(rid, None, finished=True, finish_reason="cancelled")
                )
            else:
                # request unknown/already done: just drop the route
                with self._lock:
                    self._routes.pop(rid, None)

    def _fail_all(self):
        """Device-step failure: every in-flight request gets a terminal event."""
        # recovery itself died: the flight recorder's ring is the only
        # artifact that will explain this engine — dump it before the
        # cancel sweep rewrites the lane table (best-effort, ISSUE 11)
        for e in getattr(self.engine, "engines", [self.engine]):
            try:
                dump = getattr(e, "dump_postmortem", None)
                if dump is not None:
                    dump("recovery_failed")
            except Exception:  # pragma: no cover - defensive
                logger.exception("recovery-failure postmortem dump failed")
        events = []
        for rid in list(self.engine._requests):
            req = self.engine._requests.get(rid)
            if req is not None:
                # recovery itself died: the trace still records why the
                # request ended (engine.recover_from_failure never ran
                # for these, so this is not a duplicate)
                add_event(req.trace, "engine.recover",
                          {"reason": "error:engine", "fail_all": True})
            # reason matches the event below so metrics count these as
            # engine failures (requests.failed), not client cancels
            self.engine.cancel(rid, reason="error:engine")
            events.append(
                TokenEvent(rid, None, finished=True, finish_reason="error:engine")
            )
        return events

    def check_routes(self) -> list:
        """Route-table consistency probe (chaos tests): ids with a live
        route but no engine-side request.  Call only at quiescence — a
        just-submitted request's route legitimately precedes its engine
        registration while the submit command sits in the inbox."""
        with self._lock:
            routed = list(self._routes)
        known = self.engine._requests
        return [rid for rid in routed if rid not in known]

    def _dispatch(self, ev: TokenEvent) -> None:
        failpoint("worker.dispatch")
        self._deliver(ev)

    def _deliver(self, ev: TokenEvent) -> None:
        """Route one event to its consumer queue (no fault injection —
        _dispatch_guarded's last-resort path calls this directly)."""
        with self._lock:
            route = self._routes.get(ev.request_id)
        if route is None:
            return
        try:
            route.loop.call_soon_threadsafe(route.events.put_nowait, ev)
        except RuntimeError:
            # consumer loop is gone (shutdown): cancel the request so the
            # engine doesn't decode into the void
            if not ev.finished and not route.dropped:
                route.dropped = True
                self._inbox.put(("cancel", ev.request_id))
        # the route is released only after the delivery attempt ran to
        # completion: an injected fault upstream must leave it intact so
        # the redispatch path can still deliver the terminal event
        if ev.finished:
            with self._lock:
                self._routes.pop(ev.request_id, None)
