"""A thread's time, tiled by phase (ISSUE 52).

Three layers already time the host (request spans, the TTFT tile, the
flight ring's period) and none of them can say what the engine thread was
doing while the device had nothing queued: what ran outside every span was
the larger half of the idle seconds and had no name.  A :class:`PhaseClock`
answers with the cheapest account that cannot leak: every instant of its
thread belongs to exactly ONE named phase, and a phase change is one call,

    clock.mark("drain")

which (a) charges ``now - last`` to the phase that ends, on
``time.monotonic()`` like the engine's other stamps, and (b) when
``tracing.profiler_annotations_enabled()`` closes that phase's
``jax.profiler.TraceAnnotation("<prefix><phase>")`` and opens the next one,
so the same call site feeds the counter and the span and the two cannot
disagree.  With profiling off no annotation object is built (one
module-global bool read, the rule ``engine._dispatch_scope`` has).  Marks
are flat: the worker's loop and ``step()`` are sequential, nothing nests.

The clock belongs to a THREAD.  ``llm/worker.EngineWorker`` owns the engine
thread's (:class:`SchedClock`, phases ``tracing.SCHED_PHASES``) and hands
it to the engine it drives; an engine driven without a worker
(``run_to_completion``, ``generate``, tests) has its own.  The booting
thread has a plain :class:`PhaseClock` over ``tracing.BOOT_STAGES``
(server/app.py).

Single writer, torn-free readers: the owning thread writes plain
attributes between two bumps of a sequence counter; ``/metrics`` (another
thread) re-reads until it saw an even, unchanged counter, so the phases it
reports add up to the thread's wall time exactly, open phase included.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Tuple

from ..tracing import SCHED_ITER_CLASSES, SCHED_PHASES, PhaseClock
from .metrics import (
    LATENCY_MS_BOUNDS,
    SCHED_ITER_HISTOGRAMS,
    StreamingHistogram,
)

SCHED_ANNOTATION_PREFIX = "kafka.sched."


class SchedClock(PhaseClock):
    """The engine thread's clock: the phases, what the timed waits lost,
    events delivered, one `_run` iteration's duration by what it did, and
    the device's starvation as the host knows it, charged to phases."""

    def __init__(self, now: Callable[[], float] = time.monotonic):
        super().__init__(SCHED_PHASES, SCHED_ANNOTATION_PREFIX, "inbox", now)
        self.wait_over_s = 0.0
        self.delivered = 0
        self.dev_starved_s = 0.0
        self.dev_starved_hi_s = 0.0
        self.dev_starved_gaps = 0
        self.starved = [0.0] * len(self.phases)
        self.starved_hi = [0.0] * len(self.phases)
        self.iter_hists = {c: StreamingHistogram(LATENCY_MS_BOUNDS)
                           for c in SCHED_ITER_CLASSES}
        self._did = len(SCHED_ITER_CLASSES) - 1  # "held"
        self._iter_t0 = self._t

    # -- the worker's loop ---------------------------------------------

    def wait_over(self, over_s: float) -> None:
        """A timed wait ran out `over_s` after the timeout it asked for:
        what the thread lost to the GIL and the OS before it ran again."""
        if over_s > 0.0:
            self._seq += 1
            self.wait_over_s += over_s
            self._seq += 1

    def note_delivered(self, n: int) -> None:
        self.delivered += n

    def nap(self, seconds: float) -> None:
        """The wait of a loop that drives step() itself (run_to_completion,
        generate), after an iteration that withheld decode: the worker
        waits on its inbox there."""
        self.mark("hold_wait")
        time.sleep(seconds)
        self.mark("inbox")

    def begin_iteration(self, t: float) -> None:
        """A wait ended at `t`: the iteration is timed from here."""
        self._iter_t0 = t

    def did(self, cls: str) -> None:
        """The engine dispatched something of class `cls` this iteration
        (SCHED_ITER_CLASSES; the first in that order wins)."""
        i = _ITER_INDEX[cls]
        if i < self._did:
            self._did = i

    def end_iteration(self, t: float) -> None:
        """The iteration's events were delivered at `t`: one sample under
        the class of what it dispatched.  (An iteration that did not call
        step(), an idle engine's wake-up, never gets here.)"""
        self.iter_hists[SCHED_ITER_CLASSES[self._did]].record(
            (t - self._iter_t0) * 1e3)
        self._did = len(SCHED_ITER_CLASSES) - 1
        self._iter_t0 = t

    # -- the engine's fetch pipeline -----------------------------------

    def seen_running(self, at: float) -> Tuple[float, List[float]]:
        """The last program the device was given is known to be unfinished
        at `at` (its dispatch call returned, or a poll found it running):
        the instant and the clock's vector then, for `emptied`."""
        return at, self.vector(at)

    def emptied(self, stamp: float, seen: Tuple[float, List[float]]):
        """The poll at `stamp` saw that program done, so the device's
        queue is empty, and has been since some instant between `seen`
        (a `seen_running`) and `stamp`: what a later dispatch needs to
        book the gap (`book_starved`)."""
        return (stamp, self.vector(stamp), *seen)

    def book_starved(self, since, t_call: float, t_return: float) -> None:
        """The device had nothing queued from `since` (an `emptied`) until
        the dispatch call that began at `t_call` and returned at
        `t_return`.  Lower bound: the call's start less the stamp (the
        completion was no later than the poll that saw it, the launch no
        earlier than the call).  Upper bound: the call's return less the
        last instant the program was known to be running.  What the thread
        did during each interval, by phase, is the difference of the
        clock's vectors at its two ends (no phase changes inside a
        dispatch call, so the vector at the call's start can still be
        taken at its return)."""
        stamp, at_stamp, seen, at_seen = since
        t_call = max(t_call, stamp)
        at_call, at_return = self.vector(t_call), self.vector(t_return)
        self._seq += 1
        self.dev_starved_s += t_call - stamp
        self.dev_starved_hi_s += t_return - seen
        self.dev_starved_gaps += 1
        for i in range(len(at_call)):
            self.starved[i] += at_call[i] - at_stamp[i]
            self.starved_hi[i] += at_return[i] - at_seen[i]
        self._seq += 1

    # -- any thread ----------------------------------------------------

    def section(self) -> Dict[str, Any]:
        """The `sched` section of /metrics, one consistent read."""
        def copy():
            return (self.vector(self._now()), list(self.starved),
                    list(self.starved_hi), self.wait_over_s,
                    self.dev_starved_s, self.dev_starved_hi_s,
                    self.dev_starved_gaps, self.delivered)

        (secs, starved, starved_hi, over, lo, hi, gaps,
         delivered) = self._consistent(copy)
        out: Dict[str, Any] = {"threads": 1}
        for p, s in zip(self.phases, secs):
            out[f"{p}_s"] = round(s, 6)
        out.update(wait_over_s=round(over, 6), delivered=delivered,
                   dev_starved_s=round(lo, 6),
                   dev_starved_hi_s=round(hi, 6), dev_starved_gaps=gaps)
        for p, s, h in zip(self.phases, starved, starved_hi):
            out[f"starved_{p}_s"] = round(s, 6)
            out[f"starved_hi_{p}_s"] = round(h, 6)
        return out

    def histograms(self) -> Dict[str, Dict[str, Any]]:
        return {name: self.iter_hists[c].snapshot()
                for name, c in zip(SCHED_ITER_HISTOGRAMS, SCHED_ITER_CLASSES)}


_ITER_INDEX = {c: i for i, c in enumerate(SCHED_ITER_CLASSES)}
