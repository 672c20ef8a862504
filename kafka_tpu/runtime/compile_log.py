"""Compile observatory: the record of every XLA compilation (ISSUE 18).

Three observability layers already watch the host side (tracing, the
SLO/roofline plane, the flight recorder) but none of them can answer
the question that dominates a TPU serving incident: *what compiled,
when, and why*.  A rebuild's outage window is compile-bound, a shape
regression shows up as a silent recompile storm mid-traffic, and the
persistent compile cache either saved you minutes or it didn't — all
invisible today.  This module is the device-truth answer for the
compile axis:

* **Bounded ring** — every XLA compilation lands in a fixed-size ring
  (``KAFKA_TPU_COMPILE_RING`` records, default 256; 0 = off with the
  engine byte-identical to an unobserved build — ``instrument`` returns
  the function unchanged and no listener ever registers).  One record =
  one compilation: program label (``step_programs``' cache tag),
  wall-clock seconds, persistent-cache disposition (``hit`` / ``miss``
  / ``off`` — the directory :func:`enable_compile_cache` reports; or
  ``store``: no compilation at all, the program store beside that cache
  (``program_store.py``) loaded the executable, in that many seconds),
  and the engine phase that triggered it (``boot`` / ``warmup`` /
  ``first_traffic`` / ``rebuild``).

* **Two capture paths** — the primary recorder is a
  ``jax.monitoring`` duration listener filtered on
  ``/jax/core/compile/backend_compile_duration`` (fires once per real
  backend compile, silent on already-compiled calls; cached-same-shape
  dispatches cost nothing).  The engine's compile sites additionally
  wrap their jitted callables with :func:`instrument`, which stamps a
  thread-local label so the listener can attribute the compile — and,
  on runtimes whose monitoring does not emit the event, times the
  first call itself as a wall-clock fallback.  The two paths dedupe:
  when monitoring observed a compile during the instrumented call, the
  fallback stands down.

* **Storm detection** — ``N`` compiles inside ``W`` seconds *after the
  engine reached first traffic* (``KAFKA_TPU_COMPILE_STORM_N`` /
  ``_S``, default 3 in 60s) means shapes are churning while users
  wait.  The condition is level-held here and edge-counted by the
  flight recorder's ``compile_storm`` anomaly; the autoscaler refuses
  to resize while it holds (a rebuild mid-storm doubles the very
  outage it is reacting to).  Boot / warmup / rebuild compiles are the
  expected cost of those phases and never count toward a storm.

``GET /debug/compiles`` serves the ring; the ``compiles`` sections of
``/metrics`` and ``/admin/signals`` carry the totals.  The observatory
is process-wide (XLA compilation is a process-level event — dp
replicas share one cache and one monitoring stream), so the section is
reported once, not per replica.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import program_store

logger = logging.getLogger("kafka_tpu.compile")

RING_ENV = "KAFKA_TPU_COMPILE_RING"
STORM_N_ENV = "KAFKA_TPU_COMPILE_STORM_N"
STORM_S_ENV = "KAFKA_TPU_COMPILE_STORM_S"

# the jax.monitoring duration event that brackets compile_or_get_cached
# (jax._src.dispatch.BACKEND_COMPILE_EVENT): fires once per program the
# process had not compiled yet — persistent-cache hit or miss alike —
# and stays silent for already-compiled calls
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# fired (jax._src.compiler) when the persistent cache served the request,
# BEFORE the duration event above closes: it marks the in-flight compile
# so the record that follows lands as "hit"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# the two stages ahead of the backend compile (jax._src.dispatch
# JAXPR_TRACE_EVENT, JAXPR_TO_MLIR_MODULE_EVENT): tracing a program to a
# jaxpr and lowering it to a module.  The persistent cache saves neither,
# so their sums are what a program costs at EVERY boot (a Pallas call
# lowers its kernel each time).
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
}

# JAX's own variable for the persistent-cache directory; when set, JAX
# reads it and this module only reports it (enable_compile_cache)
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# off switch ("0"/"off"/"false"/""): the test suite disables the cache
# because serializing CPU SPMD executables has crashed it
CACHE_SWITCH_ENV = "KAFKA_TPU_COMPILE_CACHE"

PHASES = ("boot", "warmup", "first_traffic", "rebuild")

# one compile above this many seconds is always worth a log line
_SLOW_COMPILE_S = 30.0


def ring_default() -> int:
    """KAFKA_TPU_COMPILE_RING with nonsense clamped to the default
    (256 records outlives any realistic warmup + rebuild history)."""
    raw = os.environ.get(RING_ENV)
    if raw is None or raw == "":
        return 256
    try:
        return max(0, int(raw))
    except ValueError:
        return 256


def _env_pos(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        v = float(raw)
    except ValueError:
        return default
    return v if v > 0 else default


class CompileObservatory:
    """Process-wide compile ring + storm detector.

    Writes arrive from whichever thread jax compiles on (engine thread,
    warmup executor, rebuild executor) under ``_lock``; reads
    (``/debug/compiles``, metrics, signals) take the same lock — the
    ring is tiny and compiles are rare, so contention is irrelevant.
    """

    def __init__(self, size: int):
        if size <= 0:
            raise ValueError("CompileObservatory size must be > 0 "
                             "(0 = off means: do not construct one)")
        self.size = size
        self._lock = threading.Lock()
        self._ring: List[Dict[str, Any]] = []
        self.next_seq = 0
        self.phase = "boot"
        self.cache_dir: Optional[str] = None  # set by configure_cache
        # totals (monotone counters)
        self.compiles_total = 0
        self.compile_seconds_total = 0.0
        self.by_cache: Dict[str, int] = {"hit": 0, "miss": 0, "off": 0,
                                         "store": 0}
        self.by_phase: Dict[str, int] = {p: 0 for p in PHASES}
        # seconds in the stages ahead of the backend compile, by stage
        # ("trace", "lower") then by phase: no ring record, sums only
        self.stage_seconds: Dict[str, Dict[str, float]] = {
            stage: {p: 0.0 for p in PHASES}
            for stage in _STAGE_EVENTS.values()}
        # storm detector: wall times of first_traffic-phase compiles
        self.storm_n = max(1, int(_env_pos(STORM_N_ENV, 3)))
        self.storm_s = _env_pos(STORM_S_ENV, 60.0)
        self._storm_times: List[float] = []
        self.storms_total = 0
        self._storm_was_active = False
        # thread-local label context set by instrument() wrappers so the
        # monitoring listener can attribute the compile it observes
        self._tls = threading.local()

    # -- label context (instrument wrappers) -----------------------------

    def _push_label(self, label: str) -> None:
        self._tls.label = label
        self._tls.observed = False

    def _pop_label(self) -> bool:
        observed = getattr(self._tls, "observed", False)
        self._tls.label = None
        self._tls.observed = False
        return observed

    def _current_label(self) -> Optional[str]:
        return getattr(self._tls, "label", None)

    # -- recording -------------------------------------------------------

    def record(self, label: str, seconds: float,
               cache: Optional[str] = None,
               now: Optional[float] = None,
               fn: Optional[str] = None) -> None:
        """One compilation happened.  ``cache`` defaults from the
        persistent-cache configuration: ``off`` when no cache dir is
        configured, ``miss`` otherwise (a hit is marked explicitly by
        the cache-hit listener).  ``fn`` is jax's own name for the
        program — what tells the unlabeled ``?`` records apart."""
        now = time.time() if now is None else now
        if cache is None:
            cache = "miss" if self.cache_dir else "off"
        with self._lock:
            rec = {
                "seq": self.next_seq,
                "t": round(now, 3),
                "label": label,
                "seconds": round(seconds, 4),
                "cache": cache,
                "phase": self.phase,
            }
            if fn:
                rec["fn"] = fn
            if len(self._ring) < self.size:
                self._ring.append(rec)
            else:
                self._ring[self.next_seq % self.size] = rec
            self.next_seq += 1
            self.compiles_total += 1
            self.compile_seconds_total += seconds
            self.by_cache[cache] = self.by_cache.get(cache, 0) + 1
            self.by_phase[self.phase] = self.by_phase.get(
                self.phase, 0) + 1
            if self.phase == "first_traffic":
                self._storm_times.append(now)
                # bound the storm window list (ring discipline)
                if len(self._storm_times) > 4 * self.storm_n:
                    del self._storm_times[: -2 * self.storm_n]
                if (self._storm_active_locked(now)
                        and not self._storm_was_active):
                    self._storm_was_active = True
                    self.storms_total += 1
                    logger.warning(
                        "compile storm: %d compiles in %.0fs while "
                        "serving (last: %s, %.2fs)", self.storm_n,
                        self.storm_s, label, seconds)
        if seconds >= _SLOW_COMPILE_S:
            logger.warning("slow compile: %s took %.1fs (phase=%s, "
                           "cache=%s)", label, seconds, self.phase,
                           cache)
        else:
            logger.info("compile: %s %.2fs (phase=%s, cache=%s)",
                        label, seconds, self.phase, cache)

    def record_stage(self, stage: str, seconds: float) -> None:
        """`seconds` of tracing or lowering happened, in the phase that
        stands now."""
        with self._lock:
            by_phase = self.stage_seconds[stage]
            by_phase[self.phase] = by_phase.get(self.phase, 0.0) + seconds

    def mark_cache_hit(self) -> None:
        """The persistent cache served the compile in flight on this
        thread (the cache-hit monitoring event, which fires before the
        compile-duration event closes): the next record this thread
        writes lands as ``hit``."""
        self._tls.cache_hit = True

    def _take_cache_hit(self) -> bool:
        hit = getattr(self._tls, "cache_hit", False)
        self._tls.cache_hit = False
        return hit

    # -- storm -----------------------------------------------------------

    def _storm_active_locked(self, now: float) -> bool:
        cutoff = now - self.storm_s
        n = 0
        for t in reversed(self._storm_times):
            if t < cutoff:
                break
            n += 1
        return n >= self.storm_n

    def storm_active(self, now: Optional[float] = None) -> bool:
        """Level-held storm condition (the flight recorder edge-counts
        it; the autoscaler vetoes resizes while it holds)."""
        now = time.time() if now is None else now
        with self._lock:
            active = self._storm_active_locked(now)
            if not active:
                self._storm_was_active = False
            return active

    # -- export ----------------------------------------------------------

    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            if len(self._ring) < self.size:
                return [dict(r) for r in self._ring]
            start = self.next_seq % self.size
            return [dict(self._ring[(start + i) % self.size])
                    for i in range(self.size)]

    def metrics_section(self) -> Dict[str, Any]:
        """The ``compiles`` section of the metrics snapshot (keys
        registered as COMPILE_METRIC_KEYS in metrics.py)."""
        now = time.time()
        storm = self.storm_active(now)
        with self._lock:
            return {
                "compiles_total": self.compiles_total,
                "compile_seconds_total": round(
                    self.compile_seconds_total, 4),
                "compile_storm_active": 1 if storm else 0,
                "compile_storms_total": self.storms_total,
                "by_cache": dict(self.by_cache),
                "by_phase": dict(self.by_phase),
                **{f"{stage}_seconds_total": round(sum(by.values()), 4)
                   for stage, by in self.stage_seconds.items()},
                **{f"{stage}_seconds_by_phase":
                   {p: round(v, 4) for p, v in by.items()}
                   for stage, by in self.stage_seconds.items()},
                **program_store.counters(),
            }

    def signals_section(self) -> Dict[str, Any]:
        """The ``compiles`` section of /admin/signals: ring summary +
        the storm flag the autoscaler contract keys on."""
        sec = self.metrics_section()
        with self._lock:
            recent = [dict(r) for r in self._ring[-8:]] \
                if len(self._ring) < self.size else None
            if recent is None:
                start = self.next_seq % self.size
                recent = [dict(self._ring[(start + i) % self.size])
                          for i in range(self.size)][-8:]
            sec.update({
                "ring_size": self.size,
                "next_seq": self.next_seq,
                "phase": self.phase,
                "cache_dir": self.cache_dir,
                "storm_n": self.storm_n,
                "storm_window_s": self.storm_s,
                "recent": recent,
            })
        sec["storm_active"] = bool(sec.pop("compile_storm_active"))
        return sec

    def snapshot(self) -> Dict[str, Any]:
        """Full ring for GET /debug/compiles."""
        sec = self.metrics_section()
        return {
            "ring_size": self.size,
            "next_seq": self.next_seq,
            "phase": self.phase,
            "cache_dir": self.cache_dir,
            "storm": {
                "active": bool(sec["compile_storm_active"]),
                "storms_total": self.storms_total,
                "n": self.storm_n,
                "window_s": self.storm_s,
            },
            "totals": {
                "compiles": sec["compiles_total"],
                "seconds": sec["compile_seconds_total"],
                "by_cache": sec["by_cache"],
                "by_phase": sec["by_phase"],
                "trace_seconds": sec["trace_seconds_total"],
                "lower_seconds": sec["lower_seconds_total"],
            },
            "records": self.records(),
        }


# ---------------------------------------------------------------------------
# module-level singleton: XLA compilation is process-global, so is this

_OBS: Optional[CompileObservatory] = None
_LISTENERS_REGISTERED = False
_INIT_LOCK = threading.Lock()


def _on_duration_event(event: str, duration_s: float, **kw: Any) -> None:
    obs = _OBS
    if obs is None:
        return
    stage = _STAGE_EVENTS.get(event)
    if stage is not None:
        obs.record_stage(stage, duration_s)
        return
    if event != _COMPILE_EVENT:
        return
    label = obs._current_label()
    if label is not None:
        obs._tls.observed = True
    try:
        obs.record(label or "?", duration_s,
                   cache="hit" if obs._take_cache_hit() else None,
                   fn=kw.get("fun_name"))
    except Exception:  # pragma: no cover - never break a compile
        logger.debug("compile record failed", exc_info=True)


def _on_event(event: str, **kw: Any) -> None:
    obs = _OBS
    if obs is None or event != _CACHE_HIT_EVENT:
        return
    try:
        obs.mark_cache_hit()
    except Exception:  # pragma: no cover - never break a compile
        logger.debug("cache-hit record failed", exc_info=True)


def _register_listeners() -> bool:
    """Hook jax.monitoring once per process (there is no public
    unregister-by-callback; the listeners are no-ops while _OBS is
    None, so enable/disable is just the singleton swap)."""
    global _LISTENERS_REGISTERED
    if _LISTENERS_REGISTERED:
        return True
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        monitoring.register_event_listener(_on_event)
        _LISTENERS_REGISTERED = True
        return True
    except Exception:  # pragma: no cover - monitoring API drift
        logger.info("jax.monitoring unavailable; compile observatory "
                    "falls back to instrument() wall timing")
        return False


def enabled() -> bool:
    return _OBS is not None


def get() -> Optional[CompileObservatory]:
    return _OBS


def init(size: Optional[int] = None) -> Optional[CompileObservatory]:
    """Build (or return) the process observatory.  size 0 disables —
    nothing is constructed and every hook below is a no-op returning
    its input, keeping the disabled build byte-identical."""
    global _OBS
    size = ring_default() if size is None else size
    if size <= 0:
        return _OBS
    with _INIT_LOCK:
        if _OBS is None:
            _OBS = CompileObservatory(size)
            _register_listeners()
        return _OBS


def reset_for_tests() -> None:
    """Drop the singleton (listeners stay registered as no-ops)."""
    global _OBS
    _OBS = None


def set_phase(phase: str) -> None:
    """Engine lifecycle transition (boot -> warmup -> first_traffic,
    with rebuild excursions).  Unknown names are kept verbatim so a
    future phase shows up in the ring rather than vanishing."""
    obs = _OBS
    if obs is not None:
        obs.phase = phase


def get_phase() -> Optional[str]:
    obs = _OBS
    return obs.phase if obs is not None else None


def configure_cache(cache_dir: Optional[str]) -> None:
    """Tell the observatory whether a persistent compile cache is in
    play (decides the default cache disposition: off vs miss).  No cache,
    no program store either: it lives under the cache's directory."""
    if not cache_dir:
        program_store.enable(None)
    obs = _OBS
    if obs is not None:
        obs.cache_dir = cache_dir or None


def record_store_load(label: str, seconds: float) -> None:
    """The program store loaded `label`'s executable on this thread: one
    ring record of disposition ``store`` (a load is what the boot did
    INSTEAD of a compile), and the instrument() wrapper around the call
    stands down as it does for a compile the listener saw."""
    obs = _OBS
    if obs is not None:
        obs._tls.observed = True
        obs.record(label, seconds, cache="store")


def compile_cache_enabled() -> bool:
    """KAFKA_TPU_COMPILE_CACHE off switch; unset = on."""
    raw = os.environ.get(CACHE_SWITCH_ENV)
    return raw is None or raw.strip().lower() not in ("", "0", "off",
                                                      "false")


def compile_cache_dir() -> str:
    """THE directory of the persistent XLA compile cache, for every
    entry point (server, bench.py, chip_smoke.py): wherever
    JAX_COMPILATION_CACHE_DIR points when set, else ``.jax_cache`` next
    to this package.  The path is part of nothing random — the same
    checkout always resolves to the same directory, so a second boot
    finds what the first one compiled."""
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def flat_locations() -> None:
    """An op's location is the line that made it, not its callers' too.  A
    Mosaic kernel rides in its custom call's `backend_config` WITH its
    locations, which the compile cache's key does not strip, and jax keeps a
    kernel's traced body for whoever lowers it next: with tracebacks in
    them, what a program lowered to depended on who had traced the kernel
    first (the benchmark's logit check missed the cache, +50 s in Granite,
    on the first boot that LOADED the step programs it used to follow).
    Set wherever the cache is on; `scripts/program_store.py verify` sets it
    to lower as a server does."""
    import jax

    jax.config.update("jax_include_full_tracebacks_in_locations", False)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on at compile_cache_dir(), and
    with it the program store under it (program_store.py: the second boot
    loads its step programs), and report it to the observatory.  Call
    before the first jax.jit."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(CACHE_DIR_ENV):
        # placed from outside when the variable is set (JAX reads it
        # itself); only the checkout-local default is set in code
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1)
    flat_locations()
    configure_cache(path)
    program_store.enable(path)
    return path


def instrument(label: str, fn: Callable[..., Any]) -> Callable[..., Any]:
    """Wrap a freshly-jitted callable at its ``step_programs`` cache miss.

    Disabled (ring 0): returns ``fn`` unchanged — the dispatch path is
    byte-identical to an uninstrumented build.  Enabled: every call
    stamps the thread-local label (so recompiles triggered by NEW
    input shapes attribute correctly too, not just the first call) and
    the first call doubles as a wall-clock fallback recorder for
    runtimes whose jax.monitoring never emits the compile event.
    """
    obs = _OBS
    if obs is None:
        return fn

    state = {"first": True}

    def wrapper(*args: Any, **kwargs: Any) -> Any:
        o = _OBS
        if o is None:
            return fn(*args, **kwargs)
        o._push_label(label)
        t0 = time.monotonic()
        try:
            return fn(*args, **kwargs)
        finally:
            dt = time.monotonic() - t0
            observed = o._pop_label()
            if state["first"]:
                state["first"] = False
                if not observed:
                    # monitoring stayed silent for a first call that
                    # necessarily traced + compiled: record wall time
                    o.record(label, dt)

    wrapper.__name__ = f"compile_log[{label}]"
    wrapper.__wrapped__ = fn  # tests / introspection
    return wrapper
