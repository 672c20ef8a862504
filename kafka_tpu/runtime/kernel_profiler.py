"""Sampled per-kernel dispatch profiling (ISSUE 18, leg c).

The flight recorder's measured dispatch latency (PR 11) is derived
from fetch-maturation order — an honest *host-side* clock on device
compute, but still one hop removed from the chip: it cannot say which
kernels a dispatch spent its time in, and on CPU smoke the numbers
fold in host scheduling noise.  This module is the ground-truth
instrument under it:

* ``KAFKA_TPU_PROFILE_SAMPLE=N`` wraps every Nth ``engine.step`` in a
  ``jax.profiler`` trace written to a bounded spill directory
  (``KAFKA_TPU_PROFILE_SPILL_DIR``, default ``/tmp/kafka_tpu_kernels``;
  the last ``KAFKA_TPU_PROFILE_KEEP`` raw traces are retained for the
  Perfetto / xplane workflow, older ones pruned).  Unset or 0 = off,
  with every dispatch path byte-identical to an unprofiled build —
  the engine holds no sampler object and each hook site is one
  ``if self.kernel_sampler is not None`` branch.

* Each sample's ``*.trace.json.gz`` (the Chrome-trace JSON jax writes
  next to the xplane.pb) is parsed with stdlib gzip+json into
  per-kernel durations: events on ``/device:*`` processes when present
  (TPU/GPU), else the XLA executor worker events on CPU, host-API
  noise filtered out.  Kernels aggregate by the dispatch-kind
  composition of the sampled step (``decode``, ``prefill+decode``, …)
  into a top-K table served at ``GET /debug/kernels``.

* The sample's total device kernel time, split across the step's
  dispatch kinds in proportion to their modeled roofline seconds, is
  fed back as ``EngineMetrics.record_kernel_sample`` — the
  ``kernel_skew`` (true-device vs modeled) gauge that calibrates the
  PR 11 fetch-maturation ``model_skew`` per kind.  This is the
  instrument a chip calibration run reads instead of hand math.

Trace windows are *deliberately offset*: a sample's trace starts
before step k and stops at the start of step k+1, so asynchronously
dispatched device work has the inter-step gap to land inside the
window without the sampler ever blocking the scheduler.  ``jax``
profiling is process-global (one trace at a time); the sampler and
``POST /admin/profile`` share :func:`try_acquire_trace` so they can
never collide.
"""

from __future__ import annotations

import glob
import gzip
import json
import logging
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger("kafka_tpu.kernels")

SAMPLE_ENV = "KAFKA_TPU_PROFILE_SAMPLE"
SPILL_ENV = "KAFKA_TPU_PROFILE_SPILL_DIR"
KEEP_ENV = "KAFKA_TPU_PROFILE_KEEP"

DEFAULT_SPILL_DIR = "/tmp/kafka_tpu_kernels"
DEFAULT_KEEP = 4

# host-API events that are not kernels (CPU traces put XLA worker
# events and python/runtime noise on the same host process)
_HOST_NOISE = ("ParseArguments", "ThreadpoolListener",
               "ThunkExecutor", "ExecuteHelper")


def sample_period() -> int:
    """KAFKA_TPU_PROFILE_SAMPLE: trace every Nth step (0/unset/junk =
    off).  Negative values clamp to off like every other knob."""
    raw = os.environ.get(SAMPLE_ENV)
    if raw is None or raw == "":
        return 0
    try:
        return max(0, int(raw))
    except ValueError:
        return 0


# -- process-global trace ownership (jax allows one trace at a time) ----

_TRACE_LOCK = threading.Lock()


def try_acquire_trace() -> bool:
    """Claim the process profiler (non-blocking).  Shared with the
    on-demand POST /admin/profile capture so the two can't collide."""
    return _TRACE_LOCK.acquire(blocking=False)


def release_trace() -> None:
    try:
        _TRACE_LOCK.release()
    except RuntimeError:  # pragma: no cover - double release guard
        pass


# -- trace parsing ------------------------------------------------------


def parse_trace_dir(d: str) -> List[Tuple[str, float]]:
    """All kernel events in a profiler session dir as (name, dur_us).

    Prefers events on ``/device:*`` processes (real accelerators);
    falls back to the heuristic host filter for CPU traces.  Raises
    nothing: an unreadable trace is an empty list.
    """
    out: List[Tuple[str, float]] = []
    try:
        paths = glob.glob(os.path.join(d, "**", "*.trace.json.gz"),
                          recursive=True)
        for p in paths:
            with gzip.open(p, "rt") as f:
                data = json.load(f)
            out.extend(_parse_events(data.get("traceEvents", [])))
    except (OSError, ValueError, EOFError):
        logger.debug("unparseable trace under %s", d, exc_info=True)
    return out


def _parse_events(events: List[Dict[str, Any]]
                  ) -> List[Tuple[str, float]]:
    device_pids = set()
    for e in events:
        if (e.get("ph") == "M" and e.get("name") == "process_name"
                and str((e.get("args") or {}).get("name", ""))
                .startswith("/device:")
                and "CPU" not in str((e.get("args") or {})["name"])):
            device_pids.add(e.get("pid"))
    out: List[Tuple[str, float]] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        name = e.get("name")
        dur = e.get("dur")
        if not name or not isinstance(dur, (int, float)) or dur <= 0:
            continue
        if device_pids:
            if e.get("pid") not in device_pids:
                continue
        elif not _looks_like_kernel(name):
            continue
        out.append((name, float(dur)))
    return out


def _looks_like_kernel(name: str) -> bool:
    """CPU-trace heuristic: XLA thunk/kernel names (``dot.4``,
    ``broadcast_add_fusion``) vs host API noise (``$profiler.py …``,
    ``PjitFunction(...)``, ``TfrtCpuExecutable::Execute``)."""
    if name.startswith("$") or "::" in name or "(" in name:
        return False
    return not any(name.startswith(p) for p in _HOST_NOISE)


# -- the sampler --------------------------------------------------------


class KernelSampler:
    """Every-Nth-step jax.profiler sampling for ONE engine.

    Engine-thread single-writer for the sampling state; the aggregated
    kernel table is read by ``/debug/kernels`` under ``_agg_lock``.
    """

    def __init__(self, period: int,
                 spill_dir: Optional[str] = None,
                 keep: Optional[int] = None):
        if period <= 0:
            raise ValueError("KernelSampler period must be > 0 "
                             "(0 = off means: do not construct one)")
        self.period = period
        self.spill_dir = spill_dir or os.environ.get(
            SPILL_ENV) or DEFAULT_SPILL_DIR
        try:
            keep = int(os.environ.get(KEEP_ENV, "")) if keep is None \
                else keep
        except ValueError:
            keep = DEFAULT_KEEP
        self.keep = max(1, keep)
        self._step_i = 0
        self._open_dir: Optional[str] = None
        self._open_modeled: Dict[str, float] = {}
        self._sample_seq = 0
        self.samples_total = 0
        self.sample_failures = 0
        self.last_sample_t: Optional[float] = None
        self._agg_lock = threading.Lock()
        # (kind_label, kernel) -> [count, total_us]
        self._kernels: Dict[Tuple[str, str], List[float]] = {}
        # kind_label -> total device us across samples
        self._kind_us: Dict[str, float] = {}

    # -- engine hooks (engine thread) -----------------------------------

    def on_step_begin(self, metrics: Any) -> None:
        """Called at the top of engine.step: closes the previous
        sample's window (async device work has had the inter-step gap
        to land), then opens a new one when the step is due."""
        if self._open_dir is not None:
            self._finish_sample(metrics)
        due = self._step_i % self.period == 0
        self._step_i += 1
        if due:
            self._start_sample(metrics)

    def close(self, metrics: Any = None) -> None:
        """Stop any open window (engine shutdown / test teardown)."""
        if self._open_dir is not None:
            self._finish_sample(metrics)

    # -- sampling internals ---------------------------------------------

    def _modeled_by_kind(self, metrics: Any) -> Dict[str, float]:
        try:
            return {k: u.modeled_s for k, u in metrics.util.items()}
        except Exception:
            return {}

    def _start_sample(self, metrics: Any) -> None:
        if not try_acquire_trace():
            return  # an on-demand capture owns the profiler
        d = os.path.join(self.spill_dir,
                         f"sample_{self._sample_seq:06d}")
        self._sample_seq += 1
        try:
            import jax
            os.makedirs(d, exist_ok=True)
            jax.profiler.start_trace(d)
        except Exception:
            self.sample_failures += 1
            release_trace()
            logger.debug("profiler start_trace failed", exc_info=True)
            return
        self._open_dir = d
        self._open_modeled = self._modeled_by_kind(metrics)

    def _finish_sample(self, metrics: Any) -> None:
        d = self._open_dir
        self._open_dir = None
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            self.sample_failures += 1
            logger.debug("profiler stop_trace failed", exc_info=True)
            release_trace()
            return
        release_trace()
        # the sampled step's dispatch-kind composition, read off the
        # same per-kind modeled-seconds deltas the calibration uses
        # (record_measured_dispatch accrues modeled_s whether or not
        # the flight recorder is on)
        deltas: Dict[str, float] = {}
        if metrics is not None:
            after = self._modeled_by_kind(metrics)
            deltas = {
                k: after.get(k, 0.0) - self._open_modeled.get(k, 0.0)
                for k in after
            }
            deltas = {k: v for k, v in deltas.items() if v > 0}
        kinds = "+".join(sorted(deltas)) or "idle"
        kernels = parse_trace_dir(d)
        total_us = sum(dur for _, dur in kernels)
        with self._agg_lock:
            self.samples_total += 1
            self.last_sample_t = time.time()
            for name, dur in kernels:
                slot = self._kernels.setdefault((kinds, name), [0, 0.0])
                slot[0] += 1
                slot[1] += dur
            if total_us > 0:
                self._kind_us[kinds] = self._kind_us.get(
                    kinds, 0.0) + total_us
        # calibration feedback: split the sample's device time across
        # the step's kinds in proportion to their modeled seconds
        modeled_total = sum(deltas.values())
        if metrics is not None and total_us > 0 and modeled_total > 0:
            try:
                for k, v in deltas.items():
                    share = v / modeled_total
                    metrics.record_kernel_sample(
                        k, total_us * 1e-6 * share, v)
            except Exception:  # pragma: no cover - defensive
                logger.debug("kernel calibration failed", exc_info=True)
        self._prune_spill()

    def _prune_spill(self) -> None:
        """Keep the newest ``keep`` raw sample dirs (Perfetto/xplane
        workflow); parsing is done, older raw traces are dead weight."""
        try:
            dirs = sorted(glob.glob(
                os.path.join(self.spill_dir, "sample_*")))
            for d in dirs[: max(0, len(dirs) - self.keep)]:
                shutil.rmtree(d, ignore_errors=True)
        except OSError:  # pragma: no cover - best effort
            pass

    # -- export ----------------------------------------------------------

    def table(self, top_k: int = 20) -> List[Dict[str, Any]]:
        """Top-K kernels by total device time, across all samples."""
        with self._agg_lock:
            rows = [
                {
                    "kind": kinds,
                    "kernel": name,
                    "count": int(c),
                    "total_us": round(us, 3),
                    "avg_us": round(us / c, 3) if c else 0.0,
                    "frac": round(
                        us / self._kind_us[kinds], 4)
                    if self._kind_us.get(kinds) else 0.0,
                }
                for (kinds, name), (c, us) in self._kernels.items()
            ]
        rows.sort(key=lambda r: -r["total_us"])
        return rows[: max(1, top_k)]

    def snapshot(self, top_k: int = 20) -> Dict[str, Any]:
        """GET /debug/kernels payload."""
        with self._agg_lock:
            kind_us = {k: round(v, 3)
                       for k, v in self._kind_us.items()}
        return {
            "period": self.period,
            "spill_dir": self.spill_dir,
            "keep": self.keep,
            "samples_total": self.samples_total,
            "sample_failures": self.sample_failures,
            "last_sample_t": self.last_sample_t,
            "device_us_by_kind": kind_us,
            "kernels": self.table(top_k),
        }


def build_from_env() -> Optional[KernelSampler]:
    """One sampler per engine when KAFKA_TPU_PROFILE_SAMPLE > 0, else
    None (the byte-identical off state)."""
    period = sample_period()
    if period <= 0:
        return None
    return KernelSampler(period)
