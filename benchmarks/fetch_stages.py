"""What the fetch-pipeline readers share (PR 35): window means of the four
stage histograms that tile `ttft_fetch_ms`, and the same first boundary read
from the capture, on the profiler's clock.

Program side.  `/metrics` `histograms` holds `ttft_dev_wait_ms`,
`ttft_dev_exec_ms`, `ttft_hold_ms` and `ttft_emit_ms`
(`kafka_tpu.runtime.metrics.ttft_fetch_stages`): per request the four add up
to its `ttft_fetch_ms` sample, so their window MEANS add up to that
histogram's window mean (`tile`), which medians would not; and a mean is the
ratio of two window deltas, where a median of ~35 samples scatters over
sqrt(2)-wide buckets.  A program without the histograms (the parent) has
nothing to read: None.

Capture side.  The engine brackets every dispatch of a step program in a host
annotation `kafka.<prefill|decode|verify>[ids]`, and the device runs the
programs in the order they were dispatched: the k-th annotation's program is
the k-th launch on the device's `XLA Modules` line, up to one unknown shift.
The shift is there because the capture cuts both lists: launches of
dispatches made before the host's tracing began have no annotation, and
annotations whose launch falls before the device's lines begin or after they
end have no launch.  `pair_dispatches` finds it from what the two lists must
agree on: the kinds (a prefill annotation faces a prefill module) and
causality (no launch starts before its annotation began).  A prefill
launch's device wait is then module start - annotation end.

    python benchmarks/fetch_stages.py <trace_dir>    # the pairing, printed
"""

from __future__ import annotations

import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

import trace_reduce

STAGES = ("ttft_dev_wait_ms", "ttft_dev_exec_ms", "ttft_hold_ms",
          "ttft_emit_ms")

ANNOTATION = re.compile(r"^kafka\.(prefill|decode|verify)\[")
# every step program the engine dispatches under an annotation
# (step_programs.program_name): jit_body_decode, jit_fn_decode_fsm,
# jit_fn_multi_decode_<k>, jit_fn_verify, jit_fn_prefill_<bucket>,
# jit_fn_bprefill_<bucket>x<width>
STEP_MODULE = re.compile(r"^jit_(?:body_decode|fn_(b?prefill|verify)?)")

Span = Tuple[str, int, int]  # kind, start_ns, end_ns
MIN_PAIRED = 0.5  # of the shorter list, for a pairing of all dispatches


def hist_delta_mean(ctx: Dict[str, Any], name: str) -> Optional[float]:
    """Mean of a server histogram's samples over the window only: sum
    after minus before over count after minus before."""
    try:
        a = ctx["after"]["histograms"][name]
        b = ctx["before"]["histograms"][name]
        n = a["count"] - b["count"]
        return (a["sum"] - b["sum"]) / n if n > 0 else None
    except (KeyError, TypeError):
        return None


def tile(ctx: Dict[str, Any]) -> Dict[str, Optional[float]]:
    """The window means of the four stages, of `ttft_fetch_ms`, and what
    the four lack of it in % (0 but for the snapshot's rounding)."""
    out = {name: hist_delta_mean(ctx, name)
           for name in STAGES + ("ttft_fetch_ms",)}
    parts = [out[name] for name in STAGES]
    whole = out["ttft_fetch_ms"]
    out["tile_error_pct"] = (
        None if None in parts or not whole
        else 100.0 * (sum(parts) - whole) / whole)
    return out


def module_kind(name: str) -> Optional[str]:
    m = STEP_MODULE.match(name)
    if m is None:
        return None
    return {"prefill": "prefill", "bprefill": "prefill",
            "verify": "verify"}.get(m.group(1), "decode")


def dispatches(planes: List[Dict[str, Any]]
               ) -> Tuple[List[Span], List[Span]]:
    """(annotations, launches) of step programs in a capture as
    `trace_reduce.load_xplane` gives it, each by start time.  Launches are
    the first device plane's: under dp each replica's chip has its own
    order, and the annotations of all replicas share the host plane, so
    the pairing is for one chip."""
    notes: List[Span] = []
    launches: List[Span] = []
    seen_device = False
    for plane in planes:
        if trace_reduce.DEVICE_PLANE.match(plane["name"]):
            if seen_device:
                continue
            seen_device = True
            for line in plane["lines"]:
                if line["name"] != trace_reduce.MODULES_LINE:
                    continue
                launches += [(module_kind(n), s, s + d)
                             for n, s, d in line["events"] if module_kind(n)]
        else:
            for line in plane["lines"]:
                for n, s, d in line["events"]:
                    m = ANNOTATION.match(n)
                    if m:
                        notes.append((m.group(1), s, s + d))
    return (sorted(notes, key=lambda e: e[1]),
            sorted(launches, key=lambda e: e[1]))


def pair_dispatches(notes: List[Span], launches: List[Span]
                    ) -> Tuple[Optional[int], int]:
    """The shift d that pairs annotation k with launch k + d, and how many
    pairs it makes.  Of the shifts under which every pair agrees in kind
    and no launch starts before its annotation began, the one that makes
    the most pairs (the true one loses only what the capture's two ends
    cut; a wrong one must also line up every prefill with a prefill);
    (None, 0) where no shift pairs anything cleanly."""
    best: Tuple[Optional[int], int] = (None, 0)
    for d in range(-len(notes) + 1, len(launches)):
        lo, hi = max(0, -d), min(len(notes), len(launches) - d)
        if hi - lo <= best[1]:
            continue
        if all(notes[k][0] == launches[k + d][0]
               and launches[k + d][1] >= notes[k][1]
               for k in range(lo, hi)):
            best = (d, hi - lo)
    return best


def prefill_dev_waits(planes: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Device waits (ms) of the capture's prefill launches: module start -
    annotation end, one per prefill annotation whose launch the capture
    holds.  `dropped` counts the prefill annotations without one (their
    module starts outside the capture), `shift`, `pairs` and `paired_by`
    say how the lists were laid against each other."""
    notes, launches = dispatches(planes)
    d, pairs = pair_dispatches(notes, launches)
    by = "all dispatches"
    if pairs < MIN_PAIRED * min(len(notes), len(launches)):
        # the capture's ends cut a backlog's worth of each list, not half:
        # a launch the engine did not annotate, or an annotation without a
        # launch, sits in mid-capture and only a stretch lines up.  The
        # prefill lists alone, which then agree on causality only (the
        # k-th annotation with the k-th launch that starts after it began)
        by = "prefill launches alone"
        notes = [n for n in notes if n[0] == "prefill"]
        launches = [m for m in launches if m[0] == "prefill"]
        d, pairs = pair_dispatches(notes, launches)
    waits = [] if d is None else [
        (launches[k + d][1] - notes[k][2]) / 1e6
        for k in range(len(notes))
        if notes[k][0] == "prefill" and 0 <= k + d < len(launches)]
    n_prefill = sum(1 for n in notes if n[0] == "prefill")
    return {"waits_ms": waits, "dropped": n_prefill - len(waits),
            "shift": d, "pairs": pairs, "paired_by": by,
            "annotations": len(notes), "launches": len(launches)}


def annotation_seconds(planes: List[Dict[str, Any]]) -> Dict[str, float]:
    """Seconds the capture's host lines spent inside each kind of the
    engine's annotations (`kafka.<kind>[...]`): inside `fetch` the scheduler
    thread sat in a read, inside `decode` / `prefill` / `verify` in the
    dispatch call itself, which returns at once unless the runtime holds
    it.  `span` is first annotation's start to the last one's end."""
    out: Dict[str, float] = {}
    t0 = t1 = None
    for plane in planes:
        if trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for n, s, d in line["events"]:
                m = re.match(r"^kafka\.(\w+)\[", n)
                if m:
                    out[m.group(1)] = out.get(m.group(1), 0.0) + d / 1e9
                    t0 = s if t0 is None else min(t0, s)
                    t1 = s + d if t1 is None else max(t1, s + d)
    if t0 is not None:
        out["span"] = (t1 - t0) / 1e9
    return out


def capture_waits(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`prefill_dev_waits` of the cell's capture and its
    `annotation_seconds`, read once from where run.py had it put; None
    without a capture."""
    if "prefill_dev_waits" not in ctx:
        path = trace_reduce.find_xplane(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".bench_out", ctx["cell"].name, "trace")
        ) if ctx.get("trace") else None
        planes = None if path is None else trace_reduce.load_xplane(path)
        ctx["prefill_dev_waits"] = None if planes is None else dict(
            prefill_dev_waits(planes),
            host_in_annotation_s=annotation_seconds(planes))
    return ctx["prefill_dev_waits"]


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json

    found = trace_reduce.find_xplane(sys.argv[1])
    loaded = trace_reduce.load_xplane(found) if found else None
    print(json.dumps(loaded and dict(
        prefill_dev_waits(loaded),
        host_in_annotation_s=annotation_seconds(loaded))))
