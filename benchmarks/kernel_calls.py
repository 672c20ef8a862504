"""The capture's calls of one Pallas kernel, event by event: what a roofline
reader needs where the sum over a kernel's name (`readers.op_seconds`) is not
enough, because the calls differ in shape.

A kernel keeps the name of the jitted function that wraps it, and the event's
name on the device's op line is the call's HLO text, `%<kernel>.3 = <result
shapes> custom-call(<operand shapes> ...)`, so a call's shapes are read from
the event itself."""
import os
import re

import scope_reduce
import trace_reduce

SHAPE = re.compile(r"\w+\[([\d,]+)\]")


def _kernel_events(ctx):
    """[(HLO text, seconds)] of every custom kernel's call in the capture,
    read once from where run.py had the capture put; None without one."""
    if "kernel_events" not in ctx:
        path = trace_reduce.find_xplane(os.path.join(
            scope_reduce.ROOT, ".bench_out", ctx["cell"].name, "trace")
        ) if ctx.get("trace") else None
        ctx["kernel_events"] = None if path is None else [
            (name, dur / 1e9)
            for plane in trace_reduce.load_xplane(path)
            if trace_reduce.DEVICE_PLANE.match(plane["name"])
            for line in plane["lines"] if line["name"] == trace_reduce.OPS_LINE
            for name, _, dur in line["events"] if trace_reduce.is_kernel(name)]
    return ctx["kernel_events"]


def calls(ctx, kernel):
    """[(HLO text, seconds)] of the capture's calls of `kernel`; None without
    a capture."""
    events = _kernel_events(ctx)
    if events is None:
        return None
    rx = re.compile(rf"^%?{re.escape(kernel)}(\.\d+)? = ")
    return [(text, s) for text, s in events if rx.match(text)]


def shapes(text, part):
    """The shapes, as tuples, in one part of a call's HLO text: `result`
    (ahead of `custom-call(`) or `operands` (after it)."""
    head, _, tail = text.partition("custom-call(")
    return [tuple(int(d) for d in m.group(1).split(","))
            for m in SHAPE.finditer(head if part == "result" else tail)]


def result_lanes(text):
    """The leading size of a call's first result: its lanes."""
    dims = shapes(text, "result")
    return dims[0][0] if dims else None
