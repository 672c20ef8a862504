"""Pallas latent-prefill fold (`latent_prefill_fold`, one call a trip of
latent prefill's key walk): the MXU work the capture's calls EXECUTED over
their measured device time times the chip's bf16 peak, in %.

Work: 2 x lanes x heads x rows x keys x (d_nope + d_rope + d_v) a call, read
from the shapes of the call's own operands in its HLO text, which is the
event's name on the device's op line (q_nope^T [B, N, dn, S], q_rope^T
[B, N, dr, S], k_nope [B, N, T, dn], k_r, v^T [B, N, dv, T], in the order the
kernel takes them).  Masked keys and padded rows count, because the kernel
multiplies them; it skips no tile.  Time: the calls' own durations.  A
capture without the kernel (the parent, the `xla` backend, a model whose
prefill does not walk) has nothing to read: None."""
import os
import re

import roofline
import scope_reduce
import trace_reduce

KERNEL = re.compile(r"^%?latent_prefill_fold(\.\d+)? = ")
SHAPE = re.compile(r"\w+\[([\d,]+)\]")


def call_flops(name):
    """MXU flops of the call whose HLO text is `name`, or None where the
    text does not hold the five operands' shapes."""
    _, _, operands = name.partition("custom-call(")
    dims = [tuple(int(d) for d in m.group(1).split(","))
            for m in SHAPE.finditer(operands)][:5]
    if len(dims) < 5 or [len(d) for d in dims] != [4, 4, 4, 3, 4]:
        return None
    (b, n, dn, s), (_, _, dr, _), (_, _, t, _), _, (_, _, dv, _) = dims
    return 2.0 * b * n * s * t * (dn + dr + dv)


def fold_calls(ctx):
    """[(HLO text, seconds)] of the capture's calls of the kernel, read
    once from where run.py had the capture put."""
    if "latent_fold_calls" not in ctx:
        path = trace_reduce.find_xplane(os.path.join(
            scope_reduce.ROOT, ".bench_out", ctx["cell"].name, "trace")
        ) if ctx.get("trace") else None
        ctx["latent_fold_calls"] = None if path is None else [
            (name, dur / 1e9)
            for plane in trace_reduce.load_xplane(path)
            if trace_reduce.DEVICE_PLANE.match(plane["name"])
            for line in plane["lines"] if line["name"] == trace_reduce.OPS_LINE
            for name, _, dur in line["events"] if KERNEL.match(name)]
    return ctx["latent_fold_calls"]


def read(ctx):
    calls = fold_calls(ctx)
    if not calls:
        return None
    flops = [call_flops(name) for name, _ in calls]
    seconds = sum(s for _, s in calls)
    if None in flops or seconds <= 0:
        return None
    share, _bound = roofline.roofline_share(
        sum(flops), 0.0, seconds, ctx["info"]["kind"])
    return share
