"""Step programs: device time of the decode programs' launches in the capture
over the forward passes they ran (their innermost `while` loops: the scan
over layers runs once per decode step), summed over the chips.

Which launches are decode programs: the engine jits its single step as
`body` and its fused multi-step scan as `fn`, and prefill chunks as `fn`
too, so a program's name does not say what it is (PERF.md section 7).  A
`jit_fn` program counts as decode when its launches hold the paged-decode
kernel; on a configuration without kernels (the XLA path) the reader takes
`jit_body` plus the `jit_fn` program with the largest total time, which in a
decode-bound capture is the fused decode.
"""
import re

DECODE_KERNEL = re.compile(r"paged_decode")


def decode_modules(trace):
    mods = trace["modules"]
    out = {k: m for k, m in mods.items() if k.startswith("jit_body")}
    fns = {k: m for k, m in mods.items() if k.startswith("jit_fn")}
    if any(m["kernels"] for m in fns.values()):
        out.update({k: m for k, m in fns.items()
                    if any(DECODE_KERNEL.search(n) for n in m["kernels"])})
    elif fns:
        k = max(fns, key=lambda n: fns[n]["total_s"])
        out[k] = fns[k]
    return out


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    mods = decode_modules(trace).values()
    steps = sum(m["loops"] for m in mods)
    total = sum(m["total_s"] for m in mods)
    return 1e3 * total / steps if steps > 0 and total > 0 else None
