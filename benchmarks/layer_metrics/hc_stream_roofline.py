"""The widened residual stream's mapping sites (`hc_map` + `hc_mix`, two a
layer): the least time the chip could take to move what the capture's sites
MUST move (`hc_roofline.site`: (2n + 2) C values a row, Phi and the stream
norm's weight once a site a pass) over the two scopes' measured device time,
in %.

Rows come from the capture's own launches and the programs' static shapes,
which their names carry: `jit_fn_prefill_<rows>` and `jit_fn_bprefill_<rows>x
<lanes>` run every row of their bucket, padding included; a decode program
runs `max_batch` rows a forward pass, and its passes are the capture's
innermost loops (the scan over layers runs once a pass; the single step
`jit_body_decode` has one a launch).  Sites a pass: 2 x `num_hidden_layers`.

Single digits in decode are the finding, not a fault of the count: 32 rows
are 1.2 MB a site, a microsecond at the chip's bandwidth, and the chain of
small XLA fusions that computes a site is bound by latency.  A capture without
the scopes (the parent, a model whose stream is one row) has nothing to read:
None."""
import re

import hc_roofline
import roofline
import scope_reduce

SCOPES = ("hc_map", "hc_mix")
PREFILL = re.compile(r"^jit_fn_b?prefill_(\d+)(?:x(\d+))?")
DECODE = re.compile(r"^jit_(body_decode|fn_multi_decode_\d+)")
VALUE_BYTES = 2  # the configuration serves a bf16 stream


def launches(trace, max_batch):
    """[(rows a pass, passes)] of the capture's step programs."""
    out = []
    for name, m in trace["modules"].items():
        pre = PREFILL.match(name)
        if pre:
            out.append((int(pre.group(1)) * int(pre.group(2) or 1),
                        m["count"]))
        elif DECODE.match(name):
            out.append((max_batch, m["loops"] or m["count"]))
    return out


def read(ctx):
    hf = ctx["cell"].config
    n = int(hf.get("hc_mult") or 1)
    acc = scope_reduce.of_ctx(ctx)
    trace = ctx.get("trace")
    if (n == 1 or not acc or not trace
            or not any(s in acc["by_component"] for s in SCOPES)
            or scope_reduce.share(acc, SCOPES) is None):
        return None
    seconds = sum(acc["by_component"].get(s, 0.0) for s in SCOPES)
    ran = launches(trace, int(hf["serving"]["max_batch"]))
    if seconds <= 0 or not ran:
        return None
    sites = 2 * hf["num_hidden_layers"]
    flops = nbytes = 0.0
    for rows, passes in ran:
        f, b = hc_roofline.site(rows, n, hf["hidden_size"], VALUE_BYTES)
        flops += f * sites * passes
        nbytes += b * sites * passes
    share, _bound = roofline.roofline_share(flops, nbytes, seconds,
                                            ctx["info"]["kind"])
    return share
