"""Pallas flash-prefill kernel: the least time the chip could take for the
prefill attention of the requests whose first token fell inside the capture
over the kernel's measured device time.  Flops (compute-bound at these
contexts) from roofline.flash_prefill with chunk = prompt_tokens -
cached_tokens and start = cached_tokens, times layers.  An estimate: a
prefill that straddles the capture's edge is counted whole or not at all."""
import e2e
import readers
import roofline

KERNEL = r"paged_prefill"


def read(ctx):
    seconds = readers.op_seconds(ctx, KERNEL)
    wall = readers.capture_wall(ctx)
    if not seconds or not wall:
        return None
    c0 = readers.to_client_clock(ctx, wall[0])
    c1 = readers.to_client_clock(ctx, wall[1])
    shape = readers.attention_shape(ctx)
    flops = nbytes = 0.0
    for r in ctx["log"]:
        if not r.get("usage") or r.get("t_first") is None:
            continue
        if not c0 <= r["t_first"] < c1:
            continue
        cached = e2e.cached_tokens(r)
        f, b = roofline.flash_prefill(
            r["usage"]["prompt_tokens"] - cached, cached,
            shape["num_heads"], shape["num_kv_heads"], shape["head_dim"])
        flops += f * shape["layers"]
        nbytes += b * shape["layers"]
    if flops <= 0:
        return None
    share, _bound = roofline.roofline_share(
        flops, nbytes, seconds, ctx["info"]["kind"])
    return share
