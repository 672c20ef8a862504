"""Pallas paged-decode kernel: the least time the chip could take for the
capture's decode attention calls over the kernel's measured device time.

Bytes (the kernel is bandwidth-bound: 4 flops per 2 bytes of bf16 KV) come
from roofline.paged_decode at the window's mean context (prompt + half the
reply of the window's finished requests; bytes are linear in the context up
to page rounding) for `decode_batch_occupancy` lanes, times the kernel's
calls in the capture (one call serves every lane of one layer)."""
import e2e
import readers
import roofline

KERNEL = r"paged_decode"


def read(ctx):
    seconds = readers.op_seconds(ctx, KERNEL)
    calls = readers.op_calls(ctx, KERNEL)
    lanes = readers.batch_occupancy(ctx)
    rows = [r for r in ctx["log"] if e2e.ok(r) and r.get("usage")
            and r["in_window"]]
    if not seconds or not calls or not lanes or not rows:
        return None
    mean_ctx = sum(r["usage"]["prompt_tokens"]
                   + r["usage"]["completion_tokens"] / 2.0
                   for r in rows) / len(rows)
    shape = readers.attention_shape(ctx)
    flops, nbytes = roofline.paged_decode(
        [int(mean_ctx)], shape["num_heads"], shape["num_kv_heads"],
        shape["head_dim"], shape["page_size"])
    share, _bound = roofline.roofline_share(
        flops * calls * lanes, nbytes * calls * lanes, seconds,
        ctx["info"]["kind"])
    return share
