"""Pallas windowed paged-decode kernel: the least time the chip could take for
the capture's sliding-window decode attention calls over their measured
device time.

Bytes (bandwidth-bound, as the global kernel) come from
window_roofline.windowed_decode at min(the window's mean context, the
configuration's `sliding_window`) for `decode_batch_occupancy` lanes, times
the windowed kernel's calls in the capture (one call serves every lane of one
windowed layer).  The kernel has a name of its own
(`paged_decode_attention_window`); a program without it (the parent, a
configuration without windowed layers) has nothing to read: None."""
import e2e
import readers
import roofline
import window_roofline

KERNEL = r"paged_decode_attention_window"


def read(ctx):
    window = ctx["cell"].config.get("sliding_window")
    seconds = readers.op_seconds(ctx, KERNEL)
    calls = readers.op_calls(ctx, KERNEL)
    lanes = readers.batch_occupancy(ctx)
    rows = [r for r in ctx["log"] if e2e.ok(r) and r.get("usage")
            and r["in_window"]]
    if not window or not seconds or not calls or not lanes or not rows:
        return None
    mean_ctx = sum(r["usage"]["prompt_tokens"]
                   + r["usage"]["completion_tokens"] / 2.0
                   for r in rows) / len(rows)
    shape = readers.attention_shape(ctx)
    flops, nbytes = window_roofline.windowed_decode(
        [int(mean_ctx)], int(window), shape["num_heads"],
        shape["num_kv_heads"], shape["head_dim"], shape["page_size"])
    share, _bound = roofline.roofline_share(
        flops * calls * lanes, nbytes * calls * lanes, seconds,
        ctx["info"]["kind"])
    return share
