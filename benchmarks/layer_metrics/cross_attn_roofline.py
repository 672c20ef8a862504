"""Pallas paged-decode kernel of a hybrid decoder, decode's reads of its ONE
full cache: the least time the chip could take for the decode programs'
`paged_decode_attention` calls over their measured device time.

A decode pass makes eight of them over the same rows: the full layer's own
read and seven cross layers' (the kernel's name does not tell them apart, and
their bytes are the same).  A prefill launch makes seven more for its last
real rows, one to four lanes wide: those are left out, by the lanes in each
call's own result shape (the event's HLO text), because the bytes below are
counted at decode's occupancy.  Bytes (bandwidth-bound: 6 flops per 2 bytes
of bf16 K/V under the differential pairing) come from roofline.paged_decode
at the window's mean context for `decode_batch_occupancy` lanes, times the
counted calls, as `paged_attn_roofline` counts them; the differential
output, twice as wide as q, is counted at q's width (under 0.1% of a call's
bytes at these contexts), so the share errs low.  The windowed kernel
(`paged_decode_attention_window`) is `diff_window_attn_roofline`'s;
`paged_attn_roofline` matches both kernels by its pattern and counts every
call at the full context, so this cell cannot join its list.  A capture
without the kernel has nothing to read: None."""
import e2e
import kernel_calls
import readers
import roofline

KERNEL = "paged_decode_attention"


def read(ctx):
    calls = kernel_calls.calls(ctx, KERNEL)
    lanes = readers.batch_occupancy(ctx)
    rows = [r for r in ctx["log"] if e2e.ok(r) and r.get("usage")
            and r["in_window"]]
    if not calls or not lanes or not rows:
        return None
    max_batch = ctx["cell"].config["serving"]["max_batch"]
    decode = [s for text, s in calls
              if kernel_calls.result_lanes(text) == max_batch]
    if not decode or sum(decode) <= 0:
        return None
    mean_ctx = sum(r["usage"]["prompt_tokens"]
                   + r["usage"]["completion_tokens"] / 2.0
                   for r in rows) / len(rows)
    shape = readers.attention_shape(ctx)
    flops, nbytes = roofline.paged_decode(
        [int(mean_ctx)], shape["num_heads"], shape["num_kv_heads"],
        shape["head_dim"], shape["page_size"])
    share, _bound = roofline.roofline_share(
        flops * len(decode) * lanes, nbytes * len(decode) * lanes,
        sum(decode), ctx["info"]["kind"])
    return share
