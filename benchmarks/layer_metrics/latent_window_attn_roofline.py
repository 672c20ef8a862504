"""Pallas windowed latent (MLA) paged-decode kernel, the sliding layers' decode
read: the least time the chip could take for the capture's calls over their
measured device time.

Operations and bytes come from sparse_roofline.latent_window_decode at
min(the window's mean context, the configuration's `sliding_window_size`) for
`decode_batch_occupancy` lanes, times the kernel's calls in the capture (one
call serves every lane of one sliding layer), at the `swa_*` sizes.  The
kernel has a name of its own (`paged_decode_attention_latent_window`); a
program without it has nothing to read: None."""
import e2e
import readers
import roofline
import sparse_roofline

KERNEL = r"paged_decode_attention_latent_window"
POOL_VALUE_BYTES = 2  # the configuration serves a bf16 pool


def read(ctx):
    hf = ctx["cell"].config
    seconds = readers.op_seconds(ctx, KERNEL)
    calls = readers.op_calls(ctx, KERNEL)
    lanes = readers.batch_occupancy(ctx)
    rows = [r for r in ctx["log"] if e2e.ok(r) and r.get("usage")
            and r["in_window"]]
    window = hf.get("sliding_window_size")
    if (not window or not seconds or not calls or not lanes or not rows
            or not hf.get("swa_kv_lora_rank")):
        return None
    mean_ctx = sum(r["usage"]["prompt_tokens"]
                   + r["usage"]["completion_tokens"] / 2.0
                   for r in rows) / len(rows)
    latent, rope = hf["swa_kv_lora_rank"], hf["swa_qk_rope_head_dim"]
    flops, nbytes = sparse_roofline.latent_window_decode(
        [int(mean_ctx)], int(window), hf["swa_num_attention_heads"], latent,
        rope, latent + -(-rope // 128) * 128, hf["serving"]["page_size"],
        dtype_bytes=POOL_VALUE_BYTES)
    share, _bound = roofline.roofline_share(
        flops * calls * lanes, nbytes * calls * lanes, seconds,
        ctx["info"]["kind"])
    return share
