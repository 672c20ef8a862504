"""Pallas latent (MLA) paged-decode kernel: the least time the chip could take
for the capture's latent decode attention calls over their measured device
time.

Operations and bytes come from mla_roofline.latent_decode at the window's
mean context (prompt + half the reply of the window's finished requests) for
`decode_batch_occupancy` lanes, times the kernel's calls in the capture (one
call serves every lane of one layer).  The stored row's width is what the
program says it allocates (`/metrics` `engine.kv_bytes_per_token` over the
layers and the pool's 2-byte values), not a width computed here.  The kernel
has a name of its own (`paged_decode_attention_latent`); a program without it
(the parent, a configuration without latent attention) has nothing to read:
None."""
import e2e
import mla_roofline
import readers
import roofline

KERNEL = r"paged_decode_attention_latent"
POOL_VALUE_BYTES = 2  # the configuration serves a bf16 pool


def read(ctx):
    hf = ctx["cell"].config
    seconds = readers.op_seconds(ctx, KERNEL)
    calls = readers.op_calls(ctx, KERNEL)
    lanes = readers.batch_occupancy(ctx)
    rows = [r for r in ctx["log"] if e2e.ok(r) and r.get("usage")
            and r["in_window"]]
    eng = (ctx["after"].get("replicas") or [ctx["after"]])[0].get("engine")
    stored = (eng or {}).get("kv_bytes_per_token")
    if (not hf.get("kv_lora_rank") or not seconds or not calls or not lanes
            or not rows or not stored):
        return None
    mean_ctx = sum(r["usage"]["prompt_tokens"]
                   + r["usage"]["completion_tokens"] / 2.0
                   for r in rows) / len(rows)
    row_values = stored // (hf["num_hidden_layers"] * POOL_VALUE_BYTES)
    flops, nbytes = mla_roofline.latent_decode(
        [int(mean_ctx)], hf["num_attention_heads"], hf["kv_lora_rank"],
        hf["qk_rope_head_dim"], row_values, hf["serving"]["page_size"],
        POOL_VALUE_BYTES)
    share, _bound = roofline.roofline_share(
        flops * calls * lanes, nbytes * calls * lanes, seconds,
        ctx["info"]["kind"])
    return share
