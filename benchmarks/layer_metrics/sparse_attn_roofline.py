"""Sparse decode attention of the full layers (XLA): the least time the chip
could take to read the rows the indexer kept and attend them, over the device
time the decode programs spend doing so.

Time: the `attn_select` (the read) and `attn_core` (attention over the rows;
the sliding layers' kernel sits under `attn_window`, the absorb einsums under
`attn_latent_proj`) components of the decode programs in the capture.
Operations and bytes: sparse_roofline.chosen_rows_decode at the window's mean
context for `decode_batch_occupancy` lanes, times the full layers' calls: the
capture's decode passes (the windowed latent kernel's calls over the sliding
layers, one call a layer a pass) times the full layers.  A capture without
the `attn_select` scope or without the kernel has nothing to read: None."""
import re

import e2e
import readers
import roofline
import scope_reduce
import sparse_roofline

KERNEL = r"paged_decode_attention_latent_window"
DECODE_PROGRAM = re.compile(r"^jit_(body_decode|fn_multi_decode)")
SCOPES = ("attn_select", "attn_core")
FULL, SLIDING = "full_attention", "sliding_attention"
POOL_VALUE_BYTES = 2  # the configuration serves a bf16 pool


def layer_counts(hf):
    kinds = (hf.get("layer_types") or [])[:hf["num_hidden_layers"]]
    return kinds.count(FULL), kinds.count(SLIDING)


def mean_context(ctx):
    rows = [r for r in ctx["log"] if e2e.ok(r) and r.get("usage")
            and r["in_window"]]
    if not rows:
        return None
    return sum(r["usage"]["prompt_tokens"]
               + r["usage"]["completion_tokens"] / 2.0
               for r in rows) / len(rows)


def read(ctx):
    hf = ctx["cell"].config
    acc = scope_reduce.of_ctx(ctx)
    calls = readers.op_calls(ctx, KERNEL)
    lanes = readers.batch_occupancy(ctx)
    mean_ctx = mean_context(ctx)
    n_full, n_sliding = layer_counts(hf)
    if (not acc or "attn_select" not in acc["by_component"] or not calls
            or not lanes or not mean_ctx or not hf.get("index_topk")
            or not n_full or not n_sliding):
        return None
    seconds = sum(row.get(c, 0.0) for prog, row in acc["table"].items()
                  if DECODE_PROGRAM.match(prog) for c in SCOPES)
    if seconds <= 0:
        return None
    latent, rope = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    flops, nbytes = sparse_roofline.chosen_rows_decode(
        [int(mean_ctx)], hf["index_topk"], hf["num_attention_heads"],
        latent, rope, latent + -(-rope // 128) * 128, POOL_VALUE_BYTES)
    reads = calls / n_sliding * n_full * lanes
    share, _bound = roofline.roofline_share(
        flops * reads, nbytes * reads, seconds, ctx["info"]["kind"])
    return share
