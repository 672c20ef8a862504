"""Pallas flash-prefill kernel at a wide grouped-query geometry: the attention
flops the MODEL needs for the prefill chunks of the requests whose first token
fell inside the capture, over the device seconds of the capture's
`paged_prefill_attention` calls times the chip's bf16 peak, in %.

Flops: `prefill_flops.gqa_prefill` (4 x pairs attended under the mask x query
heads x head_dim) with chunk = prompt_tokens - cached_tokens and start =
cached_tokens, once for every `full_attention` layer and once, under the
window, for every `sliding_attention` layer of the configuration.  The
kernel's name does not tell the two kinds of call apart, so the share is of
all of them together.  What the kernel spends beyond the model's need (the
block-diagonal q rows: Hkv x the lanes, 7/8 of them zeros at 8 KV heads;
float32 operands on the MXU; a bucket's padded rows) shows as a low share.
An estimate, as `flash_prefill_roofline`: a prefill that straddles the
capture's edge is counted whole or not at all.  The capture's interval on the
client's clock is the `/debug/profile` reply's where it came before the
window closed, else where `run.py` posts the capture, a third into the window
(my chip run 2, PR 43: `stop_trace` took 36.7 s and the reply came after the
readers ran).  A capture without the kernel (the parent, the `xla` backend)
has nothing to read: None."""
import e2e
import prefill_flops
import readers
import roofline

KERNEL = r"paged_prefill_attention"
WINDOWED, GLOBAL = "sliding_attention", "full_attention"


def capture_interval(ctx):
    """(start, end) of the traced interval on the client's clock."""
    wall = readers.capture_wall(ctx)
    if wall:
        return (readers.to_client_clock(ctx, wall[0]),
                readers.to_client_clock(ctx, wall[1]))
    start = (ctx["t_open"] + (ctx["t_close"] - ctx["t_open"]) / 3.0
             + readers.START_TRACE_S)
    return start, start + ctx["trace"]["window_s"]


def read(ctx):
    seconds = readers.op_seconds(ctx, KERNEL)
    if not seconds:
        return None
    hf = ctx["cell"].config
    kinds = list(hf.get("layer_types") or ())[:hf["num_hidden_layers"]]
    if not kinds:
        return None
    n_window, n_global = kinds.count(WINDOWED), kinds.count(GLOBAL)
    c0, c1 = capture_interval(ctx)
    shape = readers.attention_shape(ctx)
    flops = 0.0
    for r in ctx["log"]:
        if not r.get("usage") or r.get("t_first") is None:
            continue
        if not c0 <= r["t_first"] < c1:
            continue
        cached = e2e.cached_tokens(r)
        chunk = r["usage"]["prompt_tokens"] - cached
        heads, d = shape["num_heads"], shape["head_dim"]
        flops += n_global * prefill_flops.gqa_prefill(chunk, cached, heads, d)
        flops += n_window * prefill_flops.gqa_prefill(
            chunk, cached, heads, d, hf.get("sliding_window"))
    if flops <= 0:
        return None
    share, _bound = roofline.roofline_share(
        flops, 0.0, seconds, ctx["info"]["kind"])
    return share
