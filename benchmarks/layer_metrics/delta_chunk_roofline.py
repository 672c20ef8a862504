"""Pallas chunked prefill kernel of the gated delta rule (`gated_delta_chunk`,
one call a linear-attention layer of a prefill launch): the larger of the byte
time and the flop time of what the capture's calls MUST move and multiply
(`delta_roofline.chunk_call`) over their measured device time, in %.
`delta_step_roofline`'s reader over another kernel and another count.  A
capture without the kernel has nothing to read: None."""
import os

import delta_roofline
import named

_step = named.load((os.path.dirname(os.path.dirname(__file__)),),
                   "layer_metrics", "delta_step_roofline")


def read(ctx):
    return _step.read(ctx, "gated_delta_chunk", delta_roofline.chunk_call)
