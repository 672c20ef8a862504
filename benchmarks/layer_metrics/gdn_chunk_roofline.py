"""Pallas chunked prefill kernel of Gated DeltaNet (`gdn_chunk`, one call a
linear layer of a prefill launch): the larger of the byte time and the flop
time of what the capture's calls MUST move and multiply
(`gdn_roofline.chunk_call`: the scalar-decay algorithm's matmuls, its rows
and the state once in, twice out) over their measured device time, in %.
What the kernel multiplies over a pair of heads' lanes for each head of the
pair, the inverse's doubling products and its float32 passes are not
counted, so the share errs low.  `delta_step_roofline`'s reader over another
kernel and another count.  A capture without the kernel has nothing to read:
None."""
import os

import gdn_roofline
import named

_step = named.load((os.path.dirname(os.path.dirname(__file__)),),
                   "layer_metrics", "delta_step_roofline")


def read(ctx):
    return _step.read(ctx, "gdn_chunk", gdn_roofline.chunk_call)
