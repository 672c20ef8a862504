"""Pallas chunked prefill kernel of Mamba-2's recurrence (`ssd_chunk`, one
call a layer of a prefill launch of Falcon-H1): the larger of the byte time
and the flop time of what the capture's calls MUST move and multiply
(`ssd_roofline.chunk_call`) over their measured device time, in %.
`delta_step_roofline`'s reader over another kernel and another count.  A
capture without the kernel has nothing to read: None."""
import os

import named
import ssd_roofline

_step = named.load((os.path.dirname(os.path.dirname(__file__)),),
                   "layer_metrics", "delta_step_roofline")


def read(ctx):
    return _step.read(ctx, "ssd_chunk", ssd_roofline.chunk_call)
