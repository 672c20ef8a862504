"""Pallas chunked prefill kernel of Mamba-2's recurrence at 128 heads of 64 x
128 in ONE group (`ssd_chunk`, one call a Mamba-2 layer of a prefill launch
of Granite-4.0-H): the larger of the byte time and the flop time of what the
capture's calls MUST move and multiply (`ssd_roofline.chunk_call`, from the
call's own operand shapes) over their measured device time, in %.  The count
takes `C B^T` once a GROUP; the kernel holds 64 heads a grid step, half the
one group, and takes it once a grid step, twice a chunk, and it takes two
heads a 128-lane tile and multiplies over the whole tile: the share errs low
by construction, never over.  `ssd_chunk_roofline`'s reader under a name this
cell can be listed on: that metric's list of cells is a `benchmark` PR's to
edit (ROADMAP R1 folds the twins).  A capture without the kernel has nothing
to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "ssd_chunk_roofline").read
