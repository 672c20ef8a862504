"""Pallas chunked prefill kernel of Mamba-2's recurrence at heads of 64 x 128
(`ssd_chunk`, one call a Mamba-2 layer of a prefill launch of Nemotron-H): the
larger of the byte time and the flop time of what the capture's calls MUST
move and multiply (`ssd_roofline.chunk_call`, from the call's own operand
shapes) over their measured device time, in %.  The kernel takes two heads a
128-lane tile and multiplies over the whole tile, twice the products counted
here: the share errs low by construction.  `ssd_chunk_roofline`'s reader under
a name this cell can be listed on: that metric's list of cells is a
`benchmark` PR's to edit (ROADMAP R1 folds the twins).  A capture without the
kernel has nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "ssd_chunk_roofline").read
