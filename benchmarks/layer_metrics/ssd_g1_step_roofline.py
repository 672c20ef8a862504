"""Pallas decode-step kernel of Mamba-2's recurrence at 128 heads of 64 x 128 in
ONE group (`ssd_step`, one call a Mamba-2 layer of a decode pass of
Granite-4.0-H, 64 heads a grid step: half the group): the least time the chip
could take to move what the capture's calls MUST move
(`ssd_roofline.step_call`, from the call's own operand shapes: every lane's
4.19 MB of state in and out, its rows) over their measured device time, in %.
`ssd_step_roofline`'s reader under a name this cell can be listed on: that
metric's list of cells is a `benchmark` PR's to edit (ROADMAP R1 folds the
twins).  A capture without the kernel has nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "ssd_step_roofline").read
