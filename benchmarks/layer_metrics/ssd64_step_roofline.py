"""Pallas decode-step kernel of Mamba-2's recurrence at heads of 64 x 128
(`ssd_step`, one call a Mamba-2 layer of a decode pass of Nemotron-H; two
heads a 128-lane tile, eight heads and one group's B and C a grid step): the
least time the chip could take to move what the capture's calls MUST move
(`ssd_roofline.step_call`, from the call's own operand shapes: every lane's
2.1 MB of state in and out, its rows) over their measured device time, in %.
`ssd_step_roofline`'s reader under a name this cell can be listed on: that
metric's list of cells is a `benchmark` PR's to edit (ROADMAP R1 folds the
twins).  A capture without the kernel (the parent, the `xla` backend) has
nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "ssd_step_roofline").read
