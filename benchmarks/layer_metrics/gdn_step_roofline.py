"""Pallas decode-step kernel of Gated DeltaNet (`gdn_step`, one call a linear
layer of a decode pass): the least time the chip could take to move what the
capture's calls MUST move (`gdn_roofline.step_call`: every lane's PUBLISHED
state, 30 x 96 x 192 x 4 B, in and out, and its rows at their published
widths; a padded operand reads lower, never over) over their measured device
time, in %.  Bandwidth-bound by construction; under 100 by what the kernel's
arithmetic on the VPU and its grid steps cost beside its DMAs.
`delta_step_roofline`'s reader over another kernel and another count.  A
capture without the kernel (the parent, the `xla` backend, a model without
such layers) has nothing to read: None."""
import os

import gdn_roofline
import named

_step = named.load((os.path.dirname(os.path.dirname(__file__)),),
                   "layer_metrics", "delta_step_roofline")


def read(ctx):
    return _step.read(ctx, "gdn_step", gdn_roofline.step_call)
