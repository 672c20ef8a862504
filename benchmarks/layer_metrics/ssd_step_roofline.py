"""Pallas decode-step kernel of Mamba-2's recurrence (`ssd_step`, one call a
layer of a decode pass of Falcon-H1): the least time the chip could take to
move what the capture's calls MUST move (`ssd_roofline.step_call`: every
lane's 4.19 MB of state in and out, its rows) over their measured device time,
in %.  Bandwidth-bound by construction; under 100 by what the kernel's
arithmetic on the VPU costs beside its DMAs.  `delta_step_roofline`'s reader
over another kernel and another count.  A capture without the kernel (the
parent, the `xla` backend, a model without such layers) has nothing to read:
None."""
import os

import named
import ssd_roofline

_step = named.load((os.path.dirname(os.path.dirname(__file__)),),
                   "layer_metrics", "delta_step_roofline")


def read(ctx):
    return _step.read(ctx, "ssd_step", ssd_roofline.step_call)
