"""Pallas decode-step kernel of the gated delta rule (`gated_delta_step`, one
call a linear-attention layer of a decode pass): the least time the chip could
take to move what the capture's calls MUST move (`delta_roofline.step_call`:
every lane's state in and out, its rows) over their measured device time, in
%.  Bandwidth-bound by construction; under 100 by what the kernel's
arithmetic on the VPU costs beside its DMAs.

Shapes come from each call's own operands in its HLO text, which is the
event's name on the device's op line.  A capture without the kernel (the
parent, the `xla` backend, a model without such layers) has nothing to read:
None."""
import delta_roofline
import kernel_calls
import roofline

KERNEL = "gated_delta_step"
CALL = delta_roofline.step_call


def read(ctx, kernel=None, call=None):
    calls = kernel_calls.calls(ctx, kernel or KERNEL)
    if not calls:
        return None
    counted = [(call or CALL)(kernel_calls.shapes(text, "operands"))
               for text, _ in calls]
    seconds = sum(s for _, s in calls)
    if None in counted or seconds <= 0:
        return None
    share, _bound = roofline.roofline_share(
        sum(f for f, _ in counted), sum(b for _, b in counted), seconds,
        ctx["info"]["kind"])
    return share
