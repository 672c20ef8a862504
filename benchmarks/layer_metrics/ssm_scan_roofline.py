"""Pallas selective-scan kernel (`selective_scan`, one call a Mamba layer of
a prefill launch): the least time the chip could take to move what the
capture's calls MUST move (`ssm_roofline.scan_call`) over their measured
device time, in %.

Shapes come from each call's own operands in its HLO text, which is the
event's name on the device's op line: x [lanes, rows, d_inner] first, A
[d_state, d_inner] third, in the order the kernel takes them.  Time: the
calls' own durations.  A capture without the kernel (the parent, the `xla`
backend, a model without state-space layers) has nothing to read: None."""
import kernel_calls
import roofline
import ssm_roofline

KERNEL = "selective_scan"


def call_shape(text):
    """(lanes, rows, d_inner, d_state) of the call whose HLO text is `text`,
    or None where the text does not hold the operands' shapes."""
    dims = kernel_calls.shapes(text, "operands")[:3]
    if len(dims) < 3 or [len(d) for d in dims] != [3, 3, 2]:
        return None
    (lanes, rows, d_inner), _, (d_state, _) = dims
    return lanes, rows, d_inner, d_state


def read(ctx):
    calls = kernel_calls.calls(ctx, KERNEL)
    if not calls:
        return None
    shapes = [call_shape(text) for text, _ in calls]
    seconds = sum(s for _, s in calls)
    if None in shapes or seconds <= 0:
        return None
    nbytes = sum(ssm_roofline.scan_call(*shape)[1] for shape in shapes)
    share, _bound = roofline.roofline_share(
        0.0, nbytes, seconds, ctx["info"]["kind"])
    return share
