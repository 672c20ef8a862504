"""Step programs: % of the capture's device busy time in the gated short
convolutions of the conv layout (LFM2), the `conv_proj` + `conv_mix` scopes
over all programs: W_in, W_out and the residual add, and between them the
elementwise middle (B * u, the taps over [tail | pass] with the tail's read
from and write to its state slot, the C * gate).  Eleven of the cut's
fourteen layers for a twentieth of a pass is the model's point: a later PR
must not grow this.  The configuration lists the scopes (`scopes`).  A
capture without them (the parent, a configuration without conv layers) has
nothing to read: None, not 0."""
import scope_reduce

SCOPES = ("conv_proj", "conv_mix")


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or not any(s in acc["by_component"] for s in SCOPES):
        return None
    return scope_reduce.share(acc, SCOPES)
