"""Step programs: % of the capture's device busy time in the gated delta-rule
linear-attention layers (Solar-Open2), the four `kda_*` scopes over all
programs: the projections and the residual add (`kda_proj`), the three short
convolutions with their tails' read and write (`kda_conv`), the elementwise
gates and norms (`kda_gate`) and the recurrence itself, the two Pallas kernels
or the XLA scan (`kda_delta`).  Six of the cut's eight layers: what the cell
exists to show.  The configuration lists the scopes (`scopes`).  A capture
without them (the parent, a configuration without such layers) has nothing to
read: None, not 0."""
import scope_reduce

SCOPES = ("kda_proj", "kda_conv", "kda_gate", "kda_delta")


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or not any(s in acc["by_component"] for s in SCOPES):
        return None
    return scope_reduce.share(acc, SCOPES)
