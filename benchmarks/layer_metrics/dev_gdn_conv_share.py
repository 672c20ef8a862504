"""Step programs: % of the capture's device busy time under `kda_conv` alone,
the Gated DeltaNet layers' three short convolutions: what the tail of 11,520
channels laid (8, 4320) costs.  Its pieces are 1,440 values, 11.25 lane
tiles, so `tail_conv_step` declines it and decode runs the XLA chain (the
slot's read, the concatenation, the taps, the shifted tail's write) that PR
64 measured at 178 us a layer on Solar-Open2: this share times the pass is
that cost for this model, layer by layer.  A capture without the scope (the
parent, a configuration without such layers) has nothing to read: None."""
import scope_reduce

SCOPES = ("kda_conv",)


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or not any(s in acc["by_component"] for s in SCOPES):
        return None
    return scope_reduce.share(acc, SCOPES)
