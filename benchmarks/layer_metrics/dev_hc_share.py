"""Step programs: % of the capture's device busy time in the widened residual
stream of Xing4.0's layers, the two `hc_*` scopes over all programs: a
sublayer's per-token mappings (`hc_map`: the norm over a token's four rows,
the one product with Phi, the sigmoids, the clamp, exp and the 20 Sinkhorn
rounds) and the mixes (`hc_mix`: H_pre X ahead of the sublayer, H_res X +
H_post^T y after it, which is this model's residual add).  Sixteen sites a
pass of the cut: what the cell exists to show.  The configuration lists the
scopes (`scopes`).  A capture without them (the parent, a model whose stream
is one row) has nothing to read: None, not 0."""
import scope_reduce

SCOPES = ("hc_map", "hc_mix")


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or not any(s in acc["by_component"] for s in SCOPES):
        return None
    return scope_reduce.share(acc, SCOPES)
