"""Step programs: % of the capture's device busy time in the Mamba-2 (SSD)
mixers of Falcon-H1's parallel layers, the four `ssd_*` scopes over all
programs: the projections with their multipliers and the branch's add
(`ssd_proj`), the short convolution with its tail's read and write
(`ssd_conv`), the elementwise decay, gate and grouped norm (`ssd_gate`) and
the recurrence itself, the two Pallas kernels or the XLA scan (`ssd_scan`).
Every layer of the cut has one beside its attention: what the cell exists to
show.  The configuration lists the scopes (`scopes`).  A capture without them
(the parent, a configuration without such layers) has nothing to read: None,
not 0."""
import scope_reduce

SCOPES = ("ssd_proj", "ssd_conv", "ssd_gate", "ssd_scan")


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or not any(s in acc["by_component"] for s in SCOPES):
        return None
    return scope_reduce.share(acc, SCOPES)
