"""Step programs: % of the capture's device busy time in the state-space
mixers of a hybrid decoder, the `ssm_proj` + `ssm_conv` + `ssm_scan` scopes
over all programs: the Mamba layers' projections, the causal conv, and the
recurrence (the Pallas selective-scan kernel in prefill, one closed-form step
with the state's read and write in decode).  The configuration lists the
scopes (`scopes`).  A capture without them (the parent, a configuration
without state-space layers) has nothing to read: None, not 0."""
import scope_reduce

SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan")


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or not any(s in acc["by_component"] for s in SCOPES):
        return None
    return scope_reduce.share(acc, SCOPES)
