"""Step programs: % of the capture's device busy time in what only the latent
(MLA) form adds around attention proper, the `attn_latent_proj` scope (inside
`attn_core`) over all programs: the absorb (q^ = q_nope W_kvb^K) and un-absorb
(o = o^ W_kvb^V) einsums of paged decode, and the expansion of cached rows
through W_kvb in prefill.  The configuration lists the scope (`scopes`), so
`dev_attn_share` beside it holds attention proper alone.  A capture without
the scope (the parent, a configuration that does not list it) has nothing to
read: None, not 0."""
import scope_reduce

SCOPE = "attn_latent_proj"


def read(ctx):
    acc = scope_reduce.of_ctx(ctx)
    if not acc or SCOPE not in acc["by_component"]:
        return None
    return scope_reduce.share(acc, (SCOPE,))
