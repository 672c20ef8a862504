"""Step programs: % of the capture's device busy time spent moving the KV
pool around the model, over all programs: the `kv_write` scope (the scatter
of new rows) and `scan_plumbing` (ops of the layer scan's body under no leaf
scope: each layer's pool sliced out of the stacked array and written back)."""
import scope_reduce


def read(ctx):
    return scope_reduce.share(scope_reduce.of_ctx(ctx),
                              ("kv_write", scope_reduce.SCAN_PLUMBING))
