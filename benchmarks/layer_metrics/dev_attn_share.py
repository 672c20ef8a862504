"""Step programs: % of the capture's device busy time in attention proper,
the `attn_core` and `attn_gather` scopes over all programs (the Pallas paged
decode and flash-prefill kernels; on the XLA path the window gather, scores,
softmax and weighted sum).  scope_reduce.py says how an op gets its scope."""
import scope_reduce


def read(ctx):
    return scope_reduce.share(scope_reduce.of_ctx(ctx),
                              ("attn_core", "attn_gather"))
