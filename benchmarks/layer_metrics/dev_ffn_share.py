"""Step programs: % of the capture's device busy time in the feed-forward
block, the `mlp`, `moe_router` and `moe_experts` scopes over all programs.
Higher is better: with the weights read once a step this is the model's
floor, and everything else is what a serving change can remove."""
import scope_reduce


def read(ctx):
    return scope_reduce.share(scope_reduce.of_ctx(ctx),
                              ("mlp", "moe_router", "moe_experts"))
