"""Routed feed-forward block at decode: of the held experts over the routed
layers of the window's decode passes, the share whose weights a pass READ, in
% (`/metrics` `engine.moe_experts_read` / `engine.moe_experts_held`, window
deltas; under dp summed over the replicas).  100 where the block reads every
held expert whatever the lanes picked (the dense form: counted on the host);
under it where the block dispatches by token and fetches only the experts
some lane picked (counted by the program, a pass), and the expert weights'
bytes a pass fall with it.  A program without the counters
(the parent) or with no routed block (both stay 0) has nothing to read:
None."""
import readers


def read(ctx):
    read_ = readers.counter_delta(ctx, "engine", "moe_experts_read")
    held = readers.counter_delta(ctx, "engine", "moe_experts_held")
    if read_ is None or not held:
        return None
    return 100.0 * read_ / held
