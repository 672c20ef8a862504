"""Routed feed-forward block at decode: the (row, expert) pairs an expert
whose weights a pass READ was read for, over the window's decode passes:
`/metrics` `engine.moe_picks_held` (the active rows' picks that fell on an
expert this chip holds, counted by the program beside the experts read) over
`engine.moe_experts_read`, window deltas; under dp summed over the replicas.
What a held share's cut is sized by ("2.2 pairs an expert") measured instead
of worked out by hand: the weights of an expert are fetched once for that
many rows' products.  Beside it `engine.moe_picks_routed` counts all the
rows' picks, held here or not: picks_held / picks_routed is the share of its
deployment's load this chip's experts draw (~1/2 for one of two chips).
Granite-4.0-H at 16 lanes: 80 of 160 picks a layer fall on the 36 held, which
the pass reads ~32.7 of: ~2.4.  A program without the counters (the parent)
or with no routed block (both stay 0) has nothing to read: None."""
import readers


def read(ctx):
    pairs = readers.counter_delta(ctx, "engine", "moe_picks_held")
    read_ = readers.counter_delta(ctx, "engine", "moe_experts_read")
    if pairs is None or not read_:
        return None
    return pairs / read_
