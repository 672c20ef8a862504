"""Pallas kernels: of the softmax steps the paged-decode kernel's walk took in
the window's decode steps (`engine.decode_steps_all`: a global layer's walk,
every 512-key step and each lane's last, a lane and a pass, one layer's
worth), the % whose copies were under way before their lane's program began
(`/metrics` `engine.decode_steps_ahead`, window deltas).  The call's lanes are
ONE pipeline: while a lane attends its last two steps the kernel starts its
neighbour's first two, so every lane but the call's first begins attending at
once instead of waiting out a copy nothing overlaps.  Two steps of a ~17-step
walk at ~8.3k keys a lane, fifteen lanes of sixteen: ~11; two of ~57 at ~29k:
~3.4.  A program without the counters (the parent: a lane filled and drained
a ring of its own), or a decode that does not walk in the Pallas kernel (the
`xla` backend, a model whose full layers read chosen rows: nothing walked),
has nothing to read: None."""
import readers


def read(ctx):
    ahead = readers.counter_delta(ctx, "engine", "decode_steps_ahead")
    every = readers.counter_delta(ctx, "engine", "decode_steps_all")
    if ahead is None or not every:
        return None
    return 100.0 * ahead / every
