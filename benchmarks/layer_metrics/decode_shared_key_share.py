"""Jitted step programs: of the keys the XLA decode read walked in the
window's decode steps (`engine.decode_keys_walked`: lanes x trips x trip keys
a step and layer), the % it read ONCE for every lane of the pass, in whole
trips (`/metrics` `engine.decode_keys_shared`, window deltas; under dp summed
over the replicas).  A decode pass whose active lanes hold the same pages in
the same leading columns of their page tables (a prefix attached to all of
them) reads those pages once a trip of the walk and folds them into every
lane's running softmax in one product; the rest of each lane's context is
gathered lane by lane.  At ~8.3k keys a lane of which 7.4k are the shared
system prompt it reads ~82 (14 of 17 trips of 512); 0 where the lanes share
less than one trip.  A program without the counter (the parent), or a decode
that does not walk in XLA (a Pallas cell, a latent model: nothing walked), has
nothing to read: None."""
import readers


def read(ctx):
    shared = readers.counter_delta(ctx, "engine", "decode_keys_shared")
    walked = readers.counter_delta(ctx, "engine", "decode_keys_walked")
    if shared is None or not walked:
        return None
    return 100.0 * shared / walked
