"""Key selection at decode: of the keys a layer's indexer scored in the
window's decode steps, the share it scored ONCE for every lane of the pass,
in % (`/metrics` `engine.index_keys_shared` / `engine.index_keys_scored`,
window deltas; under dp summed over the replicas).  A decode pass whose lanes
hold the same pages in the same leading columns of their page tables (a prefix
attached to all of them) reads those pages' index keys once a trip of the walk
and scores all lanes against them in one product, whole trips only; the rest
of each lane's context is gathered lane by lane.  At ~29k keys a lane of which
28.2k are the shared system prompt it reads ~90; 0 where the lanes share less
than one trip.  A program without the counter (the parent), or a window in
which no key was scored (a model without an indexer), has nothing to read:
None."""
import readers


def read(ctx):
    shared = readers.counter_delta(ctx, "engine", "index_keys_shared")
    scored = readers.counter_delta(ctx, "engine", "index_keys_scored")
    if shared is None or not scored:
        return None
    return 100.0 * shared / scored
