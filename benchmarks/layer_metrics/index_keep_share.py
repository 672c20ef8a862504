"""Key selection: % of the keys a layer's indexer scored in the window's
decode steps that attention then read, from the engine's counters
(`/metrics` `engine.index_keys_kept` over `engine.index_keys_scored`, window
deltas, summed over replicas).  At ~29k keys a lane and index_topk 2,048 it
reads ~7.  A program without the counters, or a window in which no key was
scored (a model without an indexer), has nothing to read: None."""


def read(ctx):
    def total(snap, key):
        reps = snap.get("replicas") or [snap]
        return sum((r.get("engine") or {})[key] for r in reps)

    try:
        scored = (total(ctx["after"], "index_keys_scored")
                  - total(ctx["before"], "index_keys_scored"))
        kept = (total(ctx["after"], "index_keys_kept")
                - total(ctx["before"], "index_keys_kept"))
    except (KeyError, TypeError):
        return None
    return 100.0 * kept / scored if scored > 0 else None
