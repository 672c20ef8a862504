"""Boot: share (%) of the step-program variants the boot resolved that the
program store loaded (`/metrics` `compiles.store_hits` over `store_hits` +
`store_misses` + `store_fallbacks`, `runtime/program_store.py`: a hit is
loaded with no trace, no lowering and no look-up in the compile cache; a miss
is compiled and written; a fallback is a call handed back to the jit).  100 on
a warm boot, 0 on the first boot of a tree.  None on a program without the
counters, or where the boot resolved no program through the store."""


def read(ctx):
    try:
        compiles = ctx["after"]["compiles"]
        hits = int(compiles["store_hits"])
        total = hits + int(compiles["store_misses"]) + int(
            compiles["store_fallbacks"])
    except (KeyError, TypeError, ValueError):
        return None
    return 100.0 * hits / total if total else None
