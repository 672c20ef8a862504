"""Admission, parking, batching: median over the window of one iteration of
the engine thread's loop that SEATED a request (`/metrics` `histograms`
`sched_iter_admit_ms`: end of the inbox wait to end of delivery), in ms:
what every other lane waits while one is admitted.  The five classes' counts
and medians over the window are printed beside it (`admit`, `prefill`,
`multi`, `decode`, `held`: what the iteration dispatched).  0.0 where no
iteration of the window admitted; None on a program without the
histograms."""
import json
import sys

import readers

CLASSES = ("admit", "prefill", "multi", "decode", "held")


def read(ctx):
    try:
        after, before = ctx["after"]["histograms"], ctx["before"]["histograms"]
        table = {}
        for c in CLASSES:
            name = f"sched_iter_{c}_ms"
            n = sum(after[name]["counts"]) - sum(before[name]["counts"])
            table[c] = {"n": n, "p50_ms": readers.hist_delta_quantile(
                ctx, name, 0.5)}
    except (KeyError, TypeError):
        return None
    print("sched_account: iterations " + json.dumps(table),
          file=sys.stderr, flush=True)
    return table["admit"]["p50_ms"] or 0.0
