"""Fetch pipeline, on the profiler's clock: mean over the capture's prefill
launches of (the device starts the `jit_fn_prefill_*` / `jit_fn_bprefill_*`
module - the host's `kafka.prefill[...]` annotation around its dispatch
ends), in ms.  Annotations and launches are paired in dispatch order
(`fetch_stages.pair_dispatches`); prefill annotations whose module starts
outside the capture are dropped and their count printed.  It is every
launch's wait, where the program-side `ttft_dev_wait_ms_mean` holds the last
chunk's of each request over the whole window: the two agree where the
engine's stamps are honest.  None without a capture, or where nothing
pairs."""
import json
import sys

import fetch_stages


def read(ctx):
    found = fetch_stages.capture_waits(ctx)
    if found is None:
        return None
    waits = found["waits_ms"]
    print("fetch_stages: prefill launches " + json.dumps(
        dict(found, waits_ms=[round(w, 3) for w in waits])),
        file=sys.stderr, flush=True)
    return sum(waits) / len(waits) if waits else None
