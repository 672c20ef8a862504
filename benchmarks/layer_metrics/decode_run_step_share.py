"""Pallas kernels: of the whole softmax steps the paged-decode kernel's walk
fetched in the window's decode steps (`engine.decode_steps_walked`: a global
layer's walk, the 512-key steps before a lane's last, a lane and a pass, one
layer's worth), the % it fetched as ONE copy a pool (`/metrics`
`engine.decode_steps_run`, window deltas; under dp summed over the replicas).
A step whose 32 pages are one ascending run of physical pages lies side by
side in the pool, and the kernel moves it with one DMA descriptor a pool in
place of one a page.  The shared system prompt, laid down by the first request
on a fresh pool, is such a run: at ~8.3k keys a lane of which 7.4k are the
prompt it reads ~85-90 (14 of the ~16 whole steps); private tails, allocated
from recycled pages, stay a copy a page.  A program without the counter (the
parent), or a decode that does not walk in the Pallas kernel (the `xla`
backend, a model whose full layers read chosen rows: nothing walked), has
nothing to read: None."""
import readers


def read(ctx):
    run = readers.counter_delta(ctx, "engine", "decode_steps_run")
    walked = readers.counter_delta(ctx, "engine", "decode_steps_walked")
    if run is None or not walked:
        return None
    return 100.0 * run / walked
