"""Jitted step programs: of the keys the static windows of the window's
decode steps hold (lanes x max_pages_per_seq x page_size a step and layer),
the % the XLA decode read gathered: lanes x chunks x chunk keys, the chunk
count being the bound the walk's device loop computes from the longest
active lane.  The engine counts both at dispatch (`/metrics`
`engine.decode_keys_walked`, `engine.decode_keys_window`, monotonic; under
dp the aggregate's `engine` group sums the replicas'); the window's share is
100 x delta walked / delta window.  100 would be the materialised window the
walk replaced.  A program without the counters (the parent) or a decode that
does not walk in XLA (a Pallas cell: both stay 0) has nothing to read:
None."""
import readers


def read(ctx):
    walked = readers.counter_delta(ctx, "engine", "decode_keys_walked")
    window = readers.counter_delta(ctx, "engine", "decode_keys_window")
    if not window or walked is None:
        return None
    return 100.0 * walked / window
