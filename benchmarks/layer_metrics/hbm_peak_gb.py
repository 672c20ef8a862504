"""Device: `peak_bytes_in_use` of the fullest chip, in GB.  The allocator's
peak over the process' life: boot and warm-up set it, so it is the same to
the byte across seeds and mixes.  It is what a replica costs (lower is
better), not what the traffic uses: `kv_pool_used_share` says that."""
import readers


def read(ctx):
    peak = readers.memory_peak_bytes(ctx["info"])
    return peak / 1e9 if peak else None
