"""Batching: busy lanes per decode step over the window."""
import readers


def read(ctx):
    return readers.batch_occupancy(ctx)
