"""Megabytes per release copied device to host: the ``bytes`` counters of
the ``measure.multi.fetch`` and ``reconstruct.fetch`` spans (the arrays
each fetch returned), per release over whole batches."""
from bench.batches import megabytes, per_release


def read(ctx):
    return per_release(ctx, ("measure.multi.fetch", "reconstruct.fetch"),
                       megabytes)
