"""Megabytes per release copied host to device: the ``bytes`` counters of
the ``measure.multi.dispatch`` and ``reconstruct.dispatch`` spans (numpy
arguments of each program call), per release over whole batches."""
from bench.batches import megabytes, per_release


def read(ctx):
    return per_release(ctx, ("measure.multi.dispatch",
                             "reconstruct.dispatch"), megabytes)
