"""Host seconds per release spent waiting on device programs and copying
their outputs back: the ``measure.multi.fetch`` and ``reconstruct.fetch``
spans, per release over whole batches."""
from bench.batches import per_release, seconds


def read(ctx):
    return per_release(ctx, ("measure.multi.fetch", "reconstruct.fetch"),
                       seconds)
