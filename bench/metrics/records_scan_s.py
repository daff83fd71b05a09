"""Seconds per release the service spends counting a release's tables from
the records it holds: the ``records.scan`` spans (from the count's dispatch
to its tables ready on the device), per release over whole batches."""
from bench.batches import per_release, seconds


def read(ctx):
    return per_release(ctx, ("records.scan",), seconds)
