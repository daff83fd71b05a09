"""Host seconds per release spent in device program calls: the
``measure.multi.dispatch`` and ``reconstruct.dispatch`` spans (argument
copies and enqueueing; no wait on the device), per release over whole
batches."""
from bench.batches import per_release, seconds


def read(ctx):
    return per_release(ctx, ("measure.multi.dispatch",
                             "reconstruct.dispatch"), seconds)
