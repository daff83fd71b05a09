"""Host seconds per release spent embedding subset answers for
reconstruction: the ``reconstruct.embed`` spans (one per signature group:
embedding, stack, cast), per release over whole batches."""
from bench.batches import per_release, seconds


def read(ctx):
    return per_release(ctx, ("reconstruct.embed",), seconds)
