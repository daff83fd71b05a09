"""Seconds per release of Python garbage collection on any thread: the
``python.gc`` spans, per release over whole batches.  A program that records
collections also records ``reconstruct.fetch``, so a window without a
collection reads 0 there, and a program without either reads nothing."""
from bench.batches import per_release, seconds


def read(ctx):
    return per_release(ctx, ("python.gc",), seconds,
                       present=("reconstruct.fetch",))
