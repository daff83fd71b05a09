"""Percent of its roofline that the records -> tables count reaches: the
least time of the ``records.scan`` spans in the traced sub-window over the
device time inside them.

Least time: the records read once, each value in the fewest whole bytes
that hold every attribute's values, and every table written once at 4 B a
cell, over the chip's HBM bandwidth.  Any way of counting (a scatter, a
one-hot sum, an MXU matmul, packed records) moves at least these bytes, so
no implementation reads above 100%.  Device time: the union of the device's
ops inside each span; the worker waits inside the span until the tables
are ready, so the count's ops lie within it.
"""
import math

from bench import trace_reduce

TABLE_BYTES = 4      # one float32 count


def value_bytes(sizes) -> int:
    """Fewest whole bytes that hold a value of every attribute."""
    return max(1, math.ceil(math.log2(max(sizes)) / 8))


def least_bytes(rows: int, sizes, cells: int) -> int:
    """Bytes one count of ``rows`` records over ``cells`` table cells must
    move at least."""
    return rows * len(sizes) * value_bytes(sizes) + TABLE_BYTES * cells


def read(ctx):
    s = ctx.sub
    if s is None:
        return None
    # The sub-window's host spans keep the order of the span log.
    spans = ctx.named("records.scan", ctx.all_spans)
    marks = [h for h in s["host"] if h[2] == "records.scan"]
    ops = [(a, b) for a, b, _n in s["ops"]]
    sizes = ctx.cell.config["sizes"]
    least = dev = 0.0
    for span, (t0, t1, _name) in zip(spans, marks):
        if not s["lo"] <= t0 < t1 <= s["hi"]:
            continue
        least += least_bytes(int(span["attrs"]["rows"]), sizes,
                             int(span["attrs"]["cells"])) \
            / ctx.peaks["hbm_bytes_per_s"]
        dev += trace_reduce.busy_ns(ops, t0, t1) / 1e9
    if least <= 0 or dev <= 0:
        return None
    return 100.0 * least / dev
