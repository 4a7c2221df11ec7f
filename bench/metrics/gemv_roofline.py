"""Kernels: the GEMV's share of its roofline, %.

For every GEMV event in the trace (`pim_linear`, kernels/gemv_pim.py),
the operations and bytes its call needs come from its own shapes, read
from the event's HLO text (rows x in -> out), by `bench/costs.gemv`; the
least time is the larger of operations over peak and bytes over peak
bandwidth. The share is the summed least time over the summed measured
time. Decode rows (the slots) and short chunks are bound by memory;
chunks of more rows than the chip's ridge (about 240 operations a byte)
by compute."""
from bench import costs, tracing


def read(ctx):
    if ctx.trace is None:
        return None
    ideal = measured = 0.0
    for name, _, dur in ctx.trace.ops:
        if tracing.op_base(name) != tracing.GEMV:
            continue
        shape = tracing.gemv_shape(name)
        if shape is None:
            return None
        rows, r, c = shape
        ideal += costs.ideal_s(costs.gemv(rows, r, c, True), ctx.peak)[0]
        measured += dur
    return 100.0 * ideal / measured if measured else None
