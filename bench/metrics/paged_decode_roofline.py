"""Kernels: the paged decode attention's share of its roofline, %.

Measured: the summed device time of the paged decode kernel's events
(`pim_paged_attention`, kernels/paged_attention.py). Needed: for every
decode step of the window, in every layer, the K and V pages of each
live row, page-rounded from the KV lengths the harness tracks itself,
plus q in and out for every slot, and the QK and PV operations over the
live tokens (`bench/costs.paged_decode`). The kernel is bound by memory
at these sizes. Steps are matched to the trace's decode programs by
count: the mean need per window step, times the programs traced."""
from bench import costs


def read(ctx):
    progs = ctx.programs("decode")
    steps = [s for s in ctx.window_steps if s.emitted]
    if not progs or not steps:
        return None
    measured = ctx.trace.op_seconds("pim_paged_attention")
    if not measured:
        return None
    per_step = [costs.ideal_s(costs.paged_decode(
        ctx.shapes, s.decode_lens, ctx.page, ctx.slots), ctx.peak)[0]
        for s in steps]
    ideal = ctx.shapes.layers * sum(per_step) / len(steps) * len(progs)
    return 100.0 * ideal / measured
