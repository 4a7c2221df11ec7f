"""Model step: device time of one decode program, mean, ms.

Programs in the trace that hold the paged decode kernel
(`pim_paged_attention`) are decode steps (`decode_step`,
models/transformer.py, as the engine jits it)."""


def read(ctx):
    progs = ctx.programs("decode")
    if not progs:
        return None
    return 1e3 * sum(p[2] for p in progs) / len(progs)
