"""Model step: device time of one prefill-chunk program, mean, ms.

Programs in the trace that hold the chunked-prefill kernel
(`pim_paged_prefill_attention`) are prefill chunks (`prefill_chunk`,
models/transformer.py, as the engine jits it)."""


def read(ctx):
    progs = ctx.programs("prefill_chunk")
    if not progs:
        return None
    return 1e3 * sum(p[2] for p in progs) / len(progs)
