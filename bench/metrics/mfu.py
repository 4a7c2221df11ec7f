"""Device: model operations per second over the chip's bf16 peak, %.

The operations the model needs for the window's work (`bench/costs`):
for every decode step, its live rows through every matrix and the LM
head, plus attention over each row's context; for every prefill chunk,
its prompt tokens through every block matrix, causal attention, and the
LM head once. Over the traced window's seconds times the peak. Rows the
decode program computes for empty slots are not model work."""
from bench import costs


def read(ctx):
    if not ctx.window_s or not ctx.window_steps:
        return None
    s = ctx.shapes
    flops = 0.0
    for st in ctx.window_steps:
        if st.decode_lens:
            flops += costs.model_flops_decode(s, st.decode_lens)
        for start, n in st.chunks:
            flops += costs.model_flops_chunk(s, start, n)
    # the steps' own span is the host's; the trace's window is the same
    # steps on the profiler's clock
    return 100.0 * flops / (ctx.window_s * ctx.peak["bf16_flops"])
