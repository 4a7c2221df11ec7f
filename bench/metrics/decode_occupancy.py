"""Scheduler: live decode rows per decode step, as % of the slots.

From the harness's own request bookkeeping: in every window step that
returned tokens, the rows that go on decoding after it, over the
engine's slots."""


def read(ctx):
    rows = [len(s.decode_lens) for s in ctx.window_steps if s.emitted]
    if not rows:
        return None
    return 100.0 * sum(rows) / (len(rows) * ctx.slots)
