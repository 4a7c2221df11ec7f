"""Scheduler: time from admission to the request's first prefill chunk,
95th percentile, ms.

Read from the program's own request traces (`RequestTrace.chunks[0][0]
- admit_t`, serving/telemetry.py: the first chunk's stamp is taken just
before its jitted call) of the requests due in the window that ran a
chunk. The engine runs one chunk a step, oldest admission first, so
this is the wait behind the prefills admitted before it."""


def read(ctx):
    waits = []
    for r in ctx.window_recs:
        tr = ctx.request_traces.get(r.uid)
        if tr is not None and tr.admit_t is not None and tr.chunks:
            waits.append((tr.chunks[0][0] - tr.admit_t) * 1e3)
    return ctx.percentile(waits, 95) if waits else None
