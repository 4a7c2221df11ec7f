"""Scheduler: time from submit() to admission, 95th percentile, ms.

Read from the program's own request traces (`RequestTrace.admit_t -
submit_t`, serving/telemetry.py) of the requests due in the window;
a request never admitted reads nothing."""


def read(ctx):
    waits = []
    for r in ctx.window_recs:
        tr = ctx.request_traces.get(r.uid)
        if tr is not None and tr.admit_t is not None:
            waits.append((tr.admit_t - tr.submit_t) * 1e3)
    return ctx.percentile(waits, 95) if waits else None
