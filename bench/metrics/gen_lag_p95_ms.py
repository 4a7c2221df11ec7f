"""Load generator: how late requests were sent, 95th percentile, ms.

Submit time minus due time of the requests due in the window, on the
harness's clock. Open loops only: a closed loop sends when a client's
last request finished, so it is never late and this reads nothing."""


def read(ctx):
    if ctx.loop != "open":
        return None
    lags = [(r.submit_t - r.due) * 1e3 for r in ctx.window_recs]
    return ctx.percentile(lags, 95) if lags else None
