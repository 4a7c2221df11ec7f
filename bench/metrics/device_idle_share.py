"""Device: the share of the traced window in which no program ran, %.

The window runs from the first engine step's `serve_step` span to the
last one's end; busy is the union of the programs' intervals in it."""


def read(ctx):
    if not ctx.window_s:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
