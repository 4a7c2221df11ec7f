"""Page pool: the most pages in use after any window step, as % of the
pool's usable pages (the allocator's `used_pages`, serving/kvcache.py;
the trash page is not usable)."""


def read(ctx):
    if not ctx.window_steps:
        return None
    return 100.0 * max(s.used_pages for s in ctx.window_steps) / (
        ctx.pool_pages - 1)
