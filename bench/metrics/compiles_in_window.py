"""Engine loop: programs lowered while the window ran (should be 0).

Counted by a `jax.monitoring` listener on JAX's lowering event, which
fires for every program this process had not built yet, whether its
binary then came from the persistent cache or from the compiler."""


def read(ctx):
    return ctx.compiles
