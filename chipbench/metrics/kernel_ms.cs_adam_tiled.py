"""Device time of the ``cs_adam_tiled`` kernel per step, in ms."""

KERNEL = "cs_adam_tiled"


def read(ctx):
    ns = ctx.trace.kernel_ns(KERNEL)
    if ns <= 0 or ctx.steps <= 0:
        return None
    return ns / 1e6 / ctx.steps
