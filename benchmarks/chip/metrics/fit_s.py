"""Window wall time over the whole fits completed in it."""


def read(ctx):
    return ctx.window_s / ctx.work
