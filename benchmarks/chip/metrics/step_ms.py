"""Window wall time over all the training iterations completed in it."""


def read(ctx):
    return 1e3 * ctx.window_s / ctx.work
