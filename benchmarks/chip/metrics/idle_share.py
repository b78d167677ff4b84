"""Share of the traced window in which no operation ran on the device:
1 - union of device operation intervals / window.  It reads every
`idle_share.<traffic>` entry."""


def read(ctx):
    share = ctx.xplane.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
