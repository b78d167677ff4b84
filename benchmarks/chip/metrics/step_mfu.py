"""The whole step's share of the chip's peak: the least time one
iteration could take (the larger of its field MACs at the int8 peak and
its least bytes at the HBM bandwidth, counts.least_step_s) over the
traced wall time per iteration.  Prints which bound binds."""

import sys


def read(ctx):
    if not ctx.work:
        return None
    least, bound = ctx.counts.least_step_s(ctx.cfg, ctx.device_kind)
    print(f"step_mfu: least step time {least!r} s, bound by {bound}",
          file=sys.stderr)
    wall = ctx.xplane.window_s(ctx.trace) / ctx.work
    return 100.0 * least / wall
