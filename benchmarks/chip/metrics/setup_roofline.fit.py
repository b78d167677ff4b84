"""The setup program's share of its roofline: the least time setup could
take (its field MACs at the int8 peak or its least bytes at the HBM
bandwidth, whichever is longer: setup_counts.least_setup_s) over the
device time of `_setup_program` executions per fit.  Prints which bound
binds.  Nothing where no such program ran, as where setup runs op by op."""

import sys

from benchmarks.chip import setup_counts

SETUP_PROGRAM = "_setup_program"


def read(ctx):
    if not ctx.work or not ctx.xplane.program_events(ctx.trace,
                                                     SETUP_PROGRAM):
        return None
    least, bound = setup_counts.least_setup_s(ctx.cfg, ctx.device_kind)
    print(f"setup_roofline: least setup time {least!r} s, bound by {bound}",
          file=sys.stderr)
    per_fit = ctx.xplane.program_time_s(ctx.trace, SETUP_PROGRAM) / ctx.work
    return 100.0 * least / per_fit
