"""Per fit, the device time of the compiled setup program's executions
(`_setup_program`: Copml.setup's quantize, Shamir sharing, LCC encode of
X and secure X^T y).  Nothing where no such program ran, as where setup
runs op by op."""

SETUP_PROGRAM = "_setup_program"


def read(ctx):
    if not ctx.work or not ctx.xplane.program_events(ctx.trace,
                                                     SETUP_PROGRAM):
        return None
    return 1e3 * ctx.xplane.program_time_s(ctx.trace, SETUP_PROGRAM) \
        / ctx.work
