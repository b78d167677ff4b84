"""Device time of the training loop's program per iteration: the summed
durations of the traced `_scan_iterations` executions over the
iterations they ran."""


def read(ctx):
    events = ctx.xplane.program_events(ctx.trace, ctx.mix["loop_program"])
    if not events or not ctx.work:
        return None
    return 1e3 * ctx.xplane.program_time_s(
        ctx.trace, ctx.mix["loop_program"]) / ctx.work
