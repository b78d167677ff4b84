"""Per fit, the device program executions that start between api.fit
entry and the training loop's program: the eager setup's programs."""


def read(ctx):
    lead = ctx.xplane.lead_in(ctx.trace, "bench:call", ctx.mix["loop_program"])
    if not lead:
        return None
    return sum(n for _, n in lead) / len(lead)
