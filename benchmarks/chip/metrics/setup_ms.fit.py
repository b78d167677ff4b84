"""Per fit, the time from the benchmark's annotation at api.fit entry to
the first device execution of the training loop's program: eager
Copml.setup (quantize, shamir, lagrange, mpc) and its dispatch."""


def read(ctx):
    lead = ctx.xplane.lead_in(ctx.trace, "bench:call", ctx.mix["loop_program"])
    if not lead:
        return None
    return sum(ns for ns, _ in lead) / len(lead) / 1e6
