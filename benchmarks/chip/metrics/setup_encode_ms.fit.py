"""Per fit, the device self time of the setup program's ops under its
`copml.setup.encode` scope (Phases 2b-2c: the masks Z and their shares,
the LCC encode and the reconstruct of X~), inside `_setup_program`
executions.  Nothing where no such program, or no scoped one, ran."""

from benchmarks.chip import scopes

SETUP_PROGRAM = "_setup_program"
SCOPE = "copml.setup.encode"


def read(ctx):
    if not ctx.trace.ops or not ctx.work:
        return None
    texts = scopes.program_hlo(SETUP_PROGRAM)
    if len(texts) != 1:
        return None
    found = scopes.op_scopes(texts[0])
    if SCOPE not in found.values():
        return None
    ns = scopes.scope_self_ns(ctx.trace, SETUP_PROGRAM, found).get(SCOPE, 0.0)
    return ns / 1e6 / ctx.work
