"""Per fit, the device self time of the ops under the program's
`field.short_matmul` scope (the short-contraction field products that
`core/field.matmul` runs as one Pallas pass on a TPU: LCC encode, Shamir
share and reconstruct), inside `_setup_program` and loop-program
executions.  The scope sits inside the `copml.*` scopes, which keep its
time; under `jax.vmap` its op_name component reads
`vmap(field.short_matmul)`.  Nothing where neither program holds such
ops."""

import re

from benchmarks.chip import scopes

SETUP_PROGRAM = "_setup_program"
SCOPE = "field.short_matmul"
_COMPONENT = re.compile(r"(?:\w+\()*field\.short_matmul\)*")


def scoped_ops(hlo_text: str) -> dict:
    """{instruction name: SCOPE} for the instructions of `hlo_text` with
    the scope among their `op_name` components."""
    return {name: SCOPE
            for name, op_name in scopes._INSTRUCTION.findall(hlo_text)
            if any(_COMPONENT.fullmatch(p) for p in op_name.split("/"))}


def read(ctx):
    if not ctx.trace.ops or not ctx.work:
        return None
    ns, found = 0.0, False
    for program in (SETUP_PROGRAM, ctx.mix["loop_program"]):
        texts = scopes.program_hlo(program)
        ops = scoped_ops(texts[0]) if len(texts) == 1 else {}
        if ops:
            found = True
            ns += scopes.scope_self_ns(ctx.trace, program, ops).get(SCOPE, 0.0)
    return ns / 1e6 / ctx.work if found else None
