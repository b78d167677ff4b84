"""Per fit, the device self time of the ops under the program's
`field.coded_gradient` scope (Phase 3's one-pass Pallas kernel, which
`kernels/ops.py` chooses on a TPU from the shape) inside loop-program
executions.  The scope sits inside `copml.fused_step`, which keeps its
time; under `jax.vmap` its op_name component reads
`vmap(field.coded_gradient)`.  Nothing where the loop program holds no
such ops."""

import re

from benchmarks.chip import scopes

SCOPE = "field.coded_gradient"
_COMPONENT = re.compile(r"(?:\w+\()*field\.coded_gradient\)*")


def scoped_ops(hlo_text: str) -> dict:
    """{instruction name: SCOPE} for the instructions of `hlo_text` with
    the scope among their `op_name` components."""
    return {name: SCOPE
            for name, op_name in scopes._INSTRUCTION.findall(hlo_text)
            if any(_COMPONENT.fullmatch(p) for p in op_name.split("/"))}


def read(ctx):
    if not ctx.trace.ops or not ctx.work:
        return None
    program = ctx.mix["loop_program"]
    texts = scopes.program_hlo(program)
    ops = scoped_ops(texts[0]) if len(texts) == 1 else {}
    if not ops:
        return None
    ns = scopes.scope_self_ns(ctx.trace, program, ops).get(SCOPE, 0.0)
    return ns / 1e6 / ctx.work
