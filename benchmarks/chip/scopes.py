"""Device time of the program's named scopes inside one compiled program.

The program names the parts of its training loop with `jax.named_scope`
(`copml.encode_model`, `copml.step_rand`, `copml.fused_step`).  XLA keeps
the scope in each instruction's `op_name` metadata, but a TPU trace names
an op event by its HLO text without that metadata (`%fusion.610 =
s32[50,153650]{...} fusion(...), kind=..., calls=...`).  So an op's scope
is looked up by instruction name in the compiled program's own HLO: the
executable that the process still holds after the traced window, from
the device client's `live_executables()`.

An op counts for the first `copml.*` component of its `op_name`; ops with
none are left unscoped.  Times are self times (xplane.self_times) of the
ops that start inside an execution of the program, in the traced window.
"""

from __future__ import annotations

import re
from collections import defaultdict

from benchmarks.chip import xplane

SCOPE_PREFIX = "copml."
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = .*?metadata=\{op_name="([^"]*)"', re.M)


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: scope} for the instructions of `hlo_text` whose
    `op_name` holds a `copml.*` scope."""
    out = {}
    for name, op_name in _INSTRUCTION.findall(hlo_text):
        scope = next((p for p in op_name.split("/")
                      if p.startswith(SCOPE_PREFIX)), None)
        if scope is not None:
            out[name] = scope
    return out


def program_hlo(program: str) -> list:
    """The HLO text of every executable the process holds on its first
    device's client whose module name contains `program`."""
    import jax
    return [mod.to_string()
            for ex in jax.devices()[0].client.live_executables()
            for mod in ex.hlo_modules() if program in mod.name]


def instruction(op: str) -> str:
    """`%fusion.610 = s32[...] fusion(...)` -> `fusion.610`."""
    return op.split(" = ", 1)[0].lstrip("%")


def scope_self_ns(trace, program: str, scopes: dict) -> dict:
    """{scope: ns}: self time of the ops that start inside `program`'s
    executions in the window, by scope (None: unscoped), averaged over
    devices."""
    runs = xplane.program_events(trace, program)
    tot = defaultdict(float)
    n_dev = max(1, len(trace.ops))
    for evs in trace.ops.values():
        inside = [(n, s, e) for n, s, e in evs
                  if any(lo <= s < hi for lo, hi in runs)]
        for name, ns in xplane.self_times(inside):
            tot[scopes.get(instruction(name))] += ns / n_dev
    return dict(tot)


def per_iteration_ms(ctx, scope: str):
    """Device ms per training iteration of the ops under `scope` in the
    traced fits' loop programs (fits x `iters` iterations), or None where
    the trace has no device ops or the loop program has no such scope."""
    program = ctx.mix["loop_program"]
    if not ctx.trace.ops or not ctx.work:
        return None
    texts = program_hlo(program)
    if len(texts) != 1:
        return None
    scopes = op_scopes(texts[0])
    if scope not in scopes.values():
        return None
    ns = scope_self_ns(ctx.trace, program, scopes).get(scope, 0.0)
    return ns / 1e6 / (ctx.work * ctx.mix["iters"])
