"""Per training iteration, the device self time of the loop program's ops
under the program's `copml.fused_step` scope: Phases 3-4 in
`kernels/ops.fused_step` (coded gradient, decode, TruncPr and update),
the XLA composition or the Pallas kernel alike."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_iteration_ms(ctx, "copml.fused_step")
