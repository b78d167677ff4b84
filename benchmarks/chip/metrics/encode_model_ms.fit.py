"""Per training iteration, the device self time of the loop program's ops
under the program's `copml.encode_model` scope: Phase 2's model encode
(the T mask vectors, their shares, the LCC encode and reconstruct)."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_iteration_ms(ctx, "copml.encode_model")
