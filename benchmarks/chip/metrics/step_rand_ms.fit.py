"""Per training iteration, the device self time of the loop program's ops
under the program's `copml.step_rand` scope: the per-step randomness (the
share-of-zeros `mix` and its decoded `base`, TruncPr's masks)."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.per_iteration_ms(ctx, "copml.step_rand")
