"""Pinned COPML outputs shared by the engine-conformance tests.

The smoke workload, key=PRNGKey(0), 10 iterations, through api.fit: every
engine (jit, sharded, proc, the forced Pallas megakernel) must land on these
exact bits.  The hashes pin the random draws as well as the arithmetic, so
they depend on JAX's PRNG configuration: they were taken with
`jax_threefry_partitionable=True`, the default of the installed JAX
(0.9.0).  Under the old default (False) the same fit gives different shares
and must not be compared against these.  The uint64-oracle tests in
test_kernels.py / test_field.py check the field values themselves.
"""

GOLDEN_W = [0.25, -0.125, 0.5, 1.0, 0.0, 0.75, 1.0, 0.875, -0.5,
            -0.875, -0.5, 0.375]
GOLDEN_SHARES_SHA = \
    "e1b553d3571e77f32ac8ab6c105bd2e494ab3ae8c5a8bd162fa0f176184b2515"
GOLDEN_HIST_SHA = \
    "bf6540ab16400fd4137fa221a46f6dbf36e498557e0b6c4ec62a328c2f57daae"
