"""Per-kernel sweeps: Pallas (interpret=True) vs pure-jnp ref vs uint64."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import field as F
from repro.kernels import coded_gradient as cgk
from repro.kernels import field_poly as fpk
from repro.kernels import modmatmul as mmk
from repro.kernels import ops, ref
from repro.kernels import short_modmatmul as smm


@pytest.mark.parametrize("m,k,n", [
    (8, 8, 8), (128, 512, 128), (128, 1024, 128), (64, 2048, 32),
    (256, 300, 48),   # padding path
])
def test_modmatmul_shapes(rng, m, k, n):
    a = jnp.asarray(rng.integers(0, F.P, size=(m, k)).astype(np.int32))
    b = jnp.asarray(rng.integers(0, F.P, size=(k, n)).astype(np.int32))
    got = ops.modmatmul(a, b, force_pallas=True)
    assert got.shape == (m, n)          # exact shape, padding sliced off
    np.testing.assert_array_equal(
        np.asarray(got), F.np_matmul(np.asarray(a), np.asarray(b)))
    np.testing.assert_array_equal(
        np.asarray(ref.modmatmul(a, b)),
        F.np_matmul(np.asarray(a), np.asarray(b)))
    assert ops.modmatmul_exact is ops.modmatmul   # historical alias


@given(st.integers(1, 40), st.integers(1, 50), st.integers(1, 30),
       st.integers(0, 2**31 - 1))
@settings(max_examples=6, deadline=None)
def test_modmatmul_hypothesis(m, k, n, seed):
    r = np.random.default_rng(seed)
    a = jnp.asarray(r.integers(0, F.P, size=(m, k)).astype(np.int32))
    b = jnp.asarray(r.integers(0, F.P, size=(k, n)).astype(np.int32))
    got = ops.modmatmul_exact(a, b, force_pallas=True, bm=16, bn=16,
                              bk=32)
    np.testing.assert_array_equal(
        np.asarray(got), F.np_matmul(np.asarray(a), np.asarray(b)))


def test_modmatmul_extreme(rng):
    a = jnp.full((16, 1024), F.P - 1, jnp.int32)
    b = jnp.full((1024, 16), F.P - 1, jnp.int32)
    got = ops.modmatmul_exact(a, b, force_pallas=True)
    np.testing.assert_array_equal(
        np.asarray(got), F.np_matmul(np.asarray(a), np.asarray(b)))


@pytest.mark.parametrize("size,degree", [(64, 1), (4096, 1), (5000, 3),
                                         (1, 2)])
def test_poly_eval_kernel(rng, size, degree):
    z = jnp.asarray(rng.integers(0, F.P, size=size).astype(np.int32))
    c = jnp.asarray(rng.integers(0, F.P, size=degree + 1).astype(np.int32))
    got = ops.poly_eval(z, c, force_pallas=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.poly_eval(z, c)))


@pytest.mark.parametrize("m,d,r", [(8, 8, 1), (256, 130, 1), (100, 600, 3),
                                   (512, 512, 1)])
def test_coded_gradient_fused(rng, m, d, r):
    x = jnp.asarray(rng.integers(0, F.P, size=(m, d)).astype(np.int32))
    w = jnp.asarray(rng.integers(0, F.P, size=(d,)).astype(np.int32))
    c = jnp.asarray(rng.integers(0, F.P, size=(r + 1,)).astype(np.int32))
    got = ops.coded_gradient(x, w, c, force_pallas=True)
    exp = ref.coded_gradient(x, w, c)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    # independent uint64 oracle for the same composite
    z = F.np_matmul(np.asarray(x), np.asarray(w)[:, None])[:, 0]
    g = np.zeros_like(z)
    for ci in reversed(np.asarray(c).astype(np.int64)):
        g = (g * z + ci) % F.P
    exp2 = F.np_matmul(np.asarray(x).T, g[:, None].astype(np.int32))[:, 0]
    np.testing.assert_array_equal(np.asarray(got), exp2)


@pytest.mark.parametrize("nb,m,d,r", [(8, 64, 32, 1), (5, 96, 40, 3)])
def test_coded_gradient_batched_matches_vmap(rng, nb, m, d, r):
    """Batched engines == per-client vmap of the single-client kernel,
    element-for-element mod p (second case exercises the padding path)."""
    x = jnp.asarray(rng.integers(0, F.P, size=(nb, m, d)).astype(np.int32))
    w = jnp.asarray(rng.integers(0, F.P, size=(nb, d)).astype(np.int32))
    c = jnp.asarray(rng.integers(0, F.P, size=(r + 1,)).astype(np.int32))
    expected = np.asarray(jax.vmap(
        lambda xi, wi: ops.coded_gradient(xi, wi, c, force_pallas=True,
                                          bm=32, dc=16))(x, w))
    # jnp reference path (limb-packed batched GEMM)
    np.testing.assert_array_equal(
        np.asarray(ref.coded_gradient_batched(x, w, c)), expected)
    np.testing.assert_array_equal(
        np.asarray(ref.coded_gradient_vmap(x, w, c)), expected)
    # batched-grid Pallas kernel (interpret)
    got = ops.coded_gradient_batched(x, w, c, force_pallas=True,
                                     bm=32, dc=16)
    np.testing.assert_array_equal(np.asarray(got), expected)


@pytest.mark.parametrize("bsz,m,k,n", [(4, 32, 48, 24), (3, 30, 70, 18)])
def test_modmatmul_batched_matches_vmap(rng, bsz, m, k, n):
    a = jnp.asarray(rng.integers(0, F.P, size=(bsz, m, k)).astype(np.int32))
    b = jnp.asarray(rng.integers(0, F.P, size=(bsz, k, n)).astype(np.int32))
    expected = np.stack([F.np_matmul(np.asarray(a[i]), np.asarray(b[i]))
                         for i in range(bsz)])
    got = ops.modmatmul_batched(a, b, force_pallas=True, bm=16, bn=16, bk=32)
    assert got.shape == (bsz, m, n)
    np.testing.assert_array_equal(np.asarray(got), expected)
    np.testing.assert_array_equal(
        np.asarray(ref.modmatmul_batched(a, b)), expected)
    vmapped = np.asarray(jax.vmap(
        lambda ai, bi: ops.modmatmul(ai, bi, force_pallas=True,
                                     bm=16, bn=16, bk=32))(a, b))
    np.testing.assert_array_equal(vmapped, expected)


@pytest.mark.parametrize("m", [1, 8, 50])
@pytest.mark.parametrize("k", [1, 7, 8, 17, 50])
def test_short_modmatmul_matches_field_matmul(rng, m, k):
    """The short-contraction kernel equals field.matmul's jnp path and the
    uint64 oracle: N = 3073 is no multiple of 128 (an edge block; two grid
    steps at M = K = 50), random and all-(p-1) operands, plain and under
    vmap with `a` unbatched (the lcc_encode pattern), where it stays one
    pallas_call."""
    n = 3073

    def kernel(a, b):
        return smm.short_modmatmul(a, b, interpret=True)

    a = jnp.asarray(rng.integers(0, F.P, size=(m, k)).astype(np.int32))
    bs = jnp.asarray(rng.integers(0, F.P, size=(2, k, n)).astype(np.int32))
    cases = [(a, bs[0]), (jnp.full((m, k), F.P - 1, jnp.int32),
                          jnp.full((k, n), F.P - 1, jnp.int32))]
    for x, y in cases:
        expected = F.np_matmul(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(kernel(x, y)), expected)
        np.testing.assert_array_equal(np.asarray(F.matmul(x, y)), expected)
    batched = jax.vmap(kernel, in_axes=(None, 0))
    assert str(jax.make_jaxpr(batched)(a, bs)).count("pallas_call") == 1
    got = np.asarray(batched(a, bs))
    for i in range(2):
        np.testing.assert_array_equal(
            got[i], F.np_matmul(np.asarray(a), np.asarray(bs[i])))


def test_matvec_batched_extreme(rng):
    """All-(p-1) operands through the limb-packed batched GEMM."""
    a = jnp.full((3, 8, F.MATMUL_CHUNK + 5), F.P - 1, jnp.int32)
    v = jnp.full((3, F.MATMUL_CHUNK + 5), F.P - 1, jnp.int32)
    got = np.asarray(F.matvec_batched(a, v))
    exp = F.np_matmul(np.asarray(a[0]), np.asarray(v[0])[:, None])[:, 0]
    for i in range(3):
        np.testing.assert_array_equal(got[i], exp)


def test_block_shape_sweep(rng):
    """VMEM tiling choices must not change results."""
    x = jnp.asarray(rng.integers(0, F.P, size=(96, 160)).astype(np.int32))
    w = jnp.asarray(rng.integers(0, F.P, size=(160,)).astype(np.int32))
    c = jnp.asarray(rng.integers(0, F.P, size=(2,)).astype(np.int32))
    expected = np.asarray(ref.coded_gradient(x, w, c))
    # NOTE: tiny blocks (8,8) mean thousands of interpret-mode grid steps
    # (~minutes per combo on CPU); two contrasting tilings cover the
    # index-map/accumulator logic just as well.
    for bm, dc in ((32, 32), (96, 160)):
        got = ops.coded_gradient(x, w, c, force_pallas=True, bm=bm, dc=dc)
        np.testing.assert_array_equal(np.asarray(got), expected)


def test_import_leaves_backend_uninitialised():
    """Interpret mode is chosen per call, never while a module is
    imported: asking for the backend at import would claim the chip."""
    code = ("import repro.api, repro.kernels.ops, repro.serve.coded\n"
            "from jax._src import xla_bridge\n"
            "print(len(xla_bridge._backends))")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == "0"
    assert ops.interpret_mode() == (jax.default_backend() == "cpu")
