"""Fused megakernel conformance (kernels/fused_step.py + ops wiring).

Property-based (real `hypothesis` or the deterministic shim): the
one-dispatch fused Phase-3/4 step is bit-exact vs the phase-by-phase
reference over random shapes/degrees/class widths; Barrett reduction and
the grouped-limb matmul agree with plain `% P` arithmetic over the full
reachable range.  Plus the tuned-block selection contract and the
protocol-level golden: REPRO_FUSED_STEP=kernel (forced Pallas megakernel)
reproduces the pinned smoke-workload share hash bit-for-bit.
"""

import hashlib

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import field as F
from repro.kernels import ops, ref

from goldens import GOLDEN_SHARES_SHA

MAX_SEED = 2 ** 31 - 1
K1 = 8



def _operands(rng, n, m, d, c, degree):
    def fld(*s):
        return jnp.asarray(
            rng.integers(0, F.P, size=s, dtype=np.int64).astype(np.int32))
    return (fld(n, m, d), fld(n, d, c), fld(degree + 1), fld(n), fld(n),
            fld(n), fld(n, d, c), fld(n, d, c), fld(n, d, c), fld(n, d, c),
            fld(n, d, c))


@given(st.integers(0, MAX_SEED), st.integers(1, 3),
       st.sampled_from([1, 3, 10]))
@settings(max_examples=8, deadline=None)
def test_fused_step_matches_phase_reference(seed, degree, c):
    """ops.fused_step(force_pallas) == ref.fused_step over random client
    counts, ragged sample/feature dims, gradient degrees, and C."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 5))
    m = int(rng.integers(5, 40))
    d = int(rng.integers(3, 25))
    args = _operands(rng, n, m, d, c, degree)
    kw = dict(q_eta=int(rng.integers(1, F.P)), inv2k1=F.host_inv(1 << K1),
              k1=K1)
    f_ref, w_ref = ref.fused_step(*args, **kw)
    f_k, w_k = ops.fused_step(*args, bm=8, dc=8, force_pallas=True, **kw)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_ref))


@given(st.integers(0, MAX_SEED))
@settings(max_examples=8, deadline=None)
def test_barrett_reduce_equals_mod_p(seed):
    """barrett_reduce == `% P` over the whole admissible range [0, 2^31):
    boundary values pinned, the rest drawn uniformly."""
    rng = np.random.default_rng(seed)
    t = rng.integers(0, 2 ** 31, size=(4096,), dtype=np.int64)
    t[:6] = (0, 1, F.P - 1, F.P, 2 * F.P - 1, 2 ** 31 - 1)
    got = np.asarray(F.barrett_reduce(jnp.asarray(t.astype(np.int32))))
    np.testing.assert_array_equal(got, (t % F.P).astype(np.int32))


@given(st.integers(0, MAX_SEED), st.sampled_from([1, 16, 127, 1024]))
@settings(max_examples=8, deadline=None)
def test_grouped_limb_matmul_equals_int64_mod(seed, k):
    """The grouped-weight + one-Barrett-reduce contraction (jnp AND the
    Pallas modmatmul kernel) matches plain int64 `% P` up to the
    documented contraction bound k <= 1024."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, F.P, size=(5, k), dtype=np.int64)
    b = rng.integers(0, F.P, size=(k, 3), dtype=np.int64)
    want = ((a @ b) % F.P).astype(np.int32)   # < 1024 * p^2 < 2^63: exact
    aj = jnp.asarray(a.astype(np.int32))
    bj = jnp.asarray(b.astype(np.int32))
    np.testing.assert_array_equal(np.asarray(F.matmul(aj, bj)), want)
    np.testing.assert_array_equal(
        np.asarray(ops.modmatmul(aj, bj, force_pallas=True)), want)


# ------------------------------------------------------- block selection


def test_pick_blocks_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_BLOCKS", "64,32")
    assert ops.pick_blocks(390, 24, 10) == (64, 32)


def test_pick_blocks_table_and_fallback(monkeypatch):
    """Bucketed table hit wins; unknown buckets derive minima from the
    ACTUAL shape (the ragged matrix path shrinks dc when C is wide)."""
    assert ops.block_key(390, 24, 10) == "m512_d32_c16"
    monkeypatch.delenv("REPRO_PALLAS_BLOCKS", raising=False)
    monkeypatch.setattr(ops, "_block_table_cache",
                        {"m512_d32_c16": {"bm": 256, "dc": 16}})
    assert ops.pick_blocks(390, 24, 10) == (256, 16)
    # fallback: no entry for this bucket; all 13 rows make one block and
    # dc halves while dc * C (rounded up to 8) exceeds the VMEM budget
    assert ops.pick_blocks(13, 512, 300) == (13, 32)


def test_phase3_routed_by_shape_ignores_the_override_and_table(monkeypatch):
    """Where the shape alone sends Phase 3 to its kernel (on a TPU), the
    blocks are `coded_gradient.blocks`' own: neither REPRO_PALLAS_BLOCKS
    nor kernels/blocks.json reaches that path; a kernel asked for reads
    them."""
    from repro.kernels import coded_gradient as cg
    monkeypatch.setenv("REPRO_PALLAS_BLOCKS", "64,32")
    monkeypatch.setattr(ops, "_block_table_cache",
                        {"m512_d32_c16": {"bm": 256, "dc": 16}})
    monkeypatch.setattr(ops, "_routes", lambda shape, c: True)
    for shape in [(50, 902, 3073), (4, 390, 24)]:
        assert ops._gradient_blocks(shape, 1, False) == cg.blocks(
            shape[1], shape[2], 1)
        assert ops._gradient_blocks(shape, 1, True) == (64, 32)
    monkeypatch.setattr(ops, "_routes", lambda shape, c: False)
    assert ops._gradient_blocks((4, 390, 24), 1, False) is None


def test_coded_gradient_matrix_ragged_regression():
    """(m=13, C=10): the matrix path's blocks derive from the real shape
    (pre-fix the vector-path minima padded this shape pathologically)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(0, F.P, size=(3, 13, 6),
                                 dtype=np.int64).astype(np.int32))
    w = jnp.asarray(rng.integers(0, F.P, size=(3, 6, 10),
                                 dtype=np.int64).astype(np.int32))
    coeffs = jnp.asarray(rng.integers(0, F.P, size=(2,),
                                      dtype=np.int64).astype(np.int32))
    got = ops.coded_gradient_matrix(x, w, coeffs, force_pallas=True)
    want = ref.coded_gradient_matrix(x, w, coeffs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- protocol golden


def test_forced_kernel_golden_shares(monkeypatch):
    """REPRO_FUSED_STEP=kernel (the Pallas megakernel inside the jit scan)
    reproduces the pinned smoke-workload share hash bit-for-bit."""
    from repro import api
    monkeypatch.setenv("REPRO_FUSED_STEP", "kernel")
    res = api.fit("smoke", "copml", "jit", key=0, iters=10, history=False)
    sha = hashlib.sha256(
        np.asarray(res.state.w_shares, np.int32).tobytes()).hexdigest()
    assert sha == GOLDEN_SHARES_SHA
