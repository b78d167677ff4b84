"""Pure-jnp oracles for every Pallas kernel (no pallas_call anywhere).

These are the ground truth for the per-kernel allclose sweeps in
tests/test_kernels.py; field.np_matmul (numpy uint64, the paper's own 64-bit
lazy-reduction arithmetic) backs them up as a second, independent oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import field
from ..core.labels import Coded, Public


def modmatmul(a, b):
    """(a @ b) mod p -- jnp limb algorithm from core.field."""
    return field.matmul(a, b)


def modmatmul_batched(a, b):
    """(a[i] @ b[i]) mod p over a leading batch axis."""
    return jax.vmap(field.matmul)(a, b)


def poly_eval(z, coeffs):
    """Horner over F_p."""
    return field.evaluate_poly_dyn(coeffs, z)


def coded_gradient(x: Coded, w: Coded, coeffs: Public) -> Coded:
    """f = x^T ghat(x w) over F_p, unfused two-pass reference."""
    z = field.matmul(x, w[:, None])[:, 0]
    g = field.evaluate_poly_dyn(coeffs, z)
    return field.matmul(x.T, g[:, None])[:, 0]


def coded_gradient_vmap(x: Coded, w: Coded, coeffs: Public) -> Coded:
    """Per-client baseline: vmap of the single-client reference.

    Kept as the benchmark baseline and as a second oracle for the batched
    implementations (they must agree element-for-element mod p)."""
    return jax.vmap(lambda xi, wi: coded_gradient(xi, wi, coeffs))(x, w)


def coded_gradient_batched(x: Coded, w: Coded, coeffs: Public) -> Coded:
    """f[n] = x[n]^T ghat(x[n] w[n]) for all clients; coeffs shared.

    Both passes use field.matvec_batched (limb-packed batched GEMM), which
    beats the per-client vmap by reshaping 16 n=1 matvecs per client into
    one well-shaped batched matmul."""
    z = field.matvec_batched(x, w)                       # (N, m)
    g = field.evaluate_poly_dyn(coeffs, z)
    return field.matvec_batched(jnp.swapaxes(x, 1, 2), g)  # (N, d)


def coded_gradient_matrix(x: Coded, w: Coded, coeffs: Public) -> Coded:
    """f[n] = x[n]^T ghat(x[n] @ w[n]) for a MATRIX model w: (N, d, C).

    The class-batched hot loop: the matvec pair of the vector path becomes
    a batched GEMM pair with C columns in the free dimension (far better
    arithmetic intensity than C matvec dispatches over the same x), and
    ghat is evaluated elementwise over the whole (N, m, C) logit block.
    """
    z = jax.vmap(field.matmul)(x, w)                        # (N, m, C)
    g = field.evaluate_poly_dyn(coeffs, z)
    return jax.vmap(field.matmul)(jnp.swapaxes(x, 1, 2), g)  # (N, d, C)


def fused_step(x, w, coeffs, adv_off, dfull, rvec, base, xty, wsh, radd,
               r0sh, *, q_eta: int, inv2k1: int, k1: int):
    """Phase-by-phase oracle for kernels.fused_step (same operands/returns):
    the matrix coded gradient, then fused_update."""
    f = coded_gradient_matrix(x, w, coeffs)
    return f, fused_update(f, adv_off, dfull, rvec, base, xty, wsh, radd,
                           r0sh, q_eta=q_eta, inv2k1=inv2k1, k1=k1)


def fused_update(f, adv_off, dfull, rvec, base, xty, wsh, radd, r0sh, *,
                 q_eta: int, inv2k1: int, k1: int):
    """Phase 4 of kernels.fused_step on the coded gradients f: (N, d, C).

    Composes the existing references in protocol order: corruption offset,
    decode fold against the zero-scattered decode row, q_eta scale,
    TruncPr masked open (rvec = the reconstruct Lagrange row zero-padded
    over holders) and borrow-folded rescale.  Returns the new model shares.
    """
    n = f.shape[0]
    f_adj = field.add(f, adv_off[:, None, None])
    common = field.matmul(
        dfull[None], f_adj.reshape(n, -1))[0].reshape(f.shape[1:])
    xtg = field.add(base, common[None])
    grad = field.sub(xtg, xty)
    scaled = field.mul_scalar(grad, q_eta)
    c_sh = field.add(scaled, radd)
    c_open = field.matmul(
        rvec[None], c_sh.reshape(n, -1))[0].reshape(c_sh.shape[1:])
    c0 = jnp.bitwise_and(c_open, (1 << k1) - 1)
    a0 = field.sub(jnp.broadcast_to(c0[None], c_sh.shape), r0sh)
    delta = field.mul_scalar(field.sub(scaled, a0), inv2k1)
    return field.sub(wsh, delta)
