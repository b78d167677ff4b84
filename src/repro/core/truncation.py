"""Secure truncation (TruncPr, Catrina & Saxena [37]) on Shamir shares.

Given shares [a] of a fixed-point value a in (-2^{k2-1}, 2^{k2-1}) embedded in
F_p, returns shares [z] with  z = floor(a / 2^{k1}) + s,
P(s = 1) = (a mod 2^{k1}) / 2^{k1}  -- i.e. stochastic rounding of a/2^{k1}
(exactly the behavior the paper states in Section III, Phase 4).

Protocol (passively secure, statistical privacy in the k2 -> log p gap):
  offline: r uniform in [0, 2^{k2+kappa}); dealer shares [r] and [r0] where
           r0 = r mod 2^{k1}.
  online:  open c = a + 2^{k2-1} + r  (mod p); c0 = c mod 2^{k1};
           [a0] = c0 - [r0] + 2^{k1} * [b]  where b in {0,1} is the borrow
           (c0 < r0).  TruncPr folds the borrow into the stochastic rounding:
           [z] = (  [a] - [a0]  ) * inv(2^{k1})      -- mul by public const.
The borrow bit is exactly what produces the +s Bernoulli offset.

With p = 2^26 - 5 the statistical hiding gap kappa = log2(p) - k2 is small
(the paper itself reports *statistical*, not perfect, privacy for this
step); we document kappa in the returned info.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import field, shamir
from .labels import SecretRand, Share


def trunc_pr_randomness(key, shape, k1: int, k2: int, share):
    """The offline, value-INDEPENDENT half of TruncPr: draw r, deal [r], [r0].

    Extracted so the fused megakernel path (kernels/fused_step.py) can
    pre-deal the correlated randomness and hand the kernel epilogue only
    the share arrays -- consuming the key stream IDENTICALLY to
    trunc_pr_core (same split arity, same draw shapes, same share calls),
    which is what keeps the fused engines bit-exact with the reference.
    """
    kr, ks1, ks2 = jax.random.split(key, 3)
    # offline correlated randomness (crypto-service provider / PRSS, fn. 3)
    r: SecretRand = jax.random.randint(kr, shape, 0, 1 << k2,
                                       dtype=jnp.int32)
    r0 = jnp.bitwise_and(r, (1 << k1) - 1)
    r_sh = share(ks1, r.astype(field.FIELD_DTYPE))
    r0_sh = share(ks2, r0.astype(field.FIELD_DTYPE))
    return r_sh, r0_sh


def trunc_pr_core(key, a_shares: Share, k1: int, k2: int,
                  share, open_) -> Share:
    """TruncPr's arithmetic, parameterized over the share/open primitives.

    `share(key, secret)` deals Shamir shares of the offline randomness and
    `open_(c_shares)` publicly reconstructs the masked value.  trunc_pr
    below passes the plain shamir ops; the COPML step (party.update)
    passes its block's holder rows (party.share_rows) and the engine's
    open: a reconstruct on one device, an all_gather on the mesh, the
    coordinator's round in the proc engine -- ONE source of truth for the
    bias / mask / borrow-fold math, so the engines cannot drift.

    a_shares: (N_local_or_global, ...) shares.  Returns shares of
    floor(a/2^{k1}) + Bernoulli((a mod 2^{k1})/2^{k1}).
    """
    assert 0 < k1 < k2 < field.P_BITS
    shape = a_shares.shape[1:]
    r_sh, r0_sh = trunc_pr_randomness(key, shape, k1, k2, share)

    # online: open c = a + 2^{k2-1} + r  (bias makes the value positive)
    bias = 1 << (k2 - 1)
    c_sh = field.add(a_shares, field.add(r_sh, jnp.full_like(a_shares, bias)))
    c = open_(c_sh)
    c0 = jnp.bitwise_and(c, (1 << k1) - 1)

    # [a0] = c0 - [r0]  (+2^{k1} borrow, folded into the stochastic offset)
    a0_sh = field.sub(jnp.broadcast_to(c0[None], r0_sh.shape), r0_sh)
    # [z] = ([a] - [a0]) / 2^{k1}
    num = field.sub(a_shares, a0_sh)
    inv_2k1 = field.host_inv(1 << k1)
    return field.mul_scalar(num, inv_2k1)


def trunc_pr(key, a_shares: Share, k1: int, k2: int, t: int,
             points=None) -> Share:
    """Probabilistic truncation of shared fixed-point values by 2^{k1}.

    a_shares: (N, ...) Shamir shares.  Returns (N, ...) shares of
    floor(a/2^{k1}) + Bernoulli((a mod 2^{k1})/2^{k1}).
    """
    n = a_shares.shape[0]
    if points is None:
        points = shamir.default_eval_points(n)
    return trunc_pr_core(
        key, a_shares, k1, k2,
        share=lambda k, s: shamir.share(k, s, t, n, points),
        open_=lambda c_sh: shamir.reconstruct(c_sh, t, points))


def statistical_gap(k2: int) -> float:
    """kappa = log2 p - k2 bits of statistical hiding."""
    import math
    return math.log2(field.P) - k2
