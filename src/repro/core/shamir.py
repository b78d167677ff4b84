"""Shamir T-out-of-N secret sharing over F_p for arbitrary-shape arrays.

Shares are stacked on a leading axis of length N: shares[i] is client i's
share, i.e. h(lambda_i) where h(z) = secret + z*R_1 + ... + z^T * R_T.

Evaluation points lambda_1..lambda_N are public static ints, so the power /
interpolation matrices are computed exactly on the host and enter the traced
program as constants -- share generation and reconstruction are then a
single field matmul each (mul-by-public-constant + add = *local* MPC ops,
Appendix C Remark 3), fully vectorized so a 512-client protocol traces to a
handful of HLO ops.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import field
from .labels import Opened, Share


def default_eval_points(n: int, offset: int = 1) -> tuple:
    """N distinct public evaluation points (1..N by default)."""
    return tuple(range(offset, offset + n))


@lru_cache(maxsize=None)
def _power_matrix(points: tuple, t: int) -> np.ndarray:
    """P[i, j] = lambda_i^{j+1} mod p, shape (N, T)."""
    out = np.zeros((len(points), t), dtype=np.int64)
    for i, lam in enumerate(points):
        acc = 1
        for j in range(t):
            acc = (acc * (int(lam) % field.P)) % field.P
            out[i, j] = acc
    return out.astype(np.int32)


@lru_cache(maxsize=None)
def _recon_matrix(points: tuple) -> np.ndarray:
    """Lagrange weights at z=0 for the given nodes, shape (1, R)."""
    return field.host_lagrange_coeffs(points, [0])


def share(key, secret, t: int, n: int,
          points: Sequence[int] | None = None) -> Share:
    """Create N Shamir shares of `secret` with threshold t.

    Returns int32 array of shape (N, *secret.shape).  One field matmul:
    shares = secret + P @ R  with P the public (N, T) power matrix.
    """
    if points is None:
        points = default_eval_points(n)
    points = tuple(points)
    assert len(points) == n
    if t == 0:
        return jnp.broadcast_to(secret[None], (n,) + secret.shape)
    coeffs = field.random_field(key, (t,) + secret.shape)  # R_1..R_T
    return share_with(coeffs, secret, n, points)


def share_with(coeffs, secret, n: int, points: Sequence[int]) -> Share:
    """The N shares of `secret` under the given masking coefficients
    R_1..R_T, coeffs (T, *secret.shape): what `share` computes after its
    draw.  A slice of `share`'s draw shares the matching slice of the
    secret, so a caller can draw whole and share piece by piece."""
    t = coeffs.shape[0]
    pmat = jnp.asarray(_power_matrix(tuple(points), t))     # (N, T)
    mix = field.matmul(pmat, coeffs.reshape(t, -1))         # (N, numel)
    return field.add(mix.reshape((n,) + secret.shape), secret[None])


def reconstruct(shares: Share, t: int, points: Sequence[int] | None = None,
                subset: Sequence[int] | None = None) -> Opened:
    """Reconstruct the secret from shares (leading axis = clients).

    Any t+1 shares suffice; `subset` selects which client indices to use
    (defaults to the first t+1) -- exercising this is the straggler story.
    """
    n = shares.shape[0]
    if points is None:
        points = default_eval_points(n)
    if subset == "all":
        # interpolate from ALL N shares: same value (degree-T polynomial,
        # N >= T+1 nodes), but on a mesh the contraction stays fully sharded
        # (reduce-scatter) instead of idling N-T-1 devices -- the inverse of
        # the paper's footnote-4 WAN optimization (EXPERIMENTS.md Perf).
        subset = tuple(range(n))
    elif subset is None:
        subset = tuple(range(t + 1))
    else:
        subset = tuple(subset)[: t + 1]
    assert len(subset) >= t + 1
    r = len(subset)
    lams = tuple(points[i] for i in subset)
    w = jnp.asarray(_recon_matrix(lams))                    # (1, r)
    sub = shares[jnp.asarray(subset)] if list(subset) != list(range(r)) \
        else shares[: r]
    out = field.matmul(w, sub.reshape(r, -1))
    return out.reshape(shares.shape[1:])


def step_subset_arrays(step_subsets, r: int, weight_fn) -> tuple:
    """Host-compile per-step subsets into the (iters, r) gather-index and
    weight arrays the dynamic decode paths consume.

    weight_fn(subset_tuple) -> (r,) int32 public decode/reconstruction row;
    called once per DISTINCT subset (host work is O(#distinct), not
    O(iters)).  Shared by Copml.plan_constants (LCC decode rows) and
    secure_agg.selection_arrays (Shamir reconstruction weights)."""
    cache: dict = {}
    idx = np.zeros((len(step_subsets), r), np.int32)
    wts = np.zeros((len(step_subsets), r), np.int32)
    for s, sub in enumerate(step_subsets):
        sub = tuple(int(i) for i in sub)
        assert len(sub) >= r, (
            f"step {s} subset has {len(sub)} < {r} clients")
        sub = sub[:r]
        if sub not in cache:
            cache[sub] = weight_fn(sub)
        idx[s] = sub
        wts[s] = cache[sub]
    return jnp.asarray(idx), jnp.asarray(wts)


def recon_weights(points: Sequence[int], subset: Sequence[int]) -> np.ndarray:
    """Host-side (r,) Lagrange weights at z=0 for `subset` of the share
    points -- the public constant `reconstruct_dyn` pairs with its traced
    gather indices.  Computed exactly with Python ints (lru-cached)."""
    lams = tuple(int(points[i]) for i in subset)
    return _recon_matrix(lams)[0]


def reconstruct_dyn(shares: Share, idx, weights) -> Opened:
    """Reconstruct with TRACED subset indices and precomputed weights.

    idx: (r,) int32 gather indices into the client axis; weights: (r,) the
    matching `recon_weights` row.  Identical field math to `reconstruct`
    with a static subset, but the subset can change per scan step inside a
    single compiled program -- the per-step share selection of the
    fault-injection engines (any r = T+1 holders suffice).
    """
    r = idx.shape[0]
    sub = shares[idx]                                       # (r, ...)
    out = field.matmul(jnp.asarray(weights).reshape(1, r), sub.reshape(r, -1))
    return out.reshape(shares.shape[1:])


def share_batch(key, secrets, t: int, n: int,
                points: Sequence[int] | None = None) -> Share:
    """Share J independent secrets (leading axis = owners) in ONE matmul:
    secrets (J, ...) -> shares (J, N, ...).

    Because every owner uses the same public power matrix, the owner axis
    folds into the element axis -- which is exactly what `share` of the
    stacked array already computes (its coefficient draw is (T, J, ...):
    independent per-owner polynomials), so this is share + transpose."""
    return jnp.swapaxes(share(key, secrets, t, n, points), 0, 1)


def reshare(key, shares: Share, t: int, n: int,
            points: Sequence[int] | None = None) -> Share:
    """Degree reduction by re-sharing (BGW): every client re-shares its share
    with a fresh degree-t polynomial; the new shares of the secret are the
    lambda-weighted combination of the incoming sub-shares.

    `shares` may lie on a polynomial of degree up to n-1 (e.g. 2t after a
    local multiply); output shares lie on a fresh degree-t polynomial.
    """
    if points is None:
        points = default_eval_points(n)
    points = tuple(points)
    sub = share_batch(key, shares, t, n, points)  # (owner, holder, ...)
    w = field.host_lagrange_coeffs(points, [0])[0]  # (N,) weights at 0
    wj = jnp.asarray(w)[:, None]                    # (N, 1)
    flat = sub.reshape(n, -1)                       # (owner, holder*numel)
    out = field.matmul(wj.T, flat)                  # interpolate over owners
    return out.reshape(shares.shape)
