"""Share-level MPC primitives (paper Appendix C).

All values are Shamir-shared with threshold T across N clients; share arrays
carry the client axis first: (N, ...).  Operations:

* add / sub / mul-by-public-constant: LOCAL (no communication) -- these are
  the only ops COPML's encode/decode needs (Remark 3), which is the source of
  its speedup over the baselines.
* mul (share x share): requires degree reduction.  Two implementations:
    - BGW [2]:   local product -> re-share -> recombine.   O(N^2) messages.
    - BH08 [3]:  offline pair ([rho]_T, [rho]_2T); online mask, open, re-mask.
                 O(N) broadcasts.
  Both are implemented for real on the share arrays; the cost model in
  cost_model.py accounts their communication.

The "clients" axis is a plain leading array axis here; launch/ maps it onto
the production mesh's data axis with shard_map (each device then literally
holds one client's shares and collectives realize the exchanges).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from . import field, shamir
from .labels import Opened, Share


def add(xs: Share, ys: Share) -> Share:
    return field.add(xs, ys)


def sub(xs: Share, ys: Share) -> Share:
    return field.sub(xs, ys)


def mul_public(xs: Share, c: int) -> Share:
    return field.mul_scalar(xs, c)


def add_public(xs: Share, c: int) -> Share:
    """Add a public constant: by convention added to every share (the
    constant is embedded as the degree-0 coefficient on all shares)."""
    return field.add(xs, jnp.full_like(xs, int(c) % field.P))


def local_product(xs, ys, matmul: bool):
    if matmul:
        return jax.vmap(field.matmul)(xs, ys)
    return field.mul(xs, ys)


def mul_bgw(key, xs: Share, ys: Share, t: int, *, matmul: bool = False,
            points: Sequence[int] | None = None) -> Share:
    """BGW multiplication: local product (degree 2T shares) + re-share.

    Requires N >= 2T+1.  If matmul=True, xs:(N,A,B) @ ys:(N,B,C).
    """
    assert xs.shape[0] >= 2 * t + 1, "BGW needs N >= 2T+1"
    return reduce_bgw(key, local_product(xs, ys, matmul), t, points)


def reduce_bgw(key, prod: Share, t: int,
               points: Sequence[int] | None = None) -> Share:
    """BGW's degree reduction of degree-2T product shares: re-share."""
    return shamir.reshare(key, prod, t, prod.shape[0], points)


def mul_bh08(key, xs: Share, ys: Share, t: int, *, matmul: bool = False,
             points: Sequence[int] | None = None) -> Share:
    """[BH08] multiplication with an offline random pair.

    Offline: rho random; [rho]_T and [rho]_2T dealt.
    Online:  open d = x*y - rho from degree-2T shares (needs 2T+1 of them),
             output [rho]_T + d  (local add of a now-public value).
    """
    assert xs.shape[0] >= 2 * t + 1, \
        "BH08 needs N >= 2T+1 to open the 2T-degree mask"
    return reduce_bh08(key, local_product(xs, ys, matmul), t, points)


def reduce_bh08(key, prod: Share, t: int,
                points: Sequence[int] | None = None) -> Share:
    """BH08's degree reduction of degree-2T product shares (N, ...): mask
    with the offline pair, open, re-mask.  A sum of local products may be
    reduced once, as setup does for X^T y."""
    n = prod.shape[0]
    if points is None:
        points = shamir.default_eval_points(n)
    k_rho, k_t, k_2t = jax.random.split(key, 3)
    rho = field.random_field(k_rho, prod.shape[1:])
    rho_t = shamir.share(k_t, rho, t, n, points)
    rho_2t = shamir.share(k_2t, rho, 2 * t, n, points)
    masked = field.sub(prod, rho_2t)
    # "broadcast and open": interpolate the degree-2T sharing at z=0
    opened = shamir.reconstruct(masked, 2 * t, points)
    return field.add(rho_t, opened[None])


def open_shares(xs: Share, t: int, points: Sequence[int] | None = None,
                subset: Sequence[int] | None = None) -> Opened:
    """Publicly reconstruct a shared value (e.g. the final model w^(J))."""
    return shamir.reconstruct(xs, t, points, subset)
