"""The per-party step: what one block of holder rows computes on its own.

A block holds the clients [lo, lo + n_loc) of a client axis zero-padded
to n_pad.  This module is the local half of Phases 2 and 4, written once;
each engine adds only its exchange (docs/ARCHITECTURE.md, "One per-party
step, three exchanges"): the single-device engine is one block of all N
rows, the mesh engine (`Copml._sharded_scan`) a shard's rows, the proc
engine (`launch/runtime/worker.py`) a rank's rows.  The engines give the
same bits because every random draw is made at its global shape on every
party (the paper's offline dealer, fn. 3), each party keeps only its own
rows or columns of it, and every contraction across blocks is an exact
mod-p linear reduction.  Padded clients carry zero Lagrange weight and a
zero sharing polynomial.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import field, lagrange, shamir, truncation
from .labels import Coded, Public, Share


def padded_constants(proto, n_pad: int) -> tuple:
    """The public per-client constants, zero-padded to n_pad clients:
    the Shamir power matrix (n_pad, T) and the reconstruct-from-all-holders
    row (1, n_pad), both int32 numpy arrays."""
    n = proto.cfg.n_clients
    pmat = np.zeros((n_pad, proto.cfg.t), np.int32)
    pmat[:n] = shamir._power_matrix(tuple(proto.lambdas), proto.cfg.t)
    wall = np.zeros((1, n_pad), np.int32)
    wall[0, :n] = shamir._recon_matrix(tuple(proto.lambdas))[0]
    return pmat, wall


def decode_row(proto, subset) -> Public:
    """The (R,) decode row of an R-client subset: sum_k D[k, :] over the K
    decode-matrix rows, mod p.  The sum over K commutes with the decode
    matmul, so one row decodes the sum of the K block gradients."""
    sub_alphas = [proto.alphas[i] for i in subset]
    dmat = lagrange.decode_matrix(
        sub_alphas, proto.betas[: proto.cfg.k]).astype(np.int64)  # (K, R)
    return (dmat.sum(axis=0) % field.P).astype(np.int32)


def share_rows(key, secret, pmat_rows) -> Share:
    """The holder rows `pmat_rows` selects of shamir.share(key, secret):
    the coefficient draw is the dealer's whole draw, only the power-matrix
    rows are the block's own."""
    t = pmat_rows.shape[1]
    coeffs = field.random_field(key, (t,) + secret.shape)
    mix = field.matmul(pmat_rows, coeffs.reshape(t, -1))
    return field.add(mix.reshape((pmat_rows.shape[0],) + secret.shape),
                     secret[None])


def encode_model(proto, key, w_rows: Share, pmat_rows,
                 n_pad: int) -> Coded:
    """Phase 2 per iteration: the block's holders' LCC encodings of
    [w; ...; w; v] for every owner, (n_loc, n_pad, dw).

    v(beta_k) = w for all k in [K]; T random vectors v_k pad the tail,
    drawn whole and shared with `share_rows`.  The trailing model dims
    flatten to dw (a no-op for the vector objectives)."""
    t, k, dw = proto.cfg.t, proto.cfg.k, proto.dw
    n_loc = w_rows.shape[0]
    kv, ks = jax.random.split(key)
    # distinct keys: drawing v and its sharing polynomial from the same
    # key makes the sharing coefficients EQUAL v (same threefry stream),
    # letting any single share reveal the mask
    v = field.random_field(kv, (t,) + proto.w_shape)
    v_rows = share_rows(ks, v, pmat_rows)             # (n_loc, T) + w_shape
    w_flat = w_rows.reshape(n_loc, dw)
    v_flat = v_rows.reshape(n_loc, t, dw)
    blocks = jnp.broadcast_to(w_flat[:, None], (n_loc, k, dw))
    enc = jax.vmap(lambda b, vv: lagrange.lcc_encode(
        b[:, None, :], vv[:, None, :], proto.alphas, proto.betas
    )[:, 0, :])(blocks, v_flat)                       # (n_loc, N, dw)
    n = enc.shape[1]
    if n_pad > n:
        enc = jnp.concatenate(
            [enc, jnp.zeros((n_loc, n_pad - n, dw), enc.dtype)], axis=1)
    return enc


def model_partial(enc: Coded, wall_rows, j=None) -> Coded:
    """The block's share of the reconstruct from all holders: its
    holders' encodings weighted by their `wall_rows` (1, n_loc) and
    summed.  Rows of destination block j, (n_loc, dw), or all n_pad
    rows when j is None.  The coded model is the mod-p sum of every
    block's partial."""
    n_loc, _, dw = enc.shape
    if j is not None:
        enc = jax.lax.dynamic_slice_in_dim(enc, j * n_loc, n_loc, axis=1)
    out = field.matmul(wall_rows, enc.reshape(n_loc, -1))
    return out.reshape(enc.shape[1], dw)


def deal(proto, key, f_rows: Coded, pmat, lo):
    """Phase 4's deal: the block's owners Shamir-share their coded
    gradients f_rows (n_loc,) + w_shape to every holder.

    The (T, N) + w_shape coefficient draw is global; the block keeps its
    owners' columns [lo, lo + n_loc).  Returns block(j): the shares for
    holder block j, (n_loc, n_loc, dw), or for all n_pad holders when j
    is None.  `pmat` is the whole padded power matrix (n_pad, T)."""
    t, n, dw = proto.cfg.t, proto.cfg.n_clients, proto.dw
    n_loc, n_pad = f_rows.shape[0], pmat.shape[0]
    coeffs = field.random_field(key, (t, n) + proto.w_shape)
    coeffs = coeffs.reshape(t, n, dw)
    if n_pad > n:
        coeffs = jnp.concatenate(
            [coeffs, jnp.zeros((t, n_pad - n, dw), coeffs.dtype)], axis=1)
    own = jax.lax.dynamic_slice_in_dim(coeffs, lo, n_loc, axis=1)
    f_flat = f_rows.reshape(n_loc, dw)

    def block(j=None) -> Share:
        rows = pmat if j is None else jax.lax.dynamic_slice_in_dim(
            pmat, j * n_loc, n_loc, axis=0)
        mix = field.matmul(rows, own.reshape(t, -1))
        return field.add(mix.reshape(rows.shape[0], n_loc, dw),
                         f_flat[None])

    return block


def update(proto, key, evals: Share, dvec, w_rows: Share, xty_rows: Share,
           pmat_rows, open_) -> Share:
    """Phase 4's update from this block's holders' shares of the decode
    subset's coded gradients, evals (n_loc, R, dw), and the subset's
    decode row dvec (R,): decode X^T ghat, subtract X^T y, scale by q_eta,
    TruncPr back to scale lw (`open_` publicly reconstructs TruncPr's
    masked value from the block's rows), and step the model.  Returns
    the block's new w rows."""
    n_loc = w_rows.shape[0]
    xtg = jax.vmap(lambda e: field.matmul(dvec[None], e)[0])(evals)
    grad = field.sub(xtg.reshape((n_loc,) + proto.w_shape), xty_rows)
    scaled = field.mul_scalar(grad, proto.q_eta)
    delta = truncation.trunc_pr_core(
        key, scaled, proto.k1, proto.k2,
        share=lambda kc, s: share_rows(kc, s, pmat_rows), open_=open_)
    return field.sub(w_rows, delta)
