"""Pallas TPU kernel: FUSED coded gradient  f = X~^T ghat(X~ w~)  over F_p.

This is COPML's hot loop (paper Eq. 7, the first column of Table I).  A naive
implementation reads X~ twice (once for z = X~ w~, once for X~^T g).  Fusing
both passes over a single VMEM-resident row-block of X~ halves HBM traffic --
the op is memory-bound (arithmetic intensity ~ O(1) per X~ element for the
matvec pair), so this is a ~2x win on the memory roofline term.

One kernel serves every caller: the COPML hot loop computes f for ALL N
clients every iteration (each with its own coded slice X~_i and coded model
w~_i), so an (N, m/bm) grid runs the whole round as ONE pallas_call -- one
dispatch, one pipeline, w~_i resident in VMEM across a client's row blocks.
The model is class-major, w~_i: (C, d), so both passes are GEMMs with the
class width C in the MXU free dimension (C = 1 is the binary vector model,
C > 1 the multi-class one-vs-rest matrix model):

    z^T = W X_blk^T        (C, bm)   contraction over d, chunked by dc
    g^T = ghat(z^T)        (C, bm)   unrolled Horner on the VPU
    f  += g^T X_blk        (C, d)    contraction over bm

Layout for Mosaic: d rides the 128-lane axis and C the sublanes, so a vector
model is one lane-dense row instead of a 128x lane-padded column, and every
block's last two dims are either (8, 128)-aligned or the array's own.  The
gradient polynomial's coefficients are scalars read from SMEM.

Field arithmetic follows modmatmul.py: 7-bit limbs -> exact f32 MXU products
-> int32 recombination.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import field

DEFAULT_BM = 256     # rows of X~ per block (contraction width for X^T g)
DEFAULT_DC = 512     # d-chunk width (contraction width for X w)


def _limb(x, i):
    return jnp.bitwise_and(
        jax.lax.shift_right_logical(x, 7 * i), 0x7F).astype(jnp.float32)


def _limb_dot_mod(a, b, contract_a: int, contract_b: int):
    """Field 'matmul' of 2-D int32 blocks a, b contracting the given dims.

    Contraction length must be <= 1024 (exact f32).  Returns int32 mod p.
    The 16 limb-pair MXU partials are grouped by weight class s = i+j in
    int32 and recombined with ONE Barrett reduce (field.recombine_limb_
    groups) instead of the historical per-term fold26 + modular multiply.
    """
    groups = [None] * 7
    dn = (((contract_a,), (contract_b,)), ((), ()))
    for i in range(4):
        ai = _limb(a, i)
        for j in range(4):
            bj = _limb(b, j)
            s = jax.lax.dot_general(ai, bj, dn,
                                    preferred_element_type=jnp.float32)
            term = s.astype(jnp.int32)
            g = groups[i + j]
            groups[i + j] = term if g is None else g + term
    return field.recombine_limb_groups(groups)


def chunks(d: int, width: int):
    """Static [start, stop) column chunks of width <= `width` covering d
    (the last one ragged when width does not divide d)."""
    return [(s, min(s + width, d)) for s in range(0, d, width)]


def accumulate_rows(x_ref, w_ref, c_ref, f_ref, *, degree: int, dc: int):
    """f[0] += ghat(W X^T) X for one (bm, d) row block of one client.

    x_ref: (1, bm, d) coded rows; w_ref: (1, C, d) class-major coded model;
    c_ref: (r+1,) SMEM coefficients; f_ref: (1, C, d) accumulator.  Every
    contraction is <= 1024 wide (dc for pass 1, bm for pass 2), keeping the
    f32 limb products exact; d may be ragged w.r.t. dc."""
    spans = chunks(x_ref.shape[2], dc)
    z = None
    for s, e in spans:
        part = _limb_dot_mod(w_ref[0, :, s:e], x_ref[0, :, s:e], 1, 1)
        z = part if z is None else field.add(z, part)         # (C, bm)
    g = jnp.broadcast_to(c_ref[degree], z.shape)
    for t in range(degree - 1, -1, -1):
        g = field.add(field.mul(g, z), jnp.broadcast_to(c_ref[t], z.shape))
    for s, e in spans:
        upd = _limb_dot_mod(g, x_ref[0, :, s:e], 1, 0)         # (C, e - s)
        f_ref[0, :, s:e] = field.add(f_ref[0, :, s:e], upd)


def _kernel(x_ref, w_ref, c_ref, f_ref, *, degree: int, dc: int):
    @pl.when(pl.program_id(1) == 0)     # first row block of this client
    def _init():
        f_ref[...] = jnp.zeros_like(f_ref)

    accumulate_rows(x_ref, w_ref, c_ref, f_ref, degree=degree, dc=dc)


@functools.partial(jax.jit, static_argnames=("bm", "dc", "interpret"))
def coded_gradient(x, w, coeffs, *, bm: int = DEFAULT_BM,
                   dc: int = DEFAULT_DC, interpret: bool = False):
    """f[n] = (ghat(w[n] x[n]^T) x[n]) mod p for all N clients at once.

    x: (N, m, d) int32 field; w: (N, C, d) class-major coded models;
    coeffs: (r+1,) shared across clients and classes.  Returns (N, C, d).
    m % bm == 0 (ops.py pads); bm, dc <= 1024.  Grid (N, m/bm): the
    row-block dimension is innermost so client n's output block and w~_n
    stay VMEM-resident across its whole slice.
    """
    nb, m, d = x.shape
    c = w.shape[1]
    assert w.shape == (nb, c, d), (x.shape, w.shape)
    assert m % bm == 0, (x.shape, bm)
    assert bm <= 1024 and dc <= 1024
    return pl.pallas_call(
        functools.partial(_kernel, degree=coeffs.shape[0] - 1, dc=dc),
        grid=(nb, m // bm),
        in_specs=[
            pl.BlockSpec((1, bm, d), lambda n, i: (n, i, 0)),
            pl.BlockSpec((1, c, d), lambda n, i: (n, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, c, d), lambda n, i: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, c, d), jnp.int32),
        interpret=interpret,
    )(x, w, coeffs)
