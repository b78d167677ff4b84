"""Pallas TPU kernel: the coded gradient  f = X~^T ghat(X~ w~)  over F_p.

This is COPML's hot loop (paper Eq. 7, the first column of Table I): every
iteration, each of the N clients evaluates it on its coded slice X~_i, the
one large operand of Phase 3.  The pass is bound by reading X~, so the
kernel reads it from HBM once, in its stored (N, m, d) int32 layout, and
nothing of its size is written back: an (N, m/bm) grid runs the whole
round as one pallas_call, w~_i resident in VMEM across a client's row
blocks.  The model is class-major, w~_i: (C, d) (C = 1 is the binary
vector model, C > 1 the multi-class one-vs-rest matrix model):

    z^T = W X_blk^T        (C, bm)   contraction over d, chunked by dc
    g^T = ghat(z^T)        (C, bm)   unrolled Horner on the VPU
    f  += g^T X_blk        (C, d)    contraction over bm

Field arithmetic follows kernels/short_modmatmul.py.  Each row block's
four 7-bit limbs are formed once in VMEM, as int8, and both products use
them.  The small operand (W, then g) is packed by weight class, so each
product is one int8 MXU product per limb of X~ for all seven classes
s = i + j, with int32 sums: a class sum is at most 4 * 1024 * 127^2 <
2^26 for a contraction of at most 1024 (dc for the first product, the bm
rows for the second), the bound field.recombine_limb_groups asks before
its one Barrett reduce.  The result is bit-identical to the jnp path.

The last row block may be an edge block (m % bm != 0): its rows past m
hold whatever the buffer held, so their g is set to zero before the
second product and they add nothing.  d need not be a multiple of dc or
128 (the chunk loops take a ragged tail).  Layout for Mosaic: d rides
the 128-lane axis and C the sublanes; the gradient polynomial's
coefficients are scalars read from SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import field
from .short_modmatmul import (class_rows, limb_planes, recombine_classes,
                              _N_GROUPS, _N_LIMBS, _SUBLANE, _round_up)

# Mosaic's default scoped VMEM on a v5e; a kernel whose blocks need more
# asks for it (`vmem_limit`)
_SCOPED_VMEM = 16 << 20
# VMEM for a row block's double-buffered int32 rows and int8 limbs
# (`blocks`): all 902 rows of the paper's CIFAR-10 Case 2 at d = 3073
_ROWS_VMEM = 40 << 20
# kernels/ops.py routes Phase 3 here on a TPU when `routes` admits the
# shape: the binary model (C = 1) with at least ROUTE_M_MIN rows per
# client.  ROUTE_M_MIN is not timed against XLA yet (PERF.md, Open
# questions): it keeps the m/K = 8 deployments on XLA, whose 8-row slices
# are read in microseconds either way.  C > 1 keeps XLA until a timing
# shows the kernel no slower there.
ROUTE_M_MIN = 16


def routes(m: int, c: int) -> bool:
    """Whether Phase 3 on coded slices of m rows with C classes takes this
    kernel on a TPU (elsewhere the jnp path in kernels/ref.py runs)."""
    return m >= ROUTE_M_MIN and c == 1


def blocks(m: int, d: int, c: int) -> tuple[int, int]:
    """(bm, dc) for Phase 3 on (m, d) slices with C classes.  dc is the
    widest exact chunk, 1024, narrowed while a wide C's (7 Cp, dc) class
    sums would crowd VMEM.  The rows take the fewest blocks whose VMEM
    fits _ROWS_VMEM (bm shrinks as d grows), split evenly so the edge
    block wastes fewer than 8 rows: one block of all m rows where it fits
    (m/K = 902 at d = 3073, 375 at d = 5000), so each client's slice is
    one grid step."""
    dc = field.MATMUL_CHUNK
    while dc > 8 and dc * _round_up(c, _SUBLANE) > 16384:
        dc //= 2
    rows = _ROWS_VMEM // ((2 * 4 + _N_LIMBS) * _round_up(d, 128))
    rows = max(_SUBLANE, min(rows // _SUBLANE * _SUBLANE, field.MATMUL_CHUNK))
    n_blocks = -(-m // rows)
    bm = m if n_blocks == 1 else _round_up(-(-m // n_blocks), _SUBLANE)
    return bm, dc


def chunks(d: int, width: int):
    """Static [start, stop) column chunks of width <= `width` covering d
    (the last one ragged when width does not divide d)."""
    return [(s, min(s + width, d)) for s in range(0, d, width)]


def packed_classes(a):
    """The weight-class packing of a (M, K) int32 block's limbs, as four
    int8 (7 Mp, K) column blocks (zero rows pad M to Mp, a multiple of 8)."""
    m, k = a.shape
    if m % _SUBLANE:
        a = jnp.concatenate(
            [a, jnp.zeros((_round_up(m, _SUBLANE) - m, k), a.dtype)], axis=0)
    al = limb_planes(a)
    return [class_rows(al, j).astype(jnp.int8) for j in range(_N_LIMBS)]


def class_dot(packed, limbs, contract: int):
    """(a @ b) mod p, rows padded to Mp: `packed` is a's packing (four
    (7 Mp, K) int8 blocks), `limbs` b's four int8 limb planes, contracted
    on their dim `contract` (0: b is (K, N), 1: b is (N, K)).  K <= 1024."""
    dn = (((1,), (contract,)), ((), ()))
    g = None
    for a_j, b_j in zip(packed, limbs):
        t = jax.lax.dot_general(a_j, b_j, dn,
                                preferred_element_type=jnp.int32)
        g = t if g is None else g + t
    return recombine_classes(g, packed[0].shape[0] // _N_GROUPS)


def _limb_dot_mod(a, b):
    """(a @ b) mod p of int32 field blocks a: (M, K), b: (K, N), K <= 1024."""
    limbs = [l.astype(jnp.int8) for l in limb_planes(b)]
    return class_dot(packed_classes(a), limbs, 0)[:a.shape[0]]


def pack_model(w_ref, wp_ref):
    """wp_ref[j] = column block j of the client's model packing (w_ref's
    (1, C, d) block): the first product's left operand, for every row block
    of the client."""
    for j, blk in enumerate(packed_classes(w_ref[0])):
        wp_ref[j] = blk


def accumulate_rows(x_ref, wp_ref, xl_ref, c_ref, f_ref, *, m: int,
                    degree: int, dc: int):
    """f[0] += ghat(W X^T) X for one (bm, d) row block of one client.

    x_ref: (1, bm, d) coded rows (the block `program_id(1)` of m rows);
    wp_ref: (4, 7 Cp, d) int8 model packing (pack_model); xl_ref:
    (4, bm, d) int8 scratch for the block's limbs; c_ref: (r+1,) SMEM
    coefficients; f_ref: (1, C, d) accumulator."""
    bm, d = x_ref.shape[1:]
    c = f_ref.shape[1]
    for j, limb in enumerate(limb_planes(x_ref[0])):
        xl_ref[j] = limb.astype(jnp.int8)
    spans = chunks(d, dc)
    z = None
    for s, e in spans:
        part = class_dot([wp_ref[j, :, s:e] for j in range(_N_LIMBS)],
                         [xl_ref[j, :, s:e] for j in range(_N_LIMBS)], 1)
        z = part if z is None else field.add(z, part)        # (Cp, bm)
    g = jnp.broadcast_to(c_ref[degree], z.shape)
    for t in range(degree - 1, -1, -1):
        g = field.add(field.mul(g, z), jnp.broadcast_to(c_ref[t], z.shape))
    if m % bm:                          # the edge block's rows past m
        row = pl.program_id(1) * bm + jax.lax.broadcasted_iota(
            jnp.int32, g.shape, 1)
        g = jnp.where(row < m, g, 0)
    gp = packed_classes(g)
    for s, e in spans:
        upd = class_dot(gp, [xl_ref[j, :, s:e] for j in range(_N_LIMBS)],
                        0)[:c]                               # (C, e - s)
        f_ref[0, :, s:e] = field.add(f_ref[0, :, s:e], upd)


def scratch(bm: int, d: int, c: int):
    """VMEM scratch of accumulate_rows: X~'s limbs and the model packing."""
    cp = _round_up(c, _SUBLANE)
    return [pltpu.VMEM((_N_LIMBS, bm, d), jnp.int8),
            pltpu.VMEM((_N_LIMBS, _N_GROUPS * cp, d), jnp.int8)]


def vmem_limit(bm: int, d: int, c: int, extra: int = 0):
    """Compiler parameters asking for the VMEM that the blocks and scratch
    of a gradient-family kernel need (double-buffered (bm, d) rows and
    (C, d) model and gradient, the limbs, the packing, one chunk's int32
    temporaries), or None where Mosaic's default suffices."""
    lanes = _round_up(d, 128)
    cp = _round_up(c, _SUBLANE)
    need = (2 * 4 * bm * lanes + _N_LIMBS * bm * lanes
            + 4 * 4 * cp * lanes + _N_LIMBS * _N_GROUPS * cp * lanes
            + 4 * 4 * bm * 1024 + extra)
    if need <= _SCOPED_VMEM * 3 // 4:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(need * 3 // 2,
                                                     100 << 20))


def _kernel(x_ref, w_ref, c_ref, f_ref, xl_ref, wp_ref, *, m: int,
            degree: int, dc: int):
    @pl.when(pl.program_id(1) == 0)     # first row block of this client
    def _init():
        f_ref[...] = jnp.zeros_like(f_ref)
        pack_model(w_ref, wp_ref)

    accumulate_rows(x_ref, wp_ref, xl_ref, c_ref, f_ref, m=m, degree=degree,
                    dc=dc)


@functools.partial(jax.jit, static_argnames=("bm", "dc", "interpret"))
def coded_gradient(x, w, coeffs, *, bm: int, dc: int,
                   interpret: bool = False):
    """f[n] = (ghat(w[n] x[n]^T) x[n]) mod p for all N clients at once.

    x: (N, m, d) int32 field; w: (N, C, d) class-major coded models;
    coeffs: (r+1,) shared across clients and classes.  Returns (N, C, d).
    bm, dc <= 1024; bm a multiple of 8 or m itself.  Grid (N, cdiv(m, bm)):
    the row-block dimension is innermost so client n's output block, w~_n
    and its packing stay VMEM-resident across its whole slice.
    """
    nb, m, d = x.shape
    c = w.shape[1]
    assert w.shape == (nb, c, d), (x.shape, w.shape)
    bm = min(bm, m)
    assert bm <= field.MATMUL_CHUNK and dc <= field.MATMUL_CHUNK, (bm, dc)
    assert bm == m or bm % _SUBLANE == 0, (m, bm)
    return pl.pallas_call(
        functools.partial(_kernel, m=m, degree=coeffs.shape[0] - 1, dc=dc),
        grid=(nb, pl.cdiv(m, bm)),
        in_specs=[
            pl.BlockSpec((1, bm, d), lambda n, i: (n, i, 0)),
            pl.BlockSpec((1, c, d), lambda n, i: (n, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, c, d), lambda n, i: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, c, d), jnp.int32),
        scratch_shapes=scratch(bm, d, c),
        compiler_params=vmem_limit(bm, d, c),
        interpret=interpret,
    )(x, w, coeffs)
