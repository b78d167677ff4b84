"""Pallas TPU kernel: field products with a short contraction, (a @ b) mod p.

`field.matmul` forms 16 f32 limb-product planes and recombines them mod p in
a second pass; with a short contraction and a wide output (an (N, K+T)
public matrix times a (K+T, huge) operand: LCC encode, Shamir share and
reconstruct) both passes are bound by the planes' HBM traffic, 64 B per
4 B output element.  This kernel keeps them in VMEM: per (K, bn) tile of
`b` it forms the limbs, runs the limb products on the MXU, recombines mod p
and writes only the (M, bn) int32 result.

The products are packed by weight class.  With a_i, b_j the 7-bit limbs,
class s = i + j collects  G_s = sum_i a_i @ b_{s-i}, which is ONE product
of a's limbs laid out as a (7 M, 4 K) matrix (row block s, column block j
holds a_{s-j}, zero where s - j is not a limb) with b's limbs stacked
along the contraction, (4 K, bn): one MXU product of depth 4 K for all
seven classes.  Limbs are < 2^7, so int8 holds them exactly; the MXU sums
their products in int32, where G_s <= 4 K 127^2 < 2^26 for K <= 1024, the
bound field.recombine_limb_groups asks before its one Barrett reduce.  The
result is bit-identical to field.matmul.  (On a v5e the int8 product ran
10-15% faster than the same packing in bf16 with f32 sums, and the packing
faster than 4 or 16 separate limb products; PERF.md has the timings.)

Grid: the N tiles only (plus the batch axis that `jax.vmap` prepends); the
whole contraction is in each step and the packed `a` stays resident.  M
and K are rounded up to the int32 sublane tile inside the blocks: rows of
`b` past K are the edge block's padding and meet zero columns of the packed
`a`, and output rows past M are dropped by the edge block's write.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import field

# field.matmul routes (M, K) @ (K, N) here when K <= SHORT_K_MAX,
# M <= SHORT_M_MAX and N >= SHORT_N_MIN (`routes`).  On a v5e every
# protocol product of that kind timed (K <= 50, M <= 50, N >= 3073: LCC
# encode, Shamir share and reconstruct at N = 50) ran 1.3-8x faster than
# the jnp path, and so did K up to 256, M up to 512 and N down to 128 at
# N = 50 holders under vmap; a lone product of a few thousand outputs
# timed even.
SHORT_K_MAX = 128
SHORT_M_MAX = 256
SHORT_N_MIN = 512

_SUBLANE = 8
_LANE = 128
_N_LIMBS = 4
_N_GROUPS = 2 * _N_LIMBS - 1
# VMEM for one grid step's blocks and temporaries, below Mosaic's 16 MiB
# default scoped limit on a v5e (12 MiB timed a little faster than 4 or 8),
# and the widest tile timed
_VMEM_BYTES = 12 << 20
_MAX_BN = 8192


def routes(m: int, k: int, n: int) -> bool:
    """Whether an (m, k) @ (k, n) field product takes this kernel."""
    return k <= SHORT_K_MAX and m <= SHORT_M_MAX and n >= SHORT_N_MIN


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _block_n(mp: int, kp: int, n: int) -> int:
    """Lanes per grid step: the widest multiple of 128 whose blocks
    (double-buffered b and output tiles) and temporaries (b's limbs in
    int32 and int8, the int32 class sums and the recombination's terms)
    fit _VMEM_BYTES."""
    words_per_lane = (2 * kp + 2 * mp) + (4 * kp + kp) \
        + (_N_GROUPS * mp + 4 * mp)
    bn = (_VMEM_BYTES // (4 * words_per_lane)) // _LANE * _LANE
    return max(_LANE, min(bn, _MAX_BN, _round_up(n, _LANE)))


def limb_planes(x):
    """The four 7-bit limbs of int32 field elements, as int32 arrays of
    x's shape (each < 2^7, so int8 holds it exactly)."""
    return [jnp.bitwise_and(jax.lax.shift_right_logical(x, 7 * i), 0x7F)
            for i in range(_N_LIMBS)]


def class_rows(limbs, j: int):
    """Column block j of the weight-class packing of a's limbs, (7 M, K):
    row block s holds the limb s - j, zero where s - j is no limb."""
    zero = jnp.zeros_like(limbs[0])
    return jnp.concatenate([limbs[s - j] if 0 <= s - j < _N_LIMBS else zero
                            for s in range(_N_GROUPS)], axis=0)


def recombine_classes(g, rows: int):
    """(7 rows, N) int32 class sums, each < 2^26 -> (rows, N) mod p."""
    return field.recombine_limb_groups(
        [g[s * rows:(s + 1) * rows] for s in range(_N_GROUPS)])


def _packed_limbs(a, mp: int, kp: int):
    """(7 mp, 4 kp) int8: row block s, column block j holds the limb
    s - j of `a` (zero-padded to (mp, kp)), zero where s - j is no limb."""
    m, k = a.shape
    al = limb_planes(jnp.pad(a, ((0, mp - m), (0, kp - k))))
    return jnp.concatenate([class_rows(al, j) for j in range(_N_LIMBS)],
                           axis=1).astype(jnp.int8)


def _kernel(lhs_ref, b_ref, o_ref):
    limbs = jnp.concatenate(limb_planes(b_ref[...]),
                            axis=0).astype(jnp.int8)         # (4 kp, bn)
    g = jnp.dot(lhs_ref[...], limbs,
                preferred_element_type=jnp.int32)            # (7 mp, bn)
    o_ref[...] = recombine_classes(g, o_ref.shape[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def short_modmatmul(a, b, *, interpret: bool = False):
    """(a @ b) mod p for int32 field matrices a: (M, K), b: (K, N) with
    K <= 1024; bit-identical to field.matmul.  Under `jax.vmap` it
    stays one pallas_call, the batch a leading grid axis."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert k <= field.MATMUL_CHUNK, "class sums must stay below 2^26"
    mp, kp = _round_up(m, _SUBLANE), _round_up(k, _SUBLANE)
    bn = _block_n(mp, kp, n)
    return pl.pallas_call(
        _kernel,
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((_N_GROUPS * mp, _N_LIMBS * kp), lambda j: (0, 0)),
            pl.BlockSpec((kp, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((mp, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(_packed_limbs(a, mp, kp), b)
