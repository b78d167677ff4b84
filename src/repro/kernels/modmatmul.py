"""Pallas TPU kernel: finite-field matmul over F_p, p = 2^26 - 5.

TPU-native adaptation of the paper's 64-bit lazy-reduction trick (App. A):
operands are decomposed into four 7-bit limbs; the 16 limb-pair partial
matmuls run EXACTLY on the MXU in f32 (products < 2^14, accumulated over a
<= 1024-wide K block stays < 2^24, f32's exact-integer range); recombination
back to F_p is pure int32 (13-bit-limb modular multiply, every intermediate
< 2^31).  No 64-bit types anywhere -- this kernel lowers to TPU as-is.

Grid: (M/bm, N/bn, K/bk) with K innermost ("arbitrary" semantics); the
output block is revisited across the K dimension and accumulated in VMEM.

`modmatmul_batched` prepends a batch dimension -- grid (B, M/bm, N/bn, K/bk)
-- so B independent field matmuls (e.g. one per COPML client) run as a single
pallas_call instead of B launches under an outer vmap.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import field

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512  # <= 1024 for exact f32 limb accumulation


def _limb(x, i):
    return jnp.bitwise_and(
        jax.lax.shift_right_logical(x, 7 * i), 0x7F).astype(jnp.float32)


def _limb_matmul_mod(a_blk, b_blk):
    """Field matmul of one (bm, bk) x (bk, bn) block; all int32/f32.

    16 MXU matmuls + int32 modular recombination.  Requires bk <= 1024.
    Limb-pair partials sharing a weight class s = i+j are summed in int32
    and the static 2^(7s) weights applied lazily, so the whole block costs
    ONE Barrett reduce (field.recombine_limb_groups) instead of 16
    fold26 + modular-multiply chains.
    """
    groups = [None] * 7
    for i in range(4):
        ai = _limb(a_blk, i)
        for j in range(4):
            bj = _limb(b_blk, j)
            s = jnp.dot(ai, bj, preferred_element_type=jnp.float32)
            term = s.astype(jnp.int32)
            g = groups[i + j]
            groups[i + j] = term if g is None else g + term
    return field.recombine_limb_groups(groups)


def _kernel(a_ref, b_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] = field.add(o_ref[...], _limb_matmul_mod(a_ref[...], b_ref[...]))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def modmatmul(a, b, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
              bk: int = DEFAULT_BK, interpret: bool = False):
    """(a @ b) mod p.  a: (M, K), b: (K, N) int32 field elements.

    Shapes must be multiples of the block sizes (ops.py pads).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape)
    assert bk <= 1024, "bk > 1024 breaks exact f32 limb accumulation"
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(a, b)


def _kernel_batched(a_ref, b_ref, o_ref):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] = field.add(o_ref[0], _limb_matmul_mod(a_ref[0], b_ref[0]))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def modmatmul_batched(a, b, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                      bk: int = DEFAULT_BK, interpret: bool = False):
    """(a[i] @ b[i]) mod p for all i.  a: (B, M, K), b: (B, K, N) int32.

    M/N/K must be multiples of the block sizes (ops.py pads).
    """
    bsz, m, k = a.shape
    bsz2, k2, n = b.shape
    assert bsz == bsz2 and k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape)
    assert bk <= 1024, "bk > 1024 breaks exact f32 limb accumulation"
    grid = (bsz, m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _kernel_batched,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda bi, i, j, kk: (bi, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda bi, i, j, kk: (bi, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda bi, i, j, kk: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, m, n), jnp.int32),
        interpret=interpret,
    )(a, b)
