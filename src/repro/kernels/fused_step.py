"""Pallas TPU megakernel: ONE dispatch for a full COPML Phase-3/4 step.

The phase-siloed hot loop costs four dispatches per iteration -- gradient
GEMM pair (kernels/coded_gradient.py), decode matvec, q_eta scale, TruncPr
share arithmetic -- each with its own HBM round-trip over the (N, dw) share
state.  This kernel runs the whole post-encode step on the (N, m/bm) grid of
the gradient kernel and finishes the protocol arithmetic in the kernel
epilogue, so one `pallas_call` per iteration touches X~ exactly once:

  per row block (the double-buffered pipeline body):
      z = X~_blk @ W~          (limb GEMM, dc-chunked contraction)
      g = ghat(z)              (unrolled Horner, in-register on the VPU)
      f += X~_blk^T g          (limb GEMM, bm-wide contraction)
  per client (last row block):
      f_adj = f + adv_offset[n]                 (corruption injection point)
      common += dfull[n] * f_adj                (decode fold, see below)
  once (last client, last row block -- the epilogue):
      xtg    = base + common          (per-holder decode result)
      grad   = xtg - xty
      scaled = grad * q_eta           (public update constant)
      c      = open(scaled + r_sh + bias)   (TruncPr masked opening)
      delta  = (scaled - (c0 - r0_sh)) * inv(2^k1)
      w'     = w - delta

Bit-exactness with the phase-siloed path rests on two facts proven in the
property/golden tests and documented in docs/ARCHITECTURE.md:

* Decode folding.  The holder-h decode row is
  xtg[h] = sum_o dfull[o] * (mix[h,o] + f_adj[o])  where `mix` is
  shamir.share's value-INDEPENDENT masking term (its coefficients depend
  only on the key and shape).  The caller precomputes
  base[h] = sum_o dfull[o] * mix[h,o] from the same randomness stream;
  the kernel only needs the holder-independent
  common = sum_o dfull[o] * f_adj[o], accumulated across the client grid
  dimension.  `dfull` is the (R,) decode row scattered into an (N,) vector
  (zero weight = excluded client), which turns the subset gather into a
  full-length contraction -- exact mod p, and compatible with traced
  fault-plan subsets.
* TruncPr randomness.  r, [r], [r0] are value-independent draws
  (truncation.trunc_pr_randomness); the kernel receives radd = [r] + bias
  and [r0] and performs only the value-DEPENDENT close: the masked open
  c = rvec @ c_sh (rvec = the first-T+1-holders Lagrange row, zero-padded
  to N -- identical weights to shamir.reconstruct's default subset) and
  the borrow-folded rescale.

Every quantity is a canonical representative in [0, p), so any exact mod-p
evaluation order produces bit-identical int32 -- the pinned sha256 goldens
in tests/goldens.py hold with this kernel active.

Layout (what Mosaic accepts): models and gradients are class-major,
(N, C, d), and the five epilogue planes are (C, N, d), so d always rides
the 128-lane axis and the per-class epilogue works on lane-dense (N, d)
slabs, tiled over d in dc-wide chunks.  Per-client scalars (adv_off,
dfull) and the ghat coefficients live in SMEM; the open row rvec is read
as a (1, N) vector and stays in VMEM.  d need not be a multiple of dc (the
chunk loops take a ragged tail), nor m of bm (the row step of
kernels/coded_gradient.py zeroes g past m in the edge block, so those
rows add nothing to X~^T g).  VMEM budget: the six epilogue
planes stay resident, about 6 * C * N * d * 4 bytes after (8, 128) tile
padding -- 4.3 MB at the paper's N=50, d=3073, C=1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import field
from .coded_gradient import (_limb_dot_mod, accumulate_rows, chunks,
                             pack_model, scratch, vmem_limit)


def _kernel(x_ref, w_ref, c_ref, adv_ref, dfull_ref, rvec_ref, base_ref,
            xty_ref, wsh_ref, radd_ref, r0sh_ref, f_ref, wout_ref,
            common_ref, xl_ref, wp_ref, *, m: int, degree: int, dc: int,
            q_eta: int, inv2k1: int, k1: int):
    n = pl.program_id(0)                # client (outer)
    i = pl.program_id(1)                # row block (innermost)
    last_blk = i == pl.num_programs(1) - 1

    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _init_common():
        common_ref[...] = jnp.zeros_like(common_ref)

    @pl.when(i == 0)
    def _init_f():
        f_ref[...] = jnp.zeros_like(f_ref)
        pack_model(w_ref, wp_ref)

    accumulate_rows(x_ref, wp_ref, xl_ref, c_ref, f_ref, m=m, degree=degree,
                    dc=dc)

    @pl.when(last_blk)
    def _fold_client():
        # client n's gradient is complete: inject the (possibly zero)
        # corruption offset and fold into the decode accumulator with this
        # client's public decode weight (zero = excluded from the subset)
        f_adj = field.add(f_ref[0], adv_ref[n])
        common_ref[...] = field.add(common_ref[...],
                                    field.mul(f_adj, dfull_ref[n]))

    @pl.when(jnp.logical_and(n == pl.num_programs(0) - 1, last_blk))
    def _epilogue():
        # Phase 4 on shares, entirely in VMEM: decode + update + TruncPr,
        # one lane-dense (N, e - s) slab of one class at a time
        for c in range(common_ref.shape[0]):
            for s, e in chunks(common_ref.shape[1], dc):
                xtg = field.add(base_ref[c, :, s:e], common_ref[c:c + 1, s:e])
                scaled = field.mul_scalar(
                    field.sub(xtg, xty_ref[c, :, s:e]), q_eta)
                c_sh = field.add(scaled, radd_ref[c, :, s:e])
                # masked OPEN: Lagrange row over holders (contraction N)
                c_open = _limb_dot_mod(rvec_ref[...], c_sh)  # (1, e - s)
                c0 = jnp.bitwise_and(c_open, (1 << k1) - 1)
                a0 = field.sub(c0, r0sh_ref[c, :, s:e])
                delta = field.mul_scalar(field.sub(scaled, a0), inv2k1)
                wout_ref[c, :, s:e] = field.sub(wsh_ref[c, :, s:e], delta)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "dc", "q_eta", "inv2k1", "k1", "interpret"))
def fused_step(x, w, coeffs, adv_off, dfull, rvec, base, xty, wsh, radd,
               r0sh, *, q_eta: int, inv2k1: int, k1: int,
               bm: int, dc: int, interpret: bool = False):
    """One COPML GD step (post model-encode) as a single pallas_call.

    x: (N, m, d) coded slices; w: (N, C, d) class-major coded models;
    coeffs: (r+1,).  adv_off/dfull: (N,) per-client corruption offsets and
    decode row; rvec: (1, N) open row.  base/xty/wsh/radd/r0sh: (C, N, d)
    epilogue planes (see module docstring).  Returns (f, new_w): the
    per-client coded gradients (N, C, d) (pre-corruption, matching the
    gradient kernel) and the updated model shares (C, N, d).  The last row
    block may be an edge block; N <= 1024 bounds the open contraction; d
    may be ragged w.r.t. dc.
    """
    nb, m, d = x.shape
    c = w.shape[1]
    assert w.shape == (nb, c, d), (x.shape, w.shape)
    bm = min(bm, m)
    assert bm <= 1024 and dc <= 1024 and nb <= 1024
    assert bm == m or bm % 8 == 0, (m, bm)
    assert rvec.shape == (1, nb), rvec.shape
    for arr in (base, xty, wsh, radd, r0sh):
        assert arr.shape == (c, nb, d), (arr.shape, (c, nb, d))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    whole = pl.BlockSpec((c, nb, d), lambda n, i: (0, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, m=m, degree=coeffs.shape[0] - 1, dc=dc,
                          q_eta=q_eta, inv2k1=inv2k1, k1=k1),
        grid=(nb, pl.cdiv(m, bm)),
        in_specs=[
            pl.BlockSpec((1, bm, d), lambda n, i: (n, i, 0)),
            pl.BlockSpec((1, c, d), lambda n, i: (n, 0, 0)),
            smem, smem, smem,
            pl.BlockSpec((1, nb), lambda n, i: (0, 0)),
            whole, whole, whole, whole, whole,
        ],
        out_specs=[
            pl.BlockSpec((1, c, d), lambda n, i: (n, 0, 0)),
            whole,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, c, d), jnp.int32),    # f
            jax.ShapeDtypeStruct((c, nb, d), jnp.int32),    # new_w
        ],
        scratch_shapes=[pltpu.VMEM((c, d), jnp.int32)]     # common
        + scratch(bm, d, c),
        # the epilogue planes, double-buffered, and the common row
        compiler_params=vmem_limit(bm, d, c, extra=4 * 12 * c * nb * d),
        interpret=interpret,
    )(x, w, coeffs, adv_off, dfull, rvec, base, xty, wsh, radd, r0sh)
