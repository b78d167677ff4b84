"""Public wrappers for the Pallas kernels: padding, layout and dispatch.

Each entry point runs the pure-jnp reference (kernels/ref.py) unless
REPRO_USE_PALLAS=1 or the caller forces the kernel.  A kernel runs in
interpret mode exactly when the default backend is the CPU (the body
executes as written, so the tests verify its logic there) and is compiled
by Mosaic everywhere else; a kernel that Mosaic refuses raises -- nothing
falls back to the reference behind the caller's back.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from . import coded_gradient as _cg
from . import field_poly as _fp
from . import fused_step as _fs
from . import modmatmul as _mm
from . import ref
from ..core.labels import Coded, Public

# interpret-mode kernels are slow on CPU; route big shapes only when asked
USE_PALLAS = os.environ.get("REPRO_USE_PALLAS", "0") != "0"


def interpret_mode() -> bool:
    """Interpret the Pallas kernels iff the default backend is the CPU.

    Asked per call and never at import: querying the backend initialises
    it, and on a TPU host that claims the chip for this process."""
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# (bm, dc) block selection for the gradient-family kernels.
#
# Priority: REPRO_PALLAS_BLOCKS="bm,dc" env override > the offline tuner's
# JSON table (kernels/blocks.json, written by `python -m repro.kernels.tune`)
# keyed by the power-of-2 (m, d, C) bucket > a shape-derived fallback.  Any
# choice is bit-exact (every partial is fully reduced mod p before
# accumulation, so chunking cannot change the canonical int32 result);
# selection only affects padding waste and VMEM residency.

_BLOCKS_PATH = os.path.join(os.path.dirname(__file__), "blocks.json")
_block_table_cache = None


def _block_table():
    global _block_table_cache
    if _block_table_cache is None:
        try:
            with open(_BLOCKS_PATH) as fh:
                _block_table_cache = json.load(fh)
        except (OSError, ValueError):
            _block_table_cache = {}
    return _block_table_cache


def _bucket(v: int) -> int:
    """Power-of-2 ceiling, floored at 8 (the smallest legal block)."""
    b = 8
    while b < v:
        b *= 2
    return b


def block_key(m: int, d: int, c: int = 1) -> str:
    return f"m{_bucket(m)}_d{_bucket(d)}_c{_bucket(c)}"


def pick_blocks(m: int, d: int, c: int = 1) -> tuple[int, int]:
    """(bm, dc) for an (m, d, C) gradient-family shape.

    The fallback derives minima from the ACTUAL shape including the class
    width: the matrix path's VMEM block holds (bm, d) of X~ plus the
    (dc, C) output slice, so dc is shrunk when C is wide instead of
    reusing the vector-path minimum (which padded ragged class-batched
    shapes pathologically -- see the (m=13, C=10) regression test).  bm
    shrinks for wide d: the double-buffered (bm, d) X~ block is capped at
    2^20 elements (4 MiB a buffer), which with the limb temporaries keeps
    the kernel inside Mosaic's 16 MiB scoped VMEM (d = 5000 needs bm 128).
    """
    env = os.environ.get("REPRO_PALLAS_BLOCKS", "")
    if env:
        bm_s, dc_s = env.split(",")
        return int(bm_s), int(dc_s)
    entry = _block_table().get(block_key(m, d, c))
    if entry:
        return int(entry["bm"]), int(entry["dc"])
    bm = min(_cg.DEFAULT_BM, _bucket(m))
    while bm > 8 and bm * _bucket(d) > (1 << 20):
        bm //= 2
    dc = min(_cg.DEFAULT_DC, _bucket(d))
    while c > 1 and dc * _bucket(c) > 16384 and dc > 8:
        dc //= 2
    return bm, dc


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def modmatmul(a, b, *, bm=None, bn=None, bk=None, force_pallas: bool = False):
    """(a @ b) mod p with padding to block multiples; exact (M, N) output."""
    if not (USE_PALLAS or force_pallas):
        return ref.modmatmul(a, b)
    m, n = a.shape[0], b.shape[1]
    bm = bm or min(_mm.DEFAULT_BM, max(8, a.shape[0]))
    bn = bn or min(_mm.DEFAULT_BN, max(8, b.shape[1]))
    bk = bk or min(_mm.DEFAULT_BK, max(8, a.shape[1]))
    a, _ = _pad_to(a, 0, bm)
    a, _ = _pad_to(a, 1, bk)
    b, _ = _pad_to(b, 0, bk)
    b, _ = _pad_to(b, 1, bn)
    out = _mm.modmatmul(a, b, bm=bm, bn=bn, bk=bk,
                        interpret=interpret_mode())
    return out[:m, :n]


# historical alias: modmatmul itself now returns the exact shape
modmatmul_exact = modmatmul


def modmatmul_batched(a, b, *, bm=None, bn=None, bk=None,
                      force_pallas: bool = False):
    """(a[i] @ b[i]) mod p over a leading batch axis, exact (B, M, N) out.

    One (B, M/bm, N/bn, K/bk)-grid pallas_call instead of B launches.
    """
    if not (USE_PALLAS or force_pallas):
        return ref.modmatmul_batched(a, b)
    m, n = a.shape[1], b.shape[2]
    bm = bm or min(_mm.DEFAULT_BM, max(8, m))
    bn = bn or min(_mm.DEFAULT_BN, max(8, n))
    bk = bk or min(_mm.DEFAULT_BK, max(8, a.shape[2]))
    a, _ = _pad_to(a, 1, bm)
    a, _ = _pad_to(a, 2, bk)
    b, _ = _pad_to(b, 1, bk)
    b, _ = _pad_to(b, 2, bn)
    out = _mm.modmatmul_batched(a, b, bm=bm, bn=bn, bk=bk,
                                interpret=interpret_mode())
    return out[:, :m, :n]


def poly_eval(z, coeffs, *, block=None, force_pallas: bool = False):
    """Elementwise ghat(z) over F_p for any-shape z."""
    if not (USE_PALLAS or force_pallas):
        return ref.poly_eval(z, coeffs)
    shape = z.shape
    flat = z.reshape(-1)
    block = block or min(_fp.DEFAULT_BLOCK, max(8, flat.shape[0]))
    flat, pad = _pad_to(flat, 0, block)
    out = _fp.poly_eval(flat, coeffs, block=block,
                        interpret=interpret_mode())
    if pad:
        out = out[:-pad]
    return out.reshape(shape)


def _gradient_rows(x, w_rows, coeffs, bm, dc):
    """The gradient kernel on class-major models w_rows: (N, C, d), with
    m padded to bm and d to dc (zero rows/columns contribute nothing)."""
    d0 = x.shape[2]
    tbm, tdc = pick_blocks(x.shape[1], d0, w_rows.shape[1])
    bm = bm or tbm
    dc = dc or tdc
    x, _ = _pad_to(x, 1, bm)
    x, _ = _pad_to(x, 2, dc)
    w_rows, _ = _pad_to(w_rows, 2, dc)
    out = _cg.coded_gradient(x, w_rows, coeffs, bm=bm, dc=dc,
                             interpret=interpret_mode())
    return out[:, :, :d0]


def coded_gradient(x: Coded, w: Coded, coeffs: Public, *, bm=None, dc=None,
                   force_pallas: bool = False) -> Coded:
    """Fused f = x^T ghat(x w) over F_p (COPML Eq. 7) for one client."""
    if not (USE_PALLAS or force_pallas):
        return ref.coded_gradient(x, w, coeffs)
    return _gradient_rows(x[None], w[None, None], coeffs, bm, dc)[0, 0]


def coded_gradient_batched(x: Coded, w: Coded, coeffs: Public, *, bm=None,
                           dc=None, force_pallas: bool = False) -> Coded:
    """f[n] = x[n]^T ghat(x[n] w[n]) for all N clients in ONE kernel launch.

    x: (N, m, d); w: (N, d); coeffs shared.  This is COPML's whole Phase-3
    round (every client's Eq. 7 evaluation) as a single (N, m/bm) grid.
    """
    if not (USE_PALLAS or force_pallas):
        return ref.coded_gradient_batched(x, w, coeffs)
    return _gradient_rows(x, w[:, None], coeffs, bm, dc)[:, 0]


def coded_gradient_matrix(x: Coded, w: Coded, coeffs: Public, *, bm=None,
                          dc=None, force_pallas: bool = False) -> Coded:
    """f[n] = x[n]^T ghat(x[n] @ w[n]) for MATRIX models w: (N, d, C).

    The class-batched Phase-3 round of a multi-class objective: one
    (N, m/bm)-grid launch computes every client's and every class's coded
    gradient as a batched GEMM pair, instead of C matvec dispatches.
    """
    if not (USE_PALLAS or force_pallas):
        return ref.coded_gradient_matrix(x, w, coeffs)
    f = _gradient_rows(x, jnp.swapaxes(w, 1, 2), coeffs, bm, dc)
    return jnp.swapaxes(f, 1, 2)


def fused_step(x, w, coeffs, adv_off, dfull, rvec, base, xty, wsh, radd,
               r0sh, *, q_eta: int, inv2k1: int, k1: int, bm=None, dc=None,
               force_pallas: bool = False):
    """Full COPML Phase-3/4 step (post model-encode) as ONE dispatch.

    Operands and returns in the reference layout (ref.fused_step): w and
    the epilogue planes (N, d, C), adv_off/dfull/rvec (N,).  Moves them to
    the kernel's class-major layout (kernels/fused_step.py; free reshapes
    for C = 1) and pads only the sample axis m (zero rows are exact: they
    contribute nothing to X~^T g); the kernel takes d ragged.  Runs the
    phase-by-phase reference composition when Pallas is not requested.
    """
    if not (USE_PALLAS or force_pallas):
        return ref.fused_step(x, w, coeffs, adv_off, dfull, rvec, base, xty,
                              wsh, radd, r0sh, q_eta=q_eta, inv2k1=inv2k1,
                              k1=k1)
    tbm, tdc = pick_blocks(x.shape[1], x.shape[2], w.shape[2])
    bm = bm or tbm
    dc = dc or min(tdc, _bucket(x.shape[2]))
    x, _ = _pad_to(x, 1, bm)

    def plane(a):                       # (N, d, C) -> (C, N, d)
        return jnp.transpose(a, (2, 0, 1))

    f, new_w = _fs.fused_step(
        x, jnp.swapaxes(w, 1, 2), coeffs, adv_off, dfull, rvec[None],
        plane(base), plane(xty), plane(wsh), plane(radd), plane(r0sh),
        q_eta=q_eta, inv2k1=inv2k1, k1=k1, bm=bm, dc=dc,
        interpret=interpret_mode())
    return jnp.swapaxes(f, 1, 2), jnp.transpose(new_w, (1, 2, 0))
