"""Pallas TPU kernel: elementwise Horner evaluation of ghat over F_p.

VPU-bound elementwise kernel; the coefficient vector (r+1 elements, r <= 3 in
the paper) is read as scalars from SMEM and the Horner chain is unrolled
statically.  All int32 (13-bit-limb modular multiplies).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import field

DEFAULT_BLOCK = 4096


def _kernel(z_ref, c_ref, o_ref, *, degree: int):
    z = z_ref[...]
    acc = jnp.broadcast_to(c_ref[degree], z.shape)
    for i in range(degree - 1, -1, -1):
        acc = field.add(field.mul(acc, z), jnp.broadcast_to(c_ref[i], z.shape))
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def poly_eval(z, coeffs, *, block: int = DEFAULT_BLOCK, interpret: bool = False):
    """Evaluate sum_i coeffs[i] z^i over F_p elementwise.

    z: (L,) int32 field elements, L % block == 0 (ops.py pads);
    coeffs: (r+1,) int32.
    """
    (l,) = z.shape
    assert l % block == 0
    degree = coeffs.shape[0] - 1
    return pl.pallas_call(
        functools.partial(_kernel, degree=degree),
        grid=(l // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((l,), jnp.int32),
        interpret=interpret,
    )(z, coeffs)
