"""Prime-field arithmetic over F_p, p = 2^26 - 5, in pure int32 JAX.

This is the substrate for every MPC/LCC operation in COPML.  The paper's
64-bit implementation relies on "mod once per inner product" with
d * (p-1)^2 <= 2^64 - 1 (Appendix A).  TPUs have no 64-bit vector path, so we
adapt the same lazy-reduction idea to int32:

* field elements live in [0, p) and always fit in 26 bits;
* products are computed by 13-bit limb decomposition -- every intermediate
  stays strictly below 2^31 (proofs inline below);
* matmuls decompose operands into four 7-bit limbs so the partial products
  (< 2^14) can be accumulated EXACTLY in f32 on the MXU for up to 2^10
  contraction elements per chunk, then recombined modularly in int32.

Everything here is jit-able, shard_map-able, and TPU-lowerable as-is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# The paper's prime for 64-bit CIFAR-10 runs: the largest prime below 2^26
# such that d * (p-1)^2 <= 2^64 - 1 for d = 3072.  2^26 = p + 5, which gives
# the cheap folding rule  t = (t >> 26) * 5 + (t & MASK26)  (mod p).
P_BITS = 26
P = (1 << P_BITS) - 5  # 67108859, prime
_MASK26 = (1 << P_BITS) - 1
_MASK13 = (1 << 13) - 1
_MASK7 = (1 << 7) - 1

FIELD_DTYPE = jnp.int32


def _csub(t):
    """Conditional subtract: t in [0, 2p) -> t mod p."""
    return t - jnp.where(t >= P, P, 0).astype(t.dtype)


def fold26(t):
    """Reduce t in [0, 2^31) to [0, p) using 2^26 = 5 (mod p).

    t = t1 * 2^26 + t0  ==>  t = 5*t1 + t0 (mod p).
    For t < 2^31: t1 < 2^5 so 5*t1 + t0 < 2^26 + 160 < 2p; one csub finishes.
    """
    t1 = jax.lax.shift_right_logical(t, P_BITS)
    t0 = jnp.bitwise_and(t, _MASK26)
    return _csub(t1 * 5 + t0)


# Barrett reduction against p = 2^26 - 5.  mu = floor(2^32 / p) = 64 = 2^6
# EXACTLY (2^32 = 64*p + 320), so the Barrett quotient
#   q = (t * mu) >> 32  =  (t << 6) >> 32  =  t >> 26
# needs no 64-bit multiply: mu folds into a single shift.  The classic
# Barrett error bound gives q in {floor(t/p)-1, floor(t/p)} for t < 2^31
# (the gap t/p - t/2^26 = 5t/(p*2^26) < 1 over the whole range), hence
# r = t - q*p lies in [0, 2p) and one conditional subtract finishes.
BARRETT_MU = (1 << 32) // P          # 64 == 2^6, public constant
_BARRETT_SHIFT = 32 - (BARRETT_MU.bit_length() - 1)   # 26


def barrett_reduce(t):
    """Barrett-reduce t in [0, 2^31) to [0, p).

    q = (t * BARRETT_MU) >> 32 computed as a shift (mu is a power of two
    for this p); r = t - q*p < 2p, one csub.  Sanctioned field-arithmetic
    site: the mu-multiply/shift + q*p subtract is the reduction itself.
    """
    q = jax.lax.shift_right_logical(t, _BARRETT_SHIFT)
    return _csub(t - q * P)


def add(a, b):
    """(a + b) mod p.  a, b in [0, p): sum < 2^27, fits int32."""
    return _csub(a + b)


def sub(a, b):
    """(a - b) mod p."""
    d = a - b
    return d + jnp.where(d < 0, P, 0).astype(d.dtype)


def neg(a):
    """(-a) mod p."""
    return _csub(jnp.asarray(P, a.dtype) - a)


def mul(a, b):
    """(a * b) mod p via 13-bit limbs -- every intermediate < 2^31.

    a = a1*2^13 + a0, b = b1*2^13 + b0 with a1,b1 < 2^13, a0,b0 < 2^13.
      a*b = a1*b1*2^26 + (a1*b0 + a0*b1)*2^13 + a0*b0
    Let mm = a1*b0 + a0*b1 < 2^27; mm = m1*2^13 + m0 (m1 < 2^14).
      mm*2^13 = m1*2^26 + m0*2^13 == 5*m1 + m0*2^13 (mod p)
    Total t = 5*hh + 5*m1 + (m0<<13) + ll
            < 5*2^26 + 5*2^14 + 2^26 + 2^26 < 2^29.4 < 2^31.  fold26 + csub.
    """
    a1 = jax.lax.shift_right_logical(a, 13)
    a0 = jnp.bitwise_and(a, _MASK13)
    b1 = jax.lax.shift_right_logical(b, 13)
    b0 = jnp.bitwise_and(b, _MASK13)
    hh = a1 * b1
    mm = a1 * b0 + a0 * b1
    ll = a0 * b0
    m1 = jax.lax.shift_right_logical(mm, 13)
    m0 = jnp.bitwise_and(mm, _MASK13)
    t = 5 * hh + 5 * m1 + jax.lax.shift_left(m0, 13) + ll
    return fold26(t)


def mul_scalar(a, c: int):
    """a * c mod p where c is a static Python int (public constant)."""
    c = int(c) % P
    return mul(a, jnp.asarray(c, a.dtype))


def pow_const(a, e: int):
    """a ** e mod p for a static exponent, by square-and-multiply."""
    e = int(e)
    assert e >= 0
    result = jnp.ones_like(a)
    base = a
    while e:
        if e & 1:
            result = mul(result, base)
        base = mul(base, base)
        e >>= 1
    return result


def inv(a):
    """a^{-1} mod p (Fermat).  Undefined for a == 0."""
    return pow_const(a, P - 2)


# ---------------------------------------------------------------------------
# Host-side exact helpers (used for static public constants such as the
# Lagrange coefficient matrices -- evaluation points are public).
# ---------------------------------------------------------------------------

def host_inv(a: int) -> int:
    return pow(int(a) % P, P - 2, P)


def host_lagrange_coeffs(xs, targets) -> np.ndarray:
    """Exact Lagrange basis matrix  L[t, j] = prod_{l != j} (z_t - x_l)/(x_j - x_l)
    over F_p, computed with Python ints.  xs: interpolation nodes (len n);
    targets: evaluation points (len m).  Returns (m, n) int32 in [0, p).
    """
    xs = [int(x) % P for x in xs]
    ts = [int(t) % P for t in targets]
    n = len(xs)
    out = np.zeros((len(ts), n), dtype=np.int64)
    for ti, z in enumerate(ts):
        for j in range(n):
            num, den = 1, 1
            for l in range(n):
                if l == j:
                    continue
                num = (num * ((z - xs[l]) % P)) % P
                den = (den * ((xs[j] - xs[l]) % P)) % P
            out[ti, j] = (num * host_inv(den)) % P
    return out.astype(np.int32)


# ---------------------------------------------------------------------------
# Field matmul: the 7-bit-limb / f32-MXU algorithm (also used by the Pallas
# kernel, block-wise).  Pure jnp version here for small/irregular shapes and
# as a shared reference.
# ---------------------------------------------------------------------------

_N_LIMBS = 4  # 4 x 7-bit limbs cover 28 >= 26 bits
_LIMB_BITS = 7
# 2^(7*(i+j)) mod p for i+j in [0, 6]
_LIMB_WEIGHTS = tuple(pow(2, _LIMB_BITS * s, P) for s in range(2 * _N_LIMBS - 1))
# max contraction length for exact f32 accumulation: products < 2^14, f32 is
# exact below 2^24  =>  chunk <= 2^10
MATMUL_CHUNK = 1 << 10


def _limbs(x):
    """int32 [0,p) -> f32 limbs stacked on a new leading axis (4, ...)."""
    ls = []
    for i in range(_N_LIMBS):
        ls.append(jnp.bitwise_and(
            jax.lax.shift_right_logical(x, _LIMB_BITS * i), _MASK7))
    return jnp.stack(ls).astype(jnp.float32)


def _lazy_shift26(h, b: int):
    """h * 2^b (mod p) as an UNREDUCED int32 value, b in [0, 26).

    Split h = h1 * 2^(26-b) + h0 (h0 < 2^(26-b)); then
      h * 2^b = h1 * 2^26 + h0 * 2^b == 5*h1 + h0 * 2^b  (mod p).
    The result is exact mod p but deliberately NOT reduced -- callers
    accumulate several lazy terms and Barrett-reduce once.  Bound:
    for h < 2^(26+c), result < 5*2^(b+c) + 2^26.
    """
    h1 = jax.lax.shift_right_logical(h, P_BITS - b)
    h0 = jnp.bitwise_and(h, (1 << (P_BITS - b)) - 1)
    return h1 * 5 + jax.lax.shift_left(h0, b)


def recombine_limb_groups(groups):
    """Mod-p combination  sum_s groups[s] * 2^(7s)  with ONE final reduce.

    groups: 7 int32 arrays G_s < 2^26 (group s collects the limb-pair
    partial sums with i+j == s: <= 4 terms, each <= 1024*127*127 < 2^24,
    so G_s <= 66,064,384 < 2^26).  Every weight 2^(7s) mod p is applied
    lazily -- static shift/splits via 2^26 == 5 (s <= 3), a plain *20
    (s == 4, since 2^28 == 20 mod p), or *5 then shift-split (s in {5,6})
    -- so no per-term reduction happens at all.  Worst-case total:
      G_0 + (2^26 + 5*2^7) + (2^26 + 5*2^14) + (2^26 + 5*2^21)
        + 20*G_4 + (2^26 + 5*2^11) + (2^26 + 5*2^17)
      <= 1.36e9 < 2^31,
    (the dominant term is 20*G_4 <= 990,965,760), so a single
    barrett_reduce finishes.  This replaces the historical 16x
    fold26+mul+add per-term chain.
    """
    t = groups[0]                                   # w = 1
    t = t + _lazy_shift26(groups[1], 7)             # w = 2^7
    t = t + _lazy_shift26(groups[2], 14)            # w = 2^14
    t = t + _lazy_shift26(groups[3], 21)            # w = 2^21
    t = t + groups[4] * 20                          # 2^28 == 20 (mod p)
    t = t + _lazy_shift26(groups[5] * 5, 9)         # 2^35 == 5 * 2^9
    t = t + _lazy_shift26(groups[6] * 5, 16)        # 2^42 == 5 * 2^16
    return barrett_reduce(t)


def _recombine_limb_products(s):
    """s: (4, 4, M, N) f32 exact-int partial sums (< 2^24).

    Returns (M, N) int32 mod-p recombination  sum_ij s[i,j] * 2^(7(i+j)).
    Partial sums sharing a weight class s = i+j are grouped in int32
    FIRST (f32 sums could cross the 2^24 exact-integer bound), then the
    whole recombination is one Barrett reduce via recombine_limb_groups.
    """
    groups = [None] * (2 * _N_LIMBS - 1)
    for i in range(_N_LIMBS):
        for j in range(_N_LIMBS):
            term = s[i, j].astype(jnp.int32)
            g = groups[i + j]
            groups[i + j] = term if g is None else g + term
    return recombine_limb_groups(groups)


def _tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


def matmul(a, b):
    """(a @ b) mod p for int32 field matrices a:(M,K), b:(K,N).

    TPU-native: 16 exact f32 matmuls per <=1024-wide K-chunk + int32 modular
    recombination.  No intermediate exceeds f32's exact-int range or int32.

    On a TPU a short contraction with a wide output (the shapes that
    kernels/short_modmatmul.routes admits: LCC encode, Shamir share and
    reconstruct) runs as one Pallas pass that keeps the limb products in
    VMEM, under the scope `field.short_matmul`; it gives the same bits.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if _tpu_backend():
        from ..kernels import short_modmatmul   # kernels import this module
        if short_modmatmul.routes(m, k, n):
            with jax.named_scope("field.short_matmul"):
                return short_modmatmul.short_modmatmul(a, b)
    out = jnp.zeros((m, n), dtype=jnp.int32)
    for start in range(0, k, MATMUL_CHUNK):
        stop = min(start + MATMUL_CHUNK, k)
        al = _limbs(a[:, start:stop])          # (4, M, kc)
        bl = _limbs(b[start:stop, :])          # (4, kc, N)
        # s[i, j] = A_i @ B_j, exact in f32 (products < 2^14, kc <= 2^10)
        s = jnp.einsum("imk,jkn->ijmn", al, bl,
                       preferred_element_type=jnp.float32)
        out = add(out, _recombine_limb_products(s))
    return out


def matvec(a, v):
    """(a @ v) mod p, a:(M,K) v:(K,)."""
    return matmul(a, v[:, None])[:, 0]


def matvec_batched(a, v):
    """(a[i] @ v[i]) mod p for a: (B, M, K), v: (B, K) -- limb-packed GEMM.

    A vmap of matvec runs 16 (M, kc) x (kc, 1) limb matvecs per batch
    element; packing the 4 limbs of `a` into the GEMM M dimension and the 4
    limbs of `v` into its N dimension turns each K-chunk into ONE
    (B, 4M, kc) x (B, kc, 4) batched matmul -- a far better gemm shape than
    n=1 matvecs (1.25x over the vmap at B=8, 2.6x at B=32 on XLA CPU), with
    identical recombination cost.  Exactness bounds are unchanged: products
    < 2^14 accumulated over kc <= 2^10 stay in f32's exact-integer range.
    """
    bsz, m, k = a.shape
    assert v.shape == (bsz, k), (a.shape, v.shape)
    out = jnp.zeros((bsz, m), jnp.int32)
    for start in range(0, k, MATMUL_CHUNK):
        stop = min(start + MATMUL_CHUNK, k)
        al = jax.vmap(_limbs)(a[:, :, start:stop])       # (B, 4, M, kc)
        vl = jax.vmap(_limbs)(v[:, start:stop])          # (B, 4, kc)
        s = jnp.matmul(al.reshape(bsz, _N_LIMBS * m, stop - start),
                       jnp.swapaxes(vl, 1, 2),
                       preferred_element_type=jnp.float32)
        s = s.reshape(bsz, _N_LIMBS, m, _N_LIMBS)        # (B, i, M, j)
        out = add(out, _recombine_limb_products(
            jnp.transpose(s, (1, 3, 0, 2))))             # (i, j, B, M)
    return out


def evaluate_poly(coeffs, x):
    """Horner evaluation of sum_i coeffs[i] * x^i over F_p.

    coeffs: 1-D int32 field array, lowest degree first.  x: any shape.
    """
    acc = jnp.full_like(x, int(coeffs[-1]))
    for i in range(coeffs.shape[0] - 2, -1, -1):
        acc = add(mul(acc, x), jnp.full_like(x, int(coeffs[i])))
    return acc


def evaluate_poly_dyn(coeffs, x):
    """Horner with traced coefficient vector (not static)."""
    acc = jnp.broadcast_to(coeffs[-1], x.shape)
    for i in range(coeffs.shape[0] - 2, -1, -1):
        acc = add(mul(acc, x), jnp.broadcast_to(coeffs[i], x.shape))
    return acc


def random_field(key, shape):
    """Uniform elements of F_p."""
    return jax.random.randint(key, shape, 0, P, dtype=FIELD_DTYPE)


# ---------------------------------------------------------------------------
# numpy uint64 oracle (host-side ground truth for tests; NOT part of the
# TPU-lowerable path)
# ---------------------------------------------------------------------------

def np_mul(a, b):
    return ((a.astype(np.uint64) * b.astype(np.uint64)) % np.uint64(P)).astype(np.int64)


def np_matmul(a, b):
    """Exact field matmul with the paper's 64-bit lazy reduction."""
    a = a.astype(np.uint64)
    b = b.astype(np.uint64)
    k = a.shape[1]
    # d*(p-1)^2 <= 2^64-1 holds for d <= 4096 with this p; chunk to stay safe
    chunk = 4096
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint64)
    for s in range(0, k, chunk):
        out = (out + (a[:, s:s + chunk] @ b[s:s + chunk, :]) % np.uint64(P)) % np.uint64(P)
    return out.astype(np.int64)
