"""Plain reference for COPML logistic regression (paper Algorithm 1).

Straightforward numpy over F_p, p = 2^26 - 5, written from the paper and
the configuration file alone; it imports nothing of the program under
test.  It provides:

* the fixed-point constants the configuration implies (scales, the
  degree-r sigmoid fit, the update multiplier and the truncation depth);
* exact checks of what the protocol must produce whatever its randomness:
  Shamir shares lie on one degree-T polynomial, the coded data X~ is the
  Lagrange encoding of the quantized data, and X^T y opens exactly;
* the expected training trajectory in prediction space.  With r = 1 the
  gradient is affine in the model, and TruncPr's stochastic rounding is
  unbiased, so the expectation of the opened model's predictions X w
  after t iterations is the unrounded fixed-point iteration below.  The
  protocol's own masks cancel exactly; only the rounding differs from run
  to run, so a sound run stays close to this trajectory;
* the plain protocol itself (coded data, shares, training with stochastic
  rounding), with its arithmetic in int64 or in a lower precision: put in
  the program's place it is the control of the comparison.
"""

from __future__ import annotations

import numpy as np

P = (1 << 26) - 5


# ---------------------------------------------------------------- field ops

def lagrange_weights(nodes, targets) -> np.ndarray:
    """(len(targets), len(nodes)) Lagrange basis values over F_p, exact."""
    nodes = [int(v) % P for v in nodes]
    out = np.zeros((len(targets), len(nodes)), np.int64)
    for ti, z in enumerate(int(v) % P for v in targets):
        for j, xj in enumerate(nodes):
            num = den = 1
            for l, xl in enumerate(nodes):
                if l != j:
                    num = num * (z - xl) % P
                    den = den * (xj - xl) % P
            out[ti, j] = num * pow(den, P - 2, P) % P
    return out


def fmatmul(a, b, dtype=np.int64) -> np.ndarray:
    """(a @ b) mod p.  int64: exact, contraction chunked so that sums of
    products below p^2 stay under 2^63.  Any other dtype computes the
    product in that type, as a lower-precision control does."""
    if dtype != np.int64:
        prod = np.matmul(np.asarray(a, dtype), np.asarray(b, dtype))
        return np.mod(prod, dtype(P)).astype(np.int64) % P
    a = np.asarray(a, np.int64) % P
    b = np.asarray(b, np.int64) % P
    out = np.zeros((a.shape[0], b.shape[1]), np.int64)
    for s in range(0, a.shape[1], 1024):
        out = (out + a[:, s:s + 1024] @ b[s:s + 1024]) % P
    return out


def signed(u) -> np.ndarray:
    u = np.asarray(u, np.int64) % P
    return np.where(u > P // 2, u - P, u)


# ------------------------------------------------------------ the reference

class Reference:
    """The configuration's fixed-point COPML on one data set."""

    def __init__(self, cfg: dict, x: np.ndarray, y: np.ndarray):
        self.cfg = cfg
        n, k, t, r = cfg["n_clients"], cfg["k"], cfg["t"], cfg["r"]
        if r != 1:
            raise ValueError("the expected trajectory is exact for r = 1 only")
        self.n, self.k, self.t = n, k, t
        lx, lw, cb = cfg["lx"], cfg["lw"], cfg["cb"]
        self.lw = lw
        lz = lx + lw
        lg = lz + cb
        s_grad = lx + lg
        m, d = x.shape
        self.m, self.d = m, d
        self.mk = -(-m // k)
        # eta/m ~= q_eta / 2^e, then TruncPr by 2^k1 back to scale lw
        e = int(round(np.log2(m / cfg["eta"]))) + 1
        self.q_eta = max(1, int(round(cfg["eta"] / m * (1 << e))))
        self.k1 = s_grad + e - lw
        # ghat(z) = c0 + c1 z: least-squares line through the sigmoid on
        # [-B, B] (paper Eq. 5), coefficients at scales lg and lg - lz
        zz = np.linspace(-cfg["sigmoid_bound"], cfg["sigmoid_bound"], 2001)
        c, *_ = np.linalg.lstsq(np.vander(zz, 2, increasing=True),
                                1.0 / (1.0 + np.exp(-zz)), rcond=None)
        self.c0 = int(round(c[0] * (1 << lg)))
        self.c1 = int(round(c[1] * (1 << (lg - lz))))
        # Phase 1: quantize (round half to even, as float32 products)
        self.xq = np.round(np.asarray(x, np.float32) * np.float32(1 << lx)
                           ).astype(np.int64)                    # (m, d)
        self.yq = np.round(np.asarray(y, np.float32) * np.float32(1 << lg)
                           ).astype(np.int64)                    # (m,)
        pts = cfg["public_points"]
        self.betas = tuple(range(pts["beta"], pts["beta"] + k + t))
        self.alphas = tuple(range(pts["alpha"], pts["alpha"] + n))
        self.lambdas = tuple(range(pts["lambda"], pts["lambda"] + n))
        self._z_cache: dict = {}

    # ------------------------------------------------------ exact checks

    def open_shares(self, shares) -> np.ndarray:
        """Signed secret from the first T+1 holders' shares."""
        shares = np.asarray(shares, np.int64)
        w = lagrange_weights(self.lambdas[: self.t + 1], [0])
        flat = shares[: self.t + 1].reshape(self.t + 1, -1)
        return signed(fmatmul(w, flat)).reshape(shares.shape[1:])

    def share_mismatch(self, shares) -> int:
        """Shares off the degree-T polynomial through the first T+1."""
        shares = np.asarray(shares, np.int64)
        head = self.t + 1
        w = lagrange_weights(self.lambdas[:head], self.lambdas[head:])
        pred = fmatmul(w, shares[:head].reshape(head, -1))
        return int(np.count_nonzero(
            pred != shares[head:].reshape(len(self.lambdas) - head, -1) % P))

    def xtilde_mismatch(self, coded_x) -> int:
        """Elements where X~ fails to decode to the quantized data blocks,
        interpolating from the first and from the last K+T slices."""
        coded_x = np.asarray(coded_x, np.int64)
        kt = self.k + self.t
        blocks = np.zeros((self.k * self.mk, self.d), np.int64)
        blocks[: self.m] = self.xq
        want = blocks.reshape(self.k, -1) % P
        bad = 0
        for idx in (range(kt), range(self.n - kt, self.n)):
            idx = list(idx)
            w = lagrange_weights([self.alphas[i] for i in idx],
                                 self.betas[: self.k])
            got = fmatmul(w, coded_x[idx].reshape(kt, -1))
            bad += int(np.count_nonzero(got != want))
        return bad

    def xty(self) -> np.ndarray:
        """X^T y in the field at scale lx + lg."""
        return fmatmul(self.xq.T, self.yq[:, None])[:, 0]

    def xty_mismatch(self, xty_shares) -> int:
        return int(np.count_nonzero(
            self.open_shares(xty_shares) % P != self.xty()))

    # ------------------------------------------------ expected trajectory

    def expected_z(self, iters: int) -> np.ndarray:
        """E[X w_t] at t = iters, in units of 2^-(lx+lw): the unrounded
        fixed-point iteration, run in prediction space (G = X X^T)."""
        if iters not in self._z_cache:
            g = (self.xq @ self.xq.T).astype(np.float64)
            step = self.q_eta / float(1 << self.k1)
            done = max((i for i in self._z_cache if i <= iters), default=0)
            z = self._z_cache.get(done, np.zeros(self.m))
            for _ in range(iters - done):
                z = z - step * (g @ (self.c0 + self.c1 * z - self.yq))
            self._z_cache[iters] = z
        return self._z_cache[iters]

    def pred_gap(self, w_signed, iters: int) -> float:
        """||X w - E[X w_t]|| / ||E[X w_t]|| for an opened model w."""
        z_ref = self.expected_z(iters)
        z = self.xq @ np.asarray(w_signed, np.int64)
        return float(np.linalg.norm(z - z_ref) / np.linalg.norm(z_ref))

    # ------------------------------------------------- the plain protocol

    def protocol(self, rng, iters: int, dtype=np.int64,
                 fault: str | None = None) -> dict:
        """What the protocol hands back after `iters` iterations from
        setup -- coded data, X^T y shares, model shares -- computed plainly
        with field products in `dtype`.  `fault` plants one of:
          "unchanged": every iteration returns the model unchanged;
          "half":      each coded block drops half its rows, and the update
                       takes the mean over the rest (twice the step)."""
        n, k, t, d, mk = self.n, self.k, self.t, self.d, self.mk
        blocks = np.zeros((k * mk, d), np.int64)
        blocks[: self.m] = self.xq
        z_masks = rng.integers(0, P, size=(t, mk * d))
        enc = lagrange_weights(self.betas, self.alphas)            # (N, K+T)
        coded = fmatmul(enc, np.concatenate(
            [blocks.reshape(k, -1) % P, z_masks]), dtype).reshape(n, mk, d)
        powers = np.array([[pow(lam, j + 1, P) for j in range(t)]
                           for lam in self.lambdas], np.int64)

        def share(secret):
            coeffs = rng.integers(0, P, size=(t, secret.size))
            mix = fmatmul(powers, coeffs, dtype).reshape((n,) + secret.shape)
            return (mix + secret[None] % P) % P

        xty = fmatmul(self.xq.T, self.yq[:, None], dtype)[:, 0]
        rows, q_eta = np.arange(self.m), self.q_eta
        if fault == "half":
            rows = np.concatenate([np.arange(b * mk, b * mk + mk // 2)
                                   for b in range(k)])
            rows, q_eta = rows[rows < self.m], 2 * q_eta
        xs, ys = self.xq[rows], self.yq[rows]
        xty_rows = fmatmul(xs.T, ys[:, None], dtype)[:, 0]
        w = np.zeros(d, np.int64)
        for _ in range(0 if fault == "unchanged" else iters):
            z = fmatmul(xs, w[:, None], dtype)[:, 0]
            g = (self.c0 + fmatmul(z[:, None], np.array([[self.c1]]),
                                   dtype)[:, 0]) % P
            grad = (fmatmul(xs.T, g[:, None], dtype)[:, 0] - xty_rows) % P
            a = signed(fmatmul(grad[:, None], np.array([[q_eta]]),
                               dtype)[:, 0])
            r0 = rng.integers(0, 1 << self.k1, size=d)
            w = (w - np.floor_divide(a + r0, 1 << self.k1)) % P
        return {"coded_x": coded, "xty_shares": share(xty),
                "w_shares": share(w)}
