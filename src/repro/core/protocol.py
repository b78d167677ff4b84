"""COPML: the full training protocol (paper Algorithm 1) over N virtual clients.

One process simulates all N clients; every share array carries the client
axis first.  Each step below is annotated with its MPC character
(LOCAL = no communication; EXCHANGE = point-to-point shares; OPEN = broadcast
+ reconstruct), which cost_model.py prices for the Fig-3/Table-I benchmarks,
and which launch/copml_dist.py maps onto mesh collectives.

The model-specific slice (gradient polynomial, target embedding, model
shape, update constants) comes from a core/objectives.SecureObjective:
the phases are shape-polymorphic over the objective's trailing model dims
(a (d,) vector for binary logreg / linreg, a (d, C) matrix for C-class
one-vs-rest trained on ONE dataset encoding).

Fixed-point scale plumbing (the part the paper leaves implicit, Appendix A):

  X quantized at 2^lx, w at 2^lw  =>  z = Xw at lz = lx+lw.
  ghat coefficients quantized so ghat(z) comes out at lg = lz + cb
  (cb = coefficient precision bits).
  coded gradient  f = X~^T ghat(X~ w~)  at s_grad = lx + lg.
  update: multiply by public  q_eta ~= (eta/m) * 2^e, then TruncPr by
  2^{k1}, k1 = s_grad + e - lw, returning to scale lw.

All intermediate *true* values must stay within (-2^{mag_bits} - 1, ...)
* 2^{scale} < p/2; auto_scales() solves the bit budget and asserts it.
"""

from __future__ import annotations

import dataclasses
import math
import os
import warnings
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import (field, lagrange, meshutil, mpc, objectives, party, quantize,
               shamir, spans, truncation)
from .labels import Coded, Opened, Share


@dataclasses.dataclass(frozen=True)
class CopmlConfig:
    n_clients: int
    k: int                   # parallelization (dataset split)
    t: int                   # privacy threshold
    r: int = 1               # sigmoid polynomial degree
    eta: float = 1.0
    # fixed-point scales (None => auto from m at setup time)
    lx: int = 2
    lw: int = 3
    cb: int = 6
    k1: int | None = None
    k2: int = 24
    mag_bits: int = 10       # headroom for |X^T(ghat-y)| true magnitude
    sigmoid_bound: float = 10.0
    mpc_mul: str = "bh08"    # "bh08" | "bgw"

    @property
    def lz(self) -> int:
        return self.lx + self.lw

    @property
    def lg(self) -> int:
        return self.lz + self.cb

    @property
    def s_grad(self) -> int:
        return self.lx + self.lg

    @property
    def recovery_threshold(self) -> int:
        return lagrange.recovery_threshold(self.r, self.k, self.t)

    def validate(self):
        assert self.n_clients >= self.recovery_threshold, (
            f"N={self.n_clients} < recovery threshold "
            f"{self.recovery_threshold} = (2r+1)(K+T-1)+1")
        assert self.n_clients >= 2 * self.t + 1, "MPC mult needs N >= 2T+1"
        assert self.mag_bits + self.s_grad + 2 <= field.P_BITS, (
            "fixed-point budget exceeds field size")


def fused_mode() -> str:
    """The per-iteration schedule REPRO_FUSED_STEP selects:
      "0"      -- phase-siloed reference path
      "1"      -- fused one-dispatch step (ops.fused_step; Pallas if
                  REPRO_USE_PALLAS, else the fused jnp composition)
      "kernel" -- force the Pallas megakernel regardless of USE_PALLAS
    """
    return os.environ.get("REPRO_FUSED_STEP", "1")


# Corruption offset added to an adversarial client's coded gradient.  It
# must be LARGE: the decode-weighted offset passes through TruncPr's 2^{k1}
# rescale, so a small perturbation (say +1, weighted shift ~q_eta) truncates
# away invisibly and corruption would be untestable; 2^20 leaves a clearly
# visible model change whenever a corrupted contribution enters a decode.
ADV_OFFSET = 1 << 20


def case1_params(n: int, r: int = 1) -> tuple:
    """Paper Case 1 (max parallelization): K = floor((N-1)/(2r+1)), T = 1."""
    return max(1, (n - 1) // (2 * r + 1)), 1


def case2_params(n: int, r: int = 1) -> tuple:
    """Paper Case 2 (equal split between parallelization and privacy).

    Stated in the paper for r=1 as T = floor((N-3)/6),
    K = floor((N+2)/3) - T.  The general-r form keeps the same structure:
    K+T-1 = floor((N-1)/(2r+1)) (the largest budget the recovery threshold
    (2r+1)(K+T-1)+1 <= N allows, since floor((N+2r)/(2r+1)) equals
    floor((N-1)/(2r+1)) + 1) with T taking roughly half of it; at r=1 it
    reduces exactly to the published formula.  Raises ValueError when no
    valid equal split exists (N too small for this r).
    """
    if r < 1:
        raise ValueError(f"polynomial degree r must be >= 1, got {r}")
    deg = 2 * r + 1
    t = max(1, (n - 3) // (2 * deg))
    k = max(1, (n + 2 * r) // deg - t)
    if deg * (k + t - 1) + 1 > n:
        raise ValueError(
            f"case 2 has no valid (K, T) for N={n}, r={r}: the recovery "
            f"threshold {deg * (k + t - 1) + 1} = (2r+1)(K+T-1)+1 exceeds N")
    return k, t


# Setup streams its LCC encode over chunks of the row index within a block.
# One chunk's encode tensor, (N holders, N owners, rows, d) int32, may take
# at most this many bytes.  On a TPU its field products keep their limb
# products in VMEM (kernels/short_modmatmul), so a chunk holds little more
# than this tensor and its inputs; on other backends the limb products take
# 16 times as much in f32 (4 GiB).  With setup's whole-size random draws
# and X~ this leaves the training loop room on a 16 GB TPU v5e.
SETUP_ENCODE_CHUNK_BYTES = 1 << 28


def setup_chunks(n: int, mk: int, d: int) -> int:
    """How many chunks setup encodes the mk rows of each block in: the
    fewest whose encode tensors fit SETUP_ENCODE_CHUNK_BYTES."""
    row_bytes = n * n * d * np.dtype(np.int32).itemsize
    return -(-mk // max(1, SETUP_ENCODE_CHUNK_BYTES // row_bytes))


def derive_update_constants(cfg: CopmlConfig, m: int) -> tuple:
    """(q_eta, e, k1, k2): eta/m ~= q_eta / 2^e, q_eta a small public int.

    k2 auto-widens (up to log2 p - 1) when the derived k1 would collide with
    the configured k2 -- large m pushes the truncation deeper."""
    e = int(round(math.log2(m / cfg.eta))) + 1
    q_eta = max(1, int(round(cfg.eta / m * (1 << e))))
    k1 = cfg.k1 if cfg.k1 is not None else cfg.s_grad + e - cfg.lw
    k2 = max(cfg.k2, min(field.P_BITS - 1, k1 + 1))
    assert 0 < k1 < k2 <= field.P_BITS - 1, (k1, k2)
    return q_eta, e, k1, k2


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CopmlState:
    """Everything clients hold after the one-time setup.

    `w_shape` is the objective's model shape: (d,) for the vector
    objectives (binary logreg, linreg -- unchanged from the pre-objective
    protocol), (d, C) for the class-batched matrix model."""
    w_shares: Share              # (N,) + w_shape   Shamir shares of w^(t)
    coded_x: Coded               # (N, mk, d)       clear coded slices X~_i
    xty_shares: Share            # (N,) + w_shape   shares of X^T y (lx+lg)
    step: jnp.ndarray | int = 0


class Copml:
    """Functional COPML protocol driver (jit-friendly).

    `objective` (core/objectives.SecureObjective, default binary logistic)
    supplies everything model-specific: the quantized ghat coefficients,
    the target embedding, the model shape, and the update constants.  The
    phases below are shape-polymorphic over the objective's trailing model
    dims -- the binary path draws/reshapes exactly the pre-objective
    shapes, so it stays bit-exact to the seed goldens."""

    def __init__(self, cfg: CopmlConfig, m: int, d: int, objective=None):
        cfg.validate()
        self.cfg = cfg
        self.m, self.d = m, d
        self.obj = objectives.BINARY_LOGISTIC if objective is None \
            else objective
        self.obj.validate_cfg(cfg)
        self.out_shape = self.obj.out_shape      # () vector, (C,) matrix
        self.w_shape = (d,) + self.out_shape
        self.dw = d * self.obj.n_outputs         # flattened model width
        n, k, t = cfg.n_clients, cfg.k, cfg.t
        self.alphas, self.betas = lagrange.default_points(n, k, t)
        self.lambdas = tuple(range(k + t + 1 + n, k + t + 1 + 2 * n))
        self.q_eta, self.e, self.k1, self.k2 = self.obj.update_constants(
            cfg, m)
        # field coefficients of ghat at output scale lg given input scale lz
        self.poly_coeffs = self.obj.field_coeffs(cfg)
        self._reduce = mpc.reduce_bh08 if cfg.mpc_mul == "bh08" \
            else mpc.reduce_bgw
        # the megakernel gate, snapshotted per instance (api.fit caches
        # one Copml per (workload, gate), so flipping it between fits
        # builds a new driver instead of reusing the old schedule)
        self.fused_mode = fused_mode()

    # ------------------------------------------------------------------ setup

    def setup(self, key, client_xs: Sequence, client_ys: Sequence) -> CopmlState:
        """Phases 1-2 (one-time): quantize, secret-share, LCC-encode, X^T y.

        client_xs[j]: (m_j, d) float arrays; client_ys[j]: (m_j,) in {0,1}.

        The host stacks the clients' rows and embeds the targets (numpy),
        moves both to the device once, and the field work runs as ONE
        compiled program (`_setup_program`), shared by every instance of
        the same workload.  The returned state is not waited for, so the
        caller can dispatch the training loop at once.
        """
        self.pad = 0
        n, mk = self.cfg.n_clients, -(-self.m // self.cfg.k)
        with spans.span("setup", m=self.m, d=self.d, n=n,
                        chunks=setup_chunks(n, mk, self.d)):
            x = np.concatenate([np.asarray(x) for x in client_xs], axis=0)
            # the objective owns the target embedding (binary {0,1} passes
            # through; multiclass one-hots integer labels into (m, C))
            targets = self.obj.prepare_targets(
                np.concatenate([np.asarray(y) for y in client_ys], axis=0))
            return _setup_program(self.cfg, self.obj, self.m, self.d, key,
                                  jnp.asarray(x),
                                  jnp.asarray(targets, jnp.float32))

    def _setup_phases(self, key, x, targets, chunks=None) -> CopmlState:
        """The field work of `setup` on the stacked rows x (m, d) and
        targets (m,) + out_shape: the body of `_setup_program`.

        Every phase is one vectorized field op over all N clients -- no
        per-client Python loop.  Sharing the stacked rows at once is
        distribution-identical to per-client sharing (the masking
        polynomial draws independent randomness per element either way).

        LCC encoding maps row j of every block to row j of X~, so Phases
        2a-2d stream over `chunks` chunks of the row index within a block
        (`setup_chunks` when None): each chunk shares its rows of X, encodes
        and reconstructs them into X~, and adds its local X^T y products;
        the degree reduction runs once after the loop.  Every random tensor
        is drawn whole and sliced per chunk, and the field arithmetic is
        exact, so any chunking gives the same bits.
        """
        cfg, n, t, k = self.cfg, self.cfg.n_clients, self.cfg.t, self.cfg.k
        keys = jax.random.split(key, 6)
        mk = -(-self.m // k)
        chunks = setup_chunks(n, mk, self.d) if chunks is None else chunks
        rows = -(-mk // chunks)

        def whole_chunks(a, axis):
            """`a` zero-padded on its row-within-block axis from mk rows to
            rows * chunks."""
            widths = [(0, 0)] * a.ndim
            widths[axis] = (0, rows * chunks - mk)
            return jnp.pad(a, widths)

        def blocks(a, axis):
            """The K row blocks of `a` (rows on `axis`) in whole chunks:
            (K, rows * chunks) on axis, axis + 1."""
            return whole_chunks(lagrange.partition_rows(a, k, axis)[0],
                                axis + 1)

        # Phase 1 (LOCAL): quantize into F_p -- one call over all rows
        xq = quantize.quantize(x, cfg.lx)                         # (m, d)
        yq = quantize.quantize(targets, cfg.lg)         # (m,) + out_shape

        # Phase 2a (EXCHANGE): Shamir-share every client's data; X's
        # masking coefficients are drawn here and applied per chunk
        with jax.named_scope("copml.setup.share"):
            x_blocks = blocks(xq, 0)                        # (K, mk', d)
            x_coeffs = blocks(field.random_field(
                keys[0], (t, self.m, self.d)), 1)        # (T, K, mk', d)
            y_shares = shamir.share(keys[1], yq, t, n, self.lambdas)
            y_mat = y_shares if self.out_shape else y_shares[..., None]
            y_blocks = blocks(y_mat, 1)                 # (N, K, mk', C')

        with jax.named_scope("copml.setup.encode"):
            # shared random masks Z_{K+1..K+T} (offline randomness, fn. 3)
            z = field.random_field(keys[2], (t, mk, self.d))
            z_coeffs = field.random_field(keys[3], (t,) + z.shape)
            z = whole_chunks(z, 1)                          # (T, mk', d)
            z_coeffs = whole_chunks(z_coeffs, 2)          # (T, T, mk', d)

        def chunk(j):
            """(X~ rows, X^T y local products) of rows j*rows.. of every
            block."""
            def rows_of(a, axis):
                return jax.lax.dynamic_slice_in_dim(a, j * rows, rows, axis)

            with jax.named_scope("copml.setup.share"):
                x_shares = shamir.share_with(
                    rows_of(x_coeffs, 2), rows_of(x_blocks, 1), n,
                    self.lambdas)                         # (N, K, rows, d)
            with jax.named_scope("copml.setup.encode"):
                z_shares = shamir.share_with(
                    rows_of(z_coeffs, 2), rows_of(z, 1), n,
                    self.lambdas)                         # (N, T, rows, d)
                # Phase 2c (LOCAL): LCC-encode the shares; (EXCHANGE):
                # reconstruct each client's coded slice from T+1 shares
                # (fn. 4: subgrouping)
                enc = jax.vmap(lambda b, zz: lagrange.lcc_encode(
                    b, zz, self.alphas, self.betas))(x_shares, z_shares)
                # enc: (N_holder, N_owner, rows, d); reconstruct over holders
                coded = shamir.reconstruct(enc, t, self.lambdas)
            # Phase 2d: the local products of X^T y (degree 2T); a matrix
            # objective contracts against all C target columns at once
            with jax.named_scope("copml.setup.xty"):
                xs = x_shares.reshape(n, k * rows, self.d)
                ys = rows_of(y_blocks, 2).reshape(n, k * rows, -1)
                prod = mpc.local_product(jnp.swapaxes(xs, 1, 2), ys,
                                         matmul=True)       # (N, d, C')
            return coded, prod

        if chunks == 1:
            coded_x, prod = chunk(0)
        else:
            def body(j, acc):
                coded_x, prod = acc
                coded, part = chunk(j)
                return (jax.lax.dynamic_update_slice_in_dim(
                    coded_x, coded, j * rows, 1), field.add(prod, part))
            coded_x, prod = jax.lax.fori_loop(0, chunks, body, (
                jnp.zeros((n, rows * chunks, self.d), field.FIELD_DTYPE),
                jnp.zeros((n, self.d, y_blocks.shape[-1]),
                          field.FIELD_DTYPE)))
            coded_x = coded_x[:, :mk]                       # (N, mk, d)

        # Phase 2d: X^T y via one secure matmul -- the degree reduction of
        # the summed local products
        with jax.named_scope("copml.setup.xty"):
            xty_shares = self._reduce(keys[4], prod, t, self.lambdas)
        if not self.out_shape:
            xty_shares = xty_shares[..., 0]                  # (N,) + w_shape

        # model init within MPC: w^(0) = 0 shared
        w_shares = shamir.share(
            keys[5], jnp.zeros(self.w_shape, field.FIELD_DTYPE),
            t, n, self.lambdas)
        return CopmlState(w_shares=w_shares, coded_x=coded_x,
                          xty_shares=xty_shares,
                          step=jnp.asarray(0, jnp.int32))

    # ------------------------------------------------------- one GD iteration

    def encode_model(self, key, w_shares: Share) -> Coded:
        """Phase 2 per-iteration: Lagrange-encode w from its shares.

        LOCAL on shares + EXCHANGE to reconstruct w~_j at client j: one
        party block of all N holders (core/party.py), whose reconstruct
        partial is the whole coded model.
        """
        with jax.named_scope("copml.encode_model"):
            n = self.cfg.n_clients
            pmat, wall = party.padded_constants(self, n)
            enc = party.encode_model(self, key, w_shares, jnp.asarray(pmat),
                                     n)                 # (N_holder,N_owner,dw)
            # keep enc holder-sharded: otherwise GSPMD all-gathers every
            # holder's (K+T, d) limb stack (~1 GiB/step at N=256, the
            # dominant collective of the baseline -- EXPERIMENTS.md Perf,
            # COPML iter 2); reconstruct from ALL N shares so the
            # contraction reduce-scatters.
            enc = meshutil.maybe_constrain(enc, meshutil.CLIENTS)
            out = party.model_partial(enc, jnp.asarray(wall))
            return meshutil.maybe_constrain(out, meshutil.CLIENTS)  # (N, d)

    def local_gradient(self, coded_x: Coded, coded_w: Coded) -> Coded:
        """Phase 3 (LOCAL, the hot loop): f(X~_i, w~_i) = X~_i^T ghat(X~_i w~_i).

        Pure field compute on *clear coded* data.  All N clients run in ONE
        batched call: a single (N, m/bm)-grid Pallas launch on TPU,
        limb-packed batched GEMMs on the jnp reference path -- not N
        per-client dispatches via vmap.  A matrix objective's (N, dw) flat
        coded model reshapes to (N, d, C) and the matvec pair becomes a
        class-batched GEMM pair (kernels/ops.coded_gradient_matrix): one
        encoding drives all C one-vs-rest columns.

        `coded_x` may carry fewer than N leading rows (the sharded engine
        passes each shard's local clients).
        """
        from ..kernels import ops as kernel_ops
        if not self.out_shape:
            return kernel_ops.coded_gradient_batched(
                coded_x, coded_w, self.poly_coeffs)              # (N, d)
        w_mat = coded_w.reshape(coded_w.shape[0], self.d,
                                self.obj.n_outputs)
        return kernel_ops.coded_gradient_matrix(
            coded_x, w_mat, self.poly_coeffs)                    # (N, d, C)

    def decode_and_update(self, key, state: CopmlState, f_values: Coded,
                          subset: Sequence[int] | None = None, *,
                          subset_idx=None, dvec=None) -> CopmlState:
        """Phase 4: share f, decode on shares, secure model update, as one
        party block of all N clients (core/party.py).

        The decode subset comes in one of two forms: a static `subset`
        tuple (host constant, the pre-fault-plan path), or traced
        `subset_idx` (R,) gather indices with the matching `dvec` (R,)
        decode row -- the per-step form the fault-injection engines thread
        through their scans (one compiled program decodes from a different
        client subset every iteration)."""
        cfg, n = self.cfg, self.cfg.n_clients
        kf, kt = jax.random.split(key)
        rthr = cfg.recovery_threshold
        if subset_idx is None:
            if subset is None:
                subset = tuple(range(rthr))
            subset = tuple(subset)[:rthr]
            subset_idx = jnp.asarray(subset)
            dvec = jnp.asarray(party.decode_row(self, subset))   # (R,)
        else:
            assert dvec is not None, "subset_idx needs its decode row dvec"
        pmat = jnp.asarray(party.padded_constants(self, n)[0])

        # EXCHANGE: each client shares its local result with every holder
        # (the deal is holder-major), then each holder decodes LOCALLY.
        # Decoding before the owner->holder exchange makes GSPMD all-reduce
        # a (K, N, d) tensor -- ~K x more wire bytes than the (N, d)
        # exchange the protocol actually needs (EXPERIMENTS.md section
        # Perf, COPML cell, iteration 1).
        per_holder = meshutil.maybe_constrain(
            party.deal(self, kf, f_values, pmat, 0)(),
            meshutil.CLIENTS)                      # (N_holder, N_owner, dw)
        new_w = party.update(
            self, kt, per_holder[:, subset_idx], dvec, state.w_shares,
            state.xty_shares, pmat,
            open_=lambda c_sh: shamir.reconstruct(c_sh, cfg.t, self.lambdas))
        return dataclasses.replace(state, w_shares=new_w, step=state.step + 1)

    def _fused_iteration(self, key, state: CopmlState, coded_w: Coded,
                         subset=None, *, subset_idx=None, dvec=None,
                         adv=None) -> CopmlState:
        """Phases 3+4 as ONE dispatch (kernels/ops.fused_step).

        Bit-exact with local_gradient + decode_and_update because every
        operand handed to the kernel consumes the SAME randomness stream:

        * `mix` is shamir.share(kf, ZEROS) -- identical masking coefficients
          to party.deal's (T, N) + w_shape draw under kf (the coefficient
          draw depends only on key and shape), so share(h, o) = mix(h, o) + f(o)
          and the holder-h decode splits into the value-independent
          base[h] = dfull @ mix[h] (computed here) plus the holder-
          independent dfull @ f_adj (computed in the kernel epilogue).
        * TruncPr's r/[r]/[r0] come from truncation.trunc_pr_randomness
          with the same kt split arity and draw shapes as trunc_pr_core.

        The decode subset enters as the zero-scattered (N,) row `dfull`
        (excluded clients get weight 0), which works for both the static
        tuple form and the fault engines' traced (subset_idx, dvec) form.
        """
        from ..kernels import ops as kernel_ops
        cfg, n = self.cfg, self.cfg.n_clients
        kf, kt = jax.random.split(key)
        rthr = cfg.recovery_threshold
        if subset_idx is None:
            if subset is None:
                subset = tuple(range(rthr))
            subset = tuple(subset)[:rthr]
            dfull_np = np.zeros(n, np.int32)
            dfull_np[list(subset)] = party.decode_row(self, subset)
            dfull = jnp.asarray(dfull_np)
        else:
            assert dvec is not None, "subset_idx needs its decode row dvec"
            dfull = jnp.zeros((n,), jnp.int32).at[subset_idx].set(dvec)

        c = self.obj.n_outputs
        with jax.named_scope("copml.step_rand"):
            mix = shamir.share(
                kf, jnp.zeros((n,) + self.w_shape, field.FIELD_DTYPE),
                cfg.t, n, self.lambdas)                # (N_h, N_o) + w_shape
            base = jax.vmap(lambda mh: field.matmul(
                dfull[None], mh.reshape(n, self.dw))[0])(mix)   # (N_h, dw)

            r_sh, r0_sh = truncation.trunc_pr_randomness(
                kt, self.w_shape, self.k1, self.k2,
                lambda k, s: shamir.share(k, s, cfg.t, n, self.lambdas))
            bias = 1 << (self.k2 - 1)
            radd = field.add(r_sh, jnp.full_like(r_sh, bias))

            # reconstruct's default open subset: first T+1 holders,
            # zero-padded
            rvec_np = np.zeros(n, np.int32)
            rvec_np[: cfg.t + 1] = shamir.recon_weights(
                self.lambdas, tuple(range(cfg.t + 1))).astype(np.int32)
            rvec = jnp.asarray(rvec_np)

        adv_off = jnp.zeros((n,), jnp.int32) if adv is None else \
            jnp.where(adv, jnp.asarray(ADV_OFFSET, jnp.int32), 0)

        mat = (n, self.d, c)
        with jax.named_scope("copml.fused_step"):
            _, new_w = kernel_ops.fused_step(
                state.coded_x,
                coded_w.reshape(mat),
                self.poly_coeffs, adv_off, dfull, rvec,
                base.reshape(mat),
                state.xty_shares.reshape(mat),
                state.w_shares.reshape(mat),
                radd.reshape(mat),
                r0_sh.reshape(mat),
                q_eta=self.q_eta, inv2k1=field.host_inv(1 << self.k1),
                k1=self.k1, force_pallas=self.fused_mode == "kernel")
        new_w = new_w.reshape((n,) + self.w_shape)
        return dataclasses.replace(state, w_shares=new_w,
                                   step=state.step + 1)

    def iteration(self, key, state: CopmlState,
                  subset: Sequence[int] | None = None, *,
                  subset_idx=None, dvec=None, adv=None) -> CopmlState:
        k1_, k2_ = jax.random.split(key)
        coded_w = self.encode_model(k1_, state.w_shares)
        if self.fused_mode != "0":
            return self._fused_iteration(k2_, state, coded_w, subset,
                                         subset_idx=subset_idx, dvec=dvec,
                                         adv=adv)
        f_values = self.local_gradient(state.coded_x, coded_w)
        if adv is not None:
            # adversarial clients contribute a CORRUPTED coded gradient --
            # any decode including one is visibly wrong (ADV_OFFSET); the
            # fault plan keeps them out of subset_idx, and the
            # bit-exactness tests prove the exclusion is real
            adv_b = adv.reshape((adv.shape[0],) + (1,) * len(self.w_shape))
            f_values = jnp.where(adv_b,
                                 field.add(f_values, jnp.asarray(
                                     ADV_OFFSET, f_values.dtype)), f_values)
        return self.decode_and_update(k2_, state, f_values, subset,
                                      subset_idx=subset_idx, dvec=dvec)

    def _jitted_step(self, subset):
        """Per-instance cache: a fresh jax.jit(partial(...)) every call
        would retrace/recompile the step on each train_eager invocation."""
        cache = self.__dict__.setdefault("_step_cache", {})
        if subset not in cache:
            cache[subset] = jax.jit(partial(self.iteration, subset=subset))
        return cache[subset]

    def _jitted_fault_step(self, with_adv: bool):
        """One jitted step with the decode subset as TRACED arrays: the
        eager fault engine swaps the subset every iteration without a
        recompile per distinct subset (a long churn schedule would
        otherwise mean a compile per step)."""
        cache = self.__dict__.setdefault("_fault_step_cache", {})
        if with_adv not in cache:
            if with_adv:
                fn = lambda key, st, idx, dv, adv: self.iteration(  # noqa: E731
                    key, st, subset_idx=idx, dvec=dv, adv=adv)
            else:
                fn = lambda key, st, idx, dv: self.iteration(  # noqa: E731
                    key, st, subset_idx=idx, dvec=dv)
            cache[with_adv] = jax.jit(fn)
        return cache[with_adv]

    # ------------------------------------------------------ fault schedules

    def plan_constants(self, step_subsets) -> tuple:
        """Host-side compilation of a fault plan's per-step decode subsets
        into the (iters, R) gather-index and decode-row arrays the engines
        consume (exact-integer Lagrange rows, one per distinct subset)."""
        return shamir.step_subset_arrays(
            step_subsets, self.cfg.recovery_threshold,
            partial(party.decode_row, self))

    def _fault_xs(self, step_subsets, adversaries, iters: int, subset=None):
        """(idx, dvec, adv-or-None) scan inputs for a faulty run, or None."""
        if step_subsets is None:
            assert adversaries is None, "adversaries need step_subsets"
            return None
        if subset is not None:
            raise ValueError("subset and step_subsets are mutually "
                             "exclusive: the plan chooses each step's "
                             "decode subset")
        assert len(step_subsets) == iters, (len(step_subsets), iters)
        idx, dvs = self.plan_constants(step_subsets)
        adv = None
        if adversaries is not None and np.asarray(adversaries).any():
            adv = np.asarray(adversaries, bool)
            assert adv.shape == (iters, self.cfg.n_clients), adv.shape
            adv = jnp.asarray(adv)
        return idx, dvs, adv

    # ------------------------------------------------------------------ train

    def _train_jit(self, key, client_xs, client_ys, iters: int,
                   subset: Sequence[int] | None = None,
                   history: bool = False, step_subsets=None,
                   adversaries=None) -> tuple:
        """Run setup + `iters` GD iterations as ONE compiled lax.scan.

        The whole training loop is a single XLA program (one compile, one
        dispatch) instead of `iters` Python round-trips -- same per-step
        randomness (fold_in of the iteration key) and therefore bit-exact
        against the eager loop (`train_eager`).  With history=True the scan
        also stacks the opened model after every step (used by the callback
        wrapper in `train` and by convergence diagnostics); opening inside
        the scan is trace-time work, not an extra communication round.

        step_subsets/adversaries (a fault plan's per-step decode subsets and
        (iters, N) corruption mask) ride through the scan as stacked array
        inputs, so even a fully churned run stays ONE compiled dispatch.

        Returns (state, w) or (state, w, history (iters, d)).
        """
        ks, ki = jax.random.split(key)
        state = self.setup(ks, client_xs, client_ys)
        subset = None if subset is None else tuple(subset)
        faults = self._fault_xs(step_subsets, adversaries, int(iters),
                                subset)
        with spans.span("loop", iters=int(iters)):
            state, hist = _scan_iterations(self, ki, state, int(iters),
                                           subset, bool(history), faults)
        w = self.open_model(state)
        return (state, w, hist) if history else (state, w)

    def _train_eager(self, key, client_xs, client_ys, iters: int,
                     subset: Sequence[int] | None = None,
                     callback=None, step_subsets=None,
                     adversaries=None) -> tuple:
        """Reference trainer: Python loop, one jitted iteration per step.

        Kept as the ground truth the scan engine is verified against
        (tests/test_protocol.py) and for step-through debugging.  A fault
        plan's per-step subsets are swapped in every iteration (dynamic
        gather indices -- one compile covers the whole schedule).
        """
        ks, ki = jax.random.split(key)
        state = self.setup(ks, client_xs, client_ys)
        faults = self._fault_xs(step_subsets, adversaries, iters, subset)
        if faults is None:
            step = self._jitted_step(
                None if subset is None else tuple(subset))
            args = lambda t: ()                                  # noqa: E731
        else:
            idx, dvs, adv = faults
            step = self._jitted_fault_step(adv is not None)
            args = lambda t: ((idx[t], dvs[t], adv[t])           # noqa: E731
                              if adv is not None else (idx[t], dvs[t]))
        for t in range(iters):
            state = step(jax.random.fold_in(ki, t), state, *args(t))
            if callback is not None:
                callback(t, self.open_model(state))
        return state, self.open_model(state)

    def train(self, key, client_xs, client_ys, iters: int,
              subset: Sequence[int] | None = None,
              callback=None) -> tuple:
        """Public API: scan-compiled training; callback replayed post-hoc.

        The per-step model history comes out of the single compiled scan, so
        callbacks no longer force a host round-trip every iteration.
        """
        if callback is None:
            return self._train_jit(key, client_xs, client_ys, iters,
                                   subset=subset)
        state, w, hist = self._train_jit(key, client_xs, client_ys, iters,
                                         subset=subset, history=True)
        for t in range(iters):
            callback(t, hist[t])
        return state, w

    # -------------------------------------------- deprecated engine methods
    #
    # The train_* method zoo is superseded by the repro.api facade:
    # api.fit(workload, "copml", engine) with engine in
    # {"eager", "jit", "sharded"}.  The shims below delegate through the
    # api engine dispatcher (run_copml_engine) -- the exact code path the
    # facade runs -- so shim-vs-facade parity is structural and
    # regression-tested (tests/test_api.py).

    def _deprecated(self, engine_label: str):
        warnings.warn(
            f"Copml.train_{engine_label} is deprecated; use "
            f"repro.api.fit(workload, 'copml', engine='{engine_label}') "
            f"(see docs/API.md)", DeprecationWarning, stacklevel=3)
        from ..api.protocols import run_copml_engine
        return run_copml_engine

    def train_jit(self, key, client_xs, client_ys, iters: int,
                  subset: Sequence[int] | None = None,
                  history: bool = False) -> tuple:
        """Deprecated shim for the scan engine (api engine='jit')."""
        run = self._deprecated("jit")
        state, w, hist = run(self, "jit", key, client_xs, client_ys,
                             int(iters), subset=subset, history=history)
        return (state, w, hist) if history else (state, w)

    def train_eager(self, key, client_xs, client_ys, iters: int,
                    subset: Sequence[int] | None = None,
                    callback=None) -> tuple:
        """Deprecated shim for the eager engine (api engine='eager')."""
        run = self._deprecated("eager")
        state, w, _ = run(self, "eager", key, client_xs, client_ys,
                          int(iters), subset=subset, callback=callback)
        return state, w

    def train_sharded(self, key, client_xs, client_ys, iters: int,
                      mesh=None, subset: Sequence[int] | None = None,
                      history: bool = False) -> tuple:
        """Deprecated shim for the mesh engine (api engine='sharded')."""
        from ..api.engine import EngineSpec
        run = self._deprecated("sharded")
        spec = EngineSpec("sharded", mesh=mesh)
        state, w, hist = run(self, spec, key, client_xs, client_ys,
                             int(iters), subset=subset, history=history)
        return (state, w, hist) if history else (state, w)

    def open_model(self, state: CopmlState) -> Opened:
        """Reconstruct and dequantize the model (only done at the end /
        for evaluation; during training clients hold only shares)."""
        w_field = mpc.open_shares(state.w_shares, self.cfg.t, self.lambdas)
        return quantize.dequantize(w_field, self.cfg.lw)

    # ----------------------------------------------------- distributed engine

    def _train_sharded(self, key, client_xs, client_ys, iters: int,
                       mesh=None, subset: Sequence[int] | None = None,
                       history: bool = False, step_subsets=None,
                       adversaries=None) -> tuple:
        """_train_jit with the client axis PHYSICALLY sharded over a mesh.

        Every share/coded array is split over a 1-D ("clients",) mesh
        (meshutil.client_mesh) with shard_map, so each device holds only its
        clients' state, and each protocol step lowers to the collective its
        MPC character implies:

          LOCAL     Phase-3 coded gradients, share-level add/mul-by-public
                    -> per-shard compute, zero communication
          EXCHANGE  share_batch's owner->holder share distribution
                    -> all_to_all; model-encoding reconstruct
                    -> mod-p reduce-scatter (psum_scatter_mod)
          OPEN      TruncPr's masked opening, per-step model opening
                    -> all_gather + replicated decode

        Bit-exact against train_jit: the per-step key schedule is identical,
        every random draw is replicated (same key, same shape on all shards
        -- equivalent to the paper's offline dealer, fn. 3), and the only
        cross-shard contractions are mod-p linear reductions whose shard
        partials recombine to the same canonical representative (see
        meshutil.psum_scatter_mod).  N need not divide the mesh: the client
        axis is
        zero-padded to a multiple of the shard count and padded clients are
        excluded from every reconstruction (zero Lagrange weight).

        Returns (state, w) or (state, w, history) exactly like train_jit,
        with state.w_shares materialized back to the un-padded (N, d) view.
        """
        mesh = meshutil.client_mesh() if mesh is None else mesh
        assert tuple(mesh.axis_names) == (meshutil.CLIENT_AXIS,), (
            f"train_sharded needs a 1-D ('{meshutil.CLIENT_AXIS}',) mesh, "
            f"got {mesh.axis_names}")
        n = self.cfg.n_clients
        ks, ki = jax.random.split(key)
        state = self.setup(ks, client_xs, client_ys)    # one-time, replicated
        subset = None if subset is None else tuple(subset)
        faults = self._fault_xs(step_subsets, adversaries, int(iters),
                                subset)
        fault_kind = None if faults is None else (
            "plan_adv" if faults[2] is not None else "plan")
        fn, n_pad = self._sharded_scan(mesh, int(iters), subset,
                                       bool(history), fault_kind)
        fault_args = ()
        if faults is not None:
            idx, dvs, adv = faults
            fault_args = (idx, dvs)
            if adv is not None:
                # replicated (iters, n_pad) mask; padded clients honest
                adv_pad = np.zeros((int(iters), n_pad), bool)
                adv_pad[:, :n] = np.asarray(adv)
                fault_args += (jnp.asarray(adv_pad),)
        out = fn(_pad_clients(state.w_shares, n_pad),
                 _pad_clients(state.coded_x, n_pad),
                 _pad_clients(state.xty_shares, n_pad), ki, *fault_args)
        w_pad, hist = out if history else (out, None)
        state = dataclasses.replace(
            state, w_shares=w_pad[:n],
            step=state.step + jnp.asarray(iters, jnp.int32))
        w = self.open_model(state)
        return (state, w, hist) if history else (state, w)

    def sharded_step(self, mesh, subset: Sequence[int] | None = None):
        """One sharded GD iteration as a jit-able fn(w, coded_x, xty, key)
        over PADDED (n_pad, ...) client-sharded arrays; returns (fn, n_pad).
        Used by launch/copml_dist.dryrun_cell to compile the real collective
        program and by the distributed benchmark stage."""
        subset = None if subset is None else tuple(subset)
        return self._sharded_scan(mesh, 1, subset, False)

    def _sharded_scan(self, mesh, iters: int, subset, history: bool,
                      fault_kind: str | None = None):
        """Build (and cache per instance) the jitted shard_map scan.

        fault_kind: None (static subset), "plan" (per-step (iters, R)
        decode idx/row arrays scanned over, replicated), or "plan_adv"
        (additionally an (iters, n_pad) corruption mask)."""
        cache = self.__dict__.setdefault("_sharded_cache", {})
        # compute/collective overlap: produce the EXCHANGE collectives'
        # operands per destination shard and stream them around a ppermute
        # ring (meshutil.ring_*) instead of blocking on the monolithic GEMM
        # before the first byte moves.  Bit-exact either way (see the ring
        # helpers); default on, REPRO_SHARDED_OVERLAP=0 restores the
        # monolithic collectives.  Part of the cache key: the two settings
        # compile different programs.
        overlap = os.environ.get("REPRO_SHARDED_OVERLAP", "1") != "0"
        ckey = (mesh, iters, subset, history, fault_kind, overlap)
        if ckey in cache:
            return cache[ckey]

        cfg, n = self.cfg, self.cfg.n_clients
        dw, w_shape = self.dw, self.w_shape
        assert cfg.t >= 1, "sharded engine assumes T >= 1 (as all paper cases)"
        ndev = mesh.shape[meshutil.CLIENT_AXIS]
        n_loc = -(-n // ndev)
        n_pad = n_loc * ndev
        t_ = cfg.t
        axis = meshutil.CLIENT_AXIS

        pmat, wall = party.padded_constants(self, n_pad)
        sub = tuple(range(cfg.recovery_threshold)) if subset is None \
            else tuple(subset)[: cfg.recovery_threshold]
        dvec = jnp.asarray(party.decode_row(self, sub))          # (R,)
        sub_arr = jnp.asarray(sub)

        def open_(c_sh):
            """TruncPr's masked value, OPENed via all_gather."""
            c_full = meshutil.all_gather_clients(c_sh, axis)[:n]
            return shamir.reconstruct(c_full, t_, self.lambdas)

        def encode_model(k1_, w_loc, pmat_loc, wall_loc):
            """Phase-2 per-iteration model encoding, holder-sharded.  The
            reconstruct from ALL holders is this shard's weighted partial
            and a mod-p reduce-scatter that hands each shard its own
            clients' coded model rows."""
            enc = party.encode_model(self, k1_, w_loc, pmat_loc, n_pad)
            if overlap and ndev <= meshutil.NARROW_SHARDS:
                # dest shard j's rows of the partial, computed just before
                # hop j so the GEMM rides the transfer
                return meshutil.ring_reduce_scatter_mod(
                    lambda j: party.model_partial(enc, wall_loc, j),
                    axis, ndev)
            return meshutil.psum_scatter_mod(
                party.model_partial(enc, wall_loc), axis, ndev)  # (n_loc,dw)

        def decode_update(k2_, w_loc, xty_loc, f_loc, pmat_loc, pmat_all,
                          shard_ix, sub_t, dv_t):
            """Phase 4, owner->holder exchange as a real all_to_all.

            sub_t / dv_t: this step's decode gather indices and decode row
            (the closure constants on the static path, per-step scan slices
            on the fault-plan path)."""
            kf, kt = jax.random.split(k2_)
            block = party.deal(self, kf, f_loc, pmat_all, shard_ix * n_loc)
            if overlap:
                # holder rows owned by shard j, built just before the hop
                # that carries them
                blocks = meshutil.ring_all_to_all(block, axis, ndev)
                # (src, n_loc_holder, n_loc_own, dw) -> owner-major concat
                per_holder = jnp.moveaxis(blocks, 0, 1).reshape(
                    n_loc, n_pad, dw)
            else:
                per_holder = meshutil.all_to_all_clients(block(), axis)
            # (n_loc_holder, N_owner, dw): decode LOCALLY per holder
            return party.update(self, kt, per_holder[:, sub_t, :], dv_t,
                                w_loc, xty_loc, pmat_loc, open_)

        def open_w(w_loc):
            w_full = meshutil.all_gather_clients(w_loc, axis)[:n]
            wf = shamir.reconstruct(w_full, t_, self.lambdas)
            return quantize.dequantize(wf, cfg.lw)

        def loop(w, coded_x, xty, pmat_loc, wall_loc, key, *fxs):
            shard_ix = jax.lax.axis_index(axis)
            pmat_all = jnp.asarray(pmat)          # replicated full power mat

            def body(w_c, xs):
                tstep, fx = xs[0], xs[1:]
                kit = jax.random.fold_in(key, tstep)
                k1_, k2_ = jax.random.split(kit)
                coded_w = encode_model(k1_, w_c, pmat_loc, wall_loc)
                f_loc = self.local_gradient(coded_x, coded_w)    # LOCAL
                if fault_kind == "plan_adv":
                    sub_t, dv_t, adv_t = fx
                    adv_loc = jax.lax.dynamic_slice_in_dim(
                        adv_t, shard_ix * n_loc, n_loc)
                    adv_b = adv_loc.reshape((n_loc,) + (1,) * len(w_shape))
                    f_loc = jnp.where(adv_b,
                                      field.add(f_loc, jnp.asarray(
                                          ADV_OFFSET, f_loc.dtype)), f_loc)
                elif fault_kind == "plan":
                    sub_t, dv_t = fx
                else:
                    sub_t, dv_t = sub_arr, dvec
                w_n = decode_update(k2_, w_c, xty, f_loc, pmat_loc, pmat_all,
                                    shard_ix, sub_t, dv_t)
                return w_n, (open_w(w_n) if history else None)

            w_f, hist = jax.lax.scan(body, w, (jnp.arange(iters),) + fxs)
            return (w_f, hist) if history else w_f

        n_fx = {"plan": 2, "plan_adv": 3}.get(fault_kind, 0)
        cl = P(axis)
        out_specs = (cl, P()) if history else cl
        sm = jax.shard_map(loop, mesh=mesh,
                           in_specs=(cl, cl, cl, cl, P(None, axis), P())
                           + (P(),) * n_fx,
                           out_specs=out_specs, check_vma=False)
        jfn = jax.jit(sm)
        pmat_j, wall_j = jnp.asarray(pmat), jnp.asarray(wall)

        def call(w, coded_x, xty, key, *fault_args):
            return jfn(w, coded_x, xty, pmat_j, wall_j, key, *fault_args)

        cache[ckey] = (call, n_pad)
        return cache[ckey]


def _pad_clients(arr, n_pad: int):
    """Zero-pad the leading client axis to n_pad rows (mesh divisibility)."""
    n = arr.shape[0]
    if n == n_pad:
        return arr
    pad = jnp.zeros((n_pad - n,) + arr.shape[1:], arr.dtype)
    return jnp.concatenate([arr, pad], axis=0)


@partial(jax.jit, static_argnums=(0, 1, 2, 3), static_argnames="_chunks")
def _setup_program(cfg: CopmlConfig, objective, m: int, d: int, key, x,
                   targets, _chunks=None) -> CopmlState:
    """Copml.setup's field work (Phases 1-2) as one XLA program.

    The static arguments are the values that define the program, not a
    Copml instance, so every instance of one workload shares the
    executable.  Its phases are named by the device scopes
    `copml.setup.share`, `copml.setup.encode` and `copml.setup.xty`.
    Under `jax.disable_jit()` the same body runs op by op.  `_chunks`
    overrides `setup_chunks` for tests of the streamed encode.
    """
    return Copml(cfg, m, d, objective)._setup_phases(key, x, targets,
                                                     _chunks)


@partial(jax.jit, static_argnames=("proto", "iters", "subset", "history"))
def _scan_iterations(proto: Copml, key, state: CopmlState, iters: int,
                     subset, history: bool, faults=None):
    """lax.scan over GD iterations; the whole loop is one XLA program.

    `proto` is static (hashed by identity): the scan recompiles per protocol
    instance but runs every iteration inside a single dispatch.  Per-step
    keys are fold_in(key, t) -- identical to the eager loop's schedule.

    `faults` is None or (idx (iters, R), dvec (iters, R), adv (iters, N)
    or None): a fault plan's per-step decode subsets (and corruption mask)
    scanned over alongside the step counter -- churn costs zero extra
    dispatches.
    """

    def body(st, xs):
        t, fx = xs
        if fx is None:
            st = proto.iteration(jax.random.fold_in(key, t), st, subset)
        else:
            idx_t, dv_t, adv_t = fx
            st = proto.iteration(jax.random.fold_in(key, t), st,
                                 subset_idx=idx_t, dvec=dv_t, adv=adv_t)
        return st, (proto.open_model(st) if history else None)

    return jax.lax.scan(body, state, (jnp.arange(iters), faults))
