"""Encode-once coded inference on a secret-shared model.

The serving-side counterpart of the protocol's encode-once/compute-many
training structure: the trained model is re-shared ONCE into per-client
Shamir shares packed for the limb-GEMM kernels, and every incoming query
batch is scored against those shares without ever opening the model.

Why this is secure *and* exact: Shamir sharing is mod-p linear, so each
client's LOCAL field matmul  xq @ w_share_i  is itself a share of the
score polynomial evaluated at that client's point, and reconstructing
the per-query logits from any T+1 of them yields exactly  xq @ wq mod p
-- bit-identical to the quantized reference scorer `reference_scores`
(tests/test_serve.py asserts equality, not closeness).  The model never
exists in the clear anywhere on the serving path; only per-query logits
pass through the sanctioned `open_logits` sink below (registered as an
`open` effect in analysis/registry.py, annotated `-> Opened`).

Encode path:

* a COPML TrainResult carries the protocol-native final state
  (CopmlState.w_shares, shares at the protocol's serving lambdas):
  `encode_model` degree-refreshes them with `shamir.reshare` at those
  SAME points -- the model secret is never reconstructed in between;
* results without share state (float baselines, secure_agg) fall back to
  quantize + fresh `shamir.share` of the opened weights -- still served
  from shares, but the encode step sees the clear model (flagged in the
  CodedModel as `from_shares=False`).

The packed `w_cols` layout (d, N*C') turns per-batch scoring for ALL N
clients and C' model columns into ONE field GEMM (kernels.ops.modmatmul)
-- that reshape is the "encode once" amortization the serving benchmark
measures.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from ..core import field, meshutil, quantize, shamir
from ..core.labels import Opened, Public, Share
from ..kernels import ops as kernel_ops


def serving_points(cfg) -> tuple:
    """The share evaluation points of a CopmlState's w_shares: the
    protocol's serving lambdas (core/protocol.Copml.__init__), disjoint
    from the K+T encoding betas and the N coding alphas."""
    n, k, t = cfg.n_clients, cfg.k, cfg.t
    return tuple(range(k + t + 1 + n, k + t + 1 + 2 * n))


@dataclasses.dataclass
class CodedModel:
    """The encode-once serving artifact: per-client model shares, packed.

    w_stack is the canonical (N, d, C') share stack (C' = 1 for vector
    models); w_cols is the SAME shares reshaped to (d, N*C') so one
    limb-GEMM scores a whole query batch for every client and class at
    once.  Both are secret -- only `open_logits` may leave the share
    domain."""
    w_stack: Share            # (N, d, C') per-client shares of wq
    w_cols: Share             # (d, N*C') the packed scoring layout
    n: int                    # clients (shareholders)
    t: int                    # privacy threshold: any T+1 shares open
    points: tuple             # share evaluation points (len N)
    d: int                    # feature dimension
    out_shape: tuple          # () vector model | (C,) matrix model
    lx: int                   # query quantization scale
    lw: int                   # model quantization scale
    from_shares: bool         # True: re-shared protocol state, model
    #                           never opened on the encode path
    encode_s: float           # wall seconds of the one-time encode

    @property
    def n_cols(self) -> int:
        """C': model columns served per query (1 for vector models)."""
        return self.out_shape[0] if self.out_shape else 1

    @property
    def lz(self) -> int:
        """Scale of the opened field logits: lx + lw."""
        return self.lx + self.lw


def encode_model(key, result, cfg, objective) -> CodedModel:
    """One-time model encode: TrainResult -> CodedModel.

    Prefers the protocol-native share state (reshare at the protocol's
    serving lambdas -- fresh randomness, same secret, model never
    opened); falls back to quantize+share of the opened weights."""
    n, t = cfg.n_clients, cfg.t
    d = int(jnp.asarray(result.weights).shape[0])
    out_shape = tuple(objective.out_shape)
    cols = out_shape[0] if out_shape else 1

    state = getattr(result, "state", None)
    w_shares = getattr(state, "w_shares", None)
    t0 = time.perf_counter()
    if w_shares is not None:
        points = serving_points(cfg)
        shares = shamir.reshare(key, w_shares, t, n, points)
        from_shares = True
    else:
        points = shamir.default_eval_points(n)
        wq = quantize.quantize(jnp.asarray(result.weights), cfg.lw)
        shares = shamir.share(key, wq, t, n, points)
        from_shares = False
    w_stack = shares.reshape(n, d, cols)
    w_cols = jnp.moveaxis(w_stack, 0, 1).reshape(d, n * cols)
    jax.block_until_ready(w_cols)
    encode_s = time.perf_counter() - t0
    return CodedModel(w_stack=w_stack, w_cols=w_cols, n=n, t=t,
                      points=points, d=d, out_shape=out_shape,
                      lx=cfg.lx, lw=cfg.lw, from_shares=from_shares,
                      encode_s=encode_s)


def quantize_queries(model: CodedModel, queries) -> Public:
    """Float query batch (B, d) -> field domain at the data scale lx."""
    x = jnp.asarray(queries, jnp.float32)
    assert x.ndim == 2 and x.shape[1] == model.d, (x.shape, model.d)
    return quantize.quantize(x, model.lx)


def score_shares(model: CodedModel, xq: Public) -> Share:
    """Per-client share of the query logits: ONE packed limb-GEMM.

    xq: (B, d) quantized queries.  Returns (N, B, C') -- client i's rows
    are Shamir shares (at points[i]) of the logit matrix xq @ wq, because
    sharing commutes with the mod-p linear map xq @ (.)."""
    bsz = xq.shape[0]
    z = kernel_ops.modmatmul(xq, model.w_cols)          # (B, N*C')
    return jnp.moveaxis(z.reshape(bsz, model.n, model.n_cols), 1, 0)


def open_logits(z_shares: Share, model: CodedModel) -> Opened:
    """THE serving declassify sink: reconstruct per-query logits only.

    Any T+1 client scores interpolate to the exact field logits
    xq @ wq mod p, shape (B, C').  Nothing model-shaped is ever opened
    here -- (B, C') is public output, the model stays (N, d, C') shares.
    Registered as an `open` effect in analysis/registry.py."""
    return shamir.reconstruct(z_shares, model.t, model.points)


def score_open(model: CodedModel, queries) -> tuple:
    """Quantize -> share-score -> open: (field logits, float logits).

    The eager reference path: field logits are (B, C') int32 at scale
    lx + lw (bit-exact vs `reference_scores`); float logits are their
    dequantization."""
    xq = quantize_queries(model, queries)
    zf = open_logits(score_shares(model, xq), model)
    return zf, quantize.dequantize(zf, model.lz)


def sharded_scorer(model: CodedModel, mesh):
    """A jitted scoring fn with the client axis SPLIT over a 1-D
    ("clients",) mesh: each shard scores its own clients' model shares
    locally (the per-client compute really is per-device), the opened
    logits are the only cross-shard product (all_gather + reconstruct,
    replicated).  Returns fn(queries float (B, d)) -> Opened field
    logits (B, C'), bit-identical to the single-device path."""
    from jax.sharding import PartitionSpec as P

    assert mesh.axis_names == (meshutil.CLIENT_AXIS,), mesh.axis_names
    ndev = mesh.devices.size
    n, d, cols = model.n, model.d, model.n_cols
    n_loc = -(-n // ndev)
    n_pad = n_loc * ndev
    w_stack = model.w_stack
    if n_pad > n:       # zero rows: excluded from reconstruction below
        w_stack = jnp.concatenate(
            [w_stack, jnp.zeros((n_pad - n, d, cols), jnp.int32)], axis=0)

    def score(w_loc: Share, xq: Public) -> Opened:
        n_here = w_loc.shape[0]
        w_c = jnp.moveaxis(w_loc, 0, 1).reshape(d, n_here * cols)
        z = kernel_ops.modmatmul(xq, w_c)               # (B, n_loc*C')
        z = jnp.moveaxis(z.reshape(-1, n_here, cols), 1, 0)
        z_all = meshutil.all_gather_clients(z)[:n]      # OPEN step
        return shamir.reconstruct(z_all, model.t, model.points)

    cl = P(meshutil.CLIENT_AXIS)
    sm = jax.shard_map(score, mesh=mesh, in_specs=(cl, P()), out_specs=P(),
                       check_vma=False)

    def fn(queries):
        xq = quantize_queries(model, queries)
        return sm(w_stack, xq)

    return jax.jit(fn)


def reference_scores(weights, queries, cfg) -> Public:
    """The quantized reference scorer the secure path must match BIT FOR
    BIT: quantize the OPENED model and the queries exactly as the secure
    path does, one clear field matmul.  (d,) models score as one column;
    returns (B, C') int32 field logits at scale lx + lw."""
    w = jnp.asarray(weights, jnp.float32)
    wq = quantize.quantize(w.reshape(w.shape[0], -1), cfg.lw)
    xq = quantize.quantize(jnp.asarray(queries, jnp.float32), cfg.lx)
    return field.matmul(xq, wq)
