"""The per-party step (core/party.py) against the single-device iteration.

The clients are split into P host-simulated row blocks, as the mesh and
proc engines split them over shards and ranks, and every exchange between
blocks is done here in plain numpy: the reconstruct partials are summed
mod p, the dealt blocks are concatenated owner-major, and TruncPr's masked
value is opened by Lagrange interpolation at zero.  The blocks' new model
shares must equal `Copml.iteration`'s (the fused one-device schedule) bit
for bit, which also pins that the fused step's `mix` draw is the deal's.
P = 2 and 3 leave padded clients.
"""

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import field, objectives, party, protocol, shamir
from repro.core.protocol import Copml, CopmlConfig
from repro.data import pipeline

ITERS = 3
KEY = jax.random.PRNGKey(11)


def _open_np(proto, c_blocks):
    """Lagrange interpolation at zero from the first T+1 holders, mod p."""
    t = proto.cfg.t
    c = np.concatenate([np.asarray(b, np.int64) for b in c_blocks])[: t + 1]
    wts = shamir.recon_weights(proto.lambdas, tuple(range(t + 1)))
    return jnp.asarray(np.tensordot(np.asarray(wts, np.int64), c, 1)
                       % field.P, jnp.int32)


def _pad(a, n_pad):
    a = np.asarray(a)
    return np.concatenate(
        [a, np.zeros((n_pad - a.shape[0],) + a.shape[1:], a.dtype)])


def _update(proto, key, evals, dvec, w_rows, xty_rows, pmat_rows, c):
    """party.update with TruncPr's open answered by `c`; also returns the
    block's shares of the masked value, which do not depend on `c`."""
    masked = []

    def open_(c_sh):
        masked.append(c_sh)
        return c

    new_w = party.update(proto, key, evals, dvec, w_rows, xty_rows,
                         pmat_rows, open_)
    return new_w, masked[0]


def _deal_block(proto, key, f_rows, pmat, lo, j):
    return party.deal(proto, key, f_rows, pmat, lo)(j)


_encode = jax.jit(party.encode_model, static_argnums=(0, 4))
_partial = jax.jit(party.model_partial)
_deal = jax.jit(_deal_block, static_argnums=0)
_update_jit = jax.jit(_update, static_argnums=0)


def _party_step(proto, key, w, coded_x, xty, nblocks, subset, dvec,
                adv=None):
    """One iteration over `nblocks` blocks of the padded client axis;
    returns the new (n_pad,) + w_shape model shares."""
    n = proto.cfg.n_clients
    n_loc = -(-n // nblocks)
    n_pad = n_loc * nblocks
    pmat, wall = (jnp.asarray(c) for c in party.padded_constants(proto, n_pad))
    rows = [slice(b * n_loc, (b + 1) * n_loc) for b in range(nblocks)]
    k1, k2 = jax.random.split(key)

    encs = [_encode(proto, k1, jnp.asarray(w[r]), pmat[r], n_pad)
            for r in rows]
    coded_w = np.concatenate([
        sum(np.asarray(_partial(enc, wall[:, r], j), np.int64)
            for enc, r in zip(encs, rows)) % field.P
        for j in range(nblocks)]).astype(np.int32)
    f = np.asarray(jax.jit(proto.local_gradient)(
        jnp.asarray(coded_x), jnp.asarray(coded_w)), np.int64)
    if adv is not None:
        adv_pad = _pad(adv, n_pad)
        f[adv_pad] = (f[adv_pad] + protocol.ADV_OFFSET) % field.P
    f = jnp.asarray(f.astype(np.int32))

    kf, kt = jax.random.split(k2)
    args = []
    for j, r in enumerate(rows):
        per_holder = np.concatenate(
            [np.asarray(_deal(proto, kf, f[o], pmat, o.start, j))
             for o in rows], axis=1)
        evals = jnp.asarray(per_holder)[:, subset]
        args.append((kt, evals, dvec, jnp.asarray(w[r]), jnp.asarray(xty[r]),
                     pmat[r]))
    zero = jnp.zeros(proto.w_shape, jnp.int32)
    c = _open_np(proto, [_update_jit(proto, *a, zero)[1] for a in args])
    return np.concatenate([np.asarray(_update_jit(proto, *a, c)[0])
                           for a in args])


OBJECTIVES = {"binary": objectives.BINARY_LOGISTIC,
              "ovr3": objectives.multiclass_logistic(3)}

# the adversary case: R = 4 of N = 7 at K = 1, T = 1, so a decode subset
# can leave a corrupted client out
FAULT_SUBSET, FAULT_ADV = (1, 2, 4, 6), 3


@functools.lru_cache(maxsize=None)
def _reference(n, k, t, objective, faults=False):
    """(proto, state after setup, decode idx/row, adversary mask or None,
    Copml.iteration's w shares after each of ITERS iterations) under the
    fused schedule."""
    obj = OBJECTIVES[objective]
    if obj.dataset_kind == "multiclass":
        x, y = pipeline.multiclass_dataset(m=78, d=6, seed=3,
                                           n_classes=obj.n_outputs)
    else:
        x, y = pipeline.classification_dataset(m=78, d=6, seed=3)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_FUSED_STEP", "1")
        proto = Copml(CopmlConfig(n_clients=n, k=k, t=t), x.shape[0],
                      x.shape[1], objective=obj)
    cx, cy = pipeline.split_clients(x, y, n)
    state = proto.setup(jax.random.PRNGKey(5), cx, cy)
    if faults:
        idx, dvec = (a[0] for a in proto.plan_constants([FAULT_SUBSET]))
        adv = np.arange(n) == FAULT_ADV
        step = proto._jitted_fault_step(True)
        args = (idx, dvec, jnp.asarray(adv))
    else:
        subset = tuple(range(proto.cfg.recovery_threshold))
        idx = jnp.asarray(subset)
        dvec = jnp.asarray(party.decode_row(proto, subset))
        adv, step, args = None, proto._jitted_step(None), ()
    shares, st = [], state
    for i in range(ITERS):
        st = step(jax.random.fold_in(KEY, i), st, *args)
        shares.append(np.asarray(st.w_shares))
    return proto, state, idx, dvec, adv, shares


def _check_blocks(nblocks, *reference):
    """ITERS per-party iterations over `nblocks` blocks from the reference's
    state and keys; the shares equal Copml.iteration's after each."""
    proto, state, idx, dvec, adv, shares = _reference(*reference)
    n = proto.cfg.n_clients
    n_pad = -(-n // nblocks) * nblocks
    w = _pad(state.w_shares, n_pad)
    coded_x = _pad(state.coded_x, n_pad)
    xty = _pad(state.xty_shares, n_pad)
    for i in range(ITERS):
        w = _party_step(proto, jax.random.fold_in(KEY, i), w, coded_x, xty,
                        nblocks, idx, dvec, adv)
        np.testing.assert_array_equal(w[:n], shares[i])


# T = 3 needs N >= R = 3 (K + T - 1) + 1 = 10 at K = 1; N = 11 pads at
# both P = 2 and P = 3.
@pytest.mark.parametrize("nblocks", [1, 2, 3])
@pytest.mark.parametrize("objective", list(OBJECTIVES))
@pytest.mark.parametrize("n,k,t", [(7, 2, 1), (11, 1, 3)],
                         ids=["t1", "t3"])
def test_party_step_matches_iteration(n, k, t, objective, nblocks):
    _check_blocks(nblocks, n, k, t, objective)


@pytest.mark.parametrize("nblocks", [1, 3])
def test_party_step_traced_subset_with_adversary(nblocks):
    """The fault engines' form: a traced decode subset, with a corrupted
    client left out of it."""
    _check_blocks(nblocks, 7, 1, 1, "binary", True)


@pytest.fixture
def own_copml_drivers(monkeypatch):
    """api.fit's Copml drivers for this test only, and their compiled
    programs dropped after it: a loop program of the siloed schedule must
    not outlive the test (tests/bench/test_scopes.py reads every loop
    program the process holds)."""
    monkeypatch.setattr(api.PROTOCOLS["copml"], "_drivers", {})
    yield
    monkeypatch.undo()
    jax.clear_caches()
    gc.collect()


def test_siloed_schedule_matches_fused_fit(monkeypatch, own_copml_drivers):
    """REPRO_FUSED_STEP=0 runs the per-party step over all N rows; its
    fit gives the fused schedule's weights and shares bit for bit."""
    out = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("REPRO_FUSED_STEP", mode)
        res = api.fit("smoke", "copml", "jit", key=0, iters=4,
                      history=False)
        out[mode] = (res.weights, np.asarray(res.state.w_shares))
    np.testing.assert_array_equal(out["0"][0], out["1"][0])
    np.testing.assert_array_equal(out["0"][1], out["1"][1])


@pytest.mark.parametrize("mode", ["0", "1"])
def test_one_pass_gradient_kernel_matches_default_fit(monkeypatch,
                                                      own_copml_drivers,
                                                      mode):
    """Phase 3 forced onto the one-pass Pallas kernel (interpreted here),
    as `ops` routes it on a TPU, in both schedules (ops.fused_step's
    composition under "1", local_gradient under "0"): the fit's weights
    and shares equal the default path's bit for bit, and the shares hash
    to the pinned golden."""
    import hashlib

    from goldens import GOLDEN_SHARES_SHA
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_FUSED_STEP", mode)
    out = {}
    for forced in (False, True):
        routed = []
        if forced:
            monkeypatch.setattr(ops, "_routes",
                                lambda shape, c: routed.append(shape)
                                or True)
        jax.clear_caches()
        monkeypatch.setattr(api.PROTOCOLS["copml"], "_drivers", {})
        res = api.fit("smoke", "copml", "jit", key=0, iters=10,
                      history=False)
        assert bool(routed) == forced
        out[forced] = (res.weights, np.asarray(res.state.w_shares, np.int32))
    np.testing.assert_array_equal(out[True][0], out[False][0])
    np.testing.assert_array_equal(out[True][1], out[False][1])
    assert hashlib.sha256(out[True][1].tobytes()).hexdigest() == \
        GOLDEN_SHARES_SHA
