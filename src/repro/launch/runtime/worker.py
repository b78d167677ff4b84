"""Worker process: one COPML client group's compute + socket collectives.

Each worker owns `n_loc = ceil(N / P)` consecutive clients and runs the
per-party step of core/party.py on them, as the mesh-sharded engine
(core/protocol.Copml._sharded_scan) does on a shard; this module holds
only the exchange, each mesh collective replaced by its socket
equivalent:

    reduce-scatter (model encode)   peer-to-peer ENC partial rows,
                                    chained field.add (exact mod-p sum)
    all_to_all (gradient shares)    peer-to-peer SHARE blocks
    all_gather + open (TruncPr)     OPEN rows to the coordinator,
                                    OPENED broadcast back

Bit-exactness with the jit engine holds for the same reason the sharded
engine's does: every random draw is replicated dealer randomness (same
key, full global shape on every process -- the paper's offline crypto
provider, fn. 3) and every cross-process contraction is an exact mod-p
linear reduction.  The decode subset may differ per step (whichever
owners' blocks arrive before the deadline); LCC decoding is exact
polynomial interpolation, so ANY >= R-subset yields identical values --
the invariance PR 4's fault engine proved, now exercised by real
network timing.
"""

from __future__ import annotations

import contextlib
import json
import pickle
import time
import traceback

import jax
import jax.numpy as jnp

from ...core import field, party
from ...core.protocol import Copml
from . import net, wire


class _PhaseClock:
    """Cumulative wall-time per protocol phase (the measured side of
    ARCHITECTURE.md's modeled-vs-measured comparison)."""

    def __init__(self):
        self.seconds: dict = {}

    @contextlib.contextmanager
    def __call__(self, phase: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[phase] = (self.seconds.get(phase, 0.0)
                                   + time.perf_counter() - t0)


def worker_entry(rank: int, coord_host: str, coord_port: int):
    """Worker main: handshake, run the session, report, exit.

    Launched as `python -m repro.launch.runtime.worker RANK HOST PORT`
    (a plain subprocess: nothing of the parent's __main__ is re-imported,
    so the engine works from scripts, notebooks, and stdin alike)."""
    node = net.Node(rank)
    node.start(listen=True)
    try:
        node.connect(net.COORD, coord_host, coord_port)
        node.send(net.COORD, net.LISTEN, payload=pickle.dumps(
            {"host": node.cfg.host, "port": node.port}))
        sess = pickle.loads(
            node.recv(net.SESSION, src=net.COORD, retries=1,
                      timeout=node.cfg.spawn_timeout_s).payload)
        node.configure(sess["net"])
        _run_session(node, sess)
        node.recv(net.BYE, src=net.COORD)
    except net.PeerFailure:
        raise SystemExit(1)          # the coordinator already knows
    except Exception:  # noqa: BLE001 -- report ANY failure upstream
        try:
            # ERR is a plain-scalar UTF-8 JSON control frame (never
            # pickle: the coordinator must not unpickle from a possibly
            # compromised worker).
            node.send(net.COORD, net.ERR, payload=json.dumps(
                {"rank": rank, "error": traceback.format_exc()},
            ).encode("utf-8"))
            time.sleep(0.2)          # let the frame flush before exit
        except Exception:  # noqa: BLE001
            pass
        raise SystemExit(1)
    finally:
        node.stop()


def _run_session(node: net.Node, sess: dict):
    t_start = time.perf_counter()
    rank = node.rank
    proto = Copml(sess["cfg"], sess["m"], sess["d"],
                  objective=sess["objective"])
    cfg = proto.cfg
    n, P = cfg.n_clients, sess["n_procs"]
    n_loc = -(-n // P)
    n_pad = n_loc * P
    lo = rank * n_loc
    rthr = cfg.recovery_threshold
    iters, history = sess["iters"], sess["history"]
    forced = sess["subset"]          # decode subset pinned by the caller

    def real_count(r):
        """Non-padded clients owned by rank r (trailing rank may own
        fewer when P does not divide N)."""
        return max(0, min(n_loc, n - r * n_loc))

    # full-mesh links: rank i dials every lower rank, higher ranks dial us
    for peer in range(P):
        if peer < rank:
            host, port = sess["addrs"][peer]
            node.connect(peer, host, port)
    node.send(net.COORD, net.READY)
    node.recv(net.START, src=net.COORD,
              timeout=node.cfg.spawn_timeout_s, retries=1)

    pmat_np, wall_np = party.padded_constants(proto, n_pad)
    pmat_all = jnp.asarray(pmat_np)
    pmat_loc = jnp.asarray(pmat_np[lo:lo + n_loc])
    wall_loc = jnp.asarray(wall_np[:, lo:lo + n_loc])

    w_loc = jnp.asarray(wire.unpack_array(sess["w_rows"]))
    coded_x = jnp.asarray(wire.unpack_array(sess["coded_rows"]))
    xty_loc = jnp.asarray(wire.unpack_array(sess["xty_rows"]))
    key = jnp.asarray(sess["key"])

    clock = _PhaseClock()
    dvec_cache: dict = {}
    degraded = 0

    def open_via_coord(c_sh, step):
        """TruncPr's masked opening: gather at the coordinator, get the
        reconstruction broadcast back (the OPEN barrier round)."""
        with clock("trunc_open"):
            node.send(net.COORD, net.OPEN, step=step, tag=net.TAG_TRUNC,
                      payload=wire.share_payload(c_sh), phase="trunc_open")
            frm = node.recv(net.OPENED, src=net.COORD, step=step,
                            tag=net.TAG_TRUNC)
        return jnp.asarray(wire.unpack_array(frm.payload))

    def encode_model(k1_, w_c, step):
        """Per-iteration model encode; the reconstruct-from-all-holders
        contraction runs as a socket reduce-scatter: each rank weights
        its own holders' encodings, sends peer s the partial for s's
        clients, and field.adds the partials it receives (chained exact
        mod-p addition == psum_scatter_mod's sum-then-reduce).

        Each peer's partial is a row slice of the weighted contraction,
        so it is computed JUST before its send: peer s's frame is on the
        wire while the GEMM for peer s+1 runs, instead of every byte
        waiting behind the monolithic (n_pad, dw) matmul.  Same frames,
        same order, same payload bits (a row slice of a matmul is the
        same contraction) -- commlint's budget holds unchanged."""
        with clock("encode"):
            enc = party.encode_model(proto, k1_, w_c, pmat_loc, n_pad)
            for s in range(P):
                if s == rank:
                    continue
                node.send(s, net.ENC, step=step, payload=wire.share_payload(
                    party.model_partial(enc, wall_loc, s)), phase="encode")
            acc = party.model_partial(enc, wall_loc, rank)
            for s in range(P):
                if s == rank:
                    continue
                frm = node.recv(net.ENC, src=s, step=step)
                acc = field.add(
                    acc, jnp.asarray(wire.unpack_array(frm.payload)))
        return acc                                           # (n_loc, dw)

    def collect_blocks(blocks, step):
        """Gather SHARE blocks and pick this step's decode subset from
        what actually ARRIVED -- straggling emerges from the network.

        With a pinned subset, wait (recv timeout policy) for exactly the
        ranks covering it.  Otherwise wait for everyone, but once >= R
        real owners are in hand, give the rest decode_timeout_s (or the
        recv budget) before decoding from the survivors."""
        nonlocal degraded
        if forced is not None:
            for s in sorted({g // n_loc for g in forced} - set(blocks)):
                frm = node.recv(net.SHARE, src=s, step=step)
                blocks[s] = jnp.asarray(wire.unpack_array(frm.payload))
            return tuple(forced)[:rthr]
        cfg_net = node.cfg
        soft = None if cfg_net.decode_timeout_s is None else (
            time.monotonic() + cfg_net.decode_timeout_s)
        hard = time.monotonic() + (cfg_net.recv_timeout_s
                                   * max(1, cfg_net.recv_retries))
        while len(blocks) < P:
            covered = sum(real_count(s) for s in blocks)
            now = time.monotonic()
            if covered >= rthr and (now >= hard
                                    or (soft is not None and now >= soft)):
                degraded += 1
                break
            if covered < rthr and now >= hard:
                raise net.NodeTimeout(
                    f"rank {rank}: only {covered} of the {rthr} owner "
                    f"blocks needed to decode step {step} arrived")
            frm = node.recv_any(net.SHARE, step, timeout=0.01)
            if frm is not None:
                blocks[frm.src] = jnp.asarray(
                    wire.unpack_array(frm.payload))
        owners = sorted(g for s in blocks
                        for g in range(s * n_loc, s * n_loc + real_count(s)))
        return tuple(owners[:rthr])

    def decode_update(k2_, w_c, f_loc, step):
        """Phase 4: share the coded gradients (all_to_all over sockets),
        decode locally from the arrived subset, TruncPr update."""
        kf, kt = jax.random.split(k2_)
        # holder rows owned by rank s, built just before the send so the
        # SHARE frame for s rides the wire while s+1's block GEMM runs
        # (same frames/order/bits as the monolithic form)
        mine_block = party.deal(proto, kf, f_loc, pmat_all, lo)

        with clock("exchange"):
            for s in range(P):
                if s == rank:
                    continue
                node.send(s, net.SHARE, step=step,
                          payload=wire.share_payload(mine_block(s)),
                          phase="exchange")
            blocks = {rank: mine_block(rank)}
            sub = collect_blocks(blocks, step)
        if sub not in dvec_cache:
            dvec_cache[sub] = jnp.asarray(party.decode_row(proto, sub))
        evals = jnp.stack(
            [blocks[g // n_loc][:, g - (g // n_loc) * n_loc, :]
             for g in sub], axis=1)                       # (n_loc, R, dw)
        return party.update(proto, kt, evals, dvec_cache[sub], w_c, xty_loc,
                            pmat_loc,
                            open_=lambda c_sh: open_via_coord(c_sh, step))

    for t in range(iters):
        kit = jax.random.fold_in(key, t)
        k1_, k2_ = jax.random.split(kit)
        coded_w = encode_model(k1_, w_loc, t)
        with clock("gradient"):
            f_loc = proto.local_gradient(coded_x, coded_w)   # LOCAL
        w_loc = decode_update(k2_, w_loc, f_loc, t)
        if history:
            with clock("open_model"):
                node.send(net.COORD, net.OPEN, step=t, tag=net.TAG_HIST,
                          payload=wire.share_payload(w_loc),
                          phase="open_model")

    with clock("open_model"):
        node.send(net.COORD, net.RESULT, payload=pickle.dumps({
            "w": wire.share_payload(w_loc[:real_count(rank)]),
            "seconds": dict(clock.seconds),
            "bytes": dict(node.sent_bytes),
            "frames": dict(node.sent_frames),
            "dropped": dict(node.dropped_frames),
            "degraded_steps": degraded,
            "wall_s": time.perf_counter() - t_start,
        }), phase="open_model")


def main(argv=None):
    import sys
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 3:
        raise SystemExit(
            "usage: python -m repro.launch.runtime.worker RANK HOST PORT")
    worker_entry(int(args[0]), args[1], int(args[2]))


if __name__ == "__main__":
    main()
