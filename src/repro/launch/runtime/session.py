"""Coordinator: spawn workers, deal state, drive open rounds, assemble.

The parent process runs the one-time setup (Phases 1-2, identical to the
jit engine: same key split, same dealer draws), deals each worker its
padded client rows over the SESSION frame, then acts as the opening
barrier of the training loop: per step it gathers every rank's TruncPr
share rows, reconstructs, and broadcasts the public value back (plus the
per-step model opening on history runs).  Afterwards it reassembles the
final CopmlState from the workers' model share rows -- so the state the
caller sees is byte-identical to the in-process engines' -- and merges
every node's byte/time counters into the measured_comm record.

This is the `proc:N` engine behind api.fit; see docs/RUNNING.md
"Multi-process" for the knobs.
"""

from __future__ import annotations

import dataclasses
import os
import pickle
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ...core import quantize, shamir
from ...core.protocol import _pad_clients
from . import net, wire
from .config import NetConfig

#: processes a bare "proc" engine spec launches (capped at N clients)
DEFAULT_PROCS = 4


def run_copml_proc(proto, key, client_xs, client_ys, iters: int, *,
                   procs: int | None = None, net_cfg: NetConfig | None = None,
                   subset=None, history: bool = False) -> tuple:
    """Train `proto` over P OS processes on real localhost sockets.

    Returns (state, weights, history-or-None, measured_comm) with
    state/weights/history bit-exact to the jit engine (the conformance
    suite in tests/test_runtime_engine.py pins this against the goldens).
    """
    cfg = proto.cfg
    n = cfg.n_clients
    P = DEFAULT_PROCS if procs is None else int(procs)
    P = min(P, n)
    if P < 1:
        raise ValueError(f"proc engine needs >= 1 process, got {P}")
    platform = jax.default_backend()
    if platform != "cpu":
        # the coordinator's own setup holds the device, and a device belongs
        # to one process: the workers' fresh JAX runtimes could not load it
        # and would only time out at spawn_timeout_s
        raise RuntimeError(
            f"the proc engine cannot run on a {platform} host: this "
            f"coordinator process holds the {platform} device, and each "
            f"worker process would need a device of its own (no per-worker "
            f"device assignment exists). Use the jit or sharded engine, or "
            f"run proc:N with JAX_PLATFORMS=cpu.")
    ncfg = NetConfig.from_env() if net_cfg is None else net_cfg
    iters = int(iters)
    subset = None if subset is None else tuple(subset)

    t0 = time.perf_counter()
    ks, ki = jax.random.split(key)
    state = proto.setup(ks, client_xs, client_ys)   # one-time, in-process
    n_loc = -(-n // P)
    n_pad = n_loc * P
    w_pad = _pad_clients(state.w_shares, n_pad)
    cx_pad = _pad_clients(state.coded_x, n_pad)
    xty_pad = _pad_clients(state.xty_shares, n_pad)

    node = net.Node(net.COORD, cfg=ncfg).start()
    # Plain subprocesses (NOT multiprocessing spawn): each worker is
    # `python -m repro.launch.runtime.worker RANK HOST PORT`, so nothing
    # of the caller's __main__ is re-imported and each client really is
    # an independent OS process with its own fresh jax runtime.
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in sys.path if p]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    workers = [subprocess.Popen(
        [sys.executable, "-m", "repro.launch.runtime.worker",
         str(r), ncfg.host, str(node.port)], env=env)
        for r in range(P)]

    def check_workers():
        dead = [r for r, p in enumerate(workers)
                if p.poll() not in (None, 0)]
        if dead:
            raise net.PeerFailure(
                f"worker process(es) {dead} exited "
                f"(exit codes {[workers[r].poll() for r in dead]}); "
                f"see their stderr for the traceback")

    node.liveness = check_workers
    try:
        addrs = {}
        for _ in range(P):
            frm = node.recv(net.LISTEN, timeout=ncfg.spawn_timeout_s)
            info = pickle.loads(frm.payload)
            addrs[frm.src] = (info["host"], info["port"])
        base = dict(cfg=cfg, m=proto.m, d=proto.d, objective=proto.obj,
                    key=np.asarray(ki), iters=iters, n_procs=P, net=ncfg,
                    subset=subset, history=bool(history), addrs=addrs)
        for r in range(P):
            rows = slice(r * n_loc, (r + 1) * n_loc)
            node.send(r, net.SESSION, payload=pickle.dumps(dict(
                base, rank=r,
                w_rows=wire.share_payload(w_pad[rows]),
                coded_rows=wire.share_payload(cx_pad[rows]),
                xty_rows=wire.share_payload(xty_pad[rows]))))
        for r in range(P):
            node.recv(net.READY, src=r, timeout=ncfg.spawn_timeout_s)
        setup_wall = time.perf_counter() - t0
        for r in range(P):
            node.send(r, net.START)

        hist_rows = [] if history else None
        for t in range(iters):
            c_full = _gather_rows(node, P, t, net.TAG_TRUNC)[:n]
            c = shamir.reconstruct(c_full, cfg.t, proto.lambdas)
            opened = wire.pack_array(np.asarray(c))
            for r in range(P):
                node.send(r, net.OPENED, step=t, tag=net.TAG_TRUNC,
                          payload=opened, phase="trunc_open")
            if history:
                w_full = _gather_rows(node, P, t, net.TAG_HIST)[:n]
                wf = shamir.reconstruct(w_full, cfg.t, proto.lambdas)
                hist_rows.append(
                    np.asarray(quantize.dequantize(wf, cfg.lw)))

        results = {}
        result_wire = 0
        for r in range(P):
            frm = node.recv(net.RESULT, src=r)
            # the RESULT payload carries the worker's own send counters,
            # so the worker cannot count this frame itself (fixed point);
            # the coordinator meters the exact bytes it received instead.
            result_wire += wire.HEADER_SIZE + len(frm.payload)
            results[r] = pickle.loads(frm.payload)
            node.send(r, net.BYE)
        w_shares = jnp.concatenate(
            [jnp.asarray(wire.unpack_array(results[r]["w"]))
             for r in range(P)], axis=0)
        state = dataclasses.replace(
            state, w_shares=w_shares,
            step=state.step + jnp.asarray(iters, jnp.int32))
        w = proto.open_model(state)
        for p in workers:
            try:
                p.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                pass
        hist = None
        if history:
            hist = np.stack(hist_rows) if hist_rows else \
                np.zeros((0,) + proto.w_shape, np.float32)
        measured = _assemble_measured(results, node, P, iters,
                                      time.perf_counter() - t0, setup_wall,
                                      result_wire)
        return state, w, hist, measured
    finally:
        node.stop()
        for p in workers:
            if p.poll() is None:
                p.terminate()
            try:
                p.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                p.kill()


def _gather_rows(node, P: int, step: int, tag: int):
    """Stack every rank's (n_loc,)+shape OPEN rows into (n_pad,)+shape."""
    rows = [jnp.asarray(wire.unpack_array(
        node.recv(net.OPEN, src=r, step=step, tag=tag).payload))
        for r in range(P)]
    return jnp.concatenate(rows, axis=0)


def _assemble_measured(results, node, P, iters, wall, setup_wall,
                       result_wire) -> dict:
    """Merge per-node counters: bytes sum over every process (each frame
    is sent exactly once), per-phase seconds take the max over workers
    (the slowest rank is the step's critical path).  `result_wire` is the
    coordinator-metered size of the P RESULT frames, which the workers
    cannot self-count."""
    bytes_by_phase = dict(node.sent_bytes)
    frames_by_phase = dict(node.sent_frames)
    bytes_by_phase["open_model"] = (bytes_by_phase.get("open_model", 0)
                                    + result_wire)
    frames_by_phase["open_model"] = (frames_by_phase.get("open_model", 0)
                                     + P)
    # receiver-side stale-drop counts sum across every process; they are
    # deliberately NOT part of frames_by_phase, which counts sends only
    # and therefore matches the static choreography budget exactly even
    # on degraded runs (a dropped frame was still sent).
    dropped_frames = dict(node.dropped_frames)
    seconds_by_phase: dict = {}
    degraded = 0
    for res in results.values():
        for k, v in res["bytes"].items():
            bytes_by_phase[k] = bytes_by_phase.get(k, 0) + v
        for k, v in res["frames"].items():
            frames_by_phase[k] = frames_by_phase.get(k, 0) + v
        for k, v in res.get("dropped", {}).items():
            dropped_frames[k] = dropped_frames.get(k, 0) + v
        for k, v in res["seconds"].items():
            seconds_by_phase[k] = max(seconds_by_phase.get(k, 0.0), v)
        degraded = max(degraded, res["degraded_steps"])
    return {
        "engine": f"proc:{P}",
        "procs": P,
        "iters": iters,
        "bytes_by_phase": bytes_by_phase,
        "total_bytes": sum(bytes_by_phase.values()),
        "frames_by_phase": frames_by_phase,
        "dropped_frames": dropped_frames,
        "seconds_by_phase": seconds_by_phase,
        "degraded_steps": degraded,
        "setup_wall_s": setup_wall,
        "wall_s": wall,
    }
