"""COPML on a real device mesh: the distributed protocol entry point.

The paper's N clients map onto a 1-D ("clients",) mesh (each device holds a
contiguous block of clients' shares and coded slices) and the protocol runs
under shard_map (core/protocol.py, Copml.train_sharded), so every exchange
is an explicit collective rather than a GSPMD annotation:

  share distribution (owner -> holder transpose)   -> all_to_all
  model-encoding reconstruction (sum over holders) -> mod-p reduce-scatter
  TruncPr / model opening                          -> all_gather + replicated
                                                      decode

Run it for real on a CPU host (flag must precede the first jax import):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.copml_dist --devices 8 --clients 13 --iters 5

which trains api.fit(..., engine="sharded") over the mesh, re-trains on one
device with engine="jit", and asserts the two are bit-exact.  --bench
prints the CSV rows benchmarks/run.py's `distributed` stage records.

Dry-run cells (invoked from launch/dryrun.py for --arch copml-logreg) lower
and compile ONE real sharded iteration -- collectives and all -- on the
flattened production mesh; shape names map to paper-scale and pod-scale
workloads:

  train_4k    -> CIFAR-10 scale (m=9019, d=3073), paper Case 2 at N=mesh size
  prefill_32k -> GISETTE scale (m=6000, d=5000)
  decode_32k  -> pod-scale (m=262144, d=4096)
  smoke       -> tiny (m=416, d=64), used by tests/test_distributed.py
  long_500k   -> skipped (no analogue; noted in DESIGN.md)
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import meshutil
from ..core.protocol import Copml, CopmlConfig, CopmlState, case2_params
from ..sharding import partition
from . import roofline as RL

_SHAPE_MAP = {
    "train_4k": ("cifar10-scale", 9019, 3073),
    "prefill_32k": ("gisette-scale", 6000, 5000),
    "decode_32k": ("pod-scale", 262144, 4096),
    "smoke": ("smoke-scale", 416, 64),
}

# field MACs per train iteration (Table II, matvec-chain evaluation):
# encode w: d*N*(K+T); local grad: 2*(m/K)*d per client; decode: d*R per
# block; all clients in parallel.  1 field MAC ~ 16 f32 MXU MACs + ~40 int32
# VPU ops under the limb decomposition (DESIGN.md section 3.2); we price it
# at 16 MXU-equivalent flops for the compute term.
FIELD_MAC_FLOPS = 16.0


def make_config(n: int, m: int, d: int) -> CopmlConfig:
    k, t = case2_params(n)
    # The truncation depth k1 = 2*lx + cb + log2(m/eta) must stay below
    # log2(p): with the paper's 26-bit field, m beyond ~2^14 forces either
    # coarser quantization or a larger step size.  We scale eta with m
    # (documented scalability limit of the 26-bit field, EXPERIMENTS.md).
    eta = max(1.0, m / 4096.0)
    return CopmlConfig(n_clients=n, k=k, t=t, eta=eta)


def make_protocol(n: int, m: int, d: int) -> Copml:
    return Copml(make_config(n, m, d), m, d)


def flatten_mesh(mesh):
    """Any production mesh -> the 1-D ("clients",) mesh of the same devices."""
    if tuple(mesh.axis_names) == (meshutil.CLIENT_AXIS,):
        return mesh
    return meshutil.client_mesh(devices=list(mesh.devices.reshape(-1)))


def state_structs(proto: Copml, mesh) -> CopmlState:
    """Abstract padded client-sharded CopmlState; the client NamedSharding
    is built in ONE place, sharding/partition.copml_state_structs."""
    return partition.copml_state_structs(proto, mesh)


def dryrun_cell(shape_name: str, mesh, multi_pod: bool) -> dict:
    """Compile one REAL sharded iteration (shard_map + collectives) for the
    given mesh and report per-device memory + roofline, no data needed."""
    if shape_name not in _SHAPE_MAP:
        return {"arch": "copml-logreg", "shape": shape_name,
                "mesh": "multipod" if multi_pod else "pod",
                "status": "skipped (no long-context analogue for secure "
                          "logistic regression)"}
    tag, m, d = _SHAPE_MAP[shape_name]
    n = mesh.size
    cmesh = flatten_mesh(mesh)
    proto = make_protocol(n, m, d)
    cfg = proto.cfg
    step_fn, _ = proto.sharded_step(cmesh)
    state = state_structs(proto, cmesh)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(cmesh, P()))
    lowered = jax.jit(step_fn).lower(state.w_shares, state.coded_x,
                                     state.xty_shares, key)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    mk = -(-m // cfg.k)
    macs = (d * n * (cfg.k + cfg.t)            # encode w
            + 2.0 * mk * d                      # local coded gradient
            + d * cfg.recovery_threshold * cfg.k  # decode
            ) * n                               # per client, N clients
    mflops = macs * FIELD_MAC_FLOPS
    rf = RL.analyze(f"copml/{tag}", compiled, mesh.size, mflops)
    rec = rf.to_dict()
    rec.update({
        "arch": "copml-logreg", "shape": shape_name, "workload": tag,
        "mesh": "multipod" if multi_pod else "pod", "status": "ok",
        "n_clients": n, "K": cfg.k, "T": cfg.t,
        "recovery_threshold": cfg.recovery_threshold,
        "collectives": RL.collective_bytes(compiled.as_text())["counts"],
        "bytes_per_device": {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "peak": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes),
        },
    })
    print(f"--- copml-logreg[{tag}] x {'multipod(512)' if multi_pod else 'pod(256)'}"
          f" N={n} K={cfg.k} T={cfg.t} R={cfg.recovery_threshold} ---")
    print(f"memory_analysis: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
          f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB")
    print(f"collectives: {rec['collectives']}")
    print(f"roofline: compute={rf.compute_s*1e3:.3f}ms "
          f"memory={rf.memory_s*1e3:.3f}ms "
          f"collective={rf.collective_s*1e3:.3f}ms dominant={rf.dominant}")
    return rec


# ------------------------------------------------------------------ CLI


def _workload(args):
    """Ad-hoc api workload for the CLI's (m, d, clients) arguments."""
    from .. import api
    return api.Workload(
        name=f"cli_m{args.m}_d{args.d}_n{args.clients}", m=args.m, d=args.d,
        cfg=make_config(args.clients, args.m, args.d), iters=args.iters)


def run_parity(args) -> None:
    """Train sharded on the client mesh, re-train single-device, compare.

    Both runs go through api.fit -- the same facade path every other
    driver uses; only the engine axis differs.  With --straggle-p the SAME
    seeded FaultPlan is replayed by both engines (mid-training churn over
    real collectives, still bit-exact)."""
    from .. import api
    wl = _workload(args)
    cfg = wl.cfg
    mesh = meshutil.client_mesh(args.devices)
    plan = None
    if args.straggle_p is not None:
        # the SAME threshold api.fit's plan validation enforces
        thr = api.PROTOCOLS["copml"].fault_threshold(wl)
        plan = api.FaultPlan.random(
            cfg.n_clients, args.iters, seed=args.fault_seed,
            straggle_p=args.straggle_p, min_available=thr)
        print(plan.describe(thr))
    print(f"COPML distributed: N={cfg.n_clients} clients over "
          f"{mesh.size} devices, K={cfg.k} T={cfg.t} "
          f"R={cfg.recovery_threshold}, {args.iters} iterations")
    res_s = api.fit(wl, "copml", api.EngineSpec("sharded", mesh=mesh),
                    key=args.seed, iters=args.iters, history=False,
                    faults=plan)
    res_j = api.fit(wl, "copml", "jit", key=args.seed, iters=args.iters,
                    history=False, faults=plan)
    np.testing.assert_array_equal(res_s.weights, res_j.weights)
    np.testing.assert_array_equal(np.asarray(res_s.state.w_shares),
                                  np.asarray(res_j.state.w_shares))
    print(f"bit-exact: sharded == jit  "
          f"(sharded {res_s.wall_time_s:.2f}s incl. compile, "
          f"single {res_j.wall_time_s:.2f}s)")


def run_bench(args, report=print) -> None:
    """Sharded-vs-single-device wall time, interleaved best-of-reps
    (both warm; virtual CPU devices share the host's cores, so this
    measures protocol+collective overhead, not real multi-chip scaling)."""
    from .. import api
    wl = _workload(args)
    mesh = meshutil.client_mesh(args.devices)
    engines = (("train_jit_1dev", "jit"),
               (f"train_sharded_{mesh.size}dev",
                api.EngineSpec("sharded", mesh=mesh)))
    best = {}
    for name, eng in engines:                   # compile + warm
        api.fit(wl, "copml", eng, key=args.seed, iters=args.iters,
                history=False)
        best[name] = float("inf")
    for _ in range(args.reps):                  # interleaved best-of-reps
        for name, eng in engines:
            res = api.fit(wl, "copml", eng, key=args.seed, iters=args.iters,
                          history=False)
            best[name] = min(best[name], res.wall_time_s)
    base = best[engines[0][0]]
    for name, _ in engines:
        dt = best[name]
        report(f"copml_dist/{name}_{args.iters}it,{dt * 1e6:.1f},"
               f"{base / dt:.2f}x_vs_1dev")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--devices", type=int, default=None,
                    help="mesh size (default: all visible devices)")
    ap.add_argument("--clients", type=int, default=13)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--m", type=int, default=832)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--straggle-p", type=float, default=None,
                    help="replay a seeded FaultPlan (mid-training churn) "
                         "on both engines of the parity demo")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--bench", action="store_true",
                    help="print benchmark CSV rows instead of the parity demo")
    args = ap.parse_args(argv)
    from ..api import compile_cache
    compile_cache.enable()
    if args.devices is None:
        args.devices = len(jax.devices())
    if len(jax.devices()) < 2:
        print("NOTE: only one device visible; set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "before launching to exercise real collectives.")
    if args.bench:
        run_bench(args)
    else:
        run_parity(args)


if __name__ == "__main__":
    main()
