import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: prove every (arch x input-shape x mesh) combination
lowers, SPMD-partitions, and compiles for the production meshes.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/

Per cell we print compiled.memory_analysis() (fits-in-HBM proof) and
cost_analysis() (FLOPs/bytes for the roofline), and append a JSON record
consumed by benchmarks/roofline_report.py.
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from ..configs import registry
from ..models import model_zoo as MZ
from ..models.config import applicable_shapes, ALL_SHAPES
from ..sharding import partition
from . import mesh as mesh_lib
from . import roofline as RL


DEFAULT_MICROBATCH_DIV = 8   # global batch / 8 per accumulation step
DEFAULT_LOSS_CHUNK = 512     # seq-chunked CE: never materialize (B,S,V)


def _step_fn_and_args(cfg, shape, mesh, *, loss_chunk=None, microbatch=None,
                      remat=None):
    """Returns (fn, args) ready for jax.jit(fn).lower(*args)."""
    if remat is not None:
        cfg = cfg.scaled(remat=remat)
    if microbatch is None:
        microbatch = max(1, shape.global_batch // DEFAULT_MICROBATCH_DIV) \
            if shape.kind == "train" else 0
    if loss_chunk is None:
        loss_chunk = DEFAULT_LOSS_CHUNK if shape.kind == "train" else 0
    bm = MZ.build(cfg, microbatch=microbatch, loss_chunk=loss_chunk)
    if shape.kind == "train":
        params = partition.param_structs(cfg, mesh)
        opt = partition.opt_state_structs(cfg, mesh, params)
        batch = partition.batch_structs(cfg, shape, mesh)
        step = jax.ShapeDtypeStruct((), jnp.int32,
                                    sharding=partition.replicated(mesh))
        return bm.train_step, (params, opt, batch, step)
    if shape.kind == "prefill":
        params = partition.param_structs(cfg, mesh)
        batch = partition.batch_structs(cfg, shape, mesh)
        return bm.prefill_step, (params, batch)
    # decode: no gradients -- params use the data axis too (inference FSDP)
    params = partition.param_structs(
        cfg, mesh, fsdp=(cfg.param_count() * 2 / mesh.shape.get("model", 1)
                         > 2 ** 32))
    caches = partition.cache_structs(cfg, shape, mesh)
    batch = partition.batch_structs(cfg, shape, mesh)
    pos = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=partition.replicated(mesh))
    return bm.decode_step, (params, caches, batch["tokens"], pos)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir=None,
             **tuning) -> dict:
    t0 = time.perf_counter()
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    if arch == "copml-logreg":
        from . import copml_dist
        rec = copml_dist.dryrun_cell(shape_name, mesh, multi_pod)
    else:
        cfg = registry.get_config(arch)
        shape = {s.name: s for s in ALL_SHAPES}[shape_name]
        if shape not in applicable_shapes(cfg):
            return {"arch": arch, "shape": shape_name,
                    "mesh": "multipod" if multi_pod else "pod",
                    "status": "skipped (full attention at 500k context, "
                              "DESIGN.md section 6)"}
        fn, args = _step_fn_and_args(cfg, shape, mesh, **tuning)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn).lower(*args)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        mflops = RL.model_flops(cfg, shape)
        rf = RL.analyze(f"{arch}/{shape_name}", compiled, chips, mflops)
        rec = rf.to_dict()
        rec.update({
            "arch": arch, "shape": shape_name,
            "mesh": "multipod" if multi_pod else "pod",
            "status": "ok",
            "bytes_per_device": {
                "argument": mem.argument_size_in_bytes,
                "output": mem.output_size_in_bytes,
                "temp": mem.temp_size_in_bytes,
                "peak": (mem.argument_size_in_bytes
                         + mem.output_size_in_bytes
                         + mem.temp_size_in_bytes),
            },
            "collectives": RL.collective_bytes(compiled.as_text())["counts"],
        })
        print(f"--- {arch} x {shape_name} x "
              f"{'multipod(512)' if multi_pod else 'pod(256)'} ---")
        print(f"memory_analysis: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
              f"out={mem.output_size_in_bytes/2**30:.2f}GiB "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB")
        print(f"cost_analysis: flops={rf.hlo_flops:.3e} "
              f"bytes={rf.hlo_bytes:.3e} "
              f"coll_bytes/dev={rf.coll_bytes_per_device:.3e}")
        print(f"roofline: compute={rf.compute_s*1e3:.3f}ms "
              f"memory={rf.memory_s*1e3:.3f}ms "
              f"collective={rf.collective_s*1e3:.3f}ms "
              f"dominant={rf.dominant} "
              f"useful_ratio={rf.useful_flops_ratio:.3f} "
              f"roofline_frac={rf.roofline_fraction:.3f}")
    rec["compile_s"] = time.perf_counter() - t0
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'multipod' if multi_pod else 'pod'}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=2)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=registry.ARCH_IDS)
    ap.add_argument("--shape", default=None,
                    choices=[s.name for s in ALL_SHAPES] + ["all"])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod",
                                                      "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    archs = registry.ARCH_IDS if args.all or not args.arch else (args.arch,)
    shapes = [s.name for s in ALL_SHAPES] \
        if args.all or args.shape in (None, "all") else [args.shape]
    meshes = {"pod": (False,), "multipod": (True,),
              "both": (False, True)}[args.mesh]
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = run_cell(arch, shape, mp, args.out)
                    if "skipped" in rec.get("status", ""):
                        print(f"SKIP {arch} x {shape}: {rec['status']}")
                except Exception as e:  # noqa: BLE001 -- report and continue
                    failures.append((arch, shape, mp, repr(e)[:200]))
                    print(f"FAIL {arch} x {shape} multipod={mp}: {e!r}",
                          file=sys.stderr)
    if failures:
        print(f"{len(failures)} failures", file=sys.stderr)
        sys.exit(1)
    print("dry-run: all requested cells compiled")


if __name__ == "__main__":
    main()
