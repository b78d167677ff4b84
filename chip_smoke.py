"""Chip smoke run: COPML trains and serves end to end on a TPU.

    python chip_smoke.py              # one chip: checks (a)-(e) below
    python chip_smoke.py --chips 4    # sharded:4 against jit, nothing else

Drives the public entry points, repro.api.fit and repro.api.serve, on the
paper's CIFAR-10 Case 1 deployment (cifar10_case1: N=50 parties, K=16,
T=1, r=1, d=3073) with synthetic data and randomness from a fixed seed.
The sample count m is cut from 9019 to 128: one-time setup (Copml.setup)
builds its limb products at full size, and on a v5e the LCC encode at
m=256 asks for 8.22 GB in one allocation with 7.12 GB free, so m=128 is
the largest multiple of K=16 tried whose setup fits one 16 GB chip
(streamed setup is ROADMAP B1).  One chip checks:

  (a) the default path, engine "jit", trains;
  (b) the same fit with the Pallas megakernel forced
      (REPRO_FUSED_STEP=kernel) is bit-identical in shares and weights;
  (c) that compiled step holds a Mosaic kernel (tpu_custom_call), so it
      was not interpreted;
  (d) COPML's accuracy is within 0.05 of plaintext logistic regression
      ("float") on the same data;
  (e) api.serve answers 64 queries, and its in-field logits equal the
      quantized reference scorer exactly.

With --chips 4 it checks instead that engine "sharded:4" gives the same
shares and weights as "jit".  Either way it reports each device's peak
memory (setup runs replicated on device 0, which bounds m).

Times printed are one smoke run (first call: compile and run; second call:
run with everything compiled), not benchmark metrics.  The last line of
standard output is one JSON object, {"ok": true, "device": {...}}; any
failed check, or a host where JAX finds no TPU, exits non-zero before it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

WORKLOAD = "cifar10_case1"
M = 128                 # cut from the paper's 9019 (setup memory, B1)
ITERS = 5
SEED = 0
QUERIES = 64
ACCURACY_BAND = 0.05


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"check {what}: ok", flush=True)


def timed_fit(label: str, wl, protocol: str, engine):
    """api.fit twice: the first call compiles, the second runs compiled.
    Both must agree bit for bit; returns the second result."""
    from repro import api
    t0 = time.perf_counter()
    first = api.fit(wl, protocol, engine, key=SEED, iters=ITERS,
                    history=False)
    t1 = time.perf_counter()
    res = api.fit(wl, protocol, engine, key=SEED, iters=ITERS, history=False)
    t2 = time.perf_counter()
    print(f"smoke run {label}: first call (compile + run) {t1 - t0:.3f} s, "
          f"second call (run) {t2 - t1:.3f} s", flush=True)
    check(np.array_equal(first.weights, res.weights),
          f"{label}: repeated fit gives the same weights")
    return res


def same_bits(a, b) -> bool:
    return bool(np.array_equal(np.asarray(a), np.asarray(b)))


def one_chip(wl) -> None:
    """Checks (a)-(e) on one device."""
    from repro import api
    from repro.serve import coded

    # (a) the default path
    res = timed_fit("(a) copml x jit", wl, "copml", "jit")
    check(bool(np.all(np.isfinite(res.weights)))
          and res.weights.shape == wl.w_shape,
          f"(a) copml x jit: finite weights of shape {wl.w_shape}")

    # (b) the megakernel forced, on its own Workload instance: api.fit
    # caches one driver per (workload, gate), and a fresh name keeps its
    # compiled programs apart from (a)'s as well
    wl_k = dataclasses.replace(wl, name=f"{wl.name}_megakernel")
    saved = os.environ.get("REPRO_FUSED_STEP")
    os.environ["REPRO_FUSED_STEP"] = "kernel"
    try:
        res_k = timed_fit("(b) copml x jit, megakernel", wl_k, "copml",
                          "jit")
        proto = api.PROTOCOLS["copml"].driver(wl_k)
    finally:
        if saved is None:
            del os.environ["REPRO_FUSED_STEP"]
        else:
            os.environ["REPRO_FUSED_STEP"] = saved
    check(proto.fused_mode == "kernel", "(b) driver runs the megakernel")
    check(same_bits(res_k.state.w_shares, res.state.w_shares),
          "(b) megakernel shares bit-identical to (a)")
    check(same_bits(res_k.weights, res.weights),
          "(b) megakernel weights bit-identical to (a)")

    # (c) the compiled step of (b) runs a Mosaic kernel
    step = jax.jit(proto.iteration).lower(
        jax.random.PRNGKey(SEED), res_k.state).compile().as_text()
    n_kernels = step.count("tpu_custom_call")
    print(f"(c) compiled megakernel step: {n_kernels} tpu_custom_call op(s)")
    check(n_kernels > 0, "(c) megakernel compiled by Mosaic, not "
          "interpreted")

    # (d) accuracy against plaintext logistic regression
    res_f = api.fit(wl, "float", "jit", key=SEED, iters=ITERS,
                    history=False)
    gap = abs(res.final_accuracy - res_f.final_accuracy)
    print(f"(d) accuracy after {ITERS} iterations: copml "
          f"{res.final_accuracy:.4f}, float {res_f.final_accuracy:.4f}, "
          f"gap {gap:.4f}")
    check(gap <= ACCURACY_BAND,
          f"(d) accuracy within {ACCURACY_BAND} of float")

    # (e) secure serving from the trained shares
    x = np.asarray(wl.eval_set()[0][:QUERIES], np.float32)
    srv = api.serve(wl, res, "jit", key=SEED)
    secure = srv.score_field(x)
    ref = np.asarray(coded.reference_scores(res.weights, x, wl.cfg))
    check(secure.shape == (QUERIES, 1) and np.array_equal(secure, ref),
          f"(e) {QUERIES} served logits equal the reference scorer")
    preds, _ = srv.serve(x)
    check(len(preds) == QUERIES, f"(e) api.serve answered {QUERIES} queries")
    print(f"(e) {srv.summary()}")


def four_chips(wl) -> None:
    """sharded:4 against jit on the same deployment, nothing else."""
    res_j = timed_fit("copml x jit", wl, "copml", "jit")
    res_s = timed_fit("copml x sharded:4", wl, "copml", "sharded:4")
    check(same_bits(res_s.state.w_shares, res_j.state.w_shares),
          "sharded:4 shares bit-identical to jit")
    check(same_bits(res_s.weights, res_j.weights),
          "sharded:4 weights bit-identical to jit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: checks (a)-(e); 4: sharded:4 against jit")
    args = ap.parse_args(argv)
    try:
        from repro import api
        from repro.api import compile_cache
    except ImportError as exc:
        print(f"chip_smoke: cannot import the repro package from "
              f"{Path(__file__).resolve().parent / 'src'} ({exc}); run it "
              f"from a checkout of the repository", file=sys.stderr)
        return 2

    cache_dir = compile_cache.enable()
    events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"this run needs the chip and never falls back to the CPU",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX finds {len(devices)}", file=sys.stderr)
        return 1

    base = api.get_workload(WORKLOAD)
    wl = dataclasses.replace(base, m=M)
    cfg = wl.cfg
    print(f"deployment: {WORKLOAD} N={cfg.n_clients} K={cfg.k} T={cfg.t} "
          f"r={cfg.r} d={wl.d} m={wl.m} iters={ITERS} seed={SEED}")
    print(f"reduced: m {base.m}->{wl.m} (setup memory, ROADMAP B1)")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    print(f"compile cache: {cache_dir}", flush=True)

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(wl)
        else:
            one_chip(wl)
    except SmokeFailure as exc:
        print(f"chip_smoke: check failed: {exc}", file=sys.stderr)
        return 1
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"device {d.id}: peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"compile cache: {events['hits']} hits, {events['misses']} misses")
    print(f"smoke run wall time: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
