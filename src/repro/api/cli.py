"""CLI front door: run any (workload, protocol, engine) triple.

    repro-fit smoke --protocol copml --engine jit          # console script
    PYTHONPATH=src python -m repro.api.cli --list          # registries
    repro-serve smoke --engine jit --queries 64            # train + serve

Prints the TrainResult summary line (and the accuracy curve with -v).
`serve_main` (the repro-serve console script) trains the triple, then
serves the workload's eval set through api.serve's micro-batch path and
reports throughput + agreement with opened-model scoring.
"""

from __future__ import annotations

import argparse

from . import (PROTOCOLS, FaultPlan, compile_cache, engine_names, fit,
               serve, workload_names)
from . import workloads as workloads_mod


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workload", nargs="?", default=None,
                    help="registry name (see --list)")
    ap.add_argument("--workload", dest="workload_flag", default=None,
                    metavar="NAME",
                    help="alternative spelling of the positional workload")
    ap.add_argument("--protocol", default="copml",
                    choices=sorted(PROTOCOLS))
    ap.add_argument("--engine", default="jit",
                    help='"eager" | "jit" | "sharded[:N]" | "proc[:N]" '
                         '(see --list for the live registry)')
    ap.add_argument("--iters", type=int, default=None,
                    help="GD iterations (default: the workload's)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--straggle-p", type=float, default=None, metavar="P",
                    help="inject a seeded FaultPlan.random churn schedule "
                         "(per-step straggle probability; repaired to the "
                         "protocol's recovery threshold)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for --straggle-p's schedule")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the per-step model history / accuracy curve")
    ap.add_argument("-v", "--verbose", action="store_true")
    ap.add_argument("--list", action="store_true",
                    help="print the three registries and exit")
    args = ap.parse_args(argv)
    if args.workload_flag is not None:
        if args.workload is not None:
            ap.error("give the workload positionally OR via --workload, "
                     "not both")
        args.workload = args.workload_flag
    if args.workload is None:
        args.workload = "quickstart"

    if args.list:
        from . import objective_names
        print("workloads: ", ", ".join(workload_names()))
        print("protocols: ", ", ".join(sorted(PROTOCOLS)))
        # the LIVE kind registry, so engines registered after import
        # (proc today, whatever comes next) appear without a CLI edit
        print("engines:   ", ", ".join(engine_names()))
        print("objectives:", ", ".join(objective_names()))
        return
    compile_cache.enable()

    plan = None
    if args.straggle_p is not None:
        proto = PROTOCOLS[args.protocol]
        if not proto.supports_faults:
            ap.error(f"--straggle-p: protocol {args.protocol!r} has no "
                     f"fault injection")
        wl = workloads_mod.resolve(args.workload)
        iters = wl.iters if args.iters is None else args.iters
        # the SAME threshold protocol-side validation enforces
        thr = proto.fault_threshold(wl)
        plan = FaultPlan.random(wl.n_clients, iters, seed=args.fault_seed,
                                straggle_p=args.straggle_p,
                                min_available=thr)
        print(plan.describe(thr))

    res = fit(args.workload, args.protocol, args.engine, key=args.seed,
              iters=args.iters, history=not args.no_history, faults=plan)
    print(res.summary())
    if args.verbose and res.accuracy is not None:
        for t, a in enumerate(res.accuracy):
            print(f"  iter {t:3d}  accuracy {a:.3f}")


def serve_main(argv=None) -> None:
    """Train a triple, then serve its eval set from the secret-shared
    model (the repro-serve console script)."""
    import numpy as np

    ap = argparse.ArgumentParser(
        description="train a (workload, protocol, engine) triple, then "
                    "serve its eval set from the secret-shared model")
    ap.add_argument("workload", nargs="?", default="smoke",
                    help="registry name (default: smoke)")
    ap.add_argument("--protocol", default="copml",
                    choices=sorted(PROTOCOLS))
    ap.add_argument("--train-engine", default="jit", metavar="ENGINE",
                    help="engine for the training fit (default: jit)")
    ap.add_argument("--engine", default="jit",
                    help='serving engine: "eager" | "jit" | "sharded[:N]"')
    ap.add_argument("--iters", type=int, default=None,
                    help="GD iterations (default: the workload's)")
    ap.add_argument("--batch-size", type=int, default=32,
                    help="micro-batch window size (default: 32)")
    ap.add_argument("--window-ms", type=float, default=5.0,
                    help="micro-batch window in ms (default: 5)")
    ap.add_argument("--queries", type=int, default=None, metavar="Q",
                    help="serve only the first Q eval rows")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    res = fit(args.workload, args.protocol, args.train_engine,
              key=args.seed, iters=args.iters, history=False)
    print(res.summary())
    srv = serve(args.workload, res, args.engine, key=args.seed,
                batch_size=args.batch_size, window_ms=args.window_ms)
    wl = workloads_mod.resolve(args.workload)
    x, _ = wl.eval_set()
    if args.queries is not None:
        x = x[: args.queries]
    preds, _ = srv.serve(x)
    w = res.weights if res.weights.ndim > 1 else res.weights[:, None]
    open_preds = srv._decide(np.asarray(x, np.float64) @ w)
    if preds.dtype.kind == "f":      # regression: scores, not classes
        agree = float(np.isclose(preds, open_preds, atol=0.5).mean())
    else:
        agree = float((preds == open_preds).mean())
    print(srv.summary())
    print(f"agreement with opened-model scoring: {agree:.3f} "
          f"over {len(preds)} queries")


if __name__ == "__main__":
    main()
