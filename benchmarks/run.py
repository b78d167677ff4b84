"""Benchmark harness: one registered stage per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--stage fig3,fig4,...]
    PYTHONPATH=src python -m benchmarks.run --list
    PYTHONPATH=src python -m benchmarks.run --stage engine --json
    PYTHONPATH=src python -m benchmarks.run --stage engine --json out.json

Stages come from the STAGES registry (no hand-wired if/elif); each
measurement row records the (workload, protocol, engine) run triple from
the repro.api axes -- stages give a default triple, individual rows may
override.  Output is ``name,us_per_call,derived`` CSV on stdout plus,
with --json, machine-readable trajectory files: one ``BENCH_<stage>.json``
per executed stage (stage, default triple, rows with wall us_per_call and
the derived strings carrying modeled comm/comp where the stage models
them) written into the given directory (default ``.``) -- the per-PR
artifact future sessions diff for perf regressions.  Passing a path
ending in ``.json`` instead writes the legacy combined dump.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import traceback
from typing import Callable


@dataclasses.dataclass(frozen=True)
class Stage:
    """One registered benchmark stage.

    run(report, ctx): `report(name, us, derived, *, workload=, protocol=,
    engine=)` records a row (triple kwargs default to the stage's);
    `ctx` is a shared dict for cross-stage products (the kernel stage
    publishes the measured field MAC/s for the modeled stages)."""
    key: str
    run: Callable
    triple: tuple            # default (workload, protocol, engine) for rows
    doc: str


def build_stages() -> dict:
    """The stage registry, in execution order (kernel feeds fig3/table1)."""
    from . import (analysis_bench, distributed_bench, fig3_speedup,
                   fig4_accuracy, kernel_micro, multiclass_bench,
                   procnet_bench, resilience_bench, roofline_report,
                   serving_bench, table1_breakdown, table2_complexity)

    def kernel(report, ctx):
        ctx["field_macs_per_s"] = kernel_micro.run(report)

    stages = [
        Stage("kernel_micro", kernel, ("synthetic", "-", "jit"),
              "field/kernel microbenchmarks (incl. fused step vs "
              "phase-siloed); calibrates field MAC/s"),
        Stage("engine", lambda report, ctx: kernel_micro.run_engine(report),
              ("engine_micro", "copml", "-"),
              "api.fit engine comparison: eager vs jit scan"),
        Stage("distributed",
              lambda report, ctx: distributed_bench.run(report),
              ("copml_dist_cli", "copml", "sharded:8"),
              "mesh-sharded vs single-device wall time (subprocess)"),
        Stage("resilience",
              lambda report, ctx: resilience_bench.run(report),
              ("smoke_straggler", "copml", "jit"),
              "wall time under FaultPlan churn vs fault-free baseline"),
        Stage("procnet",
              lambda report, ctx: procnet_bench.run(report),
              ("smoke", "copml", "proc:4"),
              "multi-process socket runtime: measured wire bytes + wall"),
        Stage("analysis",
              lambda report, ctx: analysis_bench.run(report),
              ("src/repro", "-", "static"),
              "seclint+commlint static-analysis gate wall time"),
        Stage("multiclass",
              lambda report, ctx: multiclass_bench.run(report),
              ("mnist10_like", "copml", "jit"),
              "encode-once C-class training vs C sequential binary fits"),
        Stage("serving",
              lambda report, ctx: serving_bench.run(report),
              ("smoke", "copml", "jit"),
              "secure serving: queries/sec vs micro-batch size per engine"),
        Stage("fig4", lambda report, ctx: fig4_accuracy.run(report),
              ("fig4", "copml", "jit"),
              "accuracy parity vs plaintext (paper Fig. 4)"),
        Stage("fig3",
              lambda report, ctx: fig3_speedup.run(
                  report, ctx.get("field_macs_per_s")),
              ("paper_scale", "copml", "modeled"),
              "training-time speedup vs MPC baselines (paper Fig. 3)"),
        Stage("table1",
              lambda report, ctx: table1_breakdown.run(
                  report, ctx.get("field_macs_per_s")),
              ("cifar10_paper", "copml", "modeled"),
              "comm/comp/enc breakdown at N=50 (paper Table I)"),
        Stage("table2", lambda report, ctx: table2_complexity.run(report),
              ("table2", "copml", "jit"),
              "measured cost scaling vs complexity claims (paper Table II)"),
        Stage("roofline", lambda report, ctx: roofline_report.run(report),
              ("-", "-", "-"),
              "compiled-program roofline report"),
    ]
    return {s.key: s for s in stages}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--stage", "--only", dest="stage", default=None,
                    help="comma-separated subset of registered stages "
                         "(--only kept as an alias)")
    ap.add_argument("--json", nargs="?", const=".", default=None,
                    metavar="DIR_OR_PATH",
                    help="write machine-readable results: one "
                         "BENCH_<stage>.json per executed stage into the "
                         "given directory (default '.'); a path ending in "
                         ".json writes the legacy combined dump instead")
    ap.add_argument("--list", action="store_true",
                    help="print the stage registry and exit")
    args = ap.parse_args(argv)
    from repro.api import compile_cache
    compile_cache.enable()

    stages = build_stages()
    if args.list:
        for s in stages.values():
            print(f"{s.key:12s} {s.doc}")
        return
    selected = None
    if args.stage:
        selected = set(args.stage.split(","))
        unknown = selected - set(stages)
        if unknown:
            ap.error(f"unknown stage(s) {sorted(unknown)}; "
                     f"registered: {sorted(stages)}")

    rows: list = []
    failures: list = []
    ctx: dict = {}
    print("name,us_per_call,derived")

    def make_report(stage: Stage):
        def report(name: str, us_per_call: float, derived: str = "", *,
                   workload=None, protocol=None, engine=None):
            w, p, e = stage.triple
            rows.append({
                "stage": stage.key, "name": name,
                "us_per_call": float(us_per_call), "derived": derived,
                "workload": workload or w, "protocol": protocol or p,
                "engine": engine or e,
            })
            print(f"{name},{us_per_call:.1f},{derived}", flush=True)
        return report

    for stage in stages.values():
        if selected and stage.key not in selected:
            continue
        try:
            stage.run(make_report(stage), ctx)
        except Exception as e:  # noqa: BLE001
            failures.append((stage.key, repr(e)))
            traceback.print_exc()

    if args.json:
        write_json(args.json, rows, failures, stages)

    if failures:
        print(f"{len(failures)} benchmark stages failed", file=sys.stderr)
        sys.exit(1)


def write_json(target: str, rows: list, failures: list,
               stages: dict) -> list:
    """Persist benchmark rows as JSON; returns the file paths written.

    target ending in '.json': one legacy combined dump.  Otherwise target
    is a directory receiving one BENCH_<stage>.json trajectory file per
    stage that produced rows (or failed) -- stable names so successive PRs
    can diff the same stage's numbers."""
    if target.endswith(".json"):
        with open(target, "w") as f:
            json.dump({"rows": rows,
                       "failures": [list(f_) for f_ in failures]}, f,
                      indent=1)
        return [target]
    os.makedirs(target, exist_ok=True)
    paths = []
    failed = {k: msg for k, msg in failures}
    for key in sorted({r["stage"] for r in rows} | set(failed)):
        path = os.path.join(target, f"BENCH_{key}.json")
        with open(path, "w") as f:
            json.dump({
                "stage": key,
                "triple": list(stages[key].triple),
                "rows": [r for r in rows if r["stage"] == key],
                "failure": failed.get(key),
            }, f, indent=1)
        paths.append(path)
    return paths


if __name__ == "__main__":
    main()
