"""COPML chip benchmark: one cell, one run, one result line.

    python3 benchmarks/chip/run.py --workload cifar10_case1.steps \\
        --seed 12345 --seconds 20 --trace 0

Loads the cell named in BENCHMARK.json and its configuration, traffic mix,
driver and metric readers by name (catalog.py), makes the inputs from --seed,
warms up (set-up: import, device, inputs, the program's setup and first
call, all compilation), measures for --seconds, and then compares what the
timed path produced with the plain reference (check.py).  With --trace 0
it reports the cell's end-to-end metrics; with --trace 1 it profiles a few
calls instead and reports the cell's per-layer metrics.  The last line of
standard output is one JSON object; the compared numbers and their limits
are the last lines of standard error.  It refuses to run, and prints no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from benchmarks.chip import catalog, check, counts, data, xplane  # noqa: E402

CACHE_DIR = REPO / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def device_check(cell: dict):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r}); "
                     f"this benchmark never falls back to the CPU")
    if len(devices) < cell["chips"]:
        raise NoChip(f"{cell['name']} needs {cell['chips']} chips, JAX finds "
                     f"{len(devices)}")
    return devices


def configure_cache():
    """JAX's persistent cache at a fixed path inside the checkout, with
    every program in it: eager setup is many small programs, each under
    JAX's default one-second threshold."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Compiles:
    """Counts XLA compilations (each a compile or a persistent-cache load)
    from the moment it is made."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1


# ---------------------------------------------------------- the window

def closed_loop(drv, seconds: float, mix: dict) -> dict:
    """Calls one after another until `seconds` have passed: all the work
    and all the time.  A driver module's own `window` takes its place."""
    work, ends = 0, []
    t0 = time.perf_counter()
    while not ends or ends[-1] - t0 < seconds:
        work += drv.call()
        ends.append(time.perf_counter())
    took = [b - a for a, b in zip([t0] + ends, ends)]
    return {"window_s": ends[-1] - t0, "calls": len(took), "work": work,
            "durations": took}


def print_durations(took: list) -> None:
    took = sorted(took)
    quart = statistics.quantiles(took, n=4) if len(took) > 1 else took * 3
    print(f"window: {len(took)} calls, seconds per call: min {took[0]!r}, "
          f"quartiles {quart!r}, max {took[-1]!r}", file=sys.stderr)


def end_to_end(cat, cell, cfg, mix, window: dict) -> dict:
    ctx = SimpleNamespace(cfg=cfg, mix=mix, **window)
    return {m["name"]: {"value": cat.reader(m["name"]).read(ctx),
                        "unit": m["unit"]}
            for m in cat.metrics("end_to_end", cell)}


def traced_window(drv, n_calls: int, log_dir: str) -> dict:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    work = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench:window"):
            for _ in range(n_calls):
                with jax.profiler.TraceAnnotation("bench:call"):
                    work += drv.call()
    return {"work": work, "calls": n_calls}


# ------------------------------------------------------------ per-layer

def phases(loop_program: str):
    """(phase, cuts): what the host was doing at time t, for the idle
    breakdown, and the times at which that can change."""
    def calls(tr):
        return tr.spans("bench:call")

    def phase(tr, t):
        call = next(((s, e) for s, e in calls(tr) if s <= t < e), None)
        if call is None:
            return "between calls"
        loops = [(s, e) for s, e in loop_events(tr)
                 if call[0] <= s < call[1]]
        if not loops or t < loops[0][0]:
            return "call, before the loop program"
        if t < loops[-1][1]:
            return "call, during the loop program"
        return "call, after the loop program"

    def loop_events(tr):
        return xplane.program_events(tr, loop_program) if loop_program \
            else []

    def cuts(tr):
        return [t for span in calls(tr) + loop_events(tr) for t in span]
    return phase, cuts


def per_layer(cat, cell, cfg, mix, tr, traced, device_kind) -> tuple:
    ctx = SimpleNamespace(trace=tr, xplane=xplane, counts=counts, cfg=cfg,
                          mix=mix, work=traced["work"],
                          device_kind=device_kind)
    metrics = {}
    for m in cat.metrics("per_layer", cell):
        value = cat.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    phase, cuts = phases(mix.get("loop_program"))
    breakdown = {
        "device_ops": xplane.top_ops(tr),
        "idle_gaps": xplane.attributed_gaps(tr, phase, cuts(tr)),
    }
    return metrics, breakdown


# ---------------------------------------------------------------- a run

def run_cell(cat, cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    import jax
    import jax.numpy as jnp

    cfg = cat.config(cell["config"])
    mix = cat.traffic(cell["traffic"])
    driver = cat.driver(mix["driver"])
    reference = cat.reference(cfg["reference"])
    compiles = Compiles()

    t_inputs = time.perf_counter()
    x, y = data.dataset(cfg, seed)
    key = jnp.asarray(data.key_words(seed), jnp.uint32)
    drv = driver.Driver(mix, cfg, x, y, seed, key)
    t_warm = time.perf_counter()
    first = drv.warm_up()
    setup_s = time.perf_counter() - t_start
    print(f"set-up: {t_inputs - t_start!r} s to the inputs (imports, "
          f"device), {t_warm - t_inputs!r} s inputs, "
          f"{t_start + setup_s - t_warm!r} s warm-up (program setup, first "
          f"call, compiles: {compiles.count})", file=sys.stderr)
    mark = compiles.count

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            window = traced_window(drv, mix["traced_calls"], log_dir)
        else:
            window = getattr(driver, "window", closed_loop)(drv, seconds,
                                                            mix)
            print_durations(window["durations"])
        compiled = compiles.count - mark
        devices = jax.devices()
        used = devices[: cell["chips"]]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
        last = drv.last()
        tr = xplane.load(log_dir) if trace else None
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    del drv
    print(f"compilations inside the window: {compiled}", file=sys.stderr)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": window["calls"], "failed": 0}
    if trace:
        device["busy_s"] = xplane.device_busy_s(tr)
        device["window_s"] = xplane.window_s(tr)
        metrics, breakdown = per_layer(cat, cell, cfg, mix, tr, window,
                                       device["kind"])
    else:
        metrics = end_to_end(cat, cell, cfg, mix,
                             dict(window, setup_s=setup_s))
        breakdown = None

    ref = reference.Reference(cfg, x, y)
    values = check.numbers(ref, {"first": first, "last": last})
    ok, rows = check.judge(values, cfg["limits"])
    for name, value, limit in rows:
        print(f"check {name} = {value} (limit {limit})", file=sys.stderr)

    result.update(correct=ok, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = check.report(rows)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cat = catalog.Catalog.load()
        cell = cat.cell(args.workload)
        device_check(cell)
    except (KeyError, FileNotFoundError, ImportError, NoChip) as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2
    configure_cache()
    result = run_cell(cat, cell, args.seed, args.seconds, bool(args.trace),
                      T_START)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
