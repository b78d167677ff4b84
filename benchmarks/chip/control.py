"""The comparison's control and planted faults, at a configuration's size.

    python3 benchmarks/chip/control.py --config cifar10_case1 \\
        --seeds 11,12,13

For each seed it puts the plain reference protocol (references/<name>.py,
`Reference.protocol`) in the program's place and reads the numbers that
decide `correct` (check.py), four ways:

  sound      the reference protocol in exact int64 arithmetic;
  control    the same with its field products in float32, the nearest
             precision below the 26-bit field the configuration states;
  unchanged  every iteration returns the model unchanged;
  half       each coded block drops half its rows, the update takes the
             mean over the rest.

It prints one JSON line per seed and then a summary: for each number its
largest sound reading and the smallest reading of the control and of each
fault, and whether each failed the configuration's limits.  It needs no
device; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmarks.chip import catalog, check, data  # noqa: E402

WAYS = {
    "sound": dict(dtype=np.int64, fault=None),
    "control": dict(dtype=np.float32, fault=None),
    "unchanged": dict(dtype=np.int64, fault="unchanged"),
    "half": dict(dtype=np.int64, fault="half"),
}


def readings(cat, config: str, seed: int, iters: int) -> dict:
    """{way: (correct, {number: value})} for one seed."""
    cfg = cat.config(config)
    x, y = data.dataset(cfg, seed)
    ref = cat.reference(cfg["reference"]).Reference(cfg, x, y)
    out = {}
    for way, kw in WAYS.items():
        o = ref.protocol(data.rng(seed, 2), iters, **kw)
        o["iters"] = iters
        values = check.numbers(ref, {"first": o})
        ok, _ = check.judge(values, cfg["limits"])
        out[way] = (ok, values)
    return out


def summary(per_seed: list) -> dict:
    """Largest sound and smallest other reading of each number."""
    out = {}
    for way in WAYS:
        rows = [r[way][1] for r in per_seed]
        pick = max if way == "sound" else min
        out[way] = {"failed_runs": sum(not r[way][0] for r in per_seed),
                    "runs": len(per_seed),
                    **{k: pick(r[k] for r in rows) for k in rows[0]}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    args = ap.parse_args(argv)
    cat = catalog.Catalog.load()
    iters = cat.config(args.config)["iters_per_model"]
    per_seed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(cat, args.config, seed, iters)
        per_seed.append(r)
        print(json.dumps({"seed": seed, **{w: {"correct": ok, **v}
                                           for w, (ok, v) in r.items()}}))
    print(json.dumps({"config": args.config, "iters": iters,
                      "summary": summary(per_seed)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
