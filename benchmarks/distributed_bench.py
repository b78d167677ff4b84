"""Distributed stage: sharded-vs-single-device COPML wall time.

Multiple devices require XLA_FLAGS=--xla_force_host_platform_device_count
to be set BEFORE jax initializes, so the measurement runs in a fresh
subprocess (launch/copml_dist.py --bench) and its CSV rows are relayed to
the harness.  The child is pinned to the CPU (JAX_PLATFORMS=cpu) and its
rows say so: on one CPU host the virtual devices share physical cores:
the numbers record collective/protocol overhead (and any XLA thread-level
parallelism), not real multi-chip scaling -- see docs/ARCHITECTURE.md,
"Modeled vs measured communication".
"""

from __future__ import annotations

import os
import subprocess
import sys

DEVICES = 8


def run(report) -> None:
    env = dict(os.environ)
    # pinned to the CPU: the virtual devices below are host devices, and
    # on an accelerator host the parent may already hold the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={DEVICES} "
                        + env.get("REPRO_EXTRA_XLA_FLAGS", ""))
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.copml_dist", "--bench",
         "--devices", str(DEVICES), "--clients", "16", "--iters", "5",
         "--m", "832", "--d", "64"],
        capture_output=True, text=True, env=env, timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(f"copml_dist --bench failed:\n{out.stderr[-2000:]}")
    seen = 0
    for line in out.stdout.splitlines():
        if line.startswith("copml_dist/"):
            name, us, derived = line.split(",", 2)
            engine = f"sharded:{DEVICES}" if "sharded" in name else "jit"
            report(name, float(us), f"{derived};platform=cpu",
                   engine=engine)
            seen += 1
    assert seen >= 2, f"expected bench rows, got stdout:\n{out.stdout[-800:]}"
