"""Where the benchmark reaches into the program, and nowhere else.

The drivers (drivers/<name>.py) hand the program only inputs made from
the seed (data.py), through `workload`, `program_loop` and `api.fit`, and
keep what the comparison needs with `outputs`.  A refactor of the engine
that moves `_scan_iterations` or `Copml.setup` is repaired here.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.api.workloads import Workload
from repro.core import protocol
from repro.core.protocol import CopmlConfig


@dataclasses.dataclass(frozen=True)
class _BenchWorkload(Workload):
    """A workload whose rows are the benchmark's, not the registry's."""
    inputs: tuple = dataclasses.field(default=(), hash=False, compare=False,
                                      repr=False)

    def data(self):
        return self.inputs


def workload(cfg: dict, x, y, seed: int) -> Workload:
    keys = ("n_clients", "k", "t", "r", "eta", "lx", "lw", "cb", "k2",
            "mag_bits", "sigmoid_bound", "mpc_mul")
    copml = CopmlConfig(**{k: cfg[k] for k in keys})
    return _BenchWorkload(name=f"bench.{cfg['name']}", m=cfg["m"],
                          d=cfg["d"], cfg=copml, seed=seed,
                          iters=cfg["iters_per_model"],
                          inputs=(x, y, None, None))


def program_loop(proto, key, state, iters: int):
    """One call of the compiled loop api.fit's jit engine runs."""
    state, _ = protocol._scan_iterations(proto, key, state, iters, None,
                                         False, None)
    return state


def outputs(state, iters: int, setup: bool, weights=None) -> dict:
    """What check.numbers compares: the model shares after `iters`
    iterations, with setup's X~ and X^T y shares and the opened weights
    where the call produced them."""
    out = {"iters": iters, "w_shares": np.asarray(state.w_shares)}
    if setup:
        out["coded_x"] = np.asarray(state.coded_x)
        out["xty_shares"] = np.asarray(state.xty_shares)
    if weights is not None:
        out["weights"] = np.asarray(weights)
    return out
