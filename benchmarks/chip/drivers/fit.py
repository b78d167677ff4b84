"""A closed loop of whole trainings, `api.fit(..., engine, iters)`: eager
setup (Phases 1-2), the loop, and opening the model.  Each fit takes a
key folded from the seed; set-up is the first fit."""

import jax

from benchmarks.chip import program
from repro import api


class Driver:
    def __init__(self, mix: dict, cfg: dict, x, y, seed: int, key):
        self.wl = program.workload(cfg, x, y, seed)
        self.mix, self.key, self.calls = mix, key, 0

    def warm_up(self) -> dict:
        self.call()
        return self.last()

    def call(self) -> int:
        self.res = api.fit(self.wl, "copml", self.mix["engine"],
                           key=jax.random.fold_in(self.key, self.calls),
                           iters=self.mix["iters"], history=False)
        self.calls += 1
        return 1

    def last(self) -> dict:
        return program.outputs(self.res.state, self.mix["iters"], setup=True,
                               weights=self.res.weights)
