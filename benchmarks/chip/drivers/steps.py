"""The compiled training loop api.fit's jit engine runs (`_scan_iterations`),
one call of `iters_per_call` iterations after another, each continuing
from the state the last returned.  Set-up runs `Copml.setup` through the
protocol's cached driver, where api.fit's own path starts, and the first
call."""

import jax

from benchmarks.chip import program
from repro import api


class Driver:
    def __init__(self, mix: dict, cfg: dict, x, y, seed: int, key):
        self.wl = program.workload(cfg, x, y, seed)
        self.iters = mix["iters_per_call"]
        self.key_setup, self.key_loop = jax.random.split(key)
        self.calls = 0

    def warm_up(self) -> dict:
        """Setup and the first call; returns the first call's outputs."""
        self.proto = api.PROTOCOLS["copml"].driver(self.wl)
        xs, ys = self.wl.client_data()
        state = self.proto.setup(self.key_setup, xs, ys)
        self.state = jax.block_until_ready(state)
        self.call()
        return program.outputs(self.state, self.iters, setup=True)

    def call(self) -> int:
        key = jax.random.fold_in(self.key_loop, self.calls)
        self.state = jax.block_until_ready(
            program.program_loop(self.proto, key, self.state, self.iters))
        self.calls += 1
        return self.iters

    def last(self) -> dict:
        return program.outputs(self.state, self.calls * self.iters,
                               setup=False)
