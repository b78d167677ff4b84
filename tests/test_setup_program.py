"""Copml.setup as one compiled program: the same bits as its body run op by
op, and one executable per workload that every instance of it shares."""

import jax
import numpy as np
import pytest

from repro import api
from repro.core import protocol

STATE = ("coded_x", "xty_shares", "w_shares")


def new_proto(wl) -> protocol.Copml:
    return protocol.Copml(wl.cfg, wl.m, wl.d, wl.objective)


# smoke: binary, T = 1; cifar10_like: Case 2 at N = 15, T = 2;
# mnist10_like: the (d, 10) multiclass model
@pytest.mark.parametrize("name", ["smoke", "cifar10_like", "mnist10_like"])
def test_compiled_setup_matches_op_by_op(name):
    wl = api.get_workload(name)
    cx, cy = wl.client_data()
    key = jax.random.PRNGKey(3)
    compiled = new_proto(wl).setup(key, cx, cy)
    with jax.disable_jit():
        eager = new_proto(wl).setup(key, cx, cy)
    for part in STATE:
        got, want = getattr(compiled, part), getattr(eager, part)
        assert got.shape == want.shape, part
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=part)
    assert int(compiled.step) == 0


def test_a_new_instance_reuses_the_setup_executable(monkeypatch):
    wl = api.get_workload("smoke")
    cx, cy = wl.client_data()
    traces = []
    body = protocol.Copml._setup_phases

    def counted(self, *args):
        traces.append(self)
        return body(self, *args)

    monkeypatch.setattr(protocol.Copml, "_setup_phases", counted)
    new_proto(wl).setup(jax.random.PRNGKey(0), cx, cy)
    assert len(traces) <= 1               # 0 if an earlier test compiled it
    first = len(traces)
    again = new_proto(wl).setup(jax.random.PRNGKey(1), cx, cy)
    assert len(traces) == first
    assert again.coded_x.shape == (wl.cfg.n_clients, wl.m // wl.cfg.k, wl.d)
