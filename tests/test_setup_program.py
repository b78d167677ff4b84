"""Copml.setup as one compiled program: the same bits as its body run op by
op, one executable per workload that every instance of it shares, and the
same bits whatever the number of chunks its encode streams over."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import api
from repro.configs import copml_logreg
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


def setup_inputs(wl):
    """What Copml.setup hands `_setup_program`: stacked rows, targets."""
    x, y, _, _ = wl.data()
    targets = wl.objective.prepare_targets(np.asarray(y))
    return np.asarray(x), np.asarray(targets, np.float32)


# 2 and 3 chunks of m/K rows: smoke 24 = 2 x 12 = 3 x 8; cifar10_like
# 160 = 2 x 80, but 3 chunks of 54 rows pad it to 162; mnist10_like has
# m = 390 rows in K = 4 blocks of 98 (two rows of padding), 3 x 33 = 99
@pytest.mark.parametrize("chunks", [2, 3])
@pytest.mark.parametrize("name", ["smoke", "cifar10_like", "mnist10_like"])
def test_streamed_setup_matches_one_chunk(name, chunks):
    wl = api.get_workload(name)
    x, targets = setup_inputs(wl)
    key = jax.random.PRNGKey(5)
    args = (wl.cfg, wl.objective, wl.m, wl.d, key, x, targets)
    assert protocol.setup_chunks(wl.n_clients, -(-wl.m // wl.cfg.k),
                                 wl.d) == 1
    whole = protocol._setup_program(*args)
    streamed = protocol._setup_program(*args, _chunks=chunks)
    for part in STATE:
        np.testing.assert_array_equal(np.asarray(getattr(streamed, part)),
                                      np.asarray(getattr(whole, part)),
                                      err_msg=part)


def bench_config(name: str) -> dict:
    path = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / \
        "configs" / f"{name}.json"
    return json.loads(path.read_text())


REDUCED = sorted(set(api.workload_names()) - set(copml_logreg.WORKLOADS))


@pytest.mark.parametrize("name", REDUCED + ["cifar10_case1", "cifar10_case2"])
def test_one_chunk_for_reduced_workloads_and_bench_configs(name):
    if name in REDUCED:
        wl = api.get_workload(name)
        n, k, m, d = wl.n_clients, wl.cfg.k, wl.m, wl.d
    else:
        cfg = bench_config(name)
        n, k, m, d = cfg["n_clients"], cfg["k"], cfg["m"], cfg["d"]
    assert protocol.setup_chunks(n, -(-m // k), d) == 1


def test_the_paper_m_streams_within_the_budget():
    wl = api.get_workload("cifar10_case2")
    n, mk, d = wl.n_clients, -(-wl.m // wl.cfg.k), wl.d
    assert (wl.m, mk, d) == (9019, 902, 3073)
    chunks = protocol.setup_chunks(n, mk, d)
    rows = -(-mk // chunks)
    assert chunks > 1
    assert n * n * rows * d * 4 <= protocol.SETUP_ENCODE_CHUNK_BYTES
    # the fewest chunks: one fewer would overrun the budget
    assert n * n * -(-mk // (chunks - 1)) * d * 4 > \
        protocol.SETUP_ENCODE_CHUNK_BYTES
