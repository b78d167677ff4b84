"""`correct` separates sound runs from the control and from faults.

The control and the planted faults of the reference run at a tiny size
here; benchmarks/chip/control.py reads them at each configuration's own
size.  The program's own faults are planted under the timed path and
driven through the harness (run.run_cell) on the CPU, past its look for a
chip.  The cells run on one chip, so no exchange between chips exists to
be left out.
"""

import dataclasses
import time

import jax.numpy as jnp
import pytest

from benchmarks.chip import control, counts, run
from repro.core.protocol import Copml


@pytest.fixture(autouse=True)
def _cpu_peaks(monkeypatch):
    monkeypatch.setitem(counts.PEAKS, "cpu", counts.PEAKS["TPU v5 lite"])


@pytest.mark.parametrize("seed", [3, 2**31 + 7, 2**40 + 1])
def test_control_and_reference_faults_fail(tiny_catalog, seed):
    r = control.readings(tiny_catalog, "tiny", seed, 50)
    assert r["sound"][0], r["sound"]
    for way in ("control", "unchanged", "half"):
        assert not r[way][0], (way, r[way])
    assert r["unchanged"][1]["pred_gap.first"] == 1.0


def _run(cat, cell, seed, trace=False):
    return run.run_cell(cat, cat.cell(cell), seed, 0.3, trace,
                        time.perf_counter())


def _unchanged(self, key, state, *args, **kwargs):
    return state


def _half_batch(orig):
    def fused(self, key, state, coded_w, *args, **kwargs):
        mk = state.coded_x.shape[1]
        half = dataclasses.replace(
            state, coded_x=state.coded_x.at[:, mk // 2:].set(0))
        q_eta, self.q_eta = self.q_eta, 2 * self.q_eta
        try:
            out = orig(self, key, half, coded_w, *args, **kwargs)
        finally:
            self.q_eta = q_eta
        return dataclasses.replace(out, coded_x=state.coded_x)
    return fused


def _altered(orig):
    def iteration(self, key, state, subset=None, **kwargs):
        adv = jnp.zeros(self.cfg.n_clients, bool).at[0].set(True)
        return orig(self, key, state, subset, adv=adv)
    return iteration


def _coded_x_corrupted(orig):
    def setup(self, *args, **kwargs):
        state = orig(self, *args, **kwargs)
        return dataclasses.replace(
            state, coded_x=state.coded_x.at[0, 0, 0].add(1))
    return setup


FAULTS = {
    "unchanged": ("iteration", lambda orig: _unchanged),
    "half_batch": ("_fused_iteration", _half_batch),
    "answer_altered": ("iteration", _altered),
    "coded_x_corrupted": ("setup", _coded_x_corrupted),
}


@pytest.mark.parametrize("cell,trace,seed", [
    ("tiny.steps", False, 101), ("tiny.steps", True, 102),
    ("tiny.fit", False, 103)])
def test_sound_program_is_correct(tiny_catalog, cell, trace, seed):
    res = _run(tiny_catalog, cell, seed, trace)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"
    key = "busy_s" if trace else "setup_s"
    assert key in (res["device"] if trace else res["metrics"])


@pytest.mark.parametrize("cell", ["tiny.steps", "tiny.fit"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_program_fault_is_not_correct(tiny_catalog, monkeypatch,
                                              fault, cell):
    attr, make = FAULTS[fault]
    monkeypatch.setattr(Copml, attr, make(getattr(Copml, attr)))
    seed = 200 + 10 * sorted(FAULTS).index(fault) + cell.endswith(".fit")
    res = _run(tiny_catalog, cell, seed)
    assert not res["correct"], res["checks"]
    if fault == "coded_x_corrupted":
        assert res["checks"]["xtilde_mismatch"]["value"] > 0
