"""The step's operation and byte count, and the peaks table."""

import pytest

from benchmarks.chip import counts
from repro.core.protocol import Copml, CopmlConfig


def test_step_macs_equal_a_hand_count():
    # N=3, K=2, T=1, m/K=2, d=5
    got = counts.step_macs(3, 2, 1, 2, 5)
    assert got == {"encode": 3 * 3 * 3 * 5, "reconstruct": 45,
                   "gradient": 2 * 3 * 2 * 5, "mix": 45, "decode": 45,
                   "truncate": 2 * 3 * 1 * 5, "total": 360}


def test_cifar10_counts_match_the_paper_shapes():
    c1 = counts.step_macs(50, 16, 1, 8, 3073)
    c2 = counts.step_macs(50, 10, 7, 8, 3073)
    assert c1["encode"] == 130_602_500 and c1["gradient"] == 2_458_400
    assert c2["mix"] == 7 * 50 * 50 * 3073
    assert c1["encode"] / c1["total"] > 0.8            # T=1: encode rules
    assert 0.25 < c2["mix"] / c2["total"] < 0.3        # T=7: mix grows
    assert counts.step_bytes(50, 8, 3073) == (50 * 8 * 3073 + 3 * 50 * 3073) \
        * 26 / 8


@pytest.mark.parametrize("mode", ["0", "1", "kernel"])
def test_count_does_not_depend_on_the_step_implementation(monkeypatch, mode):
    monkeypatch.setenv("REPRO_FUSED_STEP", mode)
    proto = Copml(CopmlConfig(n_clients=13, k=4, t=1), 96, 12)
    assert proto.fused_mode == mode
    cfg = {"n_clients": proto.cfg.n_clients, "k": proto.cfg.k,
           "t": proto.cfg.t, "m": proto.m, "d": proto.d}
    assert counts.least_step_s(cfg, "TPU v5 lite") == counts.least_step_s(
        {"n_clients": 13, "k": 4, "t": 1, "m": 96, "d": 12}, "TPU v5 lite")


def test_least_time_is_the_larger_bound():
    cfg = {"n_clients": 50, "k": 16, "t": 1, "m": 128, "d": 3073}
    least, bound = counts.least_step_s(cfg, "TPU v5 lite")
    macs = counts.step_macs(50, 16, 1, 8, 3073)["total"]
    assert bound == "bytes"
    assert least == counts.step_bytes(50, 8, 3073) / 819e9
    assert least > 2 * macs / 393e12


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        counts.peaks("TPU v9 imaginary")
