"""The full-m reference agrees with the plain reference it extends: the
same exact checks, and the same expected trajectory to rounding, on the
tiny configuration and on one with more rows than features."""

import numpy as np
import pytest

from benchmarks.chip import data


@pytest.mark.parametrize("shape", [{}, {"m": 300, "d": 40}],
                         ids=["tiny", "tall"])
@pytest.mark.parametrize("seed", [7, 2**33 + 5])
def test_full_reference_matches_the_plain_one(tiny_catalog, shape, seed):
    cfg = dict(tiny_catalog.config("tiny"), **shape)
    x, y = data.dataset(cfg, seed)
    plain = tiny_catalog.reference("copml").Reference(cfg, x, y)
    full = tiny_catalog.reference("copml_full").Reference(cfg, x, y)
    out = plain.protocol(data.rng(seed, 2), cfg["iters_per_model"])
    bad_x = out["coded_x"].copy()
    bad_x[0, 0, :3] += 1
    bad_w = out["w_shares"].copy()
    bad_w[-1, :2] += 1
    for coded in (out["coded_x"], bad_x):
        assert full.xtilde_mismatch(coded) == plain.xtilde_mismatch(coded)
    assert full.xtilde_mismatch(bad_x) > 0
    np.testing.assert_array_equal(full.xty(), plain.xty())
    for shares in (out["w_shares"], out["xty_shares"], bad_w):
        assert full.share_mismatch(shares) == plain.share_mismatch(shares)
    assert full.share_mismatch(bad_w) > 0
    assert full.xty_mismatch(out["xty_shares"]) == 0
    for iters in (1, 17, cfg["iters_per_model"]):
        want = plain.expected_z(iters)
        np.testing.assert_allclose(full.expected_z(iters), want, rtol=0,
                                   atol=1e-9 * np.linalg.norm(want))
    w = plain.open_shares(out["w_shares"])
    assert full.pred_gap(w, cfg["iters_per_model"]) == pytest.approx(
        plain.pred_gap(w, cfg["iters_per_model"]), rel=1e-9)
