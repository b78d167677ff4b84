"""Decide `correct`: the timed path's outputs against the plain reference.

Each compared number has a limit from the configuration file:

  pred_gap.<call>  ||X w - E[X w_t]|| / ||E[X w_t]|| of the model the call
                   returned, opened from its shares (model encode, coded
                   gradient, share and decode, TruncPr);
  share_mismatch   model and X^T y shares off their degree-T polynomial;
  xtilde_mismatch  coded data X~ that does not decode to the data blocks;
  xty_mismatch     X^T y shares that do not open to the data's X^T y;
  open_mismatch    entries of the program's opened model that differ from
                   the opening of its shares (fit cells).

The counts are exact whatever the protocol's randomness, and their limit
is 0.  The first call's outputs come from set-up, the last call's from the
end of the window.
"""

from __future__ import annotations

import math

import numpy as np


def numbers(ref, calls: dict) -> dict:
    """{name: value} for the outputs of each labelled call."""
    out = {}
    shares = xtilde = xty = opened = 0
    has_setup = has_weights = False
    for label, o in calls.items():
        w = ref.open_shares(o["w_shares"])
        out[f"pred_gap.{label}"] = ref.pred_gap(w, o["iters"])
        shares += ref.share_mismatch(o["w_shares"])
        if "coded_x" in o:
            has_setup = True
            xtilde += ref.xtilde_mismatch(o["coded_x"])
            xty += ref.xty_mismatch(o["xty_shares"])
            shares += ref.share_mismatch(o["xty_shares"])
        if "weights" in o:
            has_weights = True
            want = w.astype(np.float64) / float(1 << ref.lw)
            opened += int(np.count_nonzero(
                np.asarray(o["weights"], np.float64) != want))
    out["share_mismatch"] = shares
    if has_setup:
        out["xtilde_mismatch"] = xtilde
        out["xty_mismatch"] = xty
    if has_weights:
        out["open_mismatch"] = opened
    return out


def judge(values: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]): every number within its limit."""
    rows = []
    for name, value in values.items():
        limit = limits[name.split(".")[0]]
        rows.append((name, value, limit))
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows


def report(rows) -> dict:
    return {name: {"value": value, "limit": limit}
            for name, value, limit in rows}
