"""Plain reference for COPML logistic regression at the paper's full m.

`references/copml.py` (the plain numpy reference over F_p, which imports
nothing of the program under test) with one change: the expected
trajectory E[X w_t] runs in weight space, z <- z - s X (X^T (ghat(z) - y)),
as float64 matrix-vector products.  The plain reference forms the int64
Gram matrix X X^T, about 2.5e11 integer multiply-adds at m = 9019, which
numpy cannot finish within a run; here each iteration takes two passes
over X.  The two agree to rounding (tests/bench/test_copml_full.py).
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "chipbench_reference_copml", Path(__file__).with_name("copml.py"))
plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plain)


class Reference(plain.Reference):
    """The configuration's fixed-point COPML on one data set, sized for
    m in the thousands."""

    def expected_z(self, iters: int) -> np.ndarray:
        """E[X w_t] at t = iters, in units of 2^-(lx+lw): the unrounded
        fixed-point iteration, X X^T applied as X (X^T .)."""
        if iters not in self._z_cache:
            x = self.xq.astype(np.float64)
            step = self.q_eta / float(1 << self.k1)
            done = max((i for i in self._z_cache if i <= iters), default=0)
            z = self._z_cache.get(done, np.zeros(self.m))
            for _ in range(iters - done):
                z = z - step * (x @ (x.T @ (self.c0 + self.c1 * z - self.yq)))
            self._z_cache[iters] = z
        return self._z_cache[iters]
