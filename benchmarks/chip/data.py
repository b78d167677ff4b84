"""Inputs made from the seed: the parties' rows and the protocol's key.

Every seed gives the same sizes: m rows of d features in [-1, 1] with
binary labels, two Gaussian classes around a planted separator (the
CIFAR-10-shaped stand-in of the paper's Section V-A), and one PRNG key.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    """A numpy generator for any whole seed, negative or above 2^63."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def dataset(cfg: dict, seed: int) -> tuple:
    """(x (m, d) float32, y (m,) float32 in {0, 1})."""
    m, d, margin = cfg["m"], cfg["d"], cfg["data"]["margin"]
    g = rng(seed)
    w_star = g.normal(size=d) / np.sqrt(d)
    x = np.clip(g.normal(size=(m, d)) * 0.5, -1.0, 1.0)
    p = 1.0 / (1.0 + np.exp(-(x @ w_star) * margin * np.sqrt(d)))
    y = (p > g.uniform(size=m)).astype(np.float32)
    return x.astype(np.float32), y


def key_words(seed: int) -> np.ndarray:
    """The two uint32 words of the run's PRNG key."""
    return rng(seed, 1).integers(0, 1 << 32, size=2, dtype=np.uint32)
