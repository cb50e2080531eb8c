"""Gaussian blobs as sklearn's ``make_blobs`` draws them.

The centres are drawn once from ``layout_seed`` uniformly in the centre box;
``n`` is split evenly over them (the remainder to the first ones); each point
is its centre plus isotropic normal noise of ``cluster_std``.  The run's
seed draws only the noise, so every seed clusters the same layout.
"""

from __future__ import annotations

import numpy as np


def layout(params: dict, d: int) -> np.ndarray:
    lo, hi = params["center_box"]
    rng = np.random.default_rng(params["layout_seed"])
    return rng.uniform(lo, hi, size=(params["centers"], d))


def generate(params: dict, n: int, d: int, seed) -> np.ndarray:
    """(n, d) float32 points; ``seed`` is an int or a numpy SeedSequence."""
    centers = layout(params, d)
    k = len(centers)
    per = np.full(k, n // k)
    per[: n % k] += 1
    rng = np.random.default_rng(seed)
    x = np.repeat(centers, per, axis=0) + rng.normal(0.0, params["cluster_std"], (n, d))
    return x.astype(np.float32)
