"""Stand-in for UMAP-reduced document embeddings: topics plus background.

Drawn once from ``layout_seed``: ``topics`` centres uniform in the box, a
Zipf(``zipf_s``) share of the documents per topic, and one Gaussian spread
per topic uniform in ``spread``.  The run's seed draws the documents:
``noise_share`` of them uniform over the box, the rest from the topics by
their shares.
"""

from __future__ import annotations

import numpy as np


def layout(params: dict, d: int):
    lo, hi = params["box"]
    rng = np.random.default_rng(params["layout_seed"])
    t = params["topics"]
    centers = rng.uniform(lo, hi, size=(t, d))
    share = np.arange(1, t + 1, dtype=np.float64) ** -params["zipf_s"]
    share = rng.permutation(share / share.sum())
    spread = rng.uniform(*params["spread"], size=t)
    return centers, share, spread


def generate(params: dict, n: int, d: int, seed) -> np.ndarray:
    """(n, d) float32 points; ``seed`` is an int or a numpy SeedSequence."""
    centers, share, spread = layout(params, d)
    lo, hi = params["box"]
    rng = np.random.default_rng(seed)
    n_noise = int(round(params["noise_share"] * n))
    topic = rng.choice(len(centers), size=n - n_noise, p=share)
    docs = centers[topic] + rng.normal(size=(len(topic), d)) * spread[topic, None]
    noise = rng.uniform(lo, hi, size=(n_noise, d))
    x = np.concatenate([docs, noise])
    return x[rng.permutation(n)].astype(np.float32)
