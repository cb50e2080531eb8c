"""Quickstart: one hundred hierarchies for the cost of ~two (paper headline).

Builds a clustered dataset, fits the `MultiHDBSCAN` estimator once, compares
against the optimized rerun baseline, and verifies the hierarchies agree.

  PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

import numpy as np

from repro.api import MultiHDBSCAN
from repro.core import multi


def main():
    rng = np.random.default_rng(0)
    centers = rng.uniform(-10, 10, size=(8, 8))
    x = np.concatenate(
        [rng.normal(c, 1.0, size=(500, 8)) for c in centers]
    ).astype(np.float32)
    kmax = 32
    print(f"dataset: n={len(x)}, d={x.shape[1]}, mpts range [2, {kmax}]")

    t0 = time.monotonic()
    est = MultiHDBSCAN(kmax=kmax).fit(x)
    profile = est.mpts_profile()  # forces extraction of the whole range
    t_multi = time.monotonic() - t0
    print(f"\nMultiHDBSCAN: {len(profile)} hierarchies in {t_multi:.2f}s")
    print(f"  graph edges: {est.n_graph_edges_:,} "
          f"(complete graph: {len(x)*(len(x)-1)//2:,})")
    print("  fit timings:", {k: round(est.timings_[k], 2) for k in multi.FIT_STAGES})

    t0 = time.monotonic()
    base, _ = multi.hdbscan_baseline(x, [kmax])
    t_one = time.monotonic() - t0
    print(f"\nbaseline, ONE hierarchy (mpts={kmax}): {t_one:.2f}s")
    print(f"=> {len(profile)} hierarchies for "
          f"{t_multi / t_one:.1f}x the cost of one (paper: ~2x at kmax=128)")

    _, _, w = est.mst_for(kmax)
    np.testing.assert_allclose(
        np.sort(w), np.sort(base[0].mst_w), rtol=1e-5, atol=1e-6
    )
    print("\nMST weight multisets agree with the baseline — hierarchies are exact.")

    print("\nclusters per mpts (sampled):")
    for row in profile[:: max(1, len(profile) // 8)]:
        print(f"  mpts={row['mpts']:3d}: {row['n_clusters']:3d} clusters, "
              f"{row['n_noise']:4d} noise pts")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
