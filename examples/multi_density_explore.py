"""Multi-density exploration with the `MultiHDBSCAN` estimator.

Fits once, then walks the whole mpts range interactively-cheap: which density
level reveals which cluster structure (paper §I motivation), scored with the
per-level stability summary.  `--sweep` additionally reproduces the paper
Table II / Fig 7 runtime harness.

  PYTHONPATH=src python examples/multi_density_explore.py [--sweep] [--full]
"""

import argparse
import os
import sys

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

import numpy as np

from repro.api import MultiHDBSCAN
from repro.core import multi


def explore(n: int, kmax: int):
    rng = np.random.default_rng(7)
    # structure at two density scales: tight twins + one diffuse blob + noise
    x = np.concatenate([
        rng.normal((0, 0), 0.25, size=(n // 4, 2)),
        rng.normal((1.6, 0), 0.25, size=(n // 4, 2)),
        rng.normal((8, 6), 1.4, size=(n // 3, 2)),
        rng.uniform(-4, 12, size=(n - n // 4 * 2 - n // 3, 2)),
    ]).astype(np.float32)

    est = MultiHDBSCAN(kmax=kmax).fit(x)
    print(f"fitted n={len(x)} in "
          f"{sum(est.timings_[k] for k in multi.FIT_STAGES):.2f}s "
          f"(mpts range [2, {kmax}] from ONE graph)\n")

    print(f"{'mpts':>5} {'clusters':>9} {'noise':>6} {'largest':>8} {'total_stab':>11}")
    for row in est.mpts_profile():
        largest = max(row["cluster_sizes"], default=0)
        print(f"{row['mpts']:>5} {row['n_clusters']:>9} {row['n_noise']:>6} "
              f"{largest:>8} {row['total_stability']:>11.1f}")

    # rank by stability among non-shattered levels (tiny mpts inflates the
    # lambda scale; see MultiHDBSCAN.mpts_profile docs)
    candidates = [r for r in est.mpts_profile() if r["n_clusters"] <= len(x) ** 0.5]
    best = max(candidates, key=lambda r: r["total_stability"])
    print(f"\nhighest-stability level: mpts={best['mpts']} "
          f"({best['n_clusters']} clusters) — labels via est.select(mpts).labels.")
    print("low mpts isolates the tight twins; high mpts merges them and")
    print("stabilizes the diffuse blob — one fit exposes both readings.")


def sweep(full: bool):
    from benchmarks.paper_sweeps import kmax_sweep

    kmaxes = (2, 4, 8, 16, 32, 64, 128) if full else (4, 8, 16, 32)
    n = 8000 if full else 3000
    rows = kmax_sweep(kmaxes=kmaxes, n=n, d=8)
    print(f"\n{'kmax':>5} {'method':>10} {'wall_s':>8} {'edges':>10} {'ratio_vs_one':>12}")
    for r in rows:
        print(f"{r['kmax']:>5} {r['method']:>10} {r['wall_s']:>8.2f} "
              f"{r['edges']:>10,} {r.get('ratio_vs_one', float('nan')):>12}")
    print("\n(paper Table II: baseline grows linearly in kmax; RNG* stays ~flat;")
    print(" paper Fig 7: RNG* ratio ~2 at kmax=128 — same shape here.)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true", help="paper Table II harness")
    ap.add_argument("--full", action="store_true", help="larger sweep")
    ap.add_argument("--n", type=int, default=2400)
    ap.add_argument("--kmax", type=int, default=24)
    args = ap.parse_args()
    if args.sweep:
        sweep(args.full)
    else:
        explore(args.n, args.kmax)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
