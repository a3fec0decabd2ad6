"""Nightly smoke sweep: the full SCAR pipeline on 8x8 and 16x16 pods.

The per-push CI matrix stays on the paper's 3x3/6x6 meshes; this script is
the nightly guard that pod-scale scheduling keeps working end to end now
that candidate construction (``paths.frontier_paths``) and window
combination (``engine.BeamEngine``) are both vectorized.  It runs a small
scenario x pattern portfolio on every mesh in ``scenarios.LARGE_MESHES``,
checks each outcome is finite and validated, and prints one CSV row per
point plus the path-cache statistics.  The sweep runs inline: its jobs keep
``eval_backend="auto"``, so large candidate batches score on the JAX path,
and a chip belongs to one process.

Usage: PYTHONPATH=src python scripts/large_mesh_smoke.py [--meshes 8x8 ...]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.core.paths import path_cache_info
from repro.core.portfolio import run_portfolio, sweep_grid
from repro.core.scenarios import LARGE_MESHES
from repro.launch.platform import use_compile_cache

SCENARIOS = ["dc4_lms_seg_image", "xr7_ar_gaming"]
PATTERNS = ["het_cb", "het_sides"]


def main() -> None:
    use_compile_cache(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--meshes",
        nargs="*",
        default=list(LARGE_MESHES),
        help="mesh presets to sweep (default: 8x8 16x16)",
    )
    args = ap.parse_args()

    jobs = sweep_grid(
        SCENARIOS,
        PATTERNS,
        meshes=args.meshes,
        path_cap=512,
        seg_cap=128,
    )
    results = run_portfolio(jobs, processes=1)

    print("name,edp,latency_s,energy_j,wall_s")
    failures = 0
    for res in results:
        out = res.outcome
        ok = (
            np.isfinite(out.result.latency)
            and np.isfinite(out.result.energy)
            and out.edp > 0
        )
        if not ok:
            failures += 1
        print(
            f"{res.job.name},{out.edp:.6g},{out.result.latency:.6g},"
            f"{out.result.energy:.6g},{res.wall_s:.2f}"
        )
    print(f"# path_cache={path_cache_info()}", file=sys.stderr)
    if failures:
        print(f"# {failures} non-finite outcomes", file=sys.stderr)
        sys.exit(1)
    print(f"# large-mesh smoke OK ({len(results)} points)", file=sys.stderr)


if __name__ == "__main__":
    main()
