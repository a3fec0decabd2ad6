"""Benchmark harness: one function per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows.  With ``--json-dir`` every row
is also written as ``BENCH_<name>.json`` ({name, us_per_call, derived}) so CI
can upload the results as an artifact and gate regressions against the
committed baselines (see ``benchmarks/compare.py``).

Usage: PYTHONPATH=src python -m benchmarks.run [--only substr]
       [--json-dir DIR]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, "src")


def write_json(json_dir: str) -> None:
    """Dump every recorded emit() row as BENCH_<name>.json."""
    from .common import RESULTS
    os.makedirs(json_dir, exist_ok=True)
    for row in RESULTS:
        path = os.path.join(json_dir, f"BENCH_{row['name']}.json")
        with open(path, "w") as fh:
            json.dump(row, fh, indent=2, sort_keys=True)
            fh.write("\n")
    print(f"# wrote {len(RESULTS)} BENCH_*.json files to {json_dir}",
          file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-dir", default=None,
                    help="write per-bench BENCH_<name>.json files here")
    args = ap.parse_args()

    from repro.launch.platform import use_compile_cache

    from . import online_benches, paper_benches, system_benches
    use_compile_cache(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    benches = paper_benches.ALL + system_benches.ALL + online_benches.ALL
    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    for b in benches:
        if args.only and args.only not in b.__name__:
            continue
        try:
            b()
        except Exception:  # noqa: BLE001 - report and continue
            failures += 1
            traceback.print_exc()
            print(f"{b.__name__},0,FAILED")
    print(f"# total_wall_s={time.time() - t0:.1f} failures={failures}",
          file=sys.stderr)
    if args.json_dir:
        write_json(args.json_dir)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
