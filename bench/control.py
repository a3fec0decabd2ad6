#!/usr/bin/env python3
"""The control of ``correct``: the reference in float32, in the program's place.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

Runs the cell as ``bench/run.py`` does (set-up, window, the same check)
with the reference planner computed in float32 (``reference.scheduler``,
``precision="float32"``) standing in for the program, once per seed, and
prints each run's compared numbers.  The check has to call every such run
not correct: its readings are the upper ends from which ``check.LIMITS``
were set (PERF.md).  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


class Float32Planner:
    """The reference planner at float32, with ``sut.Planner``'s ``plan``."""

    def __init__(self, config: dict) -> None:
        from reference import scheduler as rs

        self._rs = rs
        self._dep = rs.build(config, precision="float32")

    def plan(self, anchors: dict[int, int]) -> dict:
        return self._rs.schedule(self._dep, anchors)


def main(argv=None) -> int:
    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run(["--workload", args.workload, "--seed", str(seed),
                       "--seconds", str(args.seconds), "--trace", "0"],
                      planner_factory=Float32Planner)
        rows.append({"seed": seed, "correct": res["correct"],
                     "attempted": res["attempted"],
                     "device": res["device"], "checks": res["checks"]})
        print(json.dumps(rows[-1]), flush=True)
    return 0 if all(not r["correct"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
