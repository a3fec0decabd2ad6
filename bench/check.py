"""What decides ``correct``: each checked re-plan against the reference.

Every window of a checked re-plan is solved again by the reference
planner (``reference.scheduler``, float64, importing nothing of the
program) under the anchors the program's own earlier windows left, so a
window is judged on exactly the subproblem the program solved.  Two
numbers come out, each with its limit (``LIMITS``):

``plan_mismatch``
    Windows whose plan differs from the reference's and whose window EDP,
    as the reference prices both plans and rounded to the program's
    ordering grain (6 significant digits), differs too.  A plan that
    differs at an equal rounded score is a tie of the search's ordering,
    not an error, and is counted apart (``ties``).  Exact: limit 0.
``metric_rel_gap``
    The largest relative gap between a latency or energy the program
    reported (per window and for the whole re-plan) and the reference's
    float64 evaluation of the program's own plans.  The program re-scores
    its plans in float64 exactly as the reference does, so a sound run
    reads 0.

Beside them the run is held to ``failed_requests`` (requests that raised)
and ``compiles_in_window`` (XLA programs compiled or loaded from the
persistent cache inside the measured window), both with limit 0: a
compile in the window is a fault of the set-up and must not become a
latency.

PERF.md gives the readings each limit was set from.
"""
from __future__ import annotations

import math

import numpy as np

# 6 significant digits, the program's candidate-ordering grain
SCORE_SIG = 5
LIMITS = {"plan_mismatch": 0, "metric_rel_gap": 1e-10}


def _q(x: float) -> float:
    if not math.isfinite(x) or x == 0:
        return x
    scale = 10.0 ** (math.floor(math.log10(abs(x))) - SCORE_SIG)
    return float(np.round(x / scale) * scale)


def _score(lat: float, energy: float, metric: str) -> float:
    if metric == "latency":
        return lat
    if metric == "energy":
        return energy
    return lat * energy


def _gap(got: float, want: float) -> float:
    if got == want:
        return 0.0
    if not (math.isfinite(got) and math.isfinite(want)) or want == 0:
        return math.inf
    return abs(got / want - 1.0)


def check_replan(dep, anchors: dict[int, int], got: dict) -> dict:
    """Judge one re-plan ``got`` (``sut.as_result`` form) made under
    ``anchors``; returns ``{plan_mismatch, ties, metric_rel_gap}``."""
    from reference import scheduler as rs

    metric = dep.search.metric
    out = {"plan_mismatch": 0, "ties": 0, "metric_rel_gap": 0.0}
    if len(got["windows"]) != len(dep.ranges):
        out["plan_mismatch"] = len(dep.ranges)
        out["metric_rel_gap"] = math.inf
        return out
    prev = dict(anchors)
    lats, energies = [], []
    for w, gw in enumerate(got["windows"]):
        ref_plan, ref_res = rs.solve_window(dep, w, prev)
        try:
            mine = rs.evaluate_plan(dep, rs.plan_from_tuple(gw["plan"]), prev)
        except (ValueError, IndexError, KeyError):
            # not a valid plan of this window: shared chiplets, bad ranges
            out["plan_mismatch"] += 1
            out["metric_rel_gap"] = math.inf
            return out
        if (gw["plan"] != rs.plan_tuple(ref_plan)
                or tuple(sorted(gw["plan"])) != gw["plan"]):
            if (_q(_score(mine.latency, mine.energy, metric))
                    == _q(_score(ref_res.latency, ref_res.energy, metric))):
                out["ties"] += 1
            else:
                out["plan_mismatch"] += 1
        for g, r in ((gw["latency"], mine.latency),
                     (gw["energy"], mine.energy)):
            out["metric_rel_gap"] = max(out["metric_rel_gap"], _gap(g, r))
        lats.append(mine.latency)
        energies.append(mine.energy)
        prev = dict(prev)
        prev.update(mine.end_chiplet)
    # totals as the program's evaluate_schedule sums them (Python's sum)
    for g, r in ((got["latency"], float(sum(lats))),
                 (got["energy"], float(sum(energies)))):
        out["metric_rel_gap"] = max(out["metric_rel_gap"], _gap(g, r))
    return out


def judge(readings: list[dict], failed: int, compiles_in_window: int
          ) -> tuple[bool, dict]:
    """Fold per-re-plan readings into the run's compared numbers."""
    nums = {
        "plan_mismatch": sum(r["plan_mismatch"] for r in readings),
        "metric_rel_gap": max((r["metric_rel_gap"] for r in readings),
                              default=0.0),
    }
    ok = (bool(readings) and failed == 0 and compiles_in_window == 0
          and all(nums[k] <= LIMITS[k] for k in LIMITS))
    checks = {k: {"value": nums[k], "limit": LIMITS[k]} for k in LIMITS}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    checks["compiles_in_window"] = {"value": compiles_in_window, "limit": 0}
    checks["replans_checked"] = len(readings)
    checks["ties"] = sum(r["ties"] for r in readings)
    return ok, checks
