"""The reference planner: SCAR's pipeline on the host, in float64.

A plain copy of the program's host oracle (``schedule`` with
``algo="beam"`` and the numpy evaluator): MCM-Reconfig greedy packing,
then per window PROV, SEG top-k, (segmentation x path) candidates scored by
the vectorised float64 cost model and ordered by (tier, quantised score),
and the host beam over disjoint placements.  Under the congestion comm
model, candidates are scored against the interposer bytes of the models
placed before them, as the program's placement co-search does.

It imports nothing of the program under test and builds its own cost
tables from the tenants' layer graphs (``modelzoo``).

``precision="float32"`` is the benchmark's control: the cost tables are
cast to float32, so candidate scores and segment sums are float32 sums,
and every window's latency and energy is rounded to float32.  A check that
cannot tell this apart from the float64 reference is too loose.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .chiplet import MCM, NoCConfig, make_mcm
from .cost import (ModelWindowPlan, WindowPlan, WindowResult,
                   evaluate_window, n_interposer_links, plan_link_bytes)
from .engine import BeamEngine
from .maestro import CostDB, build_cost_db
from .modelzoo import get_model
from .provision import provision
from .reconfig import greedy_pack
from .sched import build_candidates
from .segmentation import top_k_segmentations
from .workload import Scenario

PRECISIONS = ("float64", "float32")


@dataclasses.dataclass(frozen=True)
class Search:
    """The search fields a configuration may set (program defaults)."""

    metric: str = "edp"
    n_splits: int = 4
    seg_top_k: int = 4
    seg_cap: int = 512
    path_cap: int = 128
    frontier_cap: Optional[int] = None
    keep_per_model: int = 48
    beam: int = 48
    max_nodes_per_model: Optional[int] = 6
    comm_model: str = "analytic"
    max_expansions: int = 20000


@dataclasses.dataclass
class Deployment:
    """One configuration file, built by the reference alone."""

    scenario: Scenario
    mcm: MCM
    search: Search
    db: CostDB
    ranges: tuple            # per window: {model index: (start, end)}
    precision: str = "float64"


def _cast(db: CostDB, dtype) -> CostDB:
    return dataclasses.replace(
        db, lat=db.lat.astype(dtype), energy=db.energy.astype(dtype),
        w_bytes=db.w_bytes.astype(dtype), in_bytes=db.in_bytes.astype(dtype),
        out_bytes=db.out_bytes.astype(dtype))


def build(config: dict, precision: str = "float64") -> Deployment:
    """The deployment a configuration file describes."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    sc = Scenario(name=config["scenario"],
                  models=tuple(get_model(m, batch=int(b))
                               for m, b in config["tenants"]))
    pkg = config["package"]
    mcm = make_mcm(pkg["pattern"], rows=int(pkg["rows"]),
                   cols=int(pkg["cols"]), n_pe=int(pkg["n_pe"]),
                   noc=NoCConfig(**{k: float(v)
                                    for k, v in pkg["noc"].items()}))
    fields = {f.name for f in dataclasses.fields(Search)}
    kw = {k: v for k, v in config["search"].items() if k in fields}
    search = Search(**kw)
    db = build_cost_db(sc, mcm.classes, mcm.pkg)
    wa = greedy_pack(db, mcm.class_counts(), search.n_splits)
    if precision == "float32":
        db = _cast(db, np.float32)
    return Deployment(scenario=sc, mcm=mcm, search=search, db=db,
                      ranges=tuple(wa.ranges), precision=precision)


def _greedy_best_plan(cs) -> ModelWindowPlan:
    k = int(cs.n_segs[0])
    return ModelWindowPlan(
        model_idx=cs.model_idx, start=cs.start, end=cs.end,
        seg_ends=tuple(int(x) for x in cs.seg_arr[0][:k]),
        chiplets=tuple(int(c) for c in cs.chips[0][:k]))


def window_sets(dep: Deployment, ranges: dict, prev_end: dict) -> list:
    """PROV + SEG + scored candidates of one window (every model)."""
    db, mcm, cfg = dep.db, dep.mcm, dep.search
    alloc = provision(db, mcm.class_counts(), ranges, mcm.n_chiplets,
                      metric=cfg.metric,
                      max_nodes_per_model=cfg.max_nodes_per_model)
    congestion = cfg.comm_model == "congestion"
    link_occ = (np.zeros(n_interposer_links(mcm.rows, mcm.cols))
                if congestion else None)
    sets = []
    for mi, (s, e) in sorted(ranges.items()):
        segs = top_k_segmentations(db, mcm, s, e, alloc[mi],
                                   k=cfg.seg_top_k, cap=cfg.seg_cap,
                                   metric=cfg.metric)
        cs = build_candidates(
            db, mcm, mi, (s, e), segs, n_active=len(ranges),
            prev_end=prev_end.get(mi), path_cap=cfg.path_cap,
            keep=cfg.keep_per_model, metric=cfg.metric,
            frontier_cap=cfg.frontier_cap, comm_model=cfg.comm_model,
            link_occ=link_occ)
        sets.append(cs)
        if congestion:
            link_occ = link_occ + plan_link_bytes(
                db, mcm, _greedy_best_plan(cs), prev_end)
    return sets


def _rounded(dep: Deployment, res: WindowResult) -> WindowResult:
    if dep.precision == "float64":
        return res
    return dataclasses.replace(res, latency=float(np.float32(res.latency)),
                               energy=float(np.float32(res.energy)))


def solve_window(dep: Deployment, w: int, prev_end: dict):
    """The reference's (plan, result) of window ``w`` under ``prev_end``."""
    cfg = dep.search
    sets = window_sets(dep, dep.ranges[w], prev_end)
    engine = BeamEngine(beam=cfg.beam, max_expansions=cfg.max_expansions,
                        comm_model=cfg.comm_model)
    wr = engine.combine(dep.db, dep.mcm, sets, prev_end, metric=cfg.metric)
    return wr.plan, _rounded(dep, wr.result)


def evaluate_plan(dep: Deployment, plan: WindowPlan,
                  prev_end: dict) -> WindowResult:
    """Latency and energy of one window plan, validated."""
    return _rounded(dep, evaluate_window(
        dep.db, dep.mcm, plan, prev_end, validate=True,
        comm_model=dep.search.comm_model))


def schedule(dep: Deployment, prev_end: Optional[dict] = None) -> dict:
    """A whole re-plan: every window solved in turn, anchors threaded."""
    anchors = dict(prev_end or {})
    windows = []
    for w in range(len(dep.ranges)):
        plan, res = solve_window(dep, w, anchors)
        windows.append({"plan": plan_tuple(plan), "latency": res.latency,
                        "energy": res.energy})
        anchors = dict(anchors)
        anchors.update(res.end_chiplet)
    return {"windows": windows,
            "latency": float(sum(w["latency"] for w in windows)),
            "energy": float(sum(w["energy"] for w in windows))}


def plan_tuple(plan: WindowPlan) -> tuple:
    """A window plan as plain tuples (the harness's comparison form)."""
    return tuple((int(p.model_idx), int(p.start), int(p.end),
                  tuple(int(x) for x in p.seg_ends),
                  tuple(int(c) for c in p.chiplets), bool(p.pipelined))
                 for p in sorted(plan.plans, key=lambda p: p.model_idx))


def plan_from_tuple(t: tuple) -> WindowPlan:
    """Inverse of ``plan_tuple``."""
    return WindowPlan(plans=tuple(
        ModelWindowPlan(model_idx=m, start=s, end=e, seg_ends=tuple(se),
                        chiplets=tuple(ch), pipelined=pp)
        for m, s, e, se, ch, pp in t))
