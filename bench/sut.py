"""The system under test: SCAR's planner, built from a configuration file.

This is the one module of the benchmark that imports the program.  It
builds the deployment a configuration describes through the program's own
entry points and returns each re-plan in the plain form the check reads.
"""
from __future__ import annotations

import dataclasses

# Environment overrides that would reroute the compared paths or read
# cost tables built by other code; set-up removes them.
PATH_ENV = ("SCAR_SEARCH_BACKEND", "SCAR_EVAL_BACKEND",
            "SCAR_EVAL_AUTO_THRESHOLD", "SCAR_COSTDB_CACHE", "SCAR_TRACE")


@dataclasses.dataclass
class Planner:
    """``plan(anchors)`` runs one re-plan through ``repro.core.schedule``."""

    scenario: object
    mcm: object
    cfg: object

    def plan(self, anchors: dict[int, int]):
        from repro.core import schedule
        return schedule(self.scenario, self.mcm, self.cfg, prev_end=anchors)


def build(config: dict) -> Planner:
    """The program's deployment for a configuration file."""
    from repro.core import SearchConfig, get_scenario, make_mcm
    from repro.core.chiplet import NoCConfig

    sc = get_scenario(config["scenario"])
    have = [[m.name.lower(), int(m.batch)] for m in sc.models]
    want = [[str(m).lower(), int(b)] for m, b in config["tenants"]]
    if have != want:
        raise ValueError(f"scenario {config['scenario']!r} holds {have}, "
                         f"the configuration states {want}")
    pkg = config["package"]
    mcm = make_mcm(pkg["pattern"], rows=int(pkg["rows"]),
                   cols=int(pkg["cols"]), n_pe=int(pkg["n_pe"]),
                   noc=NoCConfig(**{k: float(v)
                                    for k, v in pkg["noc"].items()}))
    return Planner(scenario=sc, mcm=mcm, cfg=SearchConfig(**config["search"]))


def as_result(outcome) -> dict:
    """A ``ScheduleOutcome`` in the harness's plain comparison form.

    A stand-in planner of the harness's tests may hand back that form
    already; it passes through.
    """
    if isinstance(outcome, dict):
        return outcome
    windows = []
    for wr in outcome.windows:
        windows.append({
            "plan": tuple(
                (int(p.model_idx), int(p.start), int(p.end),
                 tuple(int(x) for x in p.seg_ends),
                 tuple(int(c) for c in p.chiplets), bool(p.pipelined))
                for p in sorted(wr.plan.plans, key=lambda p: p.model_idx)),
            "latency": float(wr.result.latency),
            "energy": float(wr.result.energy)})
    return {"windows": windows, "latency": float(outcome.result.latency),
            "energy": float(outcome.result.energy)}
