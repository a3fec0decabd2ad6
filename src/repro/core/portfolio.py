"""Portfolio runner: multi-scenario sweeps over scenario x MCM x metric.

Benchmarks, examples and future scaling studies all need the same outer
loop — run the SCAR pipeline across a grid of (scenario, MCM pattern/size,
optimisation metric, search config) points.  This module makes that loop a
first-class, process-parallel subsystem instead of a hand-rolled ``for`` in
every caller:

* ``SweepJob`` is one picklable grid point (pattern name + mesh size + cfg
  overrides, never live objects, so jobs ship cheaply to workers);
  ``TraceJob`` is its online analogue (a ``scenarios.TRACE_PRESETS`` trace
  replayed through ``repro.online.simulate``).  The evaluator backend rides
  along in ``SearchConfig.eval_backend`` (``repro.core.evaluator``): under
  ``"auto"`` large-mesh sweeps score candidates on the jax path, so they run
  inline, while a sweep pinned to ``"numpy"`` on a host engine may be pooled.
* ``run_portfolio`` executes a job list inline (the default) or, for jobs
  that stay on the host, on a spawn-based process pool; jobs are
  dispatched grouped by CostDB affinity so identical (scenario/trace, MCM)
  points share one worker's warm caches.  A chip belongs to one process, so
  jobs that may dispatch to the JAX device never go to workers.
* ``sweep_grid`` / ``trace_sweep_grid`` build the full cross products.

Results come back as ``SweepResult`` records carrying the full
``ScheduleOutcome`` plus wall time (``TraceResult`` with a ``QoSReport`` for
trace jobs), in the same order as the submitted jobs.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time
from concurrent.futures import ProcessPoolExecutor
from typing import Optional

from repro import obs

from .engine import DeviceBeamEngine, get_engine
from .scenarios import get_scenario, mesh_shape
from .scheduler import ScheduleOutcome, SearchConfig, run_config


@dataclasses.dataclass(frozen=True)
class SweepJob:
    """One (scenario, MCM, metric) pipeline run; picklable by construction."""

    scenario: str
    pattern: str
    rows: int = 3
    cols: int = 3
    n_pe: int = 4096
    standalone: bool = False
    cfg: Optional[SearchConfig] = None
    label: Optional[str] = None          # caller-facing name for the point

    @property
    def name(self) -> str:
        if self.label is not None:
            return self.label
        tag = "standalone_" if self.standalone else ""
        metric = (self.cfg or SearchConfig()).metric
        return (f"{self.scenario}/{tag}{self.pattern}"
                f"_{self.rows}x{self.cols}/{metric}")


@dataclasses.dataclass
class SweepResult:
    job: SweepJob
    outcome: ScheduleOutcome
    wall_s: float


@dataclasses.dataclass(frozen=True)
class TraceJob:
    """One online-trace replay (preset name -> ``repro.online.simulate``).

    The portfolio treats traces like scenarios: a picklable grid point that
    workers expand locally.  ``mode`` selects the warm incremental
    re-scheduler or the cold from-scratch oracle; ``policy`` the
    epoch-boundary / preemption / MCM-reconfiguration behaviour
    (``repro.online.OnlinePolicy``, itself a frozen picklable dataclass;
    ``None`` is the class-blind fluid default).
    """

    trace: str                           # scenarios.TRACE_PRESETS name
    pattern: str
    rows: int = 6
    cols: int = 6
    n_pe: int = 4096
    mode: str = "warm"
    cfg: Optional[SearchConfig] = None
    policy: Optional["object"] = None    # repro.online.OnlinePolicy
    label: Optional[str] = None

    @property
    def name(self) -> str:
        if self.label is not None:
            return self.label
        tag = "" if self.policy is None else f"/{self.policy.boundary}"
        return (f"{self.trace}/{self.pattern}_{self.rows}x{self.cols}"
                f"/{self.mode}{tag}")


@dataclasses.dataclass
class TraceResult:
    """QoS report of one trace replay (the ``SweepResult`` analogue)."""

    job: TraceJob
    report: "object"                     # repro.online.metrics.QoSReport
    wall_s: float


def _run_job(job):
    t0 = time.time()
    with obs.span("job", cat="portfolio", job=job.name):
        if isinstance(job, TraceJob):
            # lazy: repro.online depends on repro.core, so importing it at
            # module load would be circular
            from repro.online.metrics import qos_report
            from repro.online.simulator import simulate
            from .scenarios import get_trace
            sim = simulate(get_trace(job.trace), pattern=job.pattern,
                           rows=job.rows, cols=job.cols, n_pe=job.n_pe,
                           cfg=job.cfg, mode=job.mode, policy=job.policy)
            return TraceResult(job=job, report=qos_report(sim),
                               wall_s=time.time() - t0)
        sc = get_scenario(job.scenario)
        outcome = run_config(sc, job.pattern, rows=job.rows, cols=job.cols,
                             n_pe=job.n_pe, cfg=job.cfg,
                             standalone=job.standalone)
        return SweepResult(job=job, outcome=outcome,
                           wall_s=time.time() - t0)


def _db_affinity(job) -> tuple:
    """Grouping key of jobs that want the same per-worker warm caches.

    Jobs sharing the key (same scenario-or-trace, package geometry and PE
    budget) reuse one worker's CostDB and path caches.
    """
    src = job.trace if isinstance(job, TraceJob) else job.scenario
    return (src, job.pattern, job.rows, job.cols, job.n_pe)


def _run_batch(batch: list, trace: bool = False) -> tuple:
    """Worker-side: run one affinity group in order (shared warm caches).

    Returns ``(results, telemetry)``.  ``trace=True`` (the parent had
    tracing enabled) turns tracing on in the worker and ships back an
    ``obs.snapshot()`` the parent folds into its own tracer, so one Chrome
    trace shows every process's span stream; the snapshot also carries the
    worker's counters, which the parent adds into its registry.
    """
    if trace and not obs.enabled():
        obs.enable()
    results = [_run_job(j) for j in batch]
    return results, (obs.snapshot() if trace else None)


def _init_worker(path: list[str]) -> None:
    # spawn workers re-import ``repro`` from scratch; inherit the parent's
    # sys.path so PYTHONPATH-less installs (pip install -e .) and source
    # checkouts (PYTHONPATH=src) both resolve
    for p in reversed(path):
        if p not in sys.path:
            sys.path.insert(0, p)


def default_processes() -> int:
    """Worker count: $SCAR_PORTFOLIO_PROCS, else 1 (inline)."""
    return max(1, int(os.environ.get("SCAR_PORTFOLIO_PROCS", "1")))


def touches_device(job) -> bool:
    """Whether ``job`` may dispatch work to JAX's default device.

    Only a host search engine scoring candidates on numpy stays off it:
    ``eval_backend="auto"`` sends large batches to the jax path, and
    ``algo="beam_jax"`` (or its ``SCAR_SEARCH_BACKEND`` reroute) runs the
    whole window search there.
    """
    cfg = job.cfg or SearchConfig()
    return (cfg.eval_backend != "numpy"
            or isinstance(get_engine(cfg), DeviceBeamEngine))


def run_portfolio(jobs: list,
                  processes: Optional[int] = None) -> list:
    """Run every job; results align with the input order.

    Jobs are ``SweepJob`` or ``TraceJob`` instances, freely mixed.
    ``processes``: None -> ``default_processes()``; <=1 -> inline in this
    process (no pool, easiest to debug); otherwise a spawn-based pool, which
    sidesteps fork-safety issues with an already-initialised JAX runtime in
    the parent.  A pool takes host-only jobs (``touches_device`` false):
    asking for one over jobs that may use the device raises ``ValueError``,
    because every worker would try to take the same chip.

    Jobs are submitted grouped by ``_db_affinity`` in contiguous chunks, so
    jobs sharing a (scenario/trace, MCM) land on the same worker and hit its
    per-process CostDB/path caches instead of every worker rebuilding the
    same database (the old round-robin ``chunksize=1`` dispatch paid the
    build once per worker per grid point).
    """
    if processes is None:
        processes = default_processes()
    processes = min(processes, len(jobs)) if jobs else 1
    if processes <= 1:
        return [_run_job(j) for j in jobs]
    on_device = [j.name for j in jobs if touches_device(j)]
    if on_device:
        raise ValueError(
            f"{len(on_device)} job(s) may use the JAX device (first: "
            f"{on_device[0]!r}); run them inline (processes=1), or give "
            f"them SearchConfig(eval_backend='numpy') with a host engine")
    import math
    import multiprocessing as mp
    groups: dict[tuple, list[int]] = {}
    for i, j in enumerate(jobs):
        groups.setdefault(_db_affinity(j), []).append(i)
    # one pool task per affinity group, but split oversized groups into
    # fair-share sub-chunks so a sweep whose jobs all share one (scenario,
    # MCM) — e.g. a metric or warm/cold mode axis — still parallelises
    # (the caches are per-process, so every sub-chunk re-warms its own)
    cap = max(1, math.ceil(len(jobs) / processes))
    batches = []
    for idxs in groups.values():
        for s in range(0, len(idxs), cap):
            batches.append(idxs[s:s + cap])
    ctx = mp.get_context("spawn")
    tracing = obs.enabled()
    with ProcessPoolExecutor(max_workers=processes, mp_context=ctx,
                             initializer=_init_worker,
                             initargs=(list(sys.path),)) as pool:
        outs = list(pool.map(_run_batch,
                             [[jobs[i] for i in idxs] for idxs in batches],
                             [tracing] * len(batches)))
    results: list = [None] * len(jobs)
    for k, (idxs, (out, snap)) in enumerate(zip(batches, outs)):
        # batches are numbered by submission order, so merged span streams
        # get stable, deterministic process ids across runs
        obs.merge_snapshot(snap, pid=k + 1)
        for i, r in zip(idxs, out):
            results[i] = r
    return results


def sweep_grid(scenarios: list[str], patterns: list[str],
               metrics: list[str] = ("edp",), rows: int = 3, cols: int = 3,
               n_pe: Optional[int] = None,
               standalone_patterns: list[str] = (),
               meshes: Optional[list] = None,
               **cfg_kw) -> list[SweepJob]:
    """Cross product scenario x mesh x pattern x metric -> job list.

    ``n_pe=None`` follows the paper's sizing: 4096 PEs for datacenter
    scenarios, 256 for AR/VR.  ``standalone_patterns`` adds the
    no-pipelining baseline runs for the named patterns.  ``meshes`` adds a
    mesh-size axis: a list of ``(rows, cols)`` pairs or preset names from
    ``scenarios.MESH_PRESETS`` (``"8x8"``, ``"16x16"``, ...); when given it
    overrides the scalar ``rows``/``cols``.
    """
    if meshes is None:
        mesh_list = [(rows, cols)]
    else:
        mesh_list = [mesh_shape(m) if isinstance(m, str) else tuple(m)
                     for m in meshes]
    jobs = []
    for scn in scenarios:
        npe = n_pe if n_pe is not None else (
            4096 if scn.startswith("dc") else 256)
        for mrows, mcols in mesh_list:
            for metric in metrics:
                for pat in standalone_patterns:
                    jobs.append(SweepJob(scenario=scn, pattern=pat,
                                         rows=mrows, cols=mcols, n_pe=npe,
                                         standalone=True,
                                         cfg=SearchConfig(metric=metric,
                                                          **cfg_kw)))
                for pat in patterns:
                    jobs.append(SweepJob(scenario=scn, pattern=pat,
                                         rows=mrows, cols=mcols, n_pe=npe,
                                         cfg=SearchConfig(metric=metric,
                                                          **cfg_kw)))
    return jobs


def trace_sweep_grid(traces: list[str], patterns: list[str],
                     rows: int = 6, cols: int = 6, n_pe: int = 4096,
                     modes: tuple[str, ...] = ("warm",),
                     policies: tuple = (None,),
                     meshes: Optional[list] = None,
                     **cfg_kw) -> list[TraceJob]:
    """Cross product trace x mesh x pattern x mode x policy -> job list.

    The online analogue of ``sweep_grid``: sweeps dynamic traces (preset
    names from ``scenarios.TRACE_PRESETS``) instead of static scenarios.
    ``policies`` adds an ``OnlinePolicy`` axis (``None`` = the class-blind
    fluid default), e.g. drain-vs-preempt comparisons across meshes.
    """
    if meshes is None:
        mesh_list = [(rows, cols)]
    else:
        mesh_list = [mesh_shape(m) if isinstance(m, str) else tuple(m)
                     for m in meshes]
    jobs = []
    for tr in traces:
        for mrows, mcols in mesh_list:
            for pat in patterns:
                for mode in modes:
                    for pol in policies:
                        jobs.append(TraceJob(trace=tr, pattern=pat,
                                             rows=mrows, cols=mcols,
                                             n_pe=n_pe, mode=mode,
                                             policy=pol,
                                             cfg=SearchConfig(**cfg_kw)))
    return jobs
