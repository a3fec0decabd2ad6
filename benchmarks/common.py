"""Shared helpers for the benchmark suite."""
from __future__ import annotations

import sys
import time

sys.path.insert(0, "src")

from repro import obs
from repro.core import SearchConfig
from repro.core.portfolio import SweepJob, run_portfolio

CONFIG_SET = [
    ("standalone_nvdla", "simba_nvdla", True),
    ("standalone_shi", "simba_shi", True),
    ("simba_nvdla", "simba_nvdla", False),
    ("simba_shi", "simba_shi", False),
    ("het_cb", "het_cb", False),
    ("het_sides", "het_sides", False),
    ("het_cross", "het_cross", False),
]


def npe_for(scenario_name: str) -> int:
    return 4096 if scenario_name.startswith("dc") else 256


def sweep(scenario_name: str, metric: str = "edp", configs=None,
          rows: int = 3, cols: int = 3, **cfg_kw) -> dict:
    """Run every MCM config on a scenario; returns {name: outcome}."""
    jobs = [SweepJob(scenario=scenario_name, pattern=pattern, rows=rows,
                     cols=cols, n_pe=npe_for(scenario_name),
                     standalone=standalone,
                     cfg=SearchConfig(metric=metric, **cfg_kw), label=name)
            for name, pattern, standalone in (configs or CONFIG_SET)]
    results = run_portfolio(jobs)
    return {r.job.name: r.outcome for r in results}


# Every emit() is also recorded here so the harness can dump machine-readable
# BENCH_<name>.json files (benchmarks/run.py --json-dir) for the CI
# bench-regression gate (benchmarks/compare.py).
RESULTS: list[dict] = []


def _parse_derived(derived: str) -> dict:
    """'k1=v1;k2=v2' -> dict with numeric coercion ('10.23x' -> 10.23)."""
    out: dict = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v[:-1] if v.endswith("x") else v)
        except ValueError:
            out[k] = v
    return out


def emit(name: str, us: float, derived: str) -> None:
    """CSV row per harness contract: name,us_per_call,derived.

    With tracing enabled (``SCAR_TRACE=1`` or ``obs.enable()``) each row
    also embeds the telemetry accumulated since the previous ``emit`` —
    counters, gauges and a per-phase span summary — so ``BENCH_*.json``
    files carry cache hit rates and jit-recompile counts next to the
    timing they explain.  Spans are flushed per row to keep attribution
    per-bench; counters are process-cumulative by design.
    """
    print(f"{name},{us:.1f},{derived}")
    row = {"name": name, "us_per_call": round(us, 1),
           "derived": _parse_derived(derived)}
    if obs.enabled():
        row["obs"] = obs.bench_dump()
        obs.reset(counters_too=False)
    RESULTS.append(row)


class timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.us = (time.time() - self.t0) * 1e6
