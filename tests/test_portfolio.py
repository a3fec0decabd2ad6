"""Portfolio sweep runner: grid construction, inline and process-parallel
execution, result ordering and parity."""
import numpy as np
import pytest

from repro.core import SearchConfig
from repro.core.portfolio import (SweepJob, TraceJob, default_processes,
                                  run_portfolio, sweep_grid, touches_device)


def test_sweep_grid_cross_product_and_paper_npe():
    jobs = sweep_grid(["dc1_lms", "xr8_outdoors"], ["het_sides", "het_cb"],
                      metrics=["edp", "latency"],
                      standalone_patterns=["simba_nvdla"])
    # 2 scenarios x 2 metrics x (2 patterns + 1 standalone)
    assert len(jobs) == 12
    by_scn = {j.scenario: j for j in jobs}
    assert by_scn["dc1_lms"].n_pe == 4096      # datacenter sizing
    assert by_scn["xr8_outdoors"].n_pe == 256  # AR/VR sizing
    assert sum(j.standalone for j in jobs) == 4
    assert len({j.name for j in jobs}) == len(jobs)  # names are unique


def test_run_portfolio_inline_order_and_outcomes():
    jobs = sweep_grid(["xr10_vr_gaming"], ["het_sides", "simba_nvdla"],
                      standalone_patterns=["simba_nvdla"])
    results = run_portfolio(jobs, processes=1)
    assert [r.job for r in results] == jobs
    for r in results:
        assert r.outcome.edp > 0
        assert r.wall_s >= 0
    # het beats the standalone baseline on this scenario (paper direction)
    het = results[[j.pattern for j in jobs].index("het_sides") ].outcome
    sa = results[0].outcome
    assert het.edp < sa.edp


def test_run_portfolio_process_parallel_matches_inline():
    jobs = sweep_grid(["xr10_vr_gaming", "xr8_outdoors"], ["het_cb"],
                      eval_backend="numpy")
    ser = run_portfolio(jobs, processes=1)
    par = run_portfolio(jobs, processes=2)
    assert [r.job.name for r in par] == [r.job.name for r in ser]
    for a, b in zip(par, ser):
        assert a.outcome.result.latency == b.outcome.result.latency
        assert a.outcome.result.energy == b.outcome.result.energy


def test_sweep_job_custom_label_and_cfg():
    job = SweepJob(scenario="xr8_outdoors", pattern="het_cross", n_pe=256,
                   cfg=SearchConfig(metric="latency", algo="anneal", seed=2),
                   label="my_point")
    assert job.name == "my_point"
    (res,) = run_portfolio([job], processes=1)
    assert res.outcome.config.algo == "anneal"
    assert np.isfinite(res.outcome.result.latency)


@pytest.mark.parametrize("cfg", [
    None,                                             # eval_backend="auto"
    SearchConfig(eval_backend="jax_ref"),
    SearchConfig(algo="beam_jax", eval_backend="numpy"),
], ids=["auto_eval", "jax_eval", "beam_jax"])
def test_run_portfolio_refuses_device_jobs_in_a_pool(cfg):
    """A chip belongs to one process: jobs that may dispatch to the JAX
    device never go to pool workers."""
    jobs = [SweepJob(scenario="xr8_outdoors", pattern="het_cb", n_pe=256,
                     cfg=cfg),
            TraceJob(trace="dc_churn_smoke", pattern="het_cross", rows=3,
                     cols=3, cfg=SearchConfig(eval_backend="numpy"))]
    assert touches_device(jobs[0]) and not touches_device(jobs[1])
    with pytest.raises(ValueError, match="JAX device"):
        run_portfolio(jobs, processes=2)


def test_touches_device_follows_search_backend_env(monkeypatch):
    job = SweepJob(scenario="xr8_outdoors", pattern="het_cb", n_pe=256,
                   cfg=SearchConfig(algo="beam", eval_backend="numpy"))
    monkeypatch.delenv("SCAR_SEARCH_BACKEND", raising=False)
    assert not touches_device(job)
    monkeypatch.setenv("SCAR_SEARCH_BACKEND", "beam_jax")
    assert touches_device(job)


def test_run_portfolio_runs_inline_by_default(monkeypatch):
    monkeypatch.delenv("SCAR_PORTFOLIO_PROCS", raising=False)
    assert default_processes() == 1
    # device jobs are fine inline, the default
    (res,) = run_portfolio([SweepJob(scenario="xr8_outdoors",
                                     pattern="het_cb", n_pe=256)])
    assert res.outcome.edp > 0
