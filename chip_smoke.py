"""Bring-up smoke of the scheduler's device path on one TPU chip.

    python3 chip_smoke.py

Runs in one process, which holds the chip, and refuses any platform but a
TPU.  Each phase goes through the scheduler's normal entry points and is
checked against the repository's own host oracles:

A. ``schedule`` of dc4_lms_seg_image on a 16x16 het_cb package at the
   production search width (``WIDTHS``) with ``algo="beam_jax"``: the
   engine must select the Pallas kernels, the schedule must make exactly
   one counted device fetch per window, and every window plan must equal
   the host beam over the float64 numpy evaluator.  A window whose plan
   differs passes only when both picks tie in that oracle at ``SCORE_SIG``
   digits; each such tie is printed.
B. The same comparison under ``comm_model="congestion"`` on an 8x8
   het_cross package, which compiles the fused program's in-jit routing.
C. Online re-planning of the SLO-classed churn trace ``REPLAN_TRACE`` on
   an 8x8 het_cross package with ``algo="beam_jax"``: the warm re-planner
   against the cold oracle, epoch by epoch.

First-call times include compilation; the persistent compile cache is
placed by ``launch.platform.use_compile_cache``, so a second run shows what
the cache saves.  The last line of stdout is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
"""
from __future__ import annotations

import json
import os
import sys
import time

# the production search width of a 16x16 pod (bench_fused_search's)
WIDTHS = dict(n_splits=4, path_cap=8192, keep_per_model=128, beam=128)
# Phase C's trace.  Every distinct tenant set compiles its own window
# programs; the longer dc_churn_8x8_slo (40 s, up to 4 tenants) needs 92 of
# them, too many for a cold run, so phase C replays its 12 s, 2-tenant
# variant.
REPLAN_TRACE = "dc_churn_slo_smoke"
# environment overrides that would reroute the compared paths or read
# CostDBs built by other code
_PATH_ENV = ("SCAR_SEARCH_BACKEND", "SCAR_EVAL_BACKEND",
             "SCAR_EVAL_AUTO_THRESHOLD", "SCAR_COSTDB_CACHE")


def device_info(devices) -> dict:
    """The ``device`` record of a TPU device list; exits non-zero otherwise."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "no device"
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {found!r}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def compare_schedules(dev, host, metric: str) -> int:
    """Assert ``dev`` plans what the host oracle ``host`` plans.

    Returns the number of windows whose plans differ but tie in the
    float64 oracle at ``SCORE_SIG`` digits; any other difference raises.
    """
    from repro.core.engine import metric_score
    from repro.core.quantize import SCORE_SIG, quantize_scores

    def q(r):
        return float(quantize_scores(
            [metric_score(r.latency, r.energy, metric)], sig=SCORE_SIG)[0])

    assert len(dev.windows) == len(host.windows), (
        f"{len(dev.windows)} device windows vs {len(host.windows)} host")
    ties = 0
    for k, (d, h) in enumerate(zip(dev.windows, host.windows)):
        if d.plan == h.plan:
            continue
        if q(d.result) != q(h.result):
            raise AssertionError(
                f"window {k}: device plan differs from the host oracle "
                f"({metric} {q(d.result)!r} vs {q(h.result)!r})")
        ties += 1
        print(f"  window {k}: tie at {SCORE_SIG} digits, {metric} "
              f"{q(d.result)!r}; device {d.plan} host {h.plan}")
    if ties:
        assert q(dev.result) == q(host.result), "tied schedules differ"
    else:
        assert dev.result.latency == host.result.latency
        assert dev.result.energy == host.result.energy
    return ties


def schedule_phase(name: str, label: str, sc, mcm, **cfg_kw) -> None:
    """One fused schedule against the host oracle (phases A and B)."""
    from repro import obs
    from repro.core import SearchConfig, schedule
    from repro.core.engine import DeviceBeamEngine, get_engine
    from repro.launch import platform as lp

    dev_cfg = SearchConfig(algo="beam_jax", **cfg_kw)
    host_cfg = SearchConfig(algo="beam", eval_backend="numpy", **cfg_kw)
    engine = get_engine(dev_cfg)
    assert isinstance(engine, DeviceBeamEngine)
    assert engine.uses_kernels(), "DeviceBeamEngine did not select Pallas"
    print(f"[{name}] {sc.name} on {mcm.name}, "
          f"comm_model={dev_cfg.comm_model}, kernels=pallas")

    lp.reset_sync_count()
    before = obs.registry.value("device_search.jit_recompiles")
    dev, first_s = timed(lambda: schedule(sc, mcm, dev_cfg))
    compiled = obs.registry.value("device_search.jit_recompiles") - before
    syncs = lp.sync_count()
    assert syncs == len(dev.windows), (
        f"{syncs} device fetches for {len(dev.windows)} windows")
    warm = []
    for _ in range(2):
        lp.reset_sync_count()
        again, t = timed(lambda: schedule(sc, mcm, dev_cfg))
        assert lp.sync_count() == len(again.windows)
        assert [w.plan for w in again.windows] == [w.plan for w in dev.windows]
        warm.append(t)
    host, host_s = timed(lambda: schedule(sc, mcm, host_cfg))
    ties = compare_schedules(dev, host, dev_cfg.metric)
    print(f"[{name}] windows={len(dev.windows)} fetches/window=1 "
          f"device_programs_compiled={compiled}; plans identical to the "
          f"host oracle except {ties} tie(s)")
    print(f"[{name}] first call (compile included) {first_s:.3f} s, "
          f"best warm {min(warm):.3f} s on {label}; host oracle "
          f"{host_s:.3f} s on the host CPU")


def replan_phase(label: str, trace_name: str, rows: int, cols: int) -> None:
    """Warm re-planning against the cold oracle (phase C)."""
    from repro import obs
    from repro.core import SearchConfig, get_trace
    from repro.online import simulate

    cfg = SearchConfig(algo="beam_jax")
    trace = get_trace(trace_name)
    before = obs.registry.value("device_search.jit_recompiles")
    warm, warm_s = timed(lambda: simulate(trace, rows=rows, cols=cols,
                                          cfg=cfg, mode="warm"))
    compiled = obs.registry.value("device_search.jit_recompiles") - before
    cold, cold_s = timed(lambda: simulate(trace, rows=rows, cols=cols,
                                          cfg=cfg, mode="cold"))

    def key(e):
        plans = (None if e.outcome is None
                 else tuple(w.plan for w in e.outcome.windows))
        return (e.t_start, e.t_end, e.tenants, e.tenant_order, plans,
                e.iterations, e.energy, e.n_preempted)

    assert len(warm.epochs) == len(cold.epochs) > 0
    for k, (ew, ec) in enumerate(zip(warm.epochs, cold.epochs)):
        assert key(ew) == key(ec), f"epoch {k}: warm differs from cold"
    assert warm.latency_samples == cold.latency_samples
    assert warm.slo_samples == cold.slo_samples
    assert warm.total_energy == cold.total_energy
    assert warm.busy_s == cold.busy_s
    assert warm.n_replans == cold.n_replans
    print(f"[C] {trace.name} on {rows}x{cols}: epochs={len(warm.epochs)} "
          f"replans={warm.n_replans} memo_hits={warm.n_memo_hits} "
          f"device_programs_compiled={compiled}; warm == cold oracle")
    print(f"[C] warm replay first call (compile included) {warm_s:.3f} s, "
          f"cold replay {cold_s:.3f} s on {label}")


def main() -> None:
    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(root, "src"))
    for var in _PATH_ENV:
        os.environ.pop(var, None)
    import jax

    from repro.core import get_scenario, make_mcm
    from repro.launch.platform import use_compile_cache

    info = device_info(jax.devices())
    label = f"{info['kind']} x{info['count']}"
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}; compile cache {use_compile_cache(root)}")

    dc4 = get_scenario("dc4_lms_seg_image")
    schedule_phase("A", label, dc4,
                   make_mcm("het_cb", rows=16, cols=16, n_pe=4096), **WIDTHS)
    schedule_phase("B", label, dc4,
                   make_mcm("het_cross", rows=8, cols=8, n_pe=4096),
                   comm_model="congestion", **WIDTHS)
    replan_phase(label, REPLAN_TRACE, rows=8, cols=8)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
