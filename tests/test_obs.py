"""Telemetry layer: tracer semantics, registry shims, exporters, and the
two pipeline-level contracts — tracing is plan-invariant, and worker span
streams merge across the portfolio's process boundary.

Spans only record while a tracer is installed, so every test that enables
tracing restores the prior state via the autouse fixture; counters are
process-global by design, so assertions here are about deltas and resets,
never absolute values accumulated by other tests.
"""
import json

import pytest

from repro import obs
from repro.core import SearchConfig, get_scenario, get_trace, make_mcm, \
    schedule
from repro.core.portfolio import run_portfolio, sweep_grid
from repro.core.scheduler import clear_caches
from repro.launch import platform as lp
from repro.obs.tracer import NULL_SPAN, Tracer

_SMALL = SearchConfig(path_cap=32, seg_cap=64, n_splits=2)


@pytest.fixture(autouse=True)
def _restore_tracing_state():
    was = obs.enabled()
    yield
    if not was:
        obs.disable()


def _plans(outcome):
    return (tuple(w.plan for w in outcome.windows),
            outcome.result.latency, outcome.result.energy)


# ---------------------- tracer unit semantics --------------------------------

def test_disabled_span_is_shared_noop_singleton():
    obs.disable()
    assert not obs.enabled()
    s = obs.span("anything", cat="scheduler", window=3)
    assert s is NULL_SPAN
    assert obs.span("other") is s          # cached, no per-call allocation
    with s as inner:
        assert inner.set(more=1) is inner  # set() is a no-op that chains
    obs.event("ignored", cat="scheduler")  # no tracer, no effect
    assert obs.snapshot() is None
    assert obs.summary() == []


def test_spans_nest_and_record_attributes():
    tr = Tracer()
    with tr.span("outer", "engine", {"models": 2}) as outer:
        with tr.span("inner", "engine", {"stage": 0}) as inner:
            inner.set(cands=17)
        assert inner.parent == outer.sid
    assert outer.parent == -1
    by_name = {e["name"]: e for e in tr.events}
    assert by_name["inner"]["parent"] == by_name["outer"]["sid"]
    assert by_name["inner"]["args"] == {"stage": 0, "cands": 17}
    assert by_name["outer"]["args"] == {"models": 2}
    for e in tr.events:
        assert e["dur"] >= 0 and e["cpu"] >= 0 and e["ts"] >= 0
    # instants attach to the enclosing span
    with tr.span("host", "evaluator", {}) as host:
        tr.instant("jit_compile", "evaluator", {"backend": "jax_ref"})
    inst = [e for e in tr.events if "dur" not in e]
    assert len(inst) == 1 and inst[0]["parent"] == host.sid


def test_merge_rebases_ids_onto_parent_timebase():
    parent, worker = Tracer(), Tracer()
    with parent.span("job", "portfolio", {}):
        pass
    with worker.span("outer", "scheduler", {}):
        with worker.span("inner", "scheduler", {}):
            pass
    snap = {"pid": worker.pid, "wall0": worker.wall0,
            "events": list(worker.events)}
    parent.merge(snap, pid=7)
    assert len({e["sid"] for e in parent.events}) == len(parent.events)
    merged = [e for e in parent.events if e["pid"] == 7]
    assert {e["name"] for e in merged} == {"outer", "inner"}
    by_name = {e["name"]: e for e in merged}
    assert by_name["inner"]["parent"] == by_name["outer"]["sid"]
    # new spans after the merge keep allocating unique ids
    with parent.span("after", "portfolio", {}):
        pass
    assert len({e["sid"] for e in parent.events}) == len(parent.events)


# ---------------------- registry + shims -------------------------------------

def test_counter_registry_and_cache_stats_discovery():
    c = obs.counter("test_site.cache_hit")
    assert obs.counter("test_site.cache_hit") is c   # one object per name
    obs.registry.reset("test_site.")
    c.inc()
    obs.counter("test_site.cache_miss").inc(3)
    stats = obs.cache_stats()["test_site"]
    assert stats == {"hits": 1, "misses": 3, "hit_rate": 0.25}
    g = obs.gauge("test_site.depth")
    g.set(2.5)
    g.add(0.5)
    assert obs.gauges("test_site.")["test_site.depth"] == 3.0
    obs.registry.reset("test_site.")
    assert obs.registry.value("test_site.cache_hit") == 0


def test_sync_count_is_a_registry_shim():
    lp.reset_sync_count()
    assert lp.sync_count() == 0
    assert obs.registry.value("launch.platform.sync_count") == 0
    obs.counter("launch.platform.sync_count").inc(4)
    assert lp.sync_count() == 4            # one source of truth
    lp.reset_sync_count()
    assert obs.registry.value("launch.platform.sync_count") == 0


def test_clear_caches_resets_cache_counters():
    sc = get_scenario("xr8_outdoors")
    mcm = make_mcm("het_sides", rows=3, cols=3, n_pe=256)
    clear_caches()
    for site, vals in obs.cache_stats().items():
        if site in ("costdb", "candidates", "window_memo", "paths"):
            assert vals["hits"] == 0 and vals["misses"] == 0, site
    schedule(sc, mcm, _SMALL)
    stats = obs.cache_stats()
    assert stats["costdb"]["misses"] >= 1
    assert stats["paths"]["misses"] >= 1
    schedule(sc, mcm, _SMALL)              # warm second run
    assert obs.cache_stats()["costdb"]["hits"] >= 1


def test_device_program_recompile_counter_counts_first_seen_only():
    from repro.core import device_search as ds
    before = obs.registry.value("device_search.jit_recompiles")
    key = ("test-only", 3, (1, 2))
    ds.note_program("fused", key)
    ds.note_program("fused", key)          # same signature: no recompile
    assert obs.registry.value("device_search.jit_recompiles") == before + 1
    ds.note_program("protocol", key)       # new program kind: recompile
    assert obs.registry.value("device_search.jit_recompiles") == before + 2


# ---------------------- plan invariance --------------------------------------

@pytest.mark.parametrize("scenario,pattern,n_pe", [
    ("xr8_outdoors", "het_sides", 256),
    ("dc1_lms", "het_cross", 4096),
])
def test_tracing_is_plan_invariant(scenario, pattern, n_pe):
    sc = get_scenario(scenario)
    mcm = make_mcm(pattern, rows=3, cols=3, n_pe=n_pe)
    obs.disable()
    off = _plans(schedule(sc, mcm, _SMALL))
    obs.enable()
    on = _plans(schedule(sc, mcm, _SMALL))
    assert on == off                       # bit-identical under tracing


# ---------------------- pipeline instrumentation -----------------------------

def test_schedule_emits_span_taxonomy():
    sc = get_scenario("xr8_outdoors")
    mcm = make_mcm("het_sides", rows=3, cols=3, n_pe=256)
    clear_caches()
    obs.enable()
    obs.reset()
    schedule(sc, mcm, _SMALL)
    names = {(e["cat"], e["name"]) for e in obs.tracer().events}
    for expected in [("scheduler", "schedule"), ("scheduler", "window_build"),
                     ("scheduler", "window_combine"),
                     ("scheduler", "evaluate_schedule"),
                     ("scheduler", "costdb_build"), ("engine", "combine"),
                     ("engine", "beam_stage")]:
        assert expected in names, expected
    sched = next(e for e in obs.tracer().events if e["name"] == "schedule")
    assert sched["args"]["scenario"] == "xr8_outdoors"
    assert sched["parent"] == -1
    rows = obs.summary()
    assert rows and abs(sum(r["share"] for r in rows
                            if r["name"] == "schedule") - 1.0) < 1e-6
    assert "schedule" in obs.format_summary()
    dump = obs.bench_dump()
    assert "counters" in dump and "scheduler.schedule" in dump["spans"]


def test_online_simulation_emits_spans_and_report_gauges():
    obs.enable()
    obs.reset()
    from repro.online import simulate, slo_report
    sim = simulate(get_trace("dc_churn_smoke"), pattern="het_cross",
                   rows=3, cols=3, n_pe=1024, cfg=_SMALL)
    cats = {e["cat"] for e in obs.tracer().events}
    assert "online" in cats and "scheduler" in cats
    names = {e["name"] for e in obs.tracer().events if e["cat"] == "online"}
    assert {"epoch", "serve", "replan"} <= names
    assert obs.registry.value("online.replan.memo_miss") >= 1
    rep = slo_report(sim)
    assert rep.gauges.get("online.active_tenants") is not None
    assert rep.gauges.get("online.replan.memo_miss", 0) >= 1


def test_portfolio_merges_worker_spans_and_counters():
    obs.enable()
    obs.reset()
    jobs = sweep_grid(["xr10_vr_gaming", "xr8_outdoors"], ["het_cb"],
                      eval_backend="numpy")
    run_portfolio(jobs, processes=2)
    tr = obs.tracer()
    job_evs = [e for e in tr.events if e["name"] == "job"]
    # stable submission-order process ids, one per affinity batch
    assert {e["pid"] for e in job_evs} == {1, 2}
    assert {e["args"]["job"] for e in job_evs} == {j.name for j in jobs}
    # worker-side nested spans survive the merge with parentage intact
    sids = {e["sid"] for e in tr.events}
    assert len(sids) == len(tr.events)
    scheds = [e for e in tr.events if e["name"] == "schedule"]
    assert scheds and all(e["parent"] in sids for e in scheds)
    # worker counters folded into the parent registry: each batch builds
    # its own CostDB in its own process
    assert obs.registry.value("costdb.cache_miss") >= 2


# ---------------------- exporters --------------------------------------------

def test_chrome_trace_schema(tmp_path):
    sc = get_scenario("xr8_outdoors")
    mcm = make_mcm("het_sides", rows=3, cols=3, n_pe=256)
    obs.enable()
    obs.reset(counters_too=False)
    schedule(sc, mcm, _SMALL)
    path = tmp_path / "trace.json"
    trace = obs.chrome_trace(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"] == trace["traceEvents"]
    assert loaded["displayTimeUnit"] == "ms"
    phases = {"M", "X", "i", "C"}
    for ev in loaded["traceEvents"]:
        assert ev["ph"] in phases
        assert isinstance(ev["name"], str) and "pid" in ev and "tid" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and ev["ts"] >= 0
            json.dumps(ev["args"])         # attributes are JSON-safe
        if ev["ph"] == "C":
            assert isinstance(ev["args"]["value"], (int, float))
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in loaded["traceEvents"])
    xs = [e for e in loaded["traceEvents"] if e["ph"] == "X"]
    assert xs == sorted(xs, key=lambda e: e["ts"])
    assert "counters" in loaded["otherData"]


def test_chrome_trace_requires_enabled_tracer():
    obs.disable()
    with pytest.raises(RuntimeError):
        obs.chrome_trace()
    assert obs.format_summary() == "(tracing disabled)"
