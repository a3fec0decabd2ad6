"""Telemetry layer: tracer semantics, registry shims, exporters, and the
two pipeline-level contracts — tracing is plan-invariant, and worker span
streams merge across the portfolio's process boundary.

Spans only record while a tracer is installed, so every test that enables
tracing restores the prior state via the autouse fixture; counters are
process-global by design, so assertions here are about deltas and resets,
never absolute values accumulated by other tests.
"""
import json

import jax
import pytest

from repro import obs
from repro.core import SearchConfig, get_scenario, get_trace, make_mcm, \
    schedule
from repro.core.portfolio import run_portfolio, sweep_grid
from repro.core.scheduler import clear_caches
from repro.launch import platform as lp
from repro.obs.tracer import NULL_SPAN, Tracer

_SMALL = SearchConfig(path_cap=32, seg_cap=64, n_splits=2)
_FUSED = SearchConfig(algo="beam_jax", path_cap=32, seg_cap=64, n_splits=2)
# the span names ``DeviceBeamEngine.combine_window`` nests under itself
_WINDOW_LEAVES = ("provision", "segment", "assemble", "pack", "dispatch",
                  "fetch", "rescore")
_PER_MODEL = ("segment", "assemble", "pack")


@pytest.fixture(autouse=True)
def _restore_tracing_state():
    was = obs.enabled()
    yield
    if not was:
        obs.disable()
    elif obs.tracer() is not None:
        obs.tracer().annotate = None


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

@pytest.mark.parametrize("scenario,pattern,n_pe,cfg,annotate", [
    pytest.param("xr8_outdoors", "het_sides", 256, _SMALL, None,
                 id="xr8_outdoors-het_sides-256"),
    pytest.param("dc1_lms", "het_cross", 4096, _SMALL, None,
                 id="dc1_lms-het_cross-4096"),
    pytest.param("dc4_lms_seg_image", "het_sides", 4096, _FUSED,
                 jax.profiler.TraceAnnotation,
                 id="dc4_lms_seg_image-het_sides-4096-beam_jax-mirror"),
])
def test_tracing_is_plan_invariant(scenario, pattern, n_pe, cfg, annotate):
    sc = get_scenario(scenario)
    mcm = make_mcm(pattern, rows=3, cols=3, n_pe=n_pe)
    obs.disable()
    off = _plans(schedule(sc, mcm, cfg))
    obs.enable(annotate=annotate)
    on = _plans(schedule(sc, mcm, cfg))
    assert on == off                       # bit-identical under tracing


# ---------------------- profiler mirror --------------------------------------

class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs enter/exit."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def _xr8_schedule():
    schedule(get_scenario("xr8_outdoors"),
             make_mcm("het_sides", rows=3, cols=3, n_pe=256), _SMALL)


def test_span_mirror_opens_one_annotation_per_span():
    _FakeAnnotation.log = []
    obs.disable()
    obs.enable()
    obs.enable(annotate=_FakeAnnotation)   # an installed tracer takes it
    obs.reset(counters_too=False)          # a fresh tracer keeps the factory
    with obs.span("outer", cat="test"):
        with obs.span("inner", cat="test"):
            pass
    _xr8_schedule()
    spans = [e["name"] for e in obs.tracer().events if "dur" in e]
    log = _FakeAnnotation.log
    assert spans[:2] == ["inner", "outer"]
    assert log[:4] == [("enter", "scar.outer"), ("enter", "scar.inner"),
                       ("exit", "scar.inner"), ("exit", "scar.outer")]
    for kind in ("enter", "exit"):
        names = sorted(n for k, n in log if k == kind)
        assert names == sorted("scar." + n for n in spans), kind


@pytest.mark.parametrize("state", ["disabled", "no_factory"])
def test_span_mirror_is_off_without_tracer_or_factory(state):
    _FakeAnnotation.log = []
    obs.disable()
    if state == "no_factory":
        obs.enable()
        obs.reset(counters_too=False)
    else:
        # a factory dies with its tracer: enabling again brings none back
        obs.enable(annotate=_FakeAnnotation)
        obs.disable()
    _xr8_schedule()
    with obs.span("outer", cat="test"):
        pass
    assert _FakeAnnotation.log == []
    assert obs.enabled() == (state == "no_factory")


def test_span_mirror_closes_annotation_when_span_raises():
    _FakeAnnotation.log = []
    obs.disable()
    obs.enable(annotate=_FakeAnnotation)
    with pytest.raises(ValueError):
        with obs.span("failing", cat="test"):
            raise ValueError("boom")
    assert _FakeAnnotation.log == [("enter", "scar.failing"),
                                   ("exit", "scar.failing")]


# ---------------------- pipeline instrumentation -----------------------------

def _fused_dc4():
    schedule(get_scenario("dc4_lms_seg_image"),
             make_mcm("het_sides", rows=3, cols=3, n_pe=4096), _FUSED)


def test_fused_window_leaf_spans_nest_under_combine_window():
    obs.disable()
    obs.enable()
    obs.reset(counters_too=False)
    _fused_dc4()
    evs = [e for e in obs.tracer().events if "dur" in e]
    windows = {e["sid"]: e for e in evs if e["name"] == "combine_window"}
    assert len(windows) == _FUSED.n_splits + 1
    leaves = [e for e in evs if e["name"] in _WINDOW_LEAVES]
    assert all(e["parent"] in windows and e["cat"] == "engine"
               for e in leaves)
    for sid, win in windows.items():
        mine = [e["name"] for e in leaves if e["parent"] == sid]
        n_models = win["args"]["models"]
        for name in _WINDOW_LEAVES:
            want = n_models if name in _PER_MODEL else 1
            assert mine.count(name) == want, (name, mine)
        # the leaves run one after another inside their window
        inner = sum(e["dur"] for e in leaves if e["parent"] == sid)
        assert inner <= win["dur"]
    assembled = [e["args"] for e in leaves if e["name"] == "assemble"]
    assert all(a["cands"] > 0 for a in assembled)


def test_fused_window_counters_match_the_programs_sent():
    names = ("engine.window.rows_real", "engine.window.rows_padded",
             "engine.window.upload_bytes")
    before = {n: obs.registry.value(n) for n in names}
    obs.disable()
    obs.enable()
    obs.reset(counters_too=False)
    _fused_dc4()
    real, padded, upload = (obs.registry.value(n) - before[n]
                            for n in names)
    evs = [e for e in obs.tracer().events if "dur" in e]
    windows = {e["sid"]: e["args"]["models"] for e in evs
               if e["name"] == "combine_window"}
    cands = sum(e["args"]["cands"] for e in evs if e["name"] == "assemble")
    rows = sum(e["args"]["n_pad"] * windows[e["parent"]] for e in evs
               if e["name"] == "dispatch")
    assert 0 < real <= padded
    assert real == cands                   # every real candidate, once
    assert padded == rows                  # the pool axis, every model
    packed = sum(e["args"]["rows"] for e in evs if e["name"] == "pack")
    assert upload > 4 * packed             # at least the tier of each row


def test_schedule_without_tracing_still_counts_window_rows():
    before = obs.registry.value("engine.window.rows_real")
    obs.disable()
    _fused_dc4()
    assert obs.registry.value("engine.window.rows_real") > before


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
