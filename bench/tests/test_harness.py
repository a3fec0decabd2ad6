"""CPU self-tests of the benchmark harness (no chip, no program run).

    python -m pytest -q bench/tests/test_harness.py
"""
import importlib
import itertools
import json
import os
import re
import types

import numpy as np
import pytest

import loadgen
import run
import trace_reduce
import work

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _mixes():
    return [(w["traffic"], w["config"]) for w in spec()["workloads"]]


@pytest.mark.parametrize("traffic,config", _mixes())
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_replan_stream_is_seeded_and_recorded(traffic, config, seed):
    mix = loadgen.load_mix(traffic, config)
    recorded = [{int(m): c for m, c in r.items()} for r in mix["requests"]]
    first = list(itertools.islice(loadgen.requests(mix, 4, 36, seed), 300))
    again = list(itertools.islice(loadgen.requests(mix, 4, 36, seed), 300))
    assert first == again
    assert all(r in recorded for r in first)
    other = list(itertools.islice(loadgen.requests(mix, 4, 36, seed + 1),
                                  20))
    assert other != first[:20]
    # set-up meets every distinct request once, in a seeded order
    warm = loadgen.warm_requests(mix, 4, 36, seed)
    assert len(warm) == len({tuple(sorted(r.items())) for r in recorded})
    assert {tuple(sorted(r.items())) for r in warm} == {
        tuple(sorted(r.items())) for r in recorded}
    assert warm == loadgen.warm_requests(mix, 4, 36, seed)


def _write_mix(tmp_path, monkeypatch, **over):
    mix = {"kind": "recorded_replan", "recorded_on": "cfg",
           "recording": "test", "requests": [{"0": 1}, {"1": 2}],
           "trace_requests": 4, "why": "test"}
    mix.update(over)
    (tmp_path / "traffic").mkdir(exist_ok=True)
    (tmp_path / "traffic" / "m.json").write_text(json.dumps(mix))
    monkeypatch.setattr(loadgen, "HERE", str(tmp_path))


def test_mix_is_refused_for_another_configuration(tmp_path, monkeypatch):
    _write_mix(tmp_path, monkeypatch)
    assert loadgen.load_mix("m", "cfg")["recorded_on"] == "cfg"
    with pytest.raises(ValueError, match="recorded on"):
        loadgen.load_mix("m", "other")


@pytest.mark.parametrize("key,value", [("clients", 4), ("window_memo", True),
                                       ("repeat_requests", True)])
def test_mix_key_the_harness_does_not_read_is_refused(tmp_path, monkeypatch,
                                                      key, value):
    _write_mix(tmp_path, monkeypatch, **{key: value})
    with pytest.raises(ValueError, match="does not read"):
        loadgen.load_mix("m", "cfg")


def test_request_off_the_package_is_refused(tmp_path, monkeypatch):
    _write_mix(tmp_path, monkeypatch, requests=[{"0": 36}])
    mix = loadgen.load_mix("m", "cfg")
    with pytest.raises(ValueError, match="out of range"):
        next(loadgen.requests(mix, 4, 36, 1))


def _fake_devices(platform, n):
    dev = types.SimpleNamespace(platform=platform, device_kind=platform)
    return [dev] * n


@pytest.mark.parametrize("platform,n,chips", [("cpu", 1, 1), ("gpu", 4, 1),
                                              ("tpu", 1, 4)])
def test_refuses_other_platforms_and_too_few_chips(platform, n, chips):
    with pytest.raises(SystemExit) as err:
        run.device_info(_fake_devices(platform, n), chips)
    assert err.value.code == 2


def test_accepts_a_tpu():
    info = run.device_info(_fake_devices("tpu", 4), 4)
    assert info == {"platform": "tpu", "kind": "tpu", "count": 4}


def test_run_off_the_chip_exits_nonzero_and_prints_nothing(capsys):
    with pytest.raises(SystemExit) as err:
        run.main(["--workload", spec()["workloads"][0]["name"],
                  "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert err.value.code == 2
    assert capsys.readouterr().out == ""


def test_every_name_resolves_to_its_files():
    s = spec()
    configs = {c["name"]: c for c in s["configs"]}
    for c in s["configs"]:
        with open(os.path.join(ROOT, c["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert int(cfg["check_requests"]) >= 1
    for w in s["workloads"]:
        assert w["config"] in configs
        mix = loadgen.load_mix(w["traffic"], w["config"])
        assert int(mix["trace_requests"]) >= 1
    e2e = {m["name"] for m in s["end_to_end"]}
    for m in s["per_layer"]:
        reader = importlib.import_module(f"metrics.{m['name']}")
        assert callable(reader.read)
        assert m["moves"] in e2e
    for name in ([c["name"] for c in s["configs"]]
                 + [w["name"] for w in s["workloads"]]
                 + [m["name"] for m in s["end_to_end"] + s["per_layer"]]):
        assert NAME.match(name), name


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit) as err:
        run.load_cell("no_such_cell")
    assert err.value.code == 2


def test_p90_is_taken_over_every_request():
    rng = np.random.default_rng(0)
    lat = list(rng.lognormal(0.0, 1.0, size=1000))
    assert run.percentile(lat, 90) == pytest.approx(
        float(np.percentile(lat, 90)), rel=1e-12)
    # a slow tail bunched at the end: the p90 of all requests sees it, a
    # mean of per-chunk p90s does not
    lat = [1.0] * 880 + [100.0] * 120
    chunks = [lat[i:i + 100] for i in range(0, 1000, 100)]
    per_chunk = sum(run.percentile(c, 90) for c in chunks) / len(chunks)
    assert run.percentile(lat, 90) == 100.0
    assert per_chunk == pytest.approx(20.8)


def test_sample_holds_the_slowest_and_follows_the_seed():
    lat = [0.1] * 50
    lat[17] = 9.0
    a = run.sample_indices(50, lat, 6, 12345)
    assert 17 in a and len(a) == 6 == len(set(a))
    assert a == run.sample_indices(50, lat, 6, 12345)
    assert run.sample_indices(4, lat[:4], 6, 1) == [0, 1, 2, 3]


def test_compile_log_counts_compiles_and_cache_loads():
    log = run.CompileLog.__new__(run.CompileLog)
    log.compiles, log.cache_loads = [], []
    log._on(run.XLA_COMPILE, 31.5, fun_name="jit_fused_program")
    log._on(run.XLA_COMPILE, 0.01, fun_name="jit_small")
    log._on(run.CACHE_LOAD, 0.25)
    log._on("/jax/other", 9.0)
    assert len(log.compiles) == 2 and log.cache_loads == [0.25]
    line = log.summary()
    assert "xla_compiles=2" in line and "cache_loads=1" in line
    assert "jit_fused_program:31.500" in line and "jit_small" not in line


def test_union_and_gaps():
    ivs = [(0, 2), (1, 3), (5, 6), (5.5, 5.7)]
    assert trace_reduce.union_length(ivs) == 4
    assert trace_reduce.gaps_between(ivs, -1, 8) == [(-1, 0), (3, 5), (6, 8)]
    assert trace_reduce.union_length([]) == 0


def test_kernel_bytes_count_real_candidates():
    mw = work.ModelWindow(n_cands=100, n_tier0=60, n_layers=10, n_segs=3,
                          n_classes=2, words32=2)
    assert work.eval_bytes(mw) == 100 * (2 * 10 * 4 + 3 * 3 * 4 + 4 + 8) \
        + 2 * 10 * 2 * 4
    assert work.search_bytes(100, 8, 2) == 100 * 2 * 4 + 8 * 2 * 4 \
        + 8 * 100 * 4


def test_peaks_table_names_its_source():
    with open(os.path.join(run.BENCH, "peaks.json")) as fh:
        table = json.load(fh)
    assert "TPU v5e" in table["source"]
    row = run.load_peaks("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9 and row["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        run.load_peaks("cpu")


FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "small_trace.xplane.pb")


def test_reduction_of_a_recorded_trace():
    """``record_trace.py`` on a TPU v5e: two requests of four jitted steps,
    20 ms of host pause between them in a ``bench.idle`` annotation."""
    pd = trace_reduce.load(FIXTURE)
    red = trace_reduce.reduce(pd)
    lo, hi = red.window_ns
    assert red.devices == 1
    assert 0 < red.busy_ns < hi - lo
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    # busy and idle tile the window
    assert red.busy_ns + sum(g for _n, g in red.gaps) == pytest.approx(
        hi - lo, rel=1e-9)
    # the longest gap is the 20 ms pause, named by its annotation
    name, ns = red.gaps[0]
    assert name == "bench.idle" and 20e6 <= ns < 40e6
    assert sum(red.ops.values()) >= red.busy_ns
    assert all(lo <= s and s + d <= hi for _n, s, d in red.op_events)
    names = {n for n, _s, _e in red.annotations}
    assert {"bench.window", "bench.request", "bench.idle"} <= names
