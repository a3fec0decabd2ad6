#!/usr/bin/env python3
"""The benchmark's one command: one cell, one seed, one measured window.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration file and traffic file are found by name.  The run:

1. refuses any platform but a TPU, or fewer chips than the cell asks for
   (exit 2, no result);
2. set-up: removes the ``SCAR_*`` path overrides, keeps JAX's persistent
   compile cache at ``<checkout>/.jax_cache`` (or where
   ``JAX_COMPILATION_CACHE_DIR`` says), builds the deployment, and sends
   every distinct request of the mix once, so that every program the
   window can ask for is built or loaded before it opens;
3. the window: a closed loop, one client, requests drawn from the mix
   until ``--seconds`` have passed; the window ends when the last request
   started in it returns.  A program compiled or loaded inside the window
   makes the run not correct (``compiles_in_window``, limit 0);
4. ``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
   traces at most the mix's ``trace_requests`` requests with the JAX
   profiler and the program's spans on, and reports the per-layer metrics
   (one reader per metric, ``bench/metrics/<name>.py``);
5. checks a sample of the window's re-plans, drawn from the seed and
   holding the slowest, against the reference planner (``bench/check.py``)
   and prints the compared numbers beside their limits, last on stderr and
   last in the result line.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), then
``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import os  # noqa: E402

# One process with few threads: the host's BLAS runs on one thread, so a
# run's host work does not depend on how many cores the machine lends it.
for _var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ[_var] = "1"

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
COMPILES = "device_search.jit_recompiles"
XLA_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
FETCHES = "launch.platform.sync_count"
OP_NAME_CHARS = 160      # an op's HLO text is long; its head names it
PROGRAM_SPANS = ("schedule", "window_combine", "combine_window",
                 "evaluate_schedule", "costdb_build", "window_build")

if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402
import loadgen  # noqa: E402
import sut  # noqa: E402
from reference import scheduler as reference  # noqa: E402


class Refused(SystemExit):
    """The run cannot measure this cell here; exit 2, print no result."""

    def __init__(self, msg: str) -> None:
        print(f"bench: {msg}", file=sys.stderr)
        super().__init__(2)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(spec, cell, configuration file, mix)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as fh:
        config = json.load(fh)
    return spec, cell, config, loadgen.load_mix(cell["traffic"],
                                                cell["config"])


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile of every value, linear between ranks."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def device_info(devices, chips: int) -> dict:
    """The result's ``device`` record; refuses a non-TPU or too few chips."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "no device"
        raise Refused(f"needs a TPU; JAX found {found!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found "
                      f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def use_cache(jax) -> None:
    """JAX's persistent compile cache at a fixed path in the checkout."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileLog:
    """Every XLA compile JAX reports, built or loaded from the persistent
    cache, with its seconds (``jax.monitoring`` events)."""

    def __init__(self, jax) -> None:
        self.compiles: list[tuple[str, float]] = []
        self.cache_loads: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == XLA_COMPILE:
            self.compiles.append((str(kw.get("fun_name", "?")), duration))
        elif event == CACHE_LOAD:
            self.cache_loads.append(duration)

    def summary(self, slow_s: float = 1.0) -> str:
        slow = sorted((d, n) for n, d in self.compiles if d >= slow_s)[::-1]
        return (f"xla_compiles={len(self.compiles)} "
                f"compile_s={sum(d for _n, d in self.compiles):.3f} "
                f"cache_loads={len(self.cache_loads)} "
                f"cache_load_s={sum(self.cache_loads):.3f} "
                f"slowest=" + ",".join(f"{n}:{d:.3f}" for d, n in slow[:12]))


class RunRecord:
    """What a per-layer reader may read of one traced window."""

    def __init__(self, *, plans, spans, counters, trace, peaks, search,
                 work):
        self.plans = plans
        self.spans = spans              # program spans inside the window
        self.counters = counters        # counter name -> change in window
        self.trace = trace              # trace_reduce.Reduced
        self.peaks = peaks              # this device's row of peaks.json
        self.search = search            # reference.scheduler.Search
        self._work = work
        self._work_cache = None

    def work(self) -> list:
        """Problem sizes of every traced re-plan (``work.replan_sizes``)."""
        if self._work_cache is None:
            self._work_cache = self._work()
        return self._work_cache

    def span_total_s(self, name: str) -> float:
        return sum(s["dur"] for s in self.spans if s["name"] == name)


def read_per_layer(spec: dict, rec: RunRecord) -> dict:
    """Each of the cell's per-layer metrics, by its reader's name."""
    out = {}
    for m in spec["per_layer"]:
        reader = importlib.import_module(f"metrics.{m['name']}")
        value = reader.read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def load_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def sample_indices(n_done: int, latencies: list[float], k: int,
                   seed: int) -> list[int]:
    """``k`` completed requests drawn from the seed, the slowest among them."""
    import numpy as np

    if n_done <= k:
        return list(range(n_done))
    slowest = max(range(n_done), key=lambda i: latencies[i])
    rest = [i for i in range(n_done) if i != slowest]
    s = int(seed) % (1 << 64)
    rng = np.random.default_rng(np.random.SeedSequence(
        [7, s & 0xFFFFFFFF, s >> 32]))
    pick = rng.choice(len(rest), size=k - 1, replace=False)
    return sorted([slowest] + [rest[int(i)] for i in pick])


def run(argv=None, *, planner_factory=None, require_tpu: bool = True
        ) -> dict:
    """One run; returns the result record (``main`` prints it).

    ``planner_factory`` and ``require_tpu`` exist for the harness's own
    tests, which drive a run with a broken or stand-in planner on the CPU.
    """
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        raise Refused("--seconds must be positive")

    spec, cell, config, mix = load_cell(args.workload)
    for var in sut.PATH_ENV:
        os.environ.pop(var, None)
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    use_cache(jax)
    xla = CompileLog(jax)
    devices = jax.devices()
    if require_tpu:
        info = device_info(devices, int(cell["chips"]))
    else:
        info = {"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices)}
    used = devices[:int(cell["chips"])]

    from repro import obs
    from repro.obs import registry

    t_jax = time.perf_counter()
    planner = (planner_factory or sut.build)(config)
    n_tenants = len(config["tenants"])
    n_chiplets = int(config["package"]["rows"]) * int(
        config["package"]["cols"])

    # -- set-up: every distinct request once, so every program is built ---
    warm = loadgen.warm_requests(mix, n_tenants, n_chiplets, args.seed)
    t_warm = time.perf_counter()
    first_s = 0.0
    for a in warm:
        planner.plan(a)
        first_s = first_s or time.perf_counter() - t_warm
    programs = registry.value(COMPILES)
    gc.collect()
    setup_s = time.perf_counter() - T_START
    print(f"bench: setup start_s={t_jax - T_START:.3f} "
          f"first_request_s={first_s:.3f} "
          f"warm_s={time.perf_counter() - t_warm:.3f} {xla.summary()}",
          file=sys.stderr)

    # -- the window -------------------------------------------------------
    traced = args.trace == 1
    cap = int(mix["trace_requests"]) if traced else None
    stream = loadgen.requests(mix, n_tenants, n_chiplets, args.seed)
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        obs.enable()
        obs.reset(counters_too=False)
        jax.profiler.start_trace(TRACE_DIR)
    counters0 = registry.counters()
    xla0 = len(xla.compiles)
    anchors, outcomes, latencies, failed = [], [], [], 0
    t0 = time.perf_counter()
    deadline = t0 + args.seconds
    note = (jax.profiler.TraceAnnotation("bench.window") if traced
            else contextlib.nullcontext())
    with note:
        t_note = time.perf_counter()
        while time.perf_counter() < deadline and (
                cap is None or len(anchors) < cap):
            a = next(stream)
            req = (jax.profiler.TraceAnnotation("bench.request")
                   if traced else contextlib.nullcontext())
            with req:
                t = time.perf_counter()
                try:
                    out = planner.plan(a)
                except Exception:       # a failed request: counted, shown
                    traceback.print_exc(file=sys.stderr)
                    out = None
                    failed += 1
                latencies.append(time.perf_counter() - t)
            anchors.append(a)
            outcomes.append(out)
    t1 = time.perf_counter()
    counters1 = registry.counters()
    compiles_in_window = len(xla.compiles) - xla0
    window_s = t1 - t0
    peak = memory_peak(used)

    result = {"correct": False, "attempted": len(anchors),
              "failed": failed, "metrics": {}, "device": dict(info)}
    result["device"]["memory_peak_bytes"] = peak
    done = [i for i, o in enumerate(outcomes) if o is not None]

    if traced:
        jax.profiler.stop_trace()
        tr = obs.tracer()
        spans = [dict(e) for e in (tr.events if tr else [])
                 if "dur" in e and e["name"] in PROGRAM_SPANS]
        obs.disable()
        import trace_reduce
        import work

        # program spans onto the trace clock, to name the device's gaps
        pd = trace_reduce.load(trace_reduce.find_xplane(TRACE_DIR))
        offset_ns = trace_reduce.window(pd)[0] - t_note * 1e9
        label = [(s["name"], (tr.t0 + s["ts"]) * 1e9 + offset_ns,
                  (tr.t0 + s["ts"] + s["dur"]) * 1e9 + offset_ns)
                 for s in spans]
        reduced = trace_reduce.reduce(pd, label_spans=label)
        deltas = {k: counters1.get(k, 0) - counters0.get(k, 0)
                  for k in set(counters0) | set(counters1)}
        rec = RunRecord(
            plans=len(done), spans=spans,
            counters=deltas, trace=reduced, peaks=load_peaks(info["kind"])
            if require_tpu else {},
            search=reference.build(config).search,
            work=lambda: work.replan_sizes(
                config, [anchors[i] for i in done],
                [sut.as_result(outcomes[i]) for i in done]))
        result["metrics"] = read_per_layer(spec, rec)
        beam = rec.search.beam
        print(f"bench: trace eval_calls="
              f"{len(work.kernel_calls(reduced.op_events, 'eval'))} "
              f"search_calls="
              f"{len(work.kernel_calls(reduced.op_events, 'search', beam))} "
              f"fused_programs={sum(m[0].startswith(work.FUSED_MODULE) for m in reduced.modules)} "
              f"windows={sum(len(r) for r in rec.work())} "
              f"model_windows={sum(len(w) for r in rec.work() for w in r)}",
              file=sys.stderr)
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": [[n[:OP_NAME_CHARS], t * 1e-9] for n, t in sorted(
                reduced.ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[n, t * 1e-9] for n, t in reduced.gaps[:10]]}
    else:
        lat_ms = [x * 1e3 for x in latencies]
        result["metrics"] = {
            "plans_per_s": {"value": len(done) / window_s,
                            "unit": "plans/s"},
            "plan_ms_p90": {"value": percentile(lat_ms, 90) if lat_ms
                            else float("inf"), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    info_line = (f"bench: {cell['name']} seed={args.seed} warm={len(warm)} "
                 f"programs={programs} setup_s={setup_s:.3f} "
                 f"requests={len(anchors)} window_s={window_s:.3f} "
                 f"window_compiles="
                 f"{counters1.get(COMPILES, 0) - counters0.get(COMPILES, 0)}")
    print(info_line, file=sys.stderr)

    # -- correctness: a seeded sample of the window's re-plans ------------
    del planner
    dep = reference.build(config)
    pick = sample_indices(len(done), [latencies[i] for i in done],
                          int(config["check_requests"]), args.seed)
    readings = [check.check_replan(dep, anchors[done[i]],
                                   sut.as_result(outcomes[done[i]]))
                for i in pick]
    ok, checks = check.judge(readings, failed, compiles_in_window)
    result["correct"] = ok
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {json.dumps(c)}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    result = run(argv)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
