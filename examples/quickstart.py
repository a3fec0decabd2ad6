"""Quickstart: schedule a multi-model AI workload on a heterogeneous MCM.

    PYTHONPATH=src python examples/quickstart.py

Reproduces the paper's core result on one scenario: the SCAR scheduler on a
heterogeneous MCM vs the homogeneous Simba baselines.
"""
import os
import sys

sys.path.insert(0, "src")

from repro.core import SearchConfig, get_scenario
from repro.core.portfolio import SweepJob, run_portfolio
from repro.launch.platform import use_compile_cache


def main() -> None:
    use_compile_cache(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sc = get_scenario("xr10_vr_gaming")  # EyeCod + HandSP (Table II #10)
    print(f"scenario: {sc.name}  models: "
          f"{[(m.name, len(m)) for m in sc.models]}\n")

    jobs = [SweepJob(scenario=sc.name, pattern=pattern, n_pe=256,
                     standalone=standalone, cfg=SearchConfig(metric="edp"),
                     label=name)
            for name, pattern, standalone in [
                ("standalone NVDLA", "simba_nvdla", True),
                ("Simba (NVDLA)", "simba_nvdla", False),
                ("Simba (Shi-diannao)", "simba_shi", False),
                ("Het-CB", "het_cb", False),
                ("Het-Sides", "het_sides", False),
                ("Het-Cross", "het_cross", False),
            ]]
    results = {r.job.name: r.outcome for r in run_portfolio(jobs)}

    base = results["standalone NVDLA"].edp
    print(f"{'config':22s} {'latency':>10s} {'energy':>10s} "
          f"{'EDP':>10s} {'norm EDP':>9s}")
    for name, out in results.items():
        r = out.result
        print(f"{name:22s} {r.latency:10.4g} {r.energy:10.4g} "
              f"{out.edp:10.4g} {out.edp / base:9.3f}")

    best = min(results, key=lambda k: results[k].edp)
    out = results[best]
    print(f"\nbest: {best} — schedule:")
    for w, wr in enumerate(out.windows):
        for p in wr.plan.plans:
            print(f"  window {w}: model {sc.models[p.model_idx].name:8s} "
                  f"layers [{p.start},{p.end}) -> chiplets {p.chiplets} "
                  f"({'pipelined' if p.pipelined and p.n_segments > 1 else 'sequential'})")


if __name__ == "__main__":
    main()
