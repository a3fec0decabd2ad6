#!/usr/bin/env python3
"""One ``bench/run.py`` run that also reads the window search's leaf spans.

    python3 bench/tests/measure_spans.py [--python-tracer-level N]
        -- --workload <cell> --seed <n> --seconds 30 --trace 1

Run on a machine with a TPU, from the root of a checkout.  It makes, in
memory only, the edits to ``bench/run.py`` that PERF.md's open questions
list for a benchmark change, and runs the cell as ``run.py`` would:

* the seven leaf spans of ``DeviceBeamEngine._combine_window`` join
  ``run.PROGRAM_SPANS``, so the run record carries them;
* the traced run enables the program's spans with
  ``annotate=jax.profiler.TraceAnnotation``, and the idle gaps take the
  ``scar.`` annotations as their labels
  (``trace_reduce.reduce(pd, label_spans=span_gaps.program_spans(pd))``);
  a trace with none keeps ``run.py``'s own labels;
* ``--python-tracer-level`` starts the profiler with that
  ``ProfileOptions.python_tracer_level`` instead of JAX's default.

A traced run prints, on stderr, ``measure: span_ms_per_plan {...}`` (each
leaf and ``combine_window`` in ms per plan, and the leaves' share of
``combine_window``) and ``run.py``'s would-be ``bench: idle_by_span``
line.  The last stdout line is ``run.py``'s result record.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

LEAVES = ("provision", "segment", "assemble", "pack", "dispatch", "fetch",
          "rescore")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--python-tracer-level", type=int, default=None)
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    run_args = args.run_args[1:] if args.run_args[:1] == ["--"] \
        else args.run_args

    import run
    import span_gaps
    import trace_reduce

    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import jax
    from repro import obs

    run.PROGRAM_SPANS = run.PROGRAM_SPANS + LEAVES
    enable = obs.enable
    obs.enable = lambda: enable(annotate=jax.profiler.TraceAnnotation)
    if args.python_tracer_level is not None:
        start_trace = jax.profiler.start_trace

        def start(log_dir):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = args.python_tracer_level
            return start_trace(log_dir, profiler_options=opts)
        jax.profiler.start_trace = start

    split = {}
    reduce = trace_reduce.reduce

    def reduce_by_program_spans(pd, label_spans=None):
        red = reduce(pd, label_spans=span_gaps.program_spans(pd)
                     or label_spans)
        split.update(span_gaps.idle_by_span(pd, red))
        return red
    trace_reduce.reduce = reduce_by_program_spans

    read_per_layer = run.read_per_layer

    def read_and_report(spec, rec):
        per = {n: rec.span_total_s(n) / max(rec.plans, 1) * 1e3
               for n in ("combine_window",) + LEAVES}
        share = sum(per[n] for n in LEAVES) / max(per["combine_window"],
                                                  1e-12)
        print(f"measure: span_ms_per_plan {json.dumps(per)} "
              f"leaves_share={share:.4f}", file=sys.stderr)
        print(span_gaps.idle_line(split, rec.plans), file=sys.stderr)
        return read_per_layer(spec, rec)
    run.read_per_layer = read_and_report

    print(json.dumps(run.run(run_args)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
