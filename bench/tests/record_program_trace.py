#!/usr/bin/env python3
"""Record the trace ``test_span_gaps.py`` checks the gap naming on.

    python3 bench/tests/record_program_trace.py \\
        bench/tests/data/program_trace.xplane.pb

Run on a machine with a TPU.  Like ``record_trace.py``, but the program's
own spans are on and mirrored into the profiler
(``repro.obs.enable(annotate=jax.profiler.TraceAnnotation)``): inside a
``bench.window`` annotation, two ``bench.request`` annotations each hold a
``combine_window`` span with four leaves: ``pack`` (5 ms of host work and
the input's copy to the device), ``dispatch`` (four jitted steps),
``fetch`` (the wait for the result) and ``rescore`` (20 ms of host work).
The profiler's ``.xplane.pb`` is written to the path given.
"""
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import trace_reduce
    from repro import obs

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_program_trace: needs a TPU")
    step = jax.jit(lambda x: jnp.tanh(x @ x) + 1.0)
    host = np.ones((512, 512), np.float32)
    step(jnp.asarray(host)).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        obs.enable(annotate=jax.profiler.TraceAnnotation)
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.request"), \
                        obs.span("combine_window", cat="engine"):
                    with obs.span("pack", cat="engine"):
                        time.sleep(0.005)
                        y = jnp.asarray(host)
                    with obs.span("dispatch", cat="engine"):
                        for _ in range(4):
                            y = step(y)
                    with obs.span("fetch", cat="engine"):
                        jax.device_get(y)
                    with obs.span("rescore", cat="engine"):
                        time.sleep(0.02)
        jax.profiler.stop_trace()
        obs.disable()
        shutil.copy(trace_reduce.find_xplane(tmp), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
