#!/usr/bin/env python3
"""Record the small trace ``test_harness.py`` checks the reduction on.

    python3 bench/tests/record_trace.py bench/tests/data/small_trace.xplane.pb

Run on a machine with a TPU.  Inside a ``bench.window`` annotation it runs
two ``bench.request`` annotations, each a few jitted device operations,
with a host pause of 20 ms in a ``bench.idle`` annotation between them,
and writes the profiler's ``.xplane.pb`` to the path given.
"""
import os
import shutil
import sys
import tempfile
import time


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    step = jax.jit(lambda x: jnp.tanh(x @ x) + 1.0)
    x = jnp.ones((512, 512), jnp.float32)
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(2):
                with jax.profiler.TraceAnnotation("bench.request"):
                    y = x
                    for _ in range(4):
                        y = step(y)
                    y.block_until_ready()
                if i == 0:
                    with jax.profiler.TraceAnnotation("bench.idle"):
                        time.sleep(0.02)
        jax.profiler.stop_trace()
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import trace_reduce
        shutil.copy(trace_reduce.find_xplane(tmp), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
