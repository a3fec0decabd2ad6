"""Device programs first requested inside the traced window: the change of
the program's counter ``device_search.jit_recompiles``.  Set-up warms every
program the stream touches, so this reads 0; a program found in the
persistent cache counts too, since the counter sees signatures, not XLA."""


def read(run):
    return run.counters.get("device_search.jit_recompiles", 0)
