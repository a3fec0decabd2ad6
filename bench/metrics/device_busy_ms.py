"""Device busy time per plan: the union of the intervals in which an
operation ran on the chip inside the traced window (``trace_reduce``),
divided by the plans completed in it.  Device clock."""


def read(run):
    if not run.plans or run.trace is None or run.trace.devices == 0:
        return None
    return run.trace.busy_s / run.plans * 1e3
