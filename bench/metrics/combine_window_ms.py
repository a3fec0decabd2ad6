"""Window search time per plan: the program's ``combine_window`` spans.

``core.engine.DeviceBeamEngine.combine_window`` holds PROV, SEG, path and
candidate construction, packing, the device program's dispatch and its one
fetch, and the float64 re-score of the window.  Summed over a plan's
windows, averaged over the traced plans.  Host clock.
"""


def read(run):
    total = run.span_total_s("combine_window")
    if not run.plans or total == 0.0:
        return None
    return total / run.plans * 1e3
