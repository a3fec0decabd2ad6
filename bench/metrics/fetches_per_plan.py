"""Device-to-host fetches per plan: the change of the program's counter
``launch.platform.sync_count`` (one per ``device_fetch``) over the traced
window, divided by the plans completed in it.  An exact count; the fused
search makes one per window."""


def read(run):
    if not run.plans:
        return None
    return run.counters.get("launch.platform.sync_count", 0) / run.plans
