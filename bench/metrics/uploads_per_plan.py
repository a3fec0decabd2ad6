"""Host-to-device copies per plan: the change of the program's counter
``engine.window.uploads`` (one per window whose inputs the fused search
stages and copies to the device) over the traced window, divided by the
plans completed in it.  An exact count.  ``None`` where the program keeps
no such counter."""


def read(run):
    sent = run.counters.get("engine.window.uploads", 0)
    if not run.plans or not sent:
        return None
    return sent / run.plans
