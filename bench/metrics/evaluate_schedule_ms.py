"""Final float64 evaluation per plan: the ``evaluate_schedule`` spans of
``core.scheduler`` (``core.cost.evaluate_schedule``).  Host clock."""


def read(run):
    total = run.span_total_s("evaluate_schedule")
    if not run.plans or total == 0.0:
        return None
    return total / run.plans * 1e3
