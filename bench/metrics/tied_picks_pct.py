"""Share of the window searches whose plan the beam's tie rule decided:
the change of the program's counter ``engine.window.tied_picks`` (windows
whose best last-stage beam row shares its ordering score with another
valid row) over the traced window, divided by that of
``engine.window.picks`` (windows the fused search picked a plan for).
``None`` where the program keeps no such counters."""


def read(run):
    picks = run.counters.get("engine.window.picks", 0)
    if not picks:
        return None
    return 100.0 * run.counters.get("engine.window.tied_picks", 0) / picks
