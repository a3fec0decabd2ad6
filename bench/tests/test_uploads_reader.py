"""The ``uploads_per_plan`` reader: a count per plan, and nothing where
the program keeps no upload counter."""
import run
from metrics import uploads_per_plan


def _record(counters, plans=12):
    return run.RunRecord(plans=plans, spans=[], counters=counters,
                         trace=None, peaks={}, search=None, work=list)


def test_reads_nothing_without_the_counter():
    assert uploads_per_plan.read(_record({})) is None
    assert uploads_per_plan.read(
        _record({"launch.platform.sync_count": 60})) is None


def test_reads_uploads_per_plan():
    rec = _record({"engine.window.uploads": 60,
                   "launch.platform.sync_count": 60})
    assert uploads_per_plan.read(rec) == 5.0


def test_reads_nothing_without_plans():
    assert uploads_per_plan.read(
        _record({"engine.window.uploads": 5}, plans=0)) is None
