"""The ``tied_picks_pct`` reader: a share of the window searches, and
nothing where the program keeps no pick counters."""
import run
from metrics import tied_picks_pct


def _record(counters, plans=12):
    return run.RunRecord(plans=plans, spans=[], counters=counters,
                         trace=None, peaks={}, search=None, work=list)


def test_reads_nothing_without_the_counters():
    assert tied_picks_pct.read(_record({})) is None
    assert tied_picks_pct.read(
        _record({"launch.platform.sync_count": 60})) is None


def test_reads_the_tied_share():
    rec = _record({"engine.window.picks": 60,
                   "engine.window.tied_picks": 21})
    assert tied_picks_pct.read(rec) == 35.0


def test_reads_zero_where_no_pick_was_tied():
    assert tied_picks_pct.read(_record({"engine.window.picks": 60})) == 0.0
