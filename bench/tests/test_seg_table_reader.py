"""The ``seg_table_hit_pct`` reader: a share of the SEG calls, and
nothing where the program keeps no split-table counters."""
import run
from metrics import seg_table_hit_pct


def _record(counters, plans=12):
    return run.RunRecord(plans=plans, spans=[], counters=counters,
                         trace=None, peaks={}, search=None, work=list)


def test_reads_nothing_without_the_counters():
    assert seg_table_hit_pct.read(_record({})) is None
    assert seg_table_hit_pct.read(
        _record({"launch.platform.sync_count": 60})) is None


def test_reads_the_hit_share():
    rec = _record({"seg.table_hits": 126, "seg.table_misses": 6})
    assert seg_table_hit_pct.read(rec) == 100.0 * 126 / 132


def test_reads_all_hits_and_all_misses():
    assert seg_table_hit_pct.read(_record({"seg.table_hits": 60})) == 100.0
    assert seg_table_hit_pct.read(_record({"seg.table_misses": 8})) == 0.0
