"""Share of SEG calls whose split table was memoised already: the change
of the program's counter ``seg.table_hits`` over the traced window,
divided by that of ``seg.table_hits`` plus ``seg.table_misses`` (one of
the two per ``top_k_segmentations`` call), times 100.  ``None`` where the
program keeps no such counters."""


def read(run):
    hits = run.counters.get("seg.table_hits", 0)
    calls = hits + run.counters.get("seg.table_misses", 0)
    if not calls:
        return None
    return 100.0 * hits / calls
