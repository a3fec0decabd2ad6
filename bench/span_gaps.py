"""Name the device's idle time by the program's own spans, on the trace clock.

A program traced with ``repro.obs.enable(annotate=jax.profiler.
TraceAnnotation)`` mirrors every span into the profiler trace as a
``scar.<name>`` annotation on the host plane, so host and device share
one clock and no offset has to be guessed.

* ``program_spans``: those annotations as ``(name, start_ns, end_ns)``,
  the ``scar.`` prefix dropped.  Passed as ``trace_reduce.reduce``'s
  ``label_spans``, each gap takes the innermost one covering its
  midpoint: ``combine_window`` as before, or the name of a leaf inside
  it.  A trace with no ``scar.`` annotation gives ``[]``, and the gaps
  read what they read without it;
* ``idle_by_span``: every idle nanosecond of ``reduce``'s first busy
  device, split over the innermost annotation covering it at that
  instant (``outside_annotations`` where none does), so a gap that spans
  two stages counts in both.
"""
from __future__ import annotations

import heapq

import trace_reduce

PREFIX = "scar."
OUTSIDE = "outside_annotations"


def program_spans(pd) -> list[tuple[str, float, float]]:
    """Every ``scar.`` host annotation as ``(name, start_ns, end_ns)``,
    the name without its prefix."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name[len(PREFIX):], ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def innermost(spans) -> list[tuple[float, float, str]]:
    """The union of ``(name, start, end)`` spans as disjoint, time-ordered
    ``(start, end, name)`` pieces, each named by the shortest span that
    covers it (the innermost, where spans nest)."""
    bounds = sorted({t for _n, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    live: list[tuple[float, float, str]] = []     # (length, end, name)
    out, i = [], 0
    for lo, hi in zip(bounds, bounds[1:]):
        while i < len(by_start) and by_start[i][1] <= lo:
            name, s, e = by_start[i]
            heapq.heappush(live, (e - s, e, name))
            i += 1
        while live and live[0][1] <= lo:
            heapq.heappop(live)
        if live:
            out.append((lo, hi, live[0][2]))
    return out


def idle_by_span(pd, reduced) -> dict[str, float]:
    """Idle ns under each innermost annotation (module docstring)."""
    notes = [a for a in trace_reduce.annotations(pd)
             if a[0] != trace_reduce.WINDOW_ANNOTATION]
    pieces = innermost(notes + program_spans(pd))
    busy = [(s, s + d) for _n, s, d in reduced.op_events]
    split: dict[str, float] = {}
    j = 0
    for s, e in trace_reduce.gaps_between(busy, *reduced.window_ns):
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        left = e - s
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            part = min(e, pieces[k][1]) - max(s, pieces[k][0])
            if part > 0:
                split[pieces[k][2]] = split.get(pieces[k][2], 0.0) + part
                left -= part
            k += 1
        if left > 0:
            split[OUTSIDE] = split.get(OUTSIDE, 0.0) + left
    return split


def idle_line(split: dict[str, float], plans: int) -> str:
    """The traced run's ``bench: idle_by_span`` line, in ms per plan."""
    per = max(plans, 1) * 1e6
    return "bench: idle_by_span " + " ".join(
        f"{n}={ns / per:.3f}" for n, ns in sorted(split.items(),
                                                  key=lambda kv: -kv[1]))
