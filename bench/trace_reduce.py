"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData`` only.  Host and device planes share
one clock, so the benchmark's own ``jax.profiler.TraceAnnotation`` spans
(names starting ``bench.``) on the host plane bound the measured window
and label the device's idle gaps.

* device busy time: the union of the intervals in which an operation of
  the ``XLA Ops`` line runs on a ``/device:TPU:n`` plane, inside the
  window, averaged over the devices that ran anything;
* per-operation device time (``ops``), for kernel times and the top list;
* idle gaps of the first busy device, each named by the innermost
  ``bench.`` annotation, or by ``label_spans`` (host spans moved onto the
  trace clock by the caller), that covers its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Iterable, Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench."
WINDOW_ANNOTATION = "bench.window"


@dataclasses.dataclass
class Reduced:
    window_ns: tuple[float, float]
    busy_ns: float                       # mean over busy devices
    devices: int                         # devices that ran an op
    ops: dict[str, float]                # op name -> summed device ns
    op_events: list[tuple[str, float, float]]   # (name, start, dur) dev 0
    modules: list[tuple[str, float, float]]     # (name, start, dur) dev 0
    gaps: list[tuple[str, float]]        # (label, ns), longest first
    annotations: list[tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler.trace`` directory."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def union_length(intervals: Iterable[tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_between(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Uncovered ``(start, end)`` stretches of ``[lo, hi]``."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _label(mid: float, spans: list[tuple[str, float, float]]) -> str:
    best, best_len = "outside_annotations", float("inf")
    for name, s, e in spans:
        if s <= mid <= e and e - s < best_len:
            best, best_len = name, e - s
    return best


def annotations(pd) -> list[tuple[str, float, float]]:
    """Every ``bench.`` host annotation as ``(name, start_ns, end_ns)``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def load(path: str):
    """The ``jax.profiler.ProfileData`` of an ``.xplane.pb`` file."""
    import jax

    return jax.profiler.ProfileData.from_file(path)


def window(pd) -> tuple[float, float]:
    """``(start_ns, end_ns)`` of the ``bench.window`` annotation."""
    wins = [(s, e) for n, s, e in annotations(pd) if n == WINDOW_ANNOTATION]
    if not wins:
        raise ValueError(f"no {WINDOW_ANNOTATION} annotation in the trace")
    return wins[0]


def reduce(pd, label_spans: Optional[list] = None) -> Reduced:
    """Reduce a loaded trace to busy time, op times and idle gaps.

    ``label_spans``: extra ``(name, start_ns, end_ns)`` host spans on the
    trace clock; gaps take the innermost covering span's name.
    """
    notes = annotations(pd)
    lo, hi = window(pd)
    busy, ops = [], {}
    first: Optional[list] = None
    modules: list[tuple[str, float, float]] = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ivs, evs, mods = [], [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                mods = [(ev.name, ev.start_ns, ev.duration_ns)
                        for ev in line.events
                        if lo <= ev.start_ns and ev.start_ns + ev.duration_ns
                        <= hi]
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                s, e = max(s, lo), min(e, hi)
                ivs.append((s, e))
                evs.append((ev.name, s, e - s))
                ops[ev.name] = ops.get(ev.name, 0.0) + (e - s)
        if ivs:
            busy.append(union_length(ivs))
            if first is None:
                first = (ivs, evs)
                modules = sorted(mods, key=lambda m: m[1])
    labels = [a for a in notes if a[0] != WINDOW_ANNOTATION]
    labels += list(label_spans or ())
    gaps = []
    if first is not None:
        for s, e in gaps_between(first[0], lo, hi):
            gaps.append((_label(0.5 * (s + e), labels), e - s))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_ns=(lo, hi),
                   busy_ns=sum(busy) / len(busy) if busy else 0.0,
                   devices=len(busy), ops=ops,
                   op_events=first[1] if first else [], modules=modules,
                   gaps=gaps, annotations=notes)


def describe(path: str, top: int = 40) -> str:
    """Planes, lines and the most frequent event names (for a first look)."""
    import collections

    pd = load(path)
    rows = []
    for plane in pd.planes:
        for line in plane.lines:
            names = collections.Counter()
            dur = collections.Counter()
            stats = None
            for ev in line.events:
                names[ev.name] += 1
                dur[ev.name] += ev.duration_ns
                if stats is None:
                    stats = [(k, str(v)[:120]) for k, v in ev.stats]
            rows.append(f"{plane.name} | {line.name} | events "
                        f"{sum(names.values())} | first stats {stats}")
            for n, c in dur.most_common(top):
                rows.append(f"    {names[n]:6d} x {c / 1e6:10.3f} ms  {n[:160]}")
    return "\n".join(rows)
