"""CPU tests of naming idle gaps by the program's spans (``span_gaps``).

    python -m pytest -q bench/tests/test_span_gaps.py
"""
import os
import types

import pytest

import span_gaps
import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LEAVES = ("pack", "dispatch", "fetch", "rescore")


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _trace(host, ops):
    """A stand-in ``ProfileData``: host annotations and one TPU's ops."""
    line = types.SimpleNamespace
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[
            line(name="python", events=[_ev(*h) for h in host])]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line(name=trace_reduce.OPS_LINE,
                 events=[_ev(*o) for o in ops])])])


MS = 1_000_000
# one request, in ms: combine_window [10, 90) holding pack [10, 30),
# dispatch [30, 40), fetch [40, 60), rescore [60, 85); the device runs
# [35, 55).  Idle: [0, 35) and [55, 100).
SYNTHETIC = _trace(
    host=[(n, s * MS, d * MS) for n, s, d in [
        ("bench.window", 0, 100), ("bench.request", 5, 90),
        ("scar.combine_window", 10, 80), ("scar.pack", 10, 20),
        ("scar.dispatch", 30, 10), ("scar.fetch", 40, 20),
        ("scar.rescore", 60, 25)]],
    ops=[("fusion", 35 * MS, 20 * MS)])


def test_innermost_pieces_tile_nested_spans():
    pieces = span_gaps.innermost([("outer", 0, 10), ("a", 2, 4),
                                  ("b", 4, 7), ("c", 5, 6)])
    assert pieces == [(0, 2, "outer"), (2, 4, "a"), (4, 5, "b"),
                      (5, 6, "c"), (6, 7, "b"), (7, 10, "outer")]
    assert span_gaps.innermost([]) == []


def test_gap_takes_innermost_program_span_at_its_midpoint():
    red = trace_reduce.reduce(
        SYNTHETIC, label_spans=span_gaps.program_spans(SYNTHETIC))
    # the offset-free midpoint rule: [55, 100) has its midpoint in rescore
    assert red.gaps == [("rescore", 45 * MS), ("pack", 35 * MS)]
    red = trace_reduce.reduce(SYNTHETIC)
    # without the program's spans the same gaps read the bench request
    assert [n for n, _ in red.gaps] == ["bench.request", "bench.request"]


def test_idle_is_split_over_every_span_it_crosses():
    red = trace_reduce.reduce(SYNTHETIC)
    split = span_gaps.idle_by_span(SYNTHETIC, red)
    assert split == {k: v * MS for k, v in {
        "bench.request": 5 + 5, "pack": 20, "dispatch": 5, "fetch": 5,
        "rescore": 25, "combine_window": 5, span_gaps.OUTSIDE: 5 + 5}.items()}
    assert sum(split.values()) == sum(ns for _n, ns in red.gaps)
    line = span_gaps.idle_line(split, plans=2)
    assert line.startswith("bench: idle_by_span rescore=12.500 pack=10.000 ")


def test_trace_without_program_spans_keeps_its_labels():
    """``record_trace.py``'s fixture has only ``bench.`` annotations: the
    gaps read exactly what ``trace_reduce`` gives them."""
    pd = trace_reduce.load(os.path.join(DATA, "small_trace.xplane.pb"))
    red = trace_reduce.reduce(pd)
    assert span_gaps.program_spans(pd) == []
    assert trace_reduce.reduce(
        pd, label_spans=span_gaps.program_spans(pd)).gaps == red.gaps
    split = span_gaps.idle_by_span(pd, red)
    assert sum(split.values()) == pytest.approx(sum(g for _n, g in red.gaps),
                                                rel=1e-9)
    assert max(split, key=split.get) == "bench.idle"


def test_recorded_program_trace_names_gaps_by_leaf():
    """``record_program_trace.py`` on a TPU v5e: per request 5 ms of host
    work in ``pack``, four jitted steps, 20 ms of host work in
    ``rescore``, each a mirrored ``scar.`` span."""
    pd = trace_reduce.load(os.path.join(DATA, "program_trace.xplane.pb"))
    red = trace_reduce.reduce(pd)
    names = {n for n, _s, _e in span_gaps.program_spans(pd)}
    assert names == {"combine_window", *LEAVES}
    labelled = trace_reduce.reduce(
        pd, label_spans=span_gaps.program_spans(pd)).gaps
    assert sorted(g for _n, g in labelled) == sorted(g for _n, g in red.gaps)
    name, ns = labelled[0]
    assert name == "rescore" and ns >= 20e6
    split = span_gaps.idle_by_span(pd, red)
    assert sum(split.values()) == pytest.approx(sum(g for _n, g in red.gaps),
                                                rel=1e-9)
    assert split["rescore"] >= 2 * 20e6 * 0.99
    assert split["pack"] >= 2 * 5e6 * 0.99
