"""A frozen copy of SCAR's float64 host planner: the benchmark's reference.

Copied from the program's host oracle (``repro.core``: cost model, MCM
packing, PROV, SEG, frontier path construction, candidate scoring and the
host beam) with the device paths and telemetry left out.  It imports
nothing of the program, so a change to the program cannot move it.
``scheduler`` is the entry point.
"""
