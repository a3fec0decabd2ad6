"""The benchmark's one request generator, driven by a traffic file.

A traffic mix is a JSON file under ``bench/traffic/``; this module turns it
and a seed into a stream of requests.  It imports nothing of the program
under test, so a change to the program cannot change the traffic.

Mix kind ``recorded_replan``: re-plans of one deployment's tenant set at
epoch boundaries, as a warm re-planner sends them on a memo miss.  The
file lists the recorded requests (``requests``: each a map from tenant
index, in the configuration's order, to the chiplet that tenant carries
as its data-locality anchor; a tenant that just arrived carries none) and
names the configuration they were recorded on (``recorded_on``).  How
they were recorded is in ``recording`` (``bench/record_replans.py``).

Streams keep set-up and the measured window apart:

``warm``
    every distinct recorded request once, in an order drawn from the
    seed, so set-up meets every program the window can ask for;
``window``
    request *i* drawn uniformly from the recorded requests with
    ``(seed, i)``; the same seed gives the same requests.
"""
from __future__ import annotations

import json
import os
from typing import Iterator

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
STREAMS = {"window": 0, "warm": 1}
_SEED_MOD = 1 << 64
KEYS = {"kind", "recorded_on", "recording", "requests", "trace_requests",
        "why"}


def load_mix(name: str, config: str | None = None) -> dict:
    """The traffic file ``bench/traffic/<name>.json``.

    Refuses a key the harness does not read, and a mix recorded on
    another configuration than ``config`` (when given)."""
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as fh:
        mix = json.load(fh)
    if mix.get("kind") != "recorded_replan":
        raise ValueError(f"{path}: unknown traffic kind {mix.get('kind')!r}")
    extra = set(mix) - KEYS
    if extra:
        raise ValueError(f"{path}: keys the harness does not read: "
                         f"{sorted(extra)}")
    if config is not None and mix["recorded_on"] != config:
        raise ValueError(f"{path}: recorded on {mix['recorded_on']!r}, "
                         f"not on {config!r}")
    if not mix["requests"]:
        raise ValueError(f"{path}: no requests")
    return mix


def _rng(seed: int, stream: str, i: int) -> np.random.Generator:
    s = int(seed) % _SEED_MOD
    key = [STREAMS[stream], s & 0xFFFFFFFF, s >> 32, int(i)]
    return np.random.default_rng(np.random.SeedSequence(key))


def _anchors(req: dict, n_tenants: int, n_chiplets: int) -> dict[int, int]:
    out = {int(m): int(c) for m, c in req.items()}
    if not all(0 <= m < n_tenants and 0 <= c < n_chiplets
               for m, c in out.items()):
        raise ValueError(f"request {req} is out of range")
    return dict(sorted(out.items()))


def distinct(mix: dict, n_tenants: int, n_chiplets: int
             ) -> list[dict[int, int]]:
    """The mix's distinct requests, in file order."""
    out, seen = [], set()
    for req in mix["requests"]:
        a = _anchors(req, n_tenants, n_chiplets)
        key = tuple(a.items())
        if key not in seen:
            seen.add(key)
            out.append(a)
    return out


def warm_requests(mix: dict, n_tenants: int, n_chiplets: int,
                  seed: int) -> list[dict[int, int]]:
    """Every distinct request once, in an order drawn from the seed."""
    reqs = distinct(mix, n_tenants, n_chiplets)
    order = _rng(seed, "warm", 0).permutation(len(reqs))
    return [reqs[int(k)] for k in order]


def requests(mix: dict, n_tenants: int, n_chiplets: int,
             seed: int) -> Iterator[dict[int, int]]:
    """The window's endless stream of anchor dicts ``{tenant: chiplet}``."""
    reqs = [_anchors(r, n_tenants, n_chiplets) for r in mix["requests"]]
    i = 0
    while True:
        yield dict(reqs[int(_rng(seed, "window", i).integers(len(reqs)))])
        i += 1
