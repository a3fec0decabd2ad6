#!/usr/bin/env python3
"""Record a ``recorded_replan`` traffic file from the program's own re-planner.

    JAX_PLATFORMS=cpu python3 bench/record_replans.py \\
        --config dc4_mesh6_hetcb --out replan_hetcb6 \\
        --seeds 101,202 --horizon 3000 --requests 64

Replays seeded Poisson tenant churn (``repro.online.traces``) over the
configuration's own tenants through ``repro.online.rescheduler.Rescheduler``
in its warm (production) mode, on the configuration's package and search
width.  Every re-plan that misses the re-planner's whole-plan memo and
whose active tenants are exactly the configuration's tenant set is a
request: the anchors that re-plan carried, by tenant index in the
configuration's order.  Recording stops at ``--requests`` of them.  The
search runs on the host (``algo="beam"``, numpy evaluation), which picks
the same plans, and so carries the same anchors, as the device search.

The benchmark's runs never run this; it is how the traffic files were
made, and it prints what the recorded requests look like: how many
tenants carry an anchor, how many chiplets the anchors use, and the share
of frontier-path lookups that missed the paths cache.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--horizon", type=float, default=3000.0)
    ap.add_argument("--arrival-rate", type=float, default=1.0)
    ap.add_argument("--mean-lifetime", type=float, default=2.5)
    ap.add_argument("--requests", type=int, default=64)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import sut
    from repro.obs import registry
    from repro.online.rescheduler import Rescheduler
    from repro.online.simulator import simulate
    from repro.online.traces import poisson_churn_trace

    with open(os.path.join(BENCH, "configs", f"{args.config}.json")) as fh:
        config = json.load(fh)
    zoo = tuple((str(m), int(b)) for m, b in config["tenants"])
    index = {t: i for i, t in enumerate(zoo)}
    if len(index) != len(zoo):
        raise ValueError("the configuration holds a tenant twice")
    dep = sut.build(config)
    cfg = dataclasses.replace(dep.cfg, algo="beam", eval_backend="numpy")

    got: list[dict[str, int]] = []
    stats = collections.Counter()

    class Recorder(Rescheduler):
        def replan(self, tenants, anchors=None, **kw):
            carried = self.carried_anchors(tenants)
            h0 = registry.value("paths.cache_hit")
            m0 = registry.value("paths.cache_miss")
            rec = super().replan(tenants, anchors=anchors, **kw)
            if rec.memo_hit:
                stats["memo_hit"] += 1
                return rec
            stats["memo_miss"] += 1
            stats["paths_hit"] += registry.value("paths.cache_hit") - h0
            stats["paths_miss"] += registry.value("paths.cache_miss") - m0
            mix = sorted((m, int(b)) for _t, m, b in tenants)
            if mix == sorted(zoo) and len(got) < args.requests:
                got.append(dict(sorted(
                    (str(index[(m, int(b))]), int(carried[t]))
                    for t, m, b in tenants if t in carried)))
            return rec

    seeds = [int(s) for s in args.seeds.split(",")]
    used = []
    for seed in seeds:
        if len(got) >= args.requests:
            break
        used.append(seed)
        trace = poisson_churn_trace(seed, args.horizon, args.arrival_rate,
                                    args.mean_lifetime, zoo=zoo,
                                    max_active=len(zoo))
        simulate(trace, dep.mcm, rescheduler=Recorder(dep.mcm, cfg,
                                                      mode="warm"))
    recording = (
        f"bench/record_replans.py: repro.online Rescheduler, warm mode, "
        f"over poisson_churn_trace(seeds {used}, "
        f"horizon {args.horizon:g} s, arrivals {args.arrival_rate:g}/s, "
        f"mean lifetime {args.mean_lifetime:g} s, max_active {len(zoo)}, "
        f"zoo = the configuration's tenants); the first {len(got)} memo "
        f"misses whose active set is exactly the configuration's tenants")
    out = {"kind": "recorded_replan", "recorded_on": config["name"],
           "recording": recording, "requests": got}
    path = os.path.join(BENCH, "traffic", f"{args.out}.json")
    if os.path.exists(path):
        with open(path) as fh:
            old = json.load(fh)
        out = {**old, **out}
    with open(path, "w") as fh:
        fh.write("{\n" + ",\n".join(
            f"  {json.dumps(k)}: " + (
                "[\n" + ",\n".join("    " + json.dumps(r) for r in v)
                + "\n  ]" if k == "requests" else json.dumps(v))
            for k, v in out.items()) + "\n}\n")
    anchored = collections.Counter(len(r) for r in got)
    chips = collections.Counter(c for r in got for c in r.values())
    shared = sum(len(set(r.values())) < len(r) for r in got)
    lookups = stats["paths_hit"] + stats["paths_miss"]
    print(json.dumps({
        "requests": len(got),
        "distinct": len({tuple(sorted(r.items())) for r in got}),
        "anchored_tenants": dict(sorted(anchored.items())),
        "chiplets_used": len(chips), "of": dep.mcm.n_chiplets,
        "requests_with_a_shared_anchor": shared,
        "memo_hits": stats["memo_hit"], "memo_misses": stats["memo_miss"],
        "paths_cache_miss_share": (stats["paths_miss"] / lookups
                                   if lookups else None)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
