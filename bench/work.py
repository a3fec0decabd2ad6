"""Work and bytes the scheduler's device kernels need, from problem sizes.

The sizes come from the benchmark's own reference (``reference``): for
each traced re-plan, every window is rebuilt under the anchors the
program's earlier windows left, and each model's candidate batch is
counted as constructed, before any padding to a program's shape bucket.
So the counts are the problem's, and read the same whatever later
computes it.

Neither kernel uses the matrix unit and no vector-unit peak is published,
so both roofline shares are bound by HBM bytes:

``scar_eval`` (score every candidate of one model in one window)
    reads, per real candidate, the chiplet class and the segment of each
    window layer (two int32 per layer), three float32 per segment (input
    and output communication latency, energy) and one pipelining flag, and
    writes two float32 (latency, energy); plus the window's
    (layer x class) latency and energy tables, read once.
``scar_search`` (disjointness screen of one beam stage)
    reads every screened candidate's occupancy words (uint32) and the
    beam's, and writes one int32 conflict count per (beam row, candidate).
"""
from __future__ import annotations

import dataclasses
import re

F32 = I32 = U32 = 4

# A device op of the trace that is a kernel call: its HLO text starts
# ``%name = <type>[<dims>]{layout} custom-call(``.  ``scar_eval`` returns
# f32[2, B] (latency, energy per candidate); ``scar_search`` returns
# s32[beam, N] (conflicts per beam row and candidate).
_CUSTOM = re.compile(r"^%\S+ = (\w+)\[([\d,]*)\]\{[^}]*\} custom-call\(")
FUSED_MODULE = "jit_fused_program"


@dataclasses.dataclass(frozen=True)
class ModelWindow:
    """One model's candidate batch in one window."""

    n_cands: int        # real candidates
    n_tier0: int        # rooted at a DRAM port or the model's anchor
    n_layers: int       # window layers of this model
    n_segs: int         # most segments of any candidate
    n_classes: int      # chiplet classes of the package
    words32: int        # uint32 words of one occupancy mask


def eval_bytes(mw: ModelWindow) -> float:
    """HBM bytes one ``scar_eval`` call needs for this batch."""
    per_cand = (2 * mw.n_layers * I32 + 3 * mw.n_segs * F32 + F32
                + 2 * F32)
    return mw.n_cands * per_cand + 2 * mw.n_layers * mw.n_classes * F32


def search_bytes(n_screened: int, beam: int, words32: int) -> float:
    """HBM bytes of one ``scar_search`` screen of ``n_screened`` candidates
    against a ``beam``-row beam."""
    return (n_screened * words32 * U32 + beam * words32 * U32
            + beam * n_screened * I32)


def kernel_calls(op_events, kind: str, beam: int = 0) -> list:
    """``(start_ns, dur_ns)`` of the trace's ``scar_eval`` (``kind="eval"``)
    or ``scar_search`` (``kind="search"``, output rows = ``beam``) calls."""
    out = []
    for name, start, dur in op_events:
        m = _CUSTOM.match(name)
        if not m:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        if kind == "eval" and m.group(1) == "f32" and len(dims) == 2 \
                and dims[0] == 2:
            out.append((start, dur))
        elif kind == "search" and m.group(1) == "s32" and len(dims) == 2 \
                and dims[0] == beam:
            out.append((start, dur))
    return out


def pool_screened(mw: ModelWindow, keep: int) -> int:
    """Candidates one beam stage of the fused search screens every time.

    The fused search (``core.device_search``) screens a prefix of each
    model's (tier, score) order: at most ``max(2048, 4 * keep)`` tier-0
    and ``max(256, 2 * keep)`` tier-1 candidates, and the whole batch only
    in the rare stage where some beam row finds fewer than ``keep``
    disjoint candidates there.
    """
    t0, t1 = max(2048, 4 * keep), max(256, 2 * keep)
    return min(mw.n_tier0, t0) + min(mw.n_cands - mw.n_tier0, t1)


def replan_sizes(config: dict, anchors: list[dict], results: list[dict]
                 ) -> list[list[list[ModelWindow]]]:
    """Per re-plan, per window, per model (index order): batch sizes."""
    from reference import scheduler as rs
    from reference.provision import provision
    from reference.sched import assemble_candidates
    from reference.segmentation import top_k_segmentations

    dep = rs.build(config)
    db, mcm, cfg = dep.db, dep.mcm, dep.search
    words32 = 2 * ((mcm.n_chiplets + 63) // 64)
    out = []
    for a, res in zip(anchors, results):
        prev = dict(a)
        per_window = []
        for w, ranges in enumerate(dep.ranges):
            alloc = provision(db, mcm.class_counts(), ranges, mcm.n_chiplets,
                              metric=cfg.metric,
                              max_nodes_per_model=cfg.max_nodes_per_model)
            models = []
            for mi, (s, e) in sorted(ranges.items()):
                segs = top_k_segmentations(db, mcm, s, e, alloc[mi],
                                           k=cfg.seg_top_k, cap=cfg.seg_cap,
                                           metric=cfg.metric)
                cand, tiers, _ = assemble_candidates(
                    mcm, mi, (s, e), segs, prev.get(mi),
                    path_cap=cfg.path_cap, frontier_cap=cfg.frontier_cap,
                    need_seg_id=False)
                models.append(ModelWindow(
                    n_cands=int(tiers.shape[0]),
                    n_tier0=int((tiers == 0).sum()),
                    n_layers=e - s, n_segs=int(cand.n_segs.max()),
                    n_classes=len(mcm.classes), words32=words32))
            per_window.append(models)
            if w < len(res["windows"]):
                prev = dict(prev)
                for m, _s, _e, _se, chips, _p in res["windows"][w]["plan"]:
                    prev[m] = chips[-1]
        out.append(per_window)
    return out
