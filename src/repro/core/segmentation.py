"""Segmentation engine (SEG): layer-to-segment partitioning (Sec. IV-C).

A segmentation of a model's window slice [start, end) with up to N nodes is a
choice of <= N-1 split points among the end-1-start interior positions
(segments are contiguous, Theorem 1).  Heuristic 1 scores each model's
segmentation space *independently* with a placement-agnostic score and keeps
the top-k, reducing O(prod_i |L_i| x |N_i|) to O(max_i |L_i| x |N_i|); the
cross product of per-model top-k's is handed to SCHED.
"""
from __future__ import annotations

import functools
import itertools
from typing import NamedTuple

import numpy as np

from repro import obs

from .chiplet import MCM
from .maestro import CostDB
# quantize_scores lives in repro.core.quantize since the device search path
# (which needs its traceable twin); re-exported here for backward compat.
from .quantize import quantize_scores

# one of the two per top_k_segmentations call: its split table was memoised
# already (hit) or built by the call (miss)
_TABLE_HITS = obs.counter("seg.table_hits")
_TABLE_MISSES = obs.counter("seg.table_misses")


def enumerate_segmentations(n_layers: int, max_segments: int,
                            cap: int = 4096) -> list[tuple[int, ...]]:
    """All segmentations of ``n_layers`` into <= ``max_segments`` runs.

    Returned as tuples of *relative* end offsets (1..n_layers, last ==
    n_layers).  Deterministically subsampled to ``cap`` if the space is
    larger (Heuristic 2 keeps this from exploding in practice).
    """
    max_segments = max(1, min(max_segments, n_layers))
    out: list[tuple[int, ...]] = []
    for k in range(max_segments):  # k split points -> k+1 segments
        for cuts in itertools.combinations(range(1, n_layers), k):
            out.append(cuts + (n_layers,))
            if len(out) >= 4 * cap:
                break
        if len(out) >= 4 * cap:
            break
    if len(out) > cap:
        idx = np.linspace(0, len(out) - 1, cap).astype(int)
        out = [out[i] for i in idx]
    return out


def score_segmentation(db: CostDB, mcm: MCM, start: int,
                       seg_ends_rel: tuple[int, ...],
                       metric: str = "edp") -> float:
    """Placement-agnostic score: each segment on its best-affinity class.

    Uses the best class per segment (heterogeneous upper bound on affinity),
    DRAM weight-load time, pipelined (max) latency across segments.
    """
    pkg = mcm.pkg
    seg_lat = []
    seg_e = []
    s = start
    for e_rel in seg_ends_rel:
        e = start + e_rel
        lat_per_class = db.lat[s:e].sum(axis=0)       # [n_classes]
        c = int(np.argmin(lat_per_class))
        w = float(db.w_bytes[s:e].sum())
        load = w / pkg.dram_bw + pkg.dram_lat_s
        seg_lat.append(float(lat_per_class[c]) + load)
        seg_e.append(float(db.energy[s:e, c].sum())
                     + w * 8.0 * pkg.dram_e_pj_per_bit * 1e-12)
        s = e
    lat = max(seg_lat) if len(seg_lat) > 1 else sum(seg_lat)
    energy = sum(seg_e)
    if metric == "latency":
        return lat
    if metric == "energy":
        return energy
    return lat * energy


class SplitTable(NamedTuple):
    """A window's candidate segmentations as frozen integer arrays.

    Pure combinatorics of (window length, nodes, cap): no cost table,
    anchor or package enters it, so one table serves every window of that
    shape.  Arrays are read-only.
    """

    segs: tuple[tuple[int, ...], ...]   # candidates, enumeration order
    length: int                         # window length (every last end)
    n_segs: np.ndarray                  # [n] segments per candidate
    ends: np.ndarray                    # [n, S] relative ends, 0-padded
    valid: np.ndarray                   # [n, S] real (unpadded) slots
    flat_starts: np.ndarray             # reduceat starts, n-fold tiled slice
    slots: np.ndarray                   # flat [n*S] index of each valid slot


def _table_of(segs) -> SplitTable:
    """The ``SplitTable`` of a non-empty candidate list."""
    n = len(segs)
    n_segs = np.fromiter(map(len, segs), dtype=np.int64, count=n)
    S = int(n_segs.max())
    Lw = int(segs[0][-1])
    if any(int(se[-1]) != Lw for se in segs):
        # the tiling runs each candidate's last segment to its tile end,
        # so unequal totals would silently absorb extra layers
        raise ValueError("all segmentations must cover the same window "
                         "length (relative last end)")
    ends = np.zeros((n, S), dtype=np.int64)
    for i, se in enumerate(segs):
        ends[i, :len(se)] = se
    valid = np.arange(S)[None, :] < n_segs[:, None]
    starts = np.concatenate([np.zeros((n, 1), dtype=np.int64),
                             ends[:, :-1]], axis=1)
    # each candidate's segments exactly tile its copy of the window slice,
    # so consecutive flat start indices delimit every segment
    flat_starts = (np.arange(n)[:, None] * Lw + starts)[valid]
    slots = np.flatnonzero(valid)
    for a in (n_segs, ends, valid, flat_starts, slots):
        a.setflags(write=False)
    return SplitTable(tuple(segs), Lw, n_segs, ends, valid, flat_starts,
                      slots)


@functools.lru_cache(maxsize=256)
def _build_split_table(n_layers: int, max_segments: int,
                       cap: int) -> SplitTable:
    _TABLE_MISSES.inc()
    return _table_of(enumerate_segmentations(n_layers, max_segments, cap))


def _split_table(n_layers: int, max_segments: int, cap: int) -> SplitTable:
    """The memoised ``SplitTable`` of ``enumerate_segmentations``' space.

    ``max_segments`` is clamped as the enumeration clamps it, so equal
    spaces share one entry.
    """
    n_layers = int(n_layers)
    return _build_split_table(n_layers, max(1, min(int(max_segments),
                                                   n_layers)), int(cap))


def _score_table(db: CostDB, mcm: MCM, start: int, t: SplitTable,
                 metric: str) -> np.ndarray:
    """Score every candidate of ``t`` on the window starting at ``start``.

    One ``np.add.reduceat`` over the candidate-tiled ``[lat | energy |
    w_bytes]`` slice sums every segment of every candidate: each column is
    reduced over the same rows in the same order as a separate pass per
    array would, so the sums are bit-identical to that.
    """
    pkg = mcm.pkg
    n, S = t.ends.shape
    C = db.lat.shape[1]
    sl = slice(start, start + t.length)
    rows = np.concatenate([db.lat[sl], db.energy[sl], db.w_bytes[sl, None]],
                          axis=1)                               # [Lw, 2C+1]
    sums = np.add.reduceat(np.tile(rows, (n, 1)), t.flat_starts, axis=0)
    # padded slots: +inf latency keeps them out of the argmin/max, zero
    # energy and bytes
    seg = np.empty((n * S, 2 * C + 1))
    seg[:, :C] = np.inf
    seg[:, C:] = 0.0
    seg[t.slots] = sums
    seg = seg.reshape(n, S, 2 * C + 1)
    seg_lat_c, seg_e_c, w = seg[:, :, :C], seg[:, :, C:2 * C], seg[:, :, -1]

    cls = np.argmin(seg_lat_c, axis=2)                             # [n, S]
    lat_best = np.take_along_axis(seg_lat_c, cls[:, :, None],
                                  axis=2)[:, :, 0]                 # [n, S]
    e_best = np.take_along_axis(seg_e_c, cls[:, :, None],
                                axis=2)[:, :, 0]
    load = w / pkg.dram_bw + pkg.dram_lat_s
    seg_lat = np.where(t.valid, lat_best + load, -np.inf)
    seg_e = np.where(t.valid,
                     e_best + w * 8.0 * pkg.dram_e_pj_per_bit * 1e-12, 0.0)
    # max == sum for single-segment candidates, so pipelined max covers both
    lat = seg_lat.max(axis=1)
    energy = seg_e.sum(axis=1)
    if metric == "latency":
        return lat
    if metric == "energy":
        return energy
    return lat * energy


def score_segmentations_batch(db: CostDB, mcm: MCM, start: int,
                              segs: list[tuple[int, ...]],
                              metric: str = "edp") -> np.ndarray:
    """Vectorised ``score_segmentation`` over a candidate list.

    One ``np.add.reduceat`` pass over the candidate-tiled window slice
    scores every candidate at once (this loop was ~25% of 16x16 schedule
    time when run per candidate in Python).  Reduceat sums each segment
    sequentially while the scalar loop's ``np.sum`` is pairwise, so scores
    can differ by float-rounding noise only; the scalar function is kept
    above as the parity oracle (``tests/test_segmentation.py`` pins
    agreement on all ten paper scenarios).
    """
    if not segs:
        return np.zeros(0)
    return _score_table(db, mcm, start, _table_of(segs), metric)


def top_k_segmentations(db: CostDB, mcm: MCM, start: int, end: int,
                        n_nodes: int, k: int = 4, cap: int = 1024,
                        metric: str = "edp") -> list[tuple[int, ...]]:
    """Heuristic 1 step 1: per-model top-k segmentations by solo score.

    The candidate space comes from the memoised split table; every call
    scores all of it against this window's cost rows.
    """
    built = _TABLE_MISSES.value
    table = _split_table(end - start, n_nodes, cap)
    if _TABLE_MISSES.value == built:
        _TABLE_HITS.inc()
    scores = quantize_scores(_score_table(db, mcm, start, table, metric))
    order = np.argsort(scores, kind="stable")[:k]
    return [table.segs[i] for i in order]


def co_explore(per_model_topk: dict[int, list[tuple[int, ...]]],
               cap: int = 256) -> list[dict[int, tuple[int, ...]]]:
    """Heuristic 1 step 2: combinatorial co-exploration of per-model top-k."""
    models = sorted(per_model_topk)
    pools = [per_model_topk[m] for m in models]
    combos = []
    for combo in itertools.product(*pools):
        combos.append(dict(zip(models, combo)))
        if len(combos) >= cap:
            break
    return combos


# backward-compatible alias (pre-promotion name)
_quantize_scores = quantize_scores
