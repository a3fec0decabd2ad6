"""Vectorized candidate-tensor search engine unifying SCHED / EA / anneal.

SCAR's hot path is window combination: pick one scored placement candidate
per model subject to exclusive chiplet occupancy.  The seed implementation
walked Python lists of per-candidate bitmasks one at a time; this module
re-expresses the whole combination stack over padded numpy tensors so every
search algorithm evaluates candidates in batched passes:

* ``CandidateTensors`` packs a window's per-model ``ModelCandidateSet`` list
  into ``[M, N, W]`` uint64 occupancy-mask words plus ``[M, N]`` latency /
  energy tables (``W = ceil(n_chiplets / 64)`` words, so packages beyond 64
  chiplets — e.g. 16x16 pods — keep exact masks).
* ``BeamEngine`` is a fully vectorized beam search: beam x candidate
  disjointness via one broadcast ``mask & masks == 0`` pass, stable top-k via
  ``argsort``.  It reproduces the reference Python loop *bit-identically*
  (same expansion budget accounting, same stable tie-breaking), verified by
  ``tests/test_engine.py`` against ``reference_combine``.
* ``DeviceBeamEngine`` (``algo="beam_jax"``) moves the whole window search
  onto the accelerator: candidate scoring, disjointness screening, beam
  expansion and top-k selection compile into ONE jitted device program per
  (mesh, window-shape) bucket (``core.device_search``), so a schedule does
  O(n_windows) host-device syncs instead of O(models x windows).  Its
  protocol-form ``combine`` is bit-identical to ``reference_combine`` under
  scoped float64.
* ``EvolutionaryEngine`` keeps the paper's (mu + lambda) EA trajectory (same
  RNG call sequence) but evaluates population fitness and overlap penalty in
  one ``batched_fitness`` pass — no per-row Python ``_fitness`` calls.
* ``AnnealEngine`` runs vectorized parallel simulated-annealing chains over
  the same tensors (beyond-paper; selected with ``SearchConfig.algo =
  "anneal"``).

All engines satisfy the ``SearchEngine`` protocol and return the same
``WindowSearchResult`` the scheduler consumed before, so ``scheduler.py``,
``sched.py``, ``search.py`` and ``refine.py`` all route through here.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Protocol

import numpy as np

from repro import obs

from .chiplet import MCM
from .cost import ModelWindowPlan, WindowPlan, WindowResult, evaluate_window
from .maestro import CostDB

_MASK64 = (1 << 64) - 1

# Anneal move accounting (always-on registry counters; see
# docs/observability.md).  EA/beam don't propose/accept moves, so only the
# stochastic chains engine feeds these.
_ANNEAL_PROPOSED = obs.counter("engine.anneal.moves_proposed")
_ANNEAL_ACCEPTED = obs.counter("engine.anneal.moves_accepted")
# What each fused window program is sent: real candidate rows, the rows of
# the pool axis that hold them (the bucket ``n_pad`` per model), the bytes
# of the window's staged inputs and the host-to-device copies that carry them.
_ROWS_REAL = obs.counter("engine.window.rows_real")
_ROWS_PADDED = obs.counter("engine.window.rows_padded")
_UPLOAD_BYTES = obs.counter("engine.window.upload_bytes")
_UPLOADS = obs.counter("engine.window.uploads")
# Windows the fused search picked a plan for, and those among them whose
# best last-stage beam row shares its ordering score with another valid
# row: windows whose plan the beam's tie rule decided.
_PICKS = obs.counter("engine.window.picks")
_TIED_PICKS = obs.counter("engine.window.tied_picks")


@dataclasses.dataclass(frozen=True)
class ModelCandidateSet:
    """Scored placement candidates of one model in one window.

    Candidates are sorted by (tier, score) at build time: tier 0 are
    scheduling-tree-rooted paths (DRAM ports / locality anchors), tier 1 the
    unconstrained fallback roots consulted only when tier 0 is fully blocked
    by exclusive occupancy.

    Two interchangeable representations are supported.  The hot path
    (``sched.build_candidates``) fills the *tensor* fields — ``chips`` /
    ``n_segs`` / ``seg_arr`` / ``mask_words`` — and never materialises a
    Python object per candidate; the legacy *list* fields (``paths`` /
    ``masks`` / ``seg_ends_abs``) may be passed instead (tests, ad-hoc
    construction) and either form is derived lazily from the other on first
    access, cached on the instance.
    """

    model_idx: int
    start: int
    end: int
    lat: np.ndarray
    energy: np.ndarray
    seg_ends_abs: list[tuple[int, ...]] | None = None   # per candidate
    paths: list[tuple[int, ...]] | None = None
    masks: list[int] | None = None
    keep: int = 64                           # preferred expansion width
    mask_words: np.ndarray | None = None     # [N, W] uint64 (lazy if None)
    chips: np.ndarray | None = None          # [N, S] int16, -1 padded
    n_segs: np.ndarray | None = None         # [N]
    seg_arr: np.ndarray | None = None        # [N, S] abs layer ends, -1 pad

    @property
    def n_cands(self) -> int:
        """Candidate count (representation-independent)."""
        return int(self.lat.shape[0])

    def words(self, n_words: int) -> np.ndarray:
        """Packed occupancy words, computed at build time or on demand."""
        mw = self.mask_words
        if mw is None or mw.shape[1] < n_words:
            mw = _pack_masks(self.mask_ints(), n_words)
            object.__setattr__(self, "mask_words", mw)
        return mw

    def path(self, i: int) -> tuple[int, ...]:
        """Candidate ``i``'s chiplet path as a tuple (single-row unpack)."""
        if self.paths is not None:
            return self.paths[i]
        row = self.chips[i]
        return tuple(int(c) for c in row[: int(self.n_segs[i])])

    def seg_end(self, i: int) -> tuple[int, ...]:
        """Candidate ``i``'s absolute segment ends as a tuple."""
        if self.seg_ends_abs is not None:
            return self.seg_ends_abs[i]
        row = self.seg_arr[i]
        return tuple(int(e) for e in row[: int(self.n_segs[i])])

    def path_list(self) -> list[tuple[int, ...]]:
        """All paths as tuples (materialised lazily, cached)."""
        if self.paths is None:
            object.__setattr__(
                self, "paths", [self.path(i) for i in range(self.n_cands)])
        return self.paths

    def mask_ints(self) -> list[int]:
        """Occupancy masks as Python ints (materialised lazily, cached).

        Only the scalar oracles (``reference_combine``, ``search._fitness``)
        need this form; the engines stay on ``mask_words``.
        """
        if self.masks is None:
            mw = self.mask_words
            if mw is not None:
                ints = [0] * mw.shape[0]
                for w in range(mw.shape[1]):
                    shift = 64 * w
                    col = mw[:, w].tolist()
                    ints = [m | (v << shift) for m, v in zip(ints, col)]
            else:                            # list-form set without masks
                ints = []
                for p in self.path_list():
                    m = 0
                    for c in p:
                        m |= 1 << int(c)
                    ints.append(m)
            object.__setattr__(self, "masks", ints)
        return self.masks


@dataclasses.dataclass
class WindowSearchResult:
    plan: WindowPlan
    result: WindowResult
    explored: list[tuple[float, float]]   # (lat, energy) cloud for Pareto


def _pack_masks(masks: list[int], n_words: int) -> np.ndarray:
    """Python-int occupancy masks -> [N, W] uint64 words."""
    out = np.empty((len(masks), n_words), dtype=np.uint64)
    for w in range(n_words):
        shift = 64 * w
        out[:, w] = np.array([(m >> shift) & _MASK64 for m in masks],
                             dtype=np.uint64)
    return out


@dataclasses.dataclass(frozen=True)
class CandidateTensors:
    """A window's candidate sets as padded tensors (the engine currency).

    ``masks``: [M, N_max, W] uint64 occupancy words (padding = all ones so a
    padded candidate conflicts with everything).
    ``lat``/``energy``: [M, N_max] float64 (+inf padding keeps padded rows
    out of any argmin).  ``sizes``: [M] true candidate counts.
    """

    sets: tuple[ModelCandidateSet, ...]
    masks: np.ndarray
    lat: np.ndarray
    energy: np.ndarray
    sizes: np.ndarray
    n_words: int

    @classmethod
    def from_sets(cls, sets: list[ModelCandidateSet],
                  n_chiplets: int) -> "CandidateTensors":
        n_words = max(1, (n_chiplets + 63) // 64)
        m_models = len(sets)
        sizes = np.array([cs.n_cands for cs in sets], dtype=np.int64)
        n_max = int(sizes.max()) if m_models else 0
        masks = np.full((m_models, n_max, n_words), _MASK64, dtype=np.uint64)
        lat = np.full((m_models, n_max), np.inf)
        energy = np.full((m_models, n_max), np.inf)
        for m, cs in enumerate(sets):
            n = cs.n_cands
            masks[m, :n] = cs.words(n_words)
            lat[m, :n] = cs.lat
            energy[m, :n] = cs.energy
        return cls(sets=tuple(sets), masks=masks, lat=lat, energy=energy,
                   sizes=sizes, n_words=n_words)


def metric_score(lat, energy, metric: str):
    """Scalar or vectorized schedule metric (edp is the default)."""
    if metric == "latency":
        return lat
    if metric == "energy":
        return energy
    return lat * energy


def batched_fitness(ct: CandidateTensors, picks: np.ndarray, metric: str
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Population fitness in one batched pass.

    ``picks``: [P, M] candidate index per model.  Returns ``(fitness, lmax,
    esum, overlap)``, each [P].  Accumulates across the model axis in order
    so floats match the scalar reference (``search._fitness``) bit-for-bit.
    """
    n_pop = picks.shape[0]
    lmax = np.zeros(n_pop)
    esum = np.zeros(n_pop)
    overlap = np.zeros(n_pop, dtype=np.int64)
    occ = np.zeros((n_pop, ct.n_words), dtype=np.uint64)
    for m in range(len(ct.sets)):
        idx = picks[:, m]
        mw = ct.masks[m][idx]                                    # [P, W]
        overlap += np.bitwise_count(occ & mw).sum(axis=1).astype(np.int64)
        occ |= mw
        lmax = np.maximum(lmax, ct.lat[m][idx])
        esum = esum + ct.energy[m][idx]
    base = metric_score(lmax, esum, metric)
    return base * (1.0 + 10.0 * overlap), lmax, esum, overlap


def _raise_no_disjoint(model_idx: int, n_cands: int):
    # the exact BeamEngine / reference_combine failure contract
    raise RuntimeError(
        f"no disjoint placement for model {model_idx} even "
        f"after scanning all {n_cands} candidates; "
        f"increase path_cap or reduce provisioned nodes")


def _backtrack(parents: np.ndarray, cands: np.ndarray) -> np.ndarray:
    """Per-stage picks of beam row 0 from the device scan's link tables.

    Walks the ([M, beam] each) (parent, cand) links backwards from the
    best final beam item.
    """
    m = parents.shape[0]
    picks = np.zeros(m, dtype=np.int64)
    row = 0
    for st in range(m - 1, -1, -1):
        picks[st] = cands[st, row]
        row = int(parents[st, row])
    return picks


def _explored(tlats: np.ndarray, tes: np.ndarray,
              counts: np.ndarray) -> list[tuple[float, float]]:
    """Per-stage (lat, energy) cloud, first ``counts[m]`` beam rows each.

    The rows past a stage's live count are top-k filler.
    """
    explored: list[tuple[float, float]] = []
    for m in range(tlats.shape[0]):
        n = int(counts[m])
        explored.extend(zip(tlats[m, :n].tolist(), tes[m, :n].tolist()))
    return explored


def _tied_pick(tlats: np.ndarray, tes: np.ndarray, counts: np.ndarray,
               metric: str) -> bool:
    """Whether the last stage's best beam row ties another valid row.

    The stage's rows come sorted by the device's ordering score, so a tie
    with row 0 shows in row 1.  Scores are recomputed from the fetched
    per-row latency and energy in their own precision.
    """
    if int(counts[-1]) < 2:
        return False
    score = metric_score(tlats[-1, :2], tes[-1, :2], metric)
    return bool(score[0] == score[1])


def _plans_from_picks(sets, picks) -> WindowPlan:
    plans = []
    for cs, ci in zip(sets, picks):
        ci = int(ci)
        plans.append(ModelWindowPlan(
            model_idx=cs.model_idx, start=cs.start, end=cs.end,
            seg_ends=cs.seg_end(ci), chiplets=cs.path(ci),
            pipelined=True))
    return WindowPlan(plans=tuple(sorted(plans, key=lambda p: p.model_idx)))


class SearchEngine(Protocol):
    """One window-combination solver: pick one candidate per model."""

    def combine(self, db: CostDB, mcm: MCM, sets: list[ModelCandidateSet],
                prev_end: dict[int, int],
                metric: str = "edp") -> WindowSearchResult: ...


# ---------------------------------------------------------------------------
# Beam search
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BeamEngine:
    """Vectorized beam search over disjoint per-model path combinations.

    Per model stage, disjointness of every (beam item, candidate) pair is one
    broadcast AND over the packed mask words; the reference loop's per-item
    ``keep`` width and the global expansion budget are reproduced with
    cumulative-sum bookkeeping so results stay bit-identical to
    ``reference_combine``.
    """

    beam: int = 64
    max_expansions: int = 20000
    comm_model: str = "analytic"

    def combine(self, db: CostDB, mcm: MCM, sets: list[ModelCandidateSet],
                prev_end: dict[int, int],
                metric: str = "edp") -> WindowSearchResult:
        with obs.span("combine", cat="engine", engine="beam",
                      models=len(sets), beam=self.beam):
            return self._combine(db, mcm, sets, prev_end, metric)

    def _combine(self, db: CostDB, mcm: MCM, sets: list[ModelCandidateSet],
                 prev_end: dict[int, int],
                 metric: str = "edp") -> WindowSearchResult:
        # order models by compute weight (largest first: hardest to place)
        sets = sorted(sets, key=lambda s: -float(np.min(s.lat)))
        n_words = max(1, (mcm.n_chiplets + 63) // 64)

        b_mask = np.zeros((1, n_words), dtype=np.uint64)
        b_lat = np.zeros(1)
        b_energy = np.zeros(1)
        b_picks = np.zeros((1, 0), dtype=np.int64)
        explored: list[tuple[float, float]] = []
        expansions = 0
        for cs in sets:
            with obs.span("beam_stage", cat="engine", model=cs.model_idx,
                          cands=cs.n_cands):
                n_cand = cs.n_cands
                cand_masks = cs.words(n_words)                    # [N, W]
                if n_words == 1:
                    disjoint = (b_mask[:, 0, None]
                                & cand_masks[None, :, 0]) == 0    # [B, N]
                else:
                    disjoint = ((b_mask[:, None, :]
                                 & cand_masks[None, :, :]) == 0).all(axis=-1)
                # per-beam-item expansion width (candidates are (tier, score)
                # sorted, so "first keep disjoint" == "best keep disjoint")
                if cs.keep < n_cand:
                    rank = np.add.accumulate(disjoint, axis=1, dtype=np.int32)
                    sel = disjoint & (rank <= cs.keep)
                else:
                    sel = disjoint
                total = int(np.count_nonzero(sel))
                if total == 0:
                    raise RuntimeError(
                        f"no disjoint placement for model {cs.model_idx} "
                        f"even after scanning all {n_cand} candidates; "
                        f"increase path_cap or reduce provisioned nodes")
                if expansions + total > self.max_expansions:
                    # global expansion budget, row-major acceptance order;
                    # the first acceptance of a stage always goes through
                    flat_sel = sel.ravel()
                    before = np.cumsum(flat_sel) - flat_sel
                    okf = flat_sel & (
                        (expansions + before < self.max_expansions)
                        | (before == 0))
                    sel = okf.reshape(sel.shape)
                    total = int(np.count_nonzero(sel))
                expansions += total
                rows, cand_idx = np.nonzero(sel)
                new_lat = np.maximum(b_lat[rows], cs.lat[cand_idx])
                new_energy = b_energy[rows] + cs.energy[cand_idx]
                # scarlint: ignore[SL004] -- f64 host beam ordering, stable
                # by construction; the device protocol program mirrors this
                # exact argsort (quantising here would fork the bit-parity)
                order = np.argsort(metric_score(new_lat, new_energy, metric),
                                   kind="stable")[:self.beam]
                rows, cand_idx = rows[order], cand_idx[order]
                b_mask = b_mask[rows] | cand_masks[cand_idx]
                b_lat, b_energy = new_lat[order], new_energy[order]
                b_picks = np.concatenate(
                    [b_picks[rows], cand_idx[:, None]], axis=1)
                explored.extend(zip(b_lat.tolist(), b_energy.tolist()))

        plan = _plans_from_picks(sets, b_picks[0])
        result = evaluate_window(db, mcm, plan, prev_end, validate=True,
                                 comm_model=self.comm_model)
        return WindowSearchResult(plan=plan, result=result, explored=explored)


def reference_combine(db: CostDB, mcm: MCM, sets: list[ModelCandidateSet],
                      prev_end: dict[int, int], metric: str = "edp",
                      beam: int = 64,
                      max_expansions: int = 20000,
                      comm_model: str = "analytic") -> WindowSearchResult:
    """Reference Python beam search (the seed implementation).

    Kept as the oracle for ``BeamEngine`` parity tests and as the baseline
    for ``bench_sched_throughput``; not used on the scheduling hot path.
    """
    sets = sorted(sets, key=lambda s: -float(np.min(s.lat)))
    # beam items: (mask, lat_max, energy_sum, [choice indices])
    items: list[tuple[int, float, float, list[int]]] = [(0, 0.0, 0.0, [])]
    explored: list[tuple[float, float]] = []
    expansions = 0
    for cs in sets:
        cs_masks = cs.mask_ints()
        nxt: list[tuple[int, float, float, list[int]]] = []
        for mask, lmax, esum, picks in items:
            found = 0
            for ci in range(cs.n_cands):
                if (expansions >= max_expansions or found >= cs.keep) and nxt:
                    break
                if mask & cs_masks[ci]:
                    continue
                expansions += 1
                found += 1
                nl = max(lmax, float(cs.lat[ci]))
                ne = esum + float(cs.energy[ci])
                nxt.append((mask | cs_masks[ci], nl, ne, picks + [ci]))
        if not nxt:
            raise RuntimeError(
                f"no disjoint placement for model {cs.model_idx} even after "
                f"scanning all {cs.n_cands} candidates; "
                f"increase path_cap or reduce provisioned nodes")
        nxt.sort(key=lambda it: metric_score(it[1], it[2], metric))
        explored.extend((l, e) for _, l, e, _ in nxt[:beam])
        items = nxt[:beam]

    plan = _plans_from_picks(sets, items[0][3])
    result = evaluate_window(db, mcm, plan, prev_end, validate=True,
                             comm_model=comm_model)
    return WindowSearchResult(plan=plan, result=result, explored=explored)


# ---------------------------------------------------------------------------
# Whole-search-on-device beam (jax)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceBeamEngine:
    """Beam search whose window combine runs as one jitted device program.

    Two entry points share the ``core.device_search`` beam scan:

    * ``combine`` — the ``SearchEngine`` protocol form.  Consumes host-scored
      candidate sets and runs the *combination* (disjointness screen via the
      ``kernels.scar_search`` AND+popcount op, keep/budget accounting, beam
      expansion, top-k) on device under scoped float64.  Each stage performs
      the reference's exact IEEE ops (one max, one add, one multiply per
      item) and ``lax.top_k``'s lowest-flat-index tie rule equals the
      reference's stable row-major acceptance order, so plans, metrics and
      the explored cloud are bit-identical to ``reference_combine`` /
      ``BeamEngine`` (asserted on all ten paper scenarios in
      ``tests/test_device_search.py``).  A CPU parity form: it raises on a
      TPU, whose XLA emulates float64 without IEEE rounding (on a v5e the
      explored cloud came back a few ulps off the reference).
    * ``combine_window`` — the fused throughput form ``scheduler.schedule``
      routes ``algo="beam_jax"`` through.  The host only *constructs*
      candidates (PROV + SEG + tensor assembly); scoring
      (``kernels.scar_eval``), quantised (tier, score) candidate ordering,
      model ordering, the beam scan and top-k all compile into one float32
      device program per (mesh, window-shape) bucket, and the whole window
      result returns in a single counted ``launch.platform.device_fetch`` —
      O(1) syncs per window, O(n_windows) per schedule, independent of
      models x candidates.  The final plan is re-scored and validated by the
      float64 numpy oracle (``evaluate_window``), so reported metrics stay
      exact.  The plan itself is ``BeamEngine``'s except where two float64
      scores lie closer than float32 rounding, or a score falls across a
      quantisation boundary: the float32 scores keep the float64 ties of
      placements built from the same layers, under every metric, so the
      tie rule decides as the host's does (``core.device_search`` states
      the contract; ``tests/test_device_search.py`` checks it under EDP
      and under latency, where most windows end in a tie).

    ``use_kernel=None`` auto-selects the Pallas kernels on TPU and the
    jax_ref forms elsewhere; ``interpret=True`` runs the kernels anywhere
    (tests/nightly).
    """

    beam: int = 64
    max_expansions: int = 20000
    use_kernel: Optional[bool] = None
    interpret: bool = False
    comm_model: str = "analytic"

    def uses_kernels(self) -> bool:
        """Whether this engine runs the Pallas kernels (True on a TPU)."""
        if self.use_kernel is not None:
            return self.use_kernel
        from .evaluator import _jax_platform
        return _jax_platform() == "tpu"

    def combine(self, db: CostDB, mcm: MCM, sets: list[ModelCandidateSet],
                prev_end: dict[int, int],
                metric: str = "edp") -> WindowSearchResult:
        from repro.launch import platform as launch_platform

        from . import device_search as ds
        from .evaluator import _jax_platform

        if _jax_platform() == "tpu":
            raise RuntimeError(
                "DeviceBeamEngine.combine is a CPU parity form: its float64 "
                "ops are not IEEE-exact on a TPU, so it cannot match the "
                "reference there; schedule() routes algo='beam_jax' through "
                "combine_window")
        sets = sorted(sets, key=lambda s: -float(np.min(s.lat)))
        n_words = max(1, (mcm.n_chiplets + 63) // 64)
        m_models = len(sets)
        n_pad = ds.bucket_size(max(cs.n_cands for cs in sets))
        masks = np.zeros((m_models, n_pad, 2 * n_words), dtype=np.uint32)
        lat = np.full((m_models, n_pad), np.inf)
        energy = np.full((m_models, n_pad), np.inf)
        sizes = np.zeros(m_models, dtype=np.int32)
        keeps = np.zeros(m_models, dtype=np.int32)
        for m, cs in enumerate(sets):
            n = cs.n_cands
            masks[m, :n] = ds.split_words_u32(cs.words(n_words))
            lat[m, :n] = cs.lat
            energy[m, :n] = cs.energy
            sizes[m], keeps[m] = n, cs.keep
        # scoped x64: the combination ops then run in float64 and match the
        # host reference bit-for-bit
        t0 = ds.probe_width(n_pad, int(keeps.max()))
        ds.note_program("protocol", (m_models, n_pad, n_words, self.beam,
                                     metric, self.max_expansions, t0,
                                     self.uses_kernels(), self.interpret))
        with obs.span("device_combine", cat="engine", engine="beam_jax",
                      models=m_models, n_pad=n_pad), \
                launch_platform.enable_x64():
            out = ds.protocol_program(
                masks, lat, energy, sizes, keeps, beam=self.beam,
                metric=metric, max_exp=self.max_expansions, t0=t0,
                use_kernel=self.uses_kernels(), interpret=self.interpret)
            # the single host transfer of the whole combination
            parents, cands, tlats, tes, counts, fails = \
                launch_platform.device_fetch(out)
        failed = np.flatnonzero(fails)
        if failed.size:
            cs = sets[int(failed[0])]
            _raise_no_disjoint(cs.model_idx, cs.n_cands)
        plan = _plans_from_picks(sets, _backtrack(parents, cands))
        result = evaluate_window(db, mcm, plan, prev_end, validate=True,
                                 comm_model=self.comm_model)
        return WindowSearchResult(plan=plan, result=result,
                                  explored=_explored(tlats, tes, counts))

    def combine_window(self, db: CostDB, mcm: MCM, cfg,
                       ranges: dict[int, tuple[int, int]],
                       prev_end: dict[int, int],
                       metric: Optional[str] = None) -> WindowSearchResult:
        metric = metric or cfg.metric
        with obs.span("combine_window", cat="engine", engine="beam_jax",
                      models=len(ranges), beam=self.beam):
            return self._combine_window(db, mcm, cfg, ranges, prev_end,
                                        metric)

    def _combine_window(self, db: CostDB, mcm: MCM, cfg,
                        ranges: dict[int, tuple[int, int]],
                        prev_end: dict[int, int],
                        metric: str) -> WindowSearchResult:
        # local imports: sched/scheduler import this module at module level
        import jax

        from repro.kernels.scar_eval import pack_candidates
        from repro.launch import platform as launch_platform

        from . import device_search as ds
        from .evaluator import EVAL_BLOCK_B
        from .provision import provision
        from .sched import assemble_candidates
        from .segmentation import top_k_segmentations

        # leaf spans (cat ``engine``) cover the window's host stages, so a
        # profiler trace names each of the device's idle gaps
        with obs.span("provision", cat="engine", models=len(ranges)):
            alloc = provision(db, mcm.class_counts(), ranges, mcm.n_chiplets,
                              metric=cfg.metric,
                              max_nodes_per_model=cfg.max_nodes_per_model)
        n_active = len(ranges)
        use_kernel = self.uses_kernels()
        inputs, modes, built = [], [], []
        for mi, (s, e) in sorted(ranges.items()):
            with obs.span("segment", cat="engine", model=mi):
                segs = top_k_segmentations(db, mcm, s, e, alloc[mi],
                                           k=cfg.seg_top_k, cap=cfg.seg_cap,
                                           metric=cfg.metric)
            with obs.span("assemble", cat="engine", model=mi) as sp:
                cand, tiers, (words, chips, seg_arr) = assemble_candidates(
                    mcm, mi, (s, e), segs, prev_end.get(mi),
                    path_cap=cfg.path_cap, frontier_cap=cfg.frontier_cap,
                    need_seg_id=use_kernel)
                sp.set(cands=int(cand.seg_id.shape[0]))
            with obs.span("pack", cat="engine", model=mi) as sp:
                # congestion: ship full-shape zero wait tables;
                # fused_program substitutes the traced, bg-derived tables
                args, statics, n_real = pack_candidates(
                    db, mcm, cand, n_active, prev_end=prev_end.get(mi),
                    pad_b=EVAL_BLOCK_B, dense=use_kernel,
                    comm_model=self.comm_model)
                w32 = ds.split_words_u32(words)
                t32 = tiers.astype(np.int32)
                pad = args[5].shape[0] - n_real      # chips are [B_pad, S]
                if pad:
                    w32 = np.concatenate(
                        [w32, np.zeros((pad, w32.shape[1]), np.uint32)])
                    t32 = np.concatenate([t32, np.zeros(pad, np.int32)])
                sp.set(rows=int(w32.shape[0]))
            inputs.append((args, w32, t32, np.int32(n_real)))
            modes.append((statics["pipelined"], statics["has_prev"]))
            built.append((cand, chips, seg_arr))

        n_pad = ds.bucket_size(max(i[1].shape[0] for i in inputs))
        keep = int(cfg.keep_per_model)
        t0, t1 = ds.pool_widths(keep)
        congestion = self.comm_model == "congestion"
        with obs.span("dispatch", cat="engine", n_pad=n_pad):
            shapes = tuple(tuple(a.shape for a in i[0]) + (i[1].shape,)
                           for i in inputs)
            _ROWS_REAL.inc(sum(int(i[3]) for i in inputs))
            _ROWS_PADDED.inc(n_pad * len(inputs))
            ds.note_program(
                "fused",
                (shapes, tuple(modes), n_active, n_pad, self.beam, keep,
                 metric, self.max_expansions, t0, t1, use_kernel,
                 self.interpret, congestion))
            # the window's one host-to-device copy: a copy costs a fixed
            # overhead on the chip, whatever its size
            buf, layout = ds.stage_window(tuple(inputs))
            _UPLOAD_BYTES.inc(buf.nbytes)
            _UPLOADS.inc()
            out = ds.fused_program(
                jax.device_put(buf), layout=layout, modes=tuple(modes),
                pkg=mcm.pkg, mcm_cols=mcm.cols, n_active=n_active,
                n_pad=n_pad, beam=self.beam, keep=keep, metric=metric,
                max_exp=self.max_expansions, t0=t0, t1=t1,
                use_kernel=use_kernel, interpret=self.interpret,
                mcm_rows=mcm.rows, congestion=congestion,
                noc=mcm.noc if congestion else None)
        # the single counted host transfer of the whole window search
        with obs.span("fetch", cat="engine"):
            (morder, parents, cands, tlats, tes,
             counts, fails) = launch_platform.device_fetch(out)
        with obs.span("rescore", cat="engine"):
            failed = np.flatnonzero(fails)
            if failed.size:
                cand = built[int(morder[int(failed[0])])][0]
                _raise_no_disjoint(cand.model_idx, cand.seg_id.shape[0])
            picks = _backtrack(parents, cands)
            _PICKS.inc()
            _TIED_PICKS.inc(int(_tied_pick(tlats, tes, counts, metric)))
            plans = []
            for st in range(len(built)):
                cand, chips, seg_arr = built[int(morder[st])]
                # the scan emits assembled-candidate row indices directly
                r = int(picks[st])
                ns = int(cand.n_segs[r])
                plans.append(ModelWindowPlan(
                    model_idx=cand.model_idx, start=cand.start, end=cand.end,
                    seg_ends=tuple(int(x) for x in seg_arr[r, :ns]),
                    chiplets=tuple(int(c) for c in chips[r, :ns]),
                    pipelined=True))
            plan = WindowPlan(plans=tuple(sorted(plans,
                                                 key=lambda p: p.model_idx)))
            result = evaluate_window(db, mcm, plan, prev_end, validate=True,
                                     comm_model=self.comm_model)
            explored = _explored(tlats, tes, counts)
        return WindowSearchResult(plan=plan, result=result,
                                  explored=explored)


# ---------------------------------------------------------------------------
# Evolutionary search
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EvolutionaryEngine:
    """(mu + lambda) EA with uniform crossover and overlap-penalty fitness.

    The RNG call sequence matches the paper-faithful seed implementation, so
    seeded runs reproduce its trajectory exactly; the whole population is
    scored per generation with one ``batched_fitness`` pass.
    """

    population: int = 10
    generations: int = 4
    mutation_rate: float = 0.3
    seed: int = 0
    comm_model: str = "analytic"

    def combine(self, db: CostDB, mcm: MCM, sets: list[ModelCandidateSet],
                prev_end: dict[int, int],
                metric: str = "edp") -> WindowSearchResult:
        rng = np.random.default_rng(self.seed)
        ct = CandidateTensors.from_sets(sets, mcm.n_chiplets)
        n_models = len(sets)
        sizes = np.array([cs.n_cands for cs in sets])
        pop = np.stack([rng.integers(0, sizes)
                        for _ in range(self.population)])
        pop[0] = 0  # seed with per-model greedy best
        explored: list[tuple[float, float]] = []

        outer = obs.span("combine", cat="engine", engine="evolutionary",
                         models=n_models, population=self.population)
        with outer:
            fit, lmax, esum, _ = batched_fitness(ct, pop, metric)
            for gen in range(self.generations):
                with obs.span("ea_generation", cat="engine", generation=gen):
                    children = []
                    for _ in range(self.population):
                        i, j = rng.integers(0, self.population, size=2)
                        a = pop[i] if fit[i] < fit[j] else pop[j]
                        p, q = rng.integers(0, self.population, size=2)
                        b = pop[p] if fit[p] < fit[q] else pop[q]
                        xover = rng.random(n_models) < 0.5
                        child = np.where(xover, a, b)
                        mut = rng.random(n_models) < self.mutation_rate
                        child = np.where(mut, rng.integers(0, sizes), child)
                        children.append(child)
                    cpop = np.stack(children)
                    cfit, clmax, cesum, _ = batched_fitness(ct, cpop, metric)
                    allp = np.concatenate([pop, cpop])
                    allf = np.concatenate([fit, cfit])
                    order = np.argsort(allf, kind="stable")[:self.population]
                    pop, fit = allp[order], allf[order]
                    lmax = np.concatenate([lmax, clmax])[order]
                    esum = np.concatenate([esum, cesum])[order]
                    explored.extend(zip(lmax.tolist(), esum.tolist()))

        best = pop[0]
        _, _, _, overlap = batched_fitness(ct, best[None, :], metric)
        if int(overlap[0]) > 0:
            # repair residual overlap greedily via the beam combiner
            res = BeamEngine(comm_model=self.comm_model).combine(
                db, mcm, sets, prev_end, metric=metric)
            res.explored.extend(explored)
            return res

        plan = _plans_from_picks(sets, best)
        result = evaluate_window(db, mcm, plan, prev_end, validate=True,
                                 comm_model=self.comm_model)
        return WindowSearchResult(plan=plan, result=result, explored=explored)


# ---------------------------------------------------------------------------
# Simulated annealing (beyond-paper)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AnnealEngine:
    """Parallel simulated-annealing chains over the candidate tensors.

    ``chains`` independent walkers mutate one model's pick per step; all
    proposals are scored with a single ``batched_fitness`` call per step.
    Chain 0 starts from the per-model greedy best, the rest from random
    picks.  Any residual occupancy overlap is repaired with the beam engine,
    so the result is always a valid window plan.
    """

    iters: int = 200
    chains: int = 24
    temperature: float = 0.05
    seed: int = 0
    comm_model: str = "analytic"

    def combine(self, db: CostDB, mcm: MCM, sets: list[ModelCandidateSet],
                prev_end: dict[int, int],
                metric: str = "edp") -> WindowSearchResult:
        with obs.span("combine", cat="engine", engine="anneal",
                      models=len(sets), chains=self.chains,
                      iters=self.iters):
            return self._combine(db, mcm, sets, prev_end, metric)

    def _combine(self, db: CostDB, mcm: MCM, sets: list[ModelCandidateSet],
                 prev_end: dict[int, int],
                 metric: str = "edp") -> WindowSearchResult:
        rng = np.random.default_rng(self.seed)
        ct = CandidateTensors.from_sets(sets, mcm.n_chiplets)
        n_models = len(sets)
        n_chains = self.chains
        picks = np.stack([rng.integers(0, ct.sizes)
                          for _ in range(n_chains)])
        picks[0] = 0
        fit, lmax, esum, _ = batched_fitness(ct, picks, metric)
        best_picks, best_fit = picks.copy(), fit.copy()
        explored: list[tuple[float, float]] = list(
            zip(lmax.tolist(), esum.tolist()))
        rows = np.arange(n_chains)
        for it in range(self.iters):
            with obs.span("anneal_iter", cat="engine", iter=it):
                t = self.temperature * (1.0 - it / max(1, self.iters))
                col = rng.integers(0, n_models, size=n_chains)
                new_val = rng.integers(0, ct.sizes[col])
                prop = picks.copy()
                prop[rows, col] = new_val
                pfit, plm, pes, _ = batched_fitness(ct, prop, metric)
                with np.errstate(over="ignore"):
                    accept = (pfit < fit) | (
                        rng.random(n_chains)
                        < np.exp(-(pfit / fit - 1.0) / max(t, 1e-9)))
                picks = np.where(accept[:, None], prop, picks)
                fit = np.where(accept, pfit, fit)
                improved = fit < best_fit
                best_picks = np.where(improved[:, None], picks, best_picks)
                best_fit = np.where(improved, fit, best_fit)
                _ANNEAL_PROPOSED.inc(n_chains)
                _ANNEAL_ACCEPTED.inc(int(np.count_nonzero(accept)))
                explored.extend(zip(plm[accept].tolist(),
                                    pes[accept].tolist()))

        best = best_picks[int(np.argmin(best_fit))]
        _, _, _, overlap = batched_fitness(ct, best[None, :], metric)
        if int(overlap[0]) > 0:
            res = BeamEngine(comm_model=self.comm_model).combine(
                db, mcm, sets, prev_end, metric=metric)
            res.explored.extend(explored)
            return res
        plan = _plans_from_picks(sets, best)
        result = evaluate_window(db, mcm, plan, prev_end, validate=True,
                                 comm_model=self.comm_model)
        return WindowSearchResult(plan=plan, result=result, explored=explored)


def get_engine(cfg, seed: int = 0) -> SearchEngine:
    """Engine factory keyed on ``SearchConfig.algo``.

    ``seed`` is the per-window seed (``cfg.seed + window_index``) so
    stochastic engines decorrelate across windows like the seed code did.

    The ``SCAR_SEARCH_BACKEND`` env var overrides the *beam-family* choice
    (``brute``/``beam`` -> host numpy, ``beam_jax`` -> device) without
    touching configs — mirroring ``SCAR_EVAL_BACKEND`` — and is ignored for
    the stochastic engines, whose trajectories are algorithm-specific.
    """
    algo = cfg.algo
    comm_model = getattr(cfg, "comm_model", "analytic")
    env = os.environ.get("SCAR_SEARCH_BACKEND", "").strip()
    if env and algo in ("brute", "beam", "beam_jax"):
        algo = env
    if algo in ("brute", "beam"):
        return BeamEngine(beam=cfg.beam, comm_model=comm_model)
    if algo == "beam_jax":
        return DeviceBeamEngine(beam=cfg.beam, comm_model=comm_model)
    if algo == "evolutionary":
        return EvolutionaryEngine(population=cfg.ea_population,
                                  generations=cfg.ea_generations,
                                  seed=seed, comm_model=comm_model)
    if algo == "anneal":
        return AnnealEngine(iters=cfg.anneal_iters,
                            chains=cfg.anneal_chains,
                            temperature=cfg.anneal_temperature,
                            seed=seed, comm_model=comm_model)
    raise KeyError(f"unknown search algo {algo!r}; "
                   "have brute|beam|beam_jax|evolutionary|anneal")
