"""Wrapper + bridge from ``repro.core`` candidate sets to kernel inputs.

``pack_candidates`` converts a ``BatchedModelCandidates`` + CostDB + MCM into
the compact tensors the jitted ``evaluate`` consumes: ``[B, S]`` integer
chiplet ids and segment-boundary indices (plus ``[B, Lw]`` layer segment ids
for the dense kernel form) and the per-layer cost tables.  Everything
derived — per-segment reductions and the communication terms — is computed
*inside* the jit, on device, through the SAME
``repro.core.cost.comm_from_parts`` formulas the numpy oracle uses (this
module once carried a hand-copied clone of that geometry, plus a hard-coded
``pipelined=True``; both bridge divergences are gone).

Two device forms share those terms:

* ``use_kernel=False`` (jax_ref): within a segment the chiplet class is
  constant, so segment compute sums are differences of the per-class
  prefix-summed cost tables gathered at segment boundaries — O(B*S) work,
  no ``[B, Lw]`` tensor is ever materialised.  The fast form off the TPU.
* ``use_kernel=True`` (Pallas): the dense per-layer form, where the kernel
  reduces every layer of VMEM-resident, candidate-major blocks.

Both forms sum a segment's compute latency exactly: ``split_exact`` ships
the latency table as two parts on power-of-two grids, coarse enough that
float32 sums of either part are exact in any order.  A segment's latency
is then the same bits whatever its offset in the window and whichever
form sums it, as the float64 oracle's is.  That matters under the
latency metric, where many placements share one bottleneck segment: a
float32 prefix-sum difference rounds differently at each offset, would
split those ties, and would move the beam's tie-break off the oracle's.
Energy, a sum over segments, keeps the prefix-sum differences, which
telescope: the segments' sums add back to the window's, so splits of the
same layers on one class mostly sum alike.

Shape bucketing keeps the jit cache small across a search run: the segment
axis ``S`` is shrunk to the per-batch max segment count (padded segments
carry only zeros and are masked), and the batch axis ``B`` is padded up to
a multiple of ``pad_b`` (= the kernel block), so every batch of a given
(Lw, S) lands on one of a few discrete shapes instead of recompiling per
candidate count.

Static jit keys: package params + mesh cols + ``n_active`` + the
``pipelined`` / ``has_prev`` mode flags — a handful of values per run.  The
locality anchor itself (``prev_idx``) is traced, so warm-start anchors do
not recompile.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost import (comm_from_parts, congestion_correction,
                             link_bandwidths, n_interposer_links,
                             segment_last_layers)

from .kernel import scar_eval


def evaluate_traceable(lat_tab, e_tab, w_bytes, out_bytes, class_map, chips,
                       seg_id, last, n_segs, act_in, prev_idx, wait_pair,
                       wait_dram, *, pkg,
                       mcm_cols: int, n_active: int, pipelined: bool = True,
                       has_prev: bool = False, congestion: bool = False,
                       noc=None, block_b: int = 128,
                       interpret: bool = False, use_kernel: bool = True):
    """[B, 2] (latency, energy) from compact packed inputs — traceable form.

    ``lat_tab`` is the ``split_exact`` latency table ``[2, Lw, C]``.
    ``chips``/``seg_id``/``last``/``n_segs`` are integer ids (``last`` is
    the window-relative index of each segment's final layer); reductions and
    ``comm_from_parts`` run on device, fused into the jit.  ``prev_idx`` is
    the (traced) locality anchor, consulted only when ``has_prev``.
    ``wait_pair``/``wait_dram`` are the (traced) bottleneck-wait tables of
    ``cost.route_wait_tables``, consulted — together with the static
    ``noc`` link config — only when ``congestion`` (the
    ``comm_model="congestion"`` routed corrections fold into the comm
    latency before the kernel, so the Pallas form is congestion-agnostic).

    This un-jitted form exists for *composition*: the fused device search
    program (``core.engine.DeviceBeamEngine``) inlines candidate scoring
    into its own jitted window program by calling it under trace (via
    ``core.evaluator.traceable_scores``), so scores never leave the device
    between evaluation and beam combination.  Standalone callers use the
    jitted ``evaluate`` wrapper below.
    """
    B, S = chips.shape
    _, Lw, C = lat_tab.shape
    cpos = jnp.maximum(chips, 0)
    seg_cls = class_map[cpos]                                    # [B, S]
    exists = jnp.arange(S)[None, :] < n_segs[:, None]
    lastc = jnp.clip(last, 0, Lw - 1)
    prev = jnp.concatenate([jnp.full((B, 1), -1, jnp.int32),
                            last[:, :-1]], axis=1)
    prevc = jnp.maximum(prev, -1) + 1                            # [B, S] >= 0

    # per-segment reductions as prefix-sum differences at the boundaries
    # (cf. cost.segment_reductions, device form)
    seg_last_out = jnp.where(exists, out_bytes[lastc], 0.0)
    cw = jnp.concatenate([jnp.zeros(1, jnp.float32),
                          jnp.cumsum(w_bytes)])                  # [Lw + 1]
    seg_w = jnp.where(exists, cw[lastc + 1] - cw[prevc], 0.0)

    ip_lat, ip_e, op_lat, op_e = comm_from_parts(
        jnp, pkg, mcm_cols, cpos, seg_w, seg_last_out, n_segs, n_active,
        act_in, prev_idx if has_prev else None)
    if congestion:
        ip_corr, op_corr = congestion_correction(
            jnp, pkg, noc, mcm_cols, cpos, seg_w, seg_last_out, n_segs,
            act_in, prev_idx if has_prev else None, wait_pair, wait_dram)
        ip_lat = ip_lat + ip_corr
        op_lat = op_lat + op_corr
    # the class is constant within a segment, so a segment's sum of a
    # table is a difference of its per-class prefix sums at the segment
    # boundaries — O(B*S) gathers, one class column at a time (a gather
    # from a small 1-D table compiles to a select on the TPU, a 2-D one
    # to a slow general gather)
    zrow = jnp.zeros((1, C), jnp.float32)

    def seg_sums(tab):
        cum = jnp.concatenate([zrow, jnp.cumsum(tab, axis=0)])
        out = jnp.zeros((B, S), jnp.float32)
        for c in range(C):
            col = cum[:, c]
            out = jnp.where(seg_cls == c, col[lastc + 1] - col[prevc], out)
        return out

    # the latency table's fine part rides with the comm latency; both
    # parts sum exactly, so a segment's latency does not depend on where
    # it sits (``split_exact``)
    comm_lat = ip_lat + op_lat + seg_sums(lat_tab[1])
    comm_e = ip_e + op_e
    valid = exists.astype(jnp.float32)

    if use_kernel:
        # dense per-layer form: the Pallas kernel reduces every layer of
        # VMEM-resident, candidate-major blocks
        layer_cls = jnp.take_along_axis(seg_cls, seg_id, axis=1)  # [B, Lw]
        pipe = jnp.full((1, B), 1.0 if pipelined else 0.0, jnp.float32)
        return scar_eval(lat_tab[0], e_tab, layer_cls.T, seg_id.T,
                         comm_lat.T, comm_e.T, valid.T, pipe,
                         block_b=block_b, interpret=interpret).T

    # jax_ref: prefix-sum differences, the fast non-MXU form; on the
    # latency table's coarse part they are the kernel's dense sums, bit
    # for bit.  Semantics are pinned to scar_eval_ref / the numpy oracle
    # by parity tests (tests/test_evaluator.py, tests/test_kernels.py).
    seg_lat = jnp.where(exists, seg_sums(lat_tab[0]) + comm_lat, 0.0)
    energy = jnp.where(exists, seg_sums(e_tab) + comm_e, 0.0).sum(axis=1)
    lat_sum = seg_lat.sum(axis=1)
    if pipelined:
        lat_max = jnp.max(jnp.where(exists, seg_lat, -jnp.inf), axis=1)
        lat = jnp.where(n_segs > 1, lat_max, lat_sum)
    else:
        lat = lat_sum
    return jnp.stack([lat, energy], axis=-1)


# The standalone entry point: identical signature/semantics, one jit cache
# keyed on the static mode flags (the traced ``prev_idx`` anchor does not
# recompile).
evaluate = partial(jax.jit, static_argnames=(
    "pkg", "mcm_cols", "n_active", "pipelined", "has_prev", "congestion",
    "noc", "block_b", "interpret", "use_kernel"))(evaluate_traceable)


def split_exact(tab: np.ndarray) -> np.ndarray:
    """A float64 ``[Lw, C]`` cost table as float32 ``[2, Lw, C]`` parts.

    Part 0 is each value rounded to a power-of-two grid per column, coarse
    enough that the column's total is at most 2**23 grid steps, so every
    float32 sum of its entries is exact, in any order.  Part 1 is the
    remainder on a grid as fine again, with the same property; what is
    left out is below 2**-46 of the column's total.
    """
    parts, rest = [], np.asarray(tab, np.float64)
    for _ in range(2):
        total = np.abs(rest).sum(axis=0)
        step = np.exp2(np.ceil(np.log2(np.where(total > 0, total, 1.0)
                                       / 2.0 ** 23)))
        parts.append(np.round(rest / step) * step)
        rest = rest - parts[-1]
    return np.stack(parts).astype(np.float32)


def pack_candidates(db, mcm, cand, n_active: int, prev_end=None,
                    pad_b: int = 128, *, pipelined: bool = True,
                    dense: bool = True, comm_model: str = "analytic",
                    link_occ=None):
    """Compact, shape-bucketed inputs for one model's candidate batch.

    Returns ``(args, statics, B)``: positional arrays for ``evaluate``, the
    static keyword arguments (``pkg``/``mcm_cols``/``n_active``/
    ``pipelined``/``has_prev``/``congestion``/``noc``) and the real
    (pre-padding) candidate count.  ``args`` are host numpy arrays and
    scalars, each 4 bytes wide (float32 or int32); placing them on the
    device is left to the caller (the jitted ``evaluate`` does it per
    call, the fused window search stages a whole window in one upload).
    ``pipelined=False`` selects the sequential (sum over segments) latency
    mode, matching ``eval_model_candidates(..., pipelined=False)``.

    ``comm_model="congestion"`` ships the bottleneck-wait tables built from
    ``link_occ`` (the co-tenants' ``[n_links]`` byte occupancy; None means
    uncontended) as the two trailing traced args, so a changing background
    never recompiles; under ``"analytic"`` those slots carry ``[1, 1]`` /
    ``[1]`` placeholders the trace never reads.

    ``dense=False`` ships a ``[B, 1]`` placeholder in the ``seg_id`` slot —
    the per-layer segment ids are consumed only by the ``use_kernel=True``
    dense form, and at large path caps they are the batch's largest array
    (``[B, Lw]``), so jax_ref callers skip that cast and its upload.
    """
    B, Lw = cand.seg_id.shape
    S = max(1, int(cand.n_segs.max()))           # shrink to per-batch max
    lat_tab = split_exact(db.lat[cand.start:cand.end])
    e_tab = db.energy[cand.start:cand.end].astype(np.float32)
    w_bytes = db.w_bytes[cand.start:cand.end].astype(np.float32)
    out_bytes = db.out_bytes[cand.start:cand.end].astype(np.float32)
    class_map = np.asarray(mcm.class_map, dtype=np.int32)

    chips = cand.chiplets[:, :S].astype(np.int32)
    seg_id = (cand.seg_id.astype(np.int32) if dense
              else np.zeros((B, 1), np.int32))
    n_segs = cand.n_segs.astype(np.int32)
    if cand.seg_ends is not None:                # free at construction time
        last = (cand.seg_ends[:, :S] - cand.start - 1).astype(np.int32)
    else:
        last = segment_last_layers(cand.seg_id, S).astype(np.int32)

    pad = (-B) % pad_b
    if pad:
        def z(a):
            return np.concatenate([a, np.zeros((pad,) + a.shape[1:],
                                               a.dtype)])
        chips, seg_id = z(chips), z(seg_id)
        last, n_segs = z(last), z(n_segs)
    congestion = comm_model == "congestion"
    if congestion:
        from repro.core.cost import route_wait_tables
        if link_occ is None:
            link_occ = np.zeros(n_interposer_links(mcm.rows, mcm.cols))
        wait_pair, wait_dram = route_wait_tables(
            np, np.asarray(link_occ, np.float64) / link_bandwidths(mcm),
            mcm.rows, mcm.cols)
        wait_pair = wait_pair.astype(np.float32)
        wait_dram = wait_dram.astype(np.float32)
    else:
        wait_pair = np.zeros((1, 1), np.float32)
        wait_dram = np.zeros(1, np.float32)
    args = (lat_tab, e_tab, w_bytes, out_bytes, class_map, chips, seg_id,
            last, n_segs, np.float32(db.in_bytes[cand.start]),
            np.int32(prev_end if prev_end is not None else 0),
            wait_pair, wait_dram)
    statics = dict(pkg=mcm.pkg, mcm_cols=mcm.cols, n_active=n_active,
                   pipelined=pipelined, has_prev=prev_end is not None,
                   congestion=congestion,
                   noc=mcm.noc if congestion else None)
    return args, statics, B
