"""Fused device search programs: scoring + beam combination + top-k, one jit.

The PR 4 pipeline alternates host and device per (model, window): score a
candidate batch on device, fetch it, order it on host, combine on host with
the numpy beam — O(models x windows) host-device syncs and a numpy combine
that dominates large-mesh schedule construction.  This module compiles the
whole window search into ONE jitted program per (mesh, window-shape) bucket:

* ``protocol_program`` — beam *combination* only, over host-scored float64
  candidate tables.  Run under scoped x64 (``launch.platform.enable_x64``) the
  per-stage ops are the reference's exact IEEE operations (one ``max``, one
  ``add``, one ``multiply``) and ``lax.top_k``'s lowest-flat-index tie rule
  reproduces the reference's stable row-major acceptance order, so plans,
  metrics and the explored cloud are *bit-identical* to
  ``engine.reference_combine`` — the engine-level parity contract.
* ``fused_program`` — the throughput form: per-model candidate scoring
  (``kernels.scar_eval`` via ``evaluator.traceable_scores``), quantised
  (tier, score) candidate ordering, compute-weight model ordering, the
  shared beam scan and top-k — all inside one float32 jit.  The host only
  constructs candidates and fetches the final picks: O(1) syncs per window.

Both share ``beam_scan``, a ``lax.scan`` over models whose per-stage
disjointness screen is the ``kernels.scar_search`` AND+popcount op.  The
scan works from a per-model candidate *pool* — a prefix of the full
(tier, quantised-score) candidate order — and falls back to the full pool
under ``lax.cond`` only when some beam row found fewer than ``keep``
disjoint candidates in the prefix.  Both branches implement the host
``BeamEngine`` stage semantics (keep-rank filter, row-major budget
truncation, stable score/tie top-k); the pool branch equals the fallback
because the host keep filter only ever selects a row's first ``keep``
disjoint candidates, which the completeness predicate confines to the
prefix.

What that guarantees against the float64 host beam (the oracle) depends
on the scores the scan is given.  ``protocol_program`` takes the host's
float64 tables and returns its plans bit for bit, under every metric.
``fused_program`` scores in float32, and the ordering contract it shares
with the oracle has two parts:

* the per-model pool order is (tier, score quantised to ``SCORE_SIG``),
  then enumeration, as ``sched.build_candidates``.  The float32 score's
  digits are rounded exactly (``quantize_scores_jax``), so only a
  candidate whose float32 score itself falls on the other side of a
  quantisation boundary from its float64 score sits one bucket off;
* each beam stage orders on the unquantised score, equal scores in
  row-major order, as ``BeamEngine``'s stable float64 argsort.  Segment
  compute latencies are summed exactly (``kernels.scar_eval.split_exact``),
  and energies as prefix-sum differences that telescope, so the float64
  ties that placements built from the same layers make — one bottleneck
  segment under ``metric="latency"``, where most windows end in a tie;
  splits of the same layers on one class under energy or EDP — stay ties
  in float32, and the tie rule picks the oracle's plan.  Two float64
  scores closer than float32 rounding (~1e-7 relative) may still be
  ordered otherwise.

So the fused plans equal the oracle's except at such near-ties and
quantisation boundaries, under each metric; the reported metrics are the
float64 re-score of the plan picked.  ``engine.window.tied_picks`` counts
the windows whose plan the tie rule decided.

Why pools instead of sorting every candidate up front: XLA's CPU sort costs
~16 ms per 47k-candidate model while two per-tier ``lax.top_k`` passes cost
<2 ms, and a full sort then only ever runs inside the rare fallback branch
(``lax.cond`` executes just the taken branch).  Tier-0 candidates sort
before all tier-1 candidates and positive-float score bits are
order-isomorphic to their uint32 patterns, so the pool key packs
``tier << 31 | bitcast(quantised score)`` and per-tier ``top_k`` returns
host-order prefixes with the host's lowest-index tie rule.

Static program keys: the staging layout (per-model shapes) + mode flags,
package params, mesh cols, ``n_active``, the bucketed full-pool width, beam
width, keep, metric and the pool widths — a handful of compiles per (mesh,
window shape); candidate *counts* and anchors are traced and do not
recompile.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.scar_search import conflict_counts_traceable

from .cost import route_wait_tables
from .engine import metric_score
from .evaluator import traceable_scores
from .quantize import SCORE_SIG, quantize_scores_jax

_KEY_INVALID = np.uint32(0xFFFFFFFF)

# Shape-bucket compile accounting, mirroring evaluator._SEEN_SIGNATURES:
# the engine reports each (program, static signature) it is about to
# request, and a first-seen signature counts as one XLA compile.
_RECOMPILES = obs.counter("device_search.jit_recompiles")
_SEEN_PROGRAMS: set[tuple] = set()


def note_program(kind: str, key: tuple) -> None:
    """Record a device-program request; first-seen keys count as compiles.

    ``kind`` names the program ("protocol" | "fused"), ``key`` its full
    static signature (shapes + static argument values).  Deterministic and
    jax-version-independent, unlike polling jit cache internals.
    """
    sig = (kind,) + key
    if sig not in _SEEN_PROGRAMS:
        _SEEN_PROGRAMS.add(sig)
        _RECOMPILES.inc()
        obs.event("jit_compile", cat="device_search", program=kind)


def bucket_size(n: int, base: int = 256) -> int:
    """Round ``n`` up to a shape bucket.

    Buckets are powers of two up to 8192, then multiples of 8192.
    The full-pool axis of the device programs is padded to this, so a whole
    schedule's windows land on a few discrete shapes (= a few jit entries)
    instead of recompiling per candidate count, without power-of-two
    padding waste on large pools.
    """
    b = base
    while b < n and b < 8192:
        b *= 2
    if n <= b:
        return b
    return -(-n // 8192) * 8192


def pool_widths(keep: int) -> tuple[int, int]:
    """Static (tier-0, tier-1) candidate-pool widths for a ``keep`` value.

    Sized so a beam row finding ``keep`` disjoint candidates inside the
    pool prefix is the overwhelmingly common case (the pool holds the best
    candidates of each tier); the exact-fallback branch covers the rest.
    """
    return max(2048, 4 * keep), max(256, 2 * keep)


def probe_width(n_pad: int, keep: int) -> int:
    """Static prefix width of ``protocol_program``'s candidate pool."""
    return min(n_pad, max(512, 2 * keep))


def split_words_u32(words: np.ndarray) -> np.ndarray:
    """uint64 occupancy words [N, W] -> uint32 [N, 2W], (lo, hi) per word.

    jax only carries uint64 under x64; splitting host-side keeps the device
    masks 32-bit everywhere (``lax.population_count`` on uint32) while
    preserving exact per-chiplet occupancy.
    """
    lo = (words & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (words >> np.uint64(32)).astype(np.uint32)
    out = np.empty((words.shape[0], 2 * words.shape[1]), np.uint32)
    out[:, 0::2] = lo
    out[:, 1::2] = hi
    return out


def stage_window(inputs):
    """Every array of ``inputs`` in one host ``uint32`` buffer, bitwise.

    ``inputs`` is a pytree whose leaves are 4-byte numpy arrays or scalars
    (float32, int32, uint32).  Returns ``(buf, layout)``: ``buf`` holds the
    leaves' words back to back (one host copy, so the window makes one
    host-to-device transfer instead of one per array), and ``layout`` —
    the tree structure and, per leaf, its word offset, shape and dtype —
    is the static key ``unstage_window`` rebuilds the leaves from.  It
    depends on the leaves' shapes and dtypes only, never their values.
    """
    leaves, treedef = jax.tree.flatten(inputs)
    words, slots, off = [], [], 0
    for leaf in leaves:
        a = np.asarray(leaf)
        if a.dtype.itemsize != 4:
            raise TypeError(f"a staged array must be 4 bytes wide, "
                            f"got {a.dtype}")
        words.append(a.reshape(-1).view(np.uint32))
        slots.append((off, a.shape, a.dtype))
        off += a.size
    return np.concatenate(words), (treedef, tuple(slots))


def unstage_window(buf, layout):
    """In-jit inverse of ``stage_window``: the same pytree, bit for bit.

    Each leaf is a static slice of ``buf``, bitcast to its dtype and
    reshaped; values stay traced, so a new anchor or candidate count
    never recompiles.
    """
    treedef, slots = layout
    return jax.tree.unflatten(treedef, [
        jax.lax.bitcast_convert_type(buf[off:off + math.prod(shape)],
                                     dtype).reshape(shape)
        for off, shape, dtype in slots])


def beam_scan(pool, full, *, beam: int, metric: str, max_exp: int,
              t0_width: int, use_kernel: bool, interpret: bool,
              presorted: bool):
    """The shared beam combination: one ``lax.scan`` stage per model.

    ``pool``: ``(words [M, P, 2W], lat [M, P], e [M, P], idx [M, P],
    valid [M, P], t0_only [M])`` — per model, a prefix of its full
    (tier, score) candidate order (``idx`` maps pool slot -> original
    candidate row; invalid slots are padding).  ``t0_only`` marks models
    whose tier-0 candidates overflow the pool's first ``t0_width`` slots,
    in which case only that segment is a prefix of the full order (the
    completeness predicate then ignores the pool's tier-1 tail).

    ``full``: ``(words [M, N, 2W], lat [M, N], e [M, N], key [M, N] | None,
    sizes [M], keeps [M])`` — every candidate, in host candidate order when
    ``presorted`` (the fallback scans it directly) else unsorted with
    ``key`` the packed (tier, score) order key (the fallback argsorts it
    in-branch — the cost only paid when the branch is taken).

    Per-stage semantics are ``engine.BeamEngine``'s, expressed
    unconditionally: the keep-rank filter and the row-major budget
    truncation are applied every stage (they are no-ops exactly when the
    host skips them), and ``lax.top_k`` on the negated scores reproduces
    the stable ascending sort + row-major tie order of the host's
    ``argsort(kind="stable")`` over ``np.nonzero``'s row-major listing.

    Returns per-stage ``(parent [beam], cand [beam], lat [beam],
    energy [beam], n_new, failed)`` with ``cand`` an *original* candidate
    row index — enough for the host to backtrack picks from beam row 0 and
    rebuild the explored cloud, in one fetch.
    """
    p_words, p_lat, p_e, p_idx, p_valid, p_t0only = pool
    f_words, f_lat, f_e, f_key, sizes, keeps = full
    _, n_pool, w2 = p_words.shape
    n_full = f_words.shape[1]
    fdt = p_lat.dtype

    def stage(carry, xs):
        b_mask, b_lat, b_e, valid_beam, expansions, fail = carry
        if presorted:
            pw, pl, pe, pidx, pvalid, t0only, fw, fl, fe, size, keep = xs
        else:
            pw, pl, pe, pidx, pvalid, t0only, fw, fl, fe, size, keep, \
                fkey = xs

        def conflicts(words):
            return conflict_counts_traceable(
                b_mask, words, use_kernel=use_kernel, interpret=interpret)

        def keep_budget(dis):
            # first ``keep`` disjoint per row, then the global expansion
            # budget in row-major acceptance order (a stage's first
            # acceptance always goes through) — cf. BeamEngine.combine
            rank = jnp.cumsum(dis, axis=1, dtype=jnp.int32)
            flat = (dis & (rank <= keep)).ravel()
            before = jnp.cumsum(flat, dtype=jnp.int32) - flat
            return flat & ((expansions + before < max_exp) | (before == 0))

        def score_pick(flat, n_s, cl_s, ce_s):
            total = jnp.sum(flat, dtype=jnp.int32)
            new_lat = jnp.maximum(b_lat[:, None], cl_s[None, :])
            new_e = b_e[:, None] + ce_s[None, :]
            sc = jnp.where(flat.reshape(beam, n_s),
                           metric_score(new_lat, new_e, metric), jnp.inf)
            with jax.named_scope("beam_pick"):
                # Bit for bit in the float64 protocol form; in the float32
                # fused form the oracle's ties of same-layer placements
                # stay ties, nearer scores may not (module docstring).
                # scarlint: ignore[SL004] -- mirrors BeamEngine.combine's
                # unquantised stable argsort: equal scores row-major
                _, idx = jax.lax.top_k(-sc.ravel(), beam)
            return ((idx // n_s).astype(jnp.int32),
                    (idx % n_s).astype(jnp.int32), total)

        dis_p = (conflicts(pw) == 0) & pvalid[None, :] & valid_beam[:, None]
        # the prefix of the full candidate order this pool covers: its
        # tier-0 segment when tier-0 overflowed it, the whole pool
        # otherwise.  A row with >= keep disjoint candidates there selects
        # exactly what the host's keep filter would.
        count_t0 = jnp.sum(dis_p[:, :t0_width], axis=1, dtype=jnp.int32)
        count_all = jnp.sum(dis_p, axis=1, dtype=jnp.int32)
        count_prefix = jnp.where(t0only, count_t0, count_all)
        complete = (jnp.all((count_prefix >= keep) | ~valid_beam)
                    & (jnp.sum(count_prefix) > 0))

        def small(_):
            parent, j, total = score_pick(keep_budget(dis_p), n_pool,
                                          pl, pe)
            return parent, pidx[j], total

        def big(_):
            if presorted:
                fw_s, fl_s, fe_s = fw, fl, fe
            else:
                order = jnp.argsort(fkey)      # stable: host (tier, score,
                fw_s = fw[order]               # enumeration) order; only
                fl_s = fl[order]               # paid when this branch runs
                fe_s = fe[order]
            valid_c = jnp.arange(n_full) < size
            dis = ((conflicts(fw_s) == 0) & valid_c[None, :]
                   & valid_beam[:, None])
            parent, j, total = score_pick(keep_budget(dis), n_full,
                                          fl_s, fe_s)
            cand = j if presorted else order[j]
            return parent, cand.astype(jnp.int32), total

        parent, cand, total = jax.lax.cond(complete, small, big, None)
        n_new = jnp.minimum(total, beam)
        new_lat = jnp.maximum(b_lat[parent], fl[cand])
        new_e = b_e[parent] + fe[cand]
        carry = (b_mask[parent] | fw[cand], new_lat, new_e,
                 jnp.arange(beam) < n_new, expansions + total,
                 fail | (total == 0))
        return carry, (parent, cand, new_lat, new_e, n_new, total == 0)

    carry0 = (jnp.zeros((beam, w2), jnp.uint32),
              jnp.zeros(beam, fdt), jnp.zeros(beam, fdt),
              jnp.arange(beam) < 1, jnp.int32(0), jnp.asarray(False))
    xs = (p_words, p_lat, p_e, p_idx, p_valid, p_t0only,
          f_words, f_lat, f_e, sizes, keeps)
    if not presorted:
        xs = xs + (f_key,)
    with jax.named_scope("beam_scan"):
        _, ys = jax.lax.scan(stage, carry0, xs)
    return ys


@partial(jax.jit, static_argnames=("beam", "metric", "max_exp", "t0",
                                   "use_kernel", "interpret"))
def protocol_program(masks, lat, energy, sizes, keeps, *, beam: int,
                     metric: str, max_exp: int, t0: int, use_kernel: bool,
                     interpret: bool):
    """Device combination over host-scored tables (the bit-parity form).

    The pool is simply the first ``t0`` candidates of each model —
    already a prefix of the host order.
    """
    m_models, n_pad = lat.shape
    arange = jnp.arange(t0, dtype=jnp.int32)
    pool = (masks[:, :t0], lat[:, :t0], energy[:, :t0],
            jnp.broadcast_to(arange, (m_models, t0)),
            arange[None, :] < sizes[:, None],
            jnp.zeros(m_models, bool))
    full = (masks, lat, energy, None, sizes, keeps)
    return beam_scan(pool, full, beam=beam, metric=metric, max_exp=max_exp,
                     t0_width=t0, use_kernel=use_kernel, interpret=interpret,
                     presorted=True)


def _cand_link_bytes(args, best, *, rows: int, cols: int, has_prev: bool):
    """Interposer link bytes ``[n_links]`` of ONE packed candidate, in-jit.

    ``args`` is a ``scar_eval.pack_candidates`` tuple, ``best`` a traced
    candidate row index.  Reproduces ``cost.plan_link_bytes`` — the same
    transfer set ``evaluate_window`` prices (per-segment weight streams on
    DRAM routes, first-segment activations, inter-segment XY forwards,
    last-segment writeback) — as scatter-adds on per-row/per-column
    difference arrays: a route's horizontal leg adds ``+z`` at its low
    column and ``-z`` past its high column on the source row (vertical leg
    likewise on the destination column), so a prefix ``cumsum`` recovers
    every link's byte count without materialising routes.  Zero-hop legs
    cancel out by construction.
    """
    (_, _, w_bytes, out_bytes, _, chips, _, last, n_segs,
     act_in, prev_idx, _, _) = args
    S = chips.shape[1]
    lw = w_bytes.shape[0]
    cpos = jnp.maximum(chips[best], 0)                           # [S]
    ns = n_segs[best]
    exists = jnp.arange(S) < ns
    lastc = jnp.clip(last[best], 0, lw - 1)
    prevc = jnp.concatenate(
        [jnp.zeros((1,), lastc.dtype), lastc[:-1] + 1])
    cw = jnp.concatenate([jnp.zeros(1, jnp.float32), jnp.cumsum(w_bytes)])
    seg_w = jnp.where(exists, cw[lastc + 1] - cw[prevc], 0.0)
    seg_out = jnp.where(exists, out_bytes[lastc], 0.0)
    is_last = jnp.arange(S) == ns - 1

    r, c = cpos // cols, cpos % cols
    edge = jnp.where(c <= cols - 1 - c, 0, cols - 1)
    nxtp = jnp.roll(cpos, -1)
    r2, c2 = nxtp // cols, nxtp % cols

    # bytes on each segment's DRAM route: weights, + cold input activations
    # on the first segment, + the window-output writeback on the last
    dram_b = seg_w + jnp.where(is_last, seg_out, 0.0)
    if not has_prev:
        dram_b = dram_b + jnp.where(jnp.arange(S) == 0, act_in, 0.0)
    fwd_b = jnp.where(exists & ~is_last, seg_out, 0.0)

    src_r = jnp.concatenate([r, r])
    src_c = jnp.concatenate([c, c])
    dst_r = jnp.concatenate([r, r2])
    dst_c = jnp.concatenate([edge, c2])
    z = jnp.concatenate([dram_b, fwd_b])
    if has_prev:
        # anchor -> first chiplet activation route (nothing when resident)
        pr, pc = prev_idx // cols, prev_idx % cols
        a0 = jnp.where(prev_idx == cpos[0], 0.0, act_in)
        src_r = jnp.concatenate([src_r, pr[None]])
        src_c = jnp.concatenate([src_c, pc[None]])
        dst_r = jnp.concatenate([dst_r, r[:1]])
        dst_c = jnp.concatenate([dst_c, c[:1]])
        z = jnp.concatenate([z, a0[None]])

    lo_c = jnp.minimum(src_c, dst_c)
    hi_c = jnp.maximum(src_c, dst_c)
    lo_r = jnp.minimum(src_r, dst_r)
    hi_r = jnp.maximum(src_r, dst_r)
    h = jnp.zeros((rows, cols), jnp.float32)
    h = h.at[src_r, lo_c].add(z).at[src_r, hi_c].add(-z)
    v = jnp.zeros((rows, cols), jnp.float32)
    v = v.at[lo_r, dst_c].add(z).at[hi_r, dst_c].add(-z)
    return jnp.concatenate([jnp.cumsum(h, axis=1)[:, :cols - 1].ravel(),
                            jnp.cumsum(v, axis=0)[:rows - 1].ravel()])


def _order_key(qs, tiers, valid):
    """Packed uint32 (tier, quantised score) order key.

    Non-negative float32 scores order like their bit patterns, so
    ``tier << 31 | bitcast(score)`` orders lexicographically by
    (tier, score); invalid rows get the maximal key and sort last.
    """
    bits = jax.lax.bitcast_convert_type(jnp.maximum(qs, 0.0), jnp.uint32)
    key = bits | (tiers.astype(jnp.uint32) << 31)
    return jnp.where(valid, key, _KEY_INVALID)


@partial(jax.jit, static_argnames=("layout", "modes", "pkg", "mcm_cols",
                                   "n_active", "n_pad", "beam", "keep",
                                   "metric", "max_exp", "t0", "t1",
                                   "use_kernel", "interpret", "mcm_rows",
                                   "congestion", "noc"))
def fused_program(buf, *, layout, modes, pkg, mcm_cols: int, n_active: int,
                  n_pad: int, beam: int, keep: int, metric: str,
                  max_exp: int, t0: int, t1: int, use_kernel: bool,
                  interpret: bool, mcm_rows: int = 0,
                  congestion: bool = False, noc=None):
    """The whole window search as one device program (see module docstring).

    ``buf``, ``layout``: the window's inputs as ``stage_window`` staged
    them — per model ``(eval_args, words [B, 2W] uint32, tiers [B] int32,
    n_real)`` where ``eval_args`` is ``scar_eval.pack_candidates`` output
    and ``B`` its padded batch; ``modes``: per model
    ``(pipelined, has_prev)`` static flags.  Returns
    ``(model_order,) + beam_scan ys`` — the ys candidate indices address
    the *assembled* candidate batches directly, so the host rebuilds the
    window plan from one fetch.

    ``congestion=True`` replays ``scheduler.build_window_sets``'s placement
    co-search inside the jit: models are scored in input (sorted model-idx)
    order against a running background byte occupancy ``bg [n_links]``,
    each model's bottleneck-wait tables are rebuilt from ``bg`` with
    ``cost.route_wait_tables`` and substituted into its eval args' two
    trailing slots, and after scoring the greedy-best candidate's routed
    bytes (``_cand_link_bytes``, the in-jit ``cost.plan_link_bytes``) are
    accumulated into ``bg`` for the models that follow.  ``mcm_rows`` and
    the static ``noc`` link config are only consulted in this mode.
    """
    inputs = unstage_window(buf, layout)
    if congestion:
        n_h = mcm_rows * (mcm_cols - 1)
        inv_bw = np.zeros(n_h + (mcm_rows - 1) * mcm_cols, np.float32)
        inv_bw[:n_h] = 1.0 / noc.h_bw
        inv_bw[n_h:] = 1.0 / noc.v_bw
        bg = jnp.zeros(inv_bw.shape[0], jnp.float32)
    pools, fulls, mlats = [], [], []
    for (args, words, tiers, n_real), (pipelined, has_prev) in zip(inputs,
                                                                   modes):
        statics = dict(pkg=pkg, mcm_cols=mcm_cols, n_active=n_active,
                       pipelined=pipelined, has_prev=has_prev,
                       congestion=congestion,
                       noc=noc if congestion else None)
        with jax.named_scope("score"):
            if congestion:
                wp, wd = route_wait_tables(jnp, bg * inv_bw, mcm_rows,
                                           mcm_cols)
                args = args[:11] + (wp, wd)
            lat, energy = traceable_scores(args, statics,
                                           use_kernel=use_kernel,
                                           interpret=interpret)
        b_pad = lat.shape[0]
        valid = jnp.arange(b_pad) < n_real
        with jax.named_scope("order"):
            # the host ordering contract (sched.build_candidates): stable
            # sort on (tier, score quantised to the shared grain)
            qs = quantize_scores_jax(metric_score(lat, energy, metric),
                                     sig=SCORE_SIG)
            key = _order_key(qs, tiers, valid)
        if congestion:
            # greedy best = host lexsort rank 0 (argmin of the packed key
            # breaks exact ties by enumeration order, like the stable sort)
            bg = bg + _cand_link_bytes(args, jnp.argmin(key), rows=mcm_rows,
                                       cols=mcm_cols, has_prev=has_prev)

        with jax.named_scope("order"):
            def tier_top(tier_id, width):
                neg = jnp.where(valid & (tiers == tier_id), -qs, -jnp.inf)
                vals, idx = jax.lax.top_k(neg, min(width, b_pad))
                pad = width - idx.shape[0]
                return (jnp.pad(idx.astype(jnp.int32), (0, pad)),
                        jnp.pad(vals > -jnp.inf, (0, pad)))

            i0, ok0 = tier_top(0, t0)
            i1, ok1 = tier_top(1, t1)
            p_idx = jnp.concatenate([i0, i1])
            p_valid = jnp.concatenate([ok0, ok1])
            lat_v = jnp.where(valid, lat, jnp.inf)
            e_v = jnp.where(valid, energy, jnp.inf)
            pools.append((
                jnp.where(p_valid[:, None], words[p_idx], 0),
                jnp.where(p_valid, lat_v[p_idx], jnp.inf),
                jnp.where(p_valid, e_v[p_idx], jnp.inf),
                p_idx, p_valid,
                jnp.sum(valid & (tiers == 0), dtype=jnp.int32) > t0))
            pad = n_pad - b_pad
            fulls.append((
                jnp.pad(words, ((0, pad), (0, 0))),
                jnp.pad(lat_v, (0, pad), constant_values=np.inf),
                jnp.pad(e_v, (0, pad), constant_values=np.inf),
                jnp.pad(key, (0, pad), constant_values=_KEY_INVALID)))
            mlats.append(jnp.min(lat_v))

    with jax.named_scope("order"):
        # model order by compute weight, largest min-latency first (the
        # host engines' ``sorted(key=-min(lat))``; jnp.argsort is stable)
        morder = jnp.argsort(-jnp.stack(mlats))
        pool = tuple(jnp.stack([p[k] for p in pools])[morder]
                     for k in range(6))
        full = tuple(jnp.stack([f[k] for f in fulls])[morder]
                     for k in range(4))
        sizes = jnp.stack([jnp.asarray(i[3], jnp.int32)
                           for i in inputs])[morder]
    keeps = jnp.full((len(inputs),), keep, jnp.int32)
    ys = beam_scan(pool, full[:3] + (full[3], sizes, keeps), beam=beam,
                   metric=metric, max_exp=max_exp, t0_width=t0,
                   use_kernel=use_kernel, interpret=interpret,
                   presorted=False)
    return (morder,) + ys
