"""Batched segmentation scoring: parity with the scalar oracle on all ten
paper scenarios, plus tie/quantisation semantics."""
import numpy as np
import pytest

from repro import obs
from repro.core import SCENARIO_NAMES, SearchConfig, get_scenario, make_mcm
from repro.core.provision import provision
from repro.core.reconfig import greedy_pack
from repro.core.scheduler import get_cost_db
from repro.core.segmentation import (_build_split_table, _quantize_scores,
                                     _split_table, enumerate_segmentations,
                                     score_segmentation,
                                     score_segmentations_batch,
                                     top_k_segmentations)


@pytest.mark.parametrize("scenario", SCENARIO_NAMES)
def test_batched_scores_match_scalar_oracle(scenario):
    """Every model of every paper scenario, all three metrics: the batched
    pass reproduces the per-candidate scalar loop's scores (<=1e-9 relative;
    the implementations sum segments in different orders) and selects a
    top-k with identical oracle scores (exactly-tied candidates — repeated
    transformer blocks make ties structural — may swap, scored order may
    not)."""
    sc = get_scenario(scenario)
    npe = 4096 if scenario.startswith("dc") else 256
    mcm = make_mcm("het_sides", n_pe=npe)
    db = get_cost_db(sc, mcm)
    for metric in ("edp", "latency", "energy"):
        for mi in range(db.n_models):
            sl = db.model_slice(mi)
            cands = enumerate_segmentations(sl.stop - sl.start, 4, cap=128)
            scalar = np.array([score_segmentation(db, mcm, sl.start, se,
                                                  metric) for se in cands])
            batch = score_segmentations_batch(db, mcm, sl.start, cands,
                                              metric)
            np.testing.assert_allclose(batch, scalar, rtol=1e-9, atol=0)
            smap = dict(zip(cands, scalar))
            ref = sorted(cands, key=smap.get)[:4]
            got = top_k_segmentations(db, mcm, sl.start, sl.stop, 4, k=4,
                                      cap=128, metric=metric)
            np.testing.assert_array_equal(
                _quantize_scores(np.array([smap[se] for se in got])),
                _quantize_scores(np.array([smap[se] for se in ref])),
                err_msg=f"{scenario}/{metric}/model{mi}: top-k selection is "
                        f"not score-equivalent to the scalar oracle")


def test_batch_handles_single_and_full_split():
    sc = get_scenario("xr8_outdoors")
    mcm = make_mcm("het_sides", n_pe=256)
    db = get_cost_db(sc, mcm)
    sl = db.model_slice(0)
    n = sl.stop - sl.start
    cands = [(n,), tuple(range(1, n + 1))]      # 1 segment vs all-singleton
    batch = score_segmentations_batch(db, mcm, sl.start, cands, "edp")
    scalar = [score_segmentation(db, mcm, sl.start, se, "edp")
              for se in cands]
    np.testing.assert_allclose(batch, scalar, rtol=1e-9)
    assert score_segmentations_batch(db, mcm, sl.start, [], "edp").size == 0


def test_quantize_scores_merges_float_noise_only():
    s = np.array([1.0, 1.0 + 1e-14, 2.0, 0.0, 1e-30])
    q = _quantize_scores(s)
    assert q[0] == q[1]                  # noise-level difference merged
    assert q[2] != q[0]
    assert q[3] == 0.0
    assert q[4] > 0.0                    # subnormal-ish values survive


def _reference_scores(db, mcm, start, segs, metric):
    """The batched scorer as it was before the split table: the ends
    matrix built per candidate and one tile + reduceat pass per array."""
    pkg = mcm.pkg
    n = len(segs)
    n_segs = np.array([len(se) for se in segs], dtype=np.int64)
    S = int(n_segs.max())
    Lw = int(segs[0][-1])
    ends = np.zeros((n, S), dtype=np.int64)
    for i, se in enumerate(segs):
        ends[i, :len(se)] = se
    valid = np.arange(S)[None, :] < n_segs[:, None]
    starts = np.concatenate([np.zeros((n, 1), dtype=np.int64),
                             ends[:, :-1]], axis=1)
    sl = slice(start, start + Lw)
    flat_starts = (np.arange(n)[:, None] * Lw + starts)[valid]
    seg_lat_c = np.zeros((n, S, db.lat.shape[1]))
    seg_e_c = np.zeros_like(seg_lat_c)
    w = np.zeros((n, S))
    seg_lat_c[valid] = np.add.reduceat(
        np.tile(db.lat[sl], (n, 1)), flat_starts, axis=0)
    seg_e_c[valid] = np.add.reduceat(
        np.tile(db.energy[sl], (n, 1)), flat_starts, axis=0)
    w[valid] = np.add.reduceat(np.tile(db.w_bytes[sl], n), flat_starts)
    seg_lat_c[~valid] = np.inf
    cls = np.argmin(seg_lat_c, axis=2)
    lat_best = np.take_along_axis(seg_lat_c, cls[:, :, None], axis=2)[:, :, 0]
    e_best = np.take_along_axis(seg_e_c, cls[:, :, None], axis=2)[:, :, 0]
    load = w / pkg.dram_bw + pkg.dram_lat_s
    seg_lat = np.where(valid, lat_best + load, -np.inf)
    seg_e = np.where(valid, e_best + w * 8.0 * pkg.dram_e_pj_per_bit * 1e-12,
                     0.0)
    lat = seg_lat.max(axis=1)
    energy = seg_e.sum(axis=1)
    if metric == "latency":
        return lat
    if metric == "energy":
        return energy
    return lat * energy


@pytest.mark.parametrize("metric", ["edp", "latency", "energy"])
@pytest.mark.parametrize("cap", [128, 512])
@pytest.mark.parametrize("scenario", SCENARIO_NAMES)
def test_split_table_scores_equal_the_prior_scorer(scenario, cap, metric):
    """Every model of every window the default search plans on a 6x6
    package, with its provisioned node count: the split-table scores and
    top-k equal the prior scorer's bit for bit."""
    sc = get_scenario(scenario)
    npe = 4096 if scenario.startswith("dc") else 256
    mcm = make_mcm("het_sides", rows=6, cols=6, n_pe=npe)
    db = get_cost_db(sc, mcm)
    cfg = SearchConfig()
    counts = mcm.class_counts()
    for ranges in greedy_pack(db, counts, cfg.n_splits).ranges:
        alloc = provision(db, counts, ranges, mcm.n_chiplets, metric=metric,
                          max_nodes_per_model=cfg.max_nodes_per_model)
        for mi, (s, e) in sorted(ranges.items()):
            cands = enumerate_segmentations(e - s, alloc[mi], cap=cap)
            ref = _reference_scores(db, mcm, s, cands, metric)
            assert np.array_equal(
                score_segmentations_batch(db, mcm, s, cands, metric), ref)
            order = np.argsort(_quantize_scores(ref), kind="stable")[:4]
            got = top_k_segmentations(db, mcm, s, e, alloc[mi], k=4,
                                      cap=cap, metric=metric)
            assert got == [cands[i] for i in order], (mi, (s, e))


def _table_counts():
    return (obs.registry.value("seg.table_hits"),
            obs.registry.value("seg.table_misses"))


def test_split_table_is_built_once_per_space():
    sc = get_scenario("xr8_outdoors")
    mcm = make_mcm("het_sides", n_pe=256)
    db = get_cost_db(sc, mcm)
    sl = db.model_slice(0)
    _build_split_table.cache_clear()
    h0, m0 = _table_counts()
    top_k_segmentations(db, mcm, sl.start, sl.stop, 3, k=2, cap=64)
    h1, m1 = _table_counts()
    assert (h1 - h0, m1 - m0) == (0, 1)
    # same (length, nodes, cap) at another start: one hit, no build
    top_k_segmentations(db, mcm, sl.start + 1, sl.stop + 1, 3, k=2, cap=64)
    h2, m2 = _table_counts()
    assert (h2 - h1, m2 - m1) == (1, 0)
    # nodes beyond the length clamp to the same space
    n = sl.stop - sl.start
    top_k_segmentations(db, mcm, sl.start, sl.start + 2, n, cap=64)
    top_k_segmentations(db, mcm, sl.start, sl.start + 2, n + 5, cap=64)
    h3, m3 = _table_counts()
    assert (h3 - h2, m3 - m2) == (1, 1)


def test_split_table_is_read_only_and_in_enumeration_order():
    t = _split_table(12, 4, 64)
    assert list(t.segs) == enumerate_segmentations(12, 4, cap=64)
    for a in (t.n_segs, t.ends, t.valid, t.flat_starts, t.slots):
        assert not a.flags.writeable
        with pytest.raises(ValueError):
            a[0] = 0
