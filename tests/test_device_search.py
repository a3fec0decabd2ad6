"""Device search path tests: protocol bit-parity with the reference beam,
fused-schedule plan identity, the O(1)-syncs-per-window contract, shared
quantisation parity, and the shape-bucketing helpers.

The hypothesis property over randomized meshes/beam widths lives in
``test_cost_properties.py`` (hypothesis-gated); everything here is
deterministic."""
import numpy as np
import pytest

from repro.core import (SCENARIO_NAMES, SearchConfig, get_scenario,
                        make_mcm, schedule)
from repro.core.engine import DeviceBeamEngine, reference_combine
from repro.core.quantize import SCORE_SIG, quantize_scores
from repro.core.reconfig import greedy_pack
from repro.core.scheduler import build_window_sets, get_cost_db
from repro.launch import platform as lp

pytest.importorskip("jax")


def _windows(sc, mcm, cfg):
    """Per-window (sets, anchors) exactly as the scheduler builds them,
    advancing anchors along the reference trajectory."""
    db = get_cost_db(sc, mcm)
    wa = greedy_pack(db, mcm.class_counts(), cfg.n_splits)
    prev_end: dict[int, int] = {}
    out = []
    for ranges in wa.ranges:
        sets = build_window_sets(db, mcm, cfg, ranges, prev_end)
        out.append((sets, dict(prev_end)))
        wr = reference_combine(db, mcm, sets, prev_end, metric=cfg.metric,
                               beam=cfg.beam)
        prev_end = dict(prev_end)
        prev_end.update(wr.result.end_chiplet)
    return db, out


# --------------------- protocol bit-parity (oracle) -------------------------

@pytest.mark.parametrize("scenario", SCENARIO_NAMES)
def test_device_beam_bit_identical_to_reference(scenario):
    """Every window of every 3x3 paper scenario: the device combination
    (scoped float64) returns the same best WindowPlan, the same metrics, and
    the same explored cloud — bit-for-bit — as the reference Python beam."""
    npe = 4096 if scenario.startswith("dc") else 256
    sc = get_scenario(scenario)
    mcm = make_mcm("het_sides", n_pe=npe)
    cfg = SearchConfig()
    db, windows = _windows(sc, mcm, cfg)
    engine = DeviceBeamEngine(beam=cfg.beam)
    for sets, prev_end in windows:
        ref = reference_combine(db, mcm, sets, prev_end, metric=cfg.metric,
                                beam=cfg.beam)
        dev = engine.combine(db, mcm, sets, prev_end, metric=cfg.metric)
        assert dev.plan == ref.plan
        assert dev.result.latency == ref.result.latency
        assert dev.result.energy == ref.result.energy
        assert dev.explored == ref.explored


@pytest.mark.parametrize("budget", [1, 7, 50])
def test_device_beam_expansion_budget_parity(budget):
    """The device scan's cumulative-sum budget truncation reproduces the
    reference's row-major acceptance order at tight budgets (which force
    the exact-fallback branch deep into the candidate order)."""
    sc = get_scenario("xr10_vr_gaming")
    mcm = make_mcm("het_sides", n_pe=256)
    cfg = SearchConfig()
    db, windows = _windows(sc, mcm, cfg)
    engine = DeviceBeamEngine(beam=cfg.beam, max_expansions=budget)
    for sets, prev_end in windows:
        ref = reference_combine(db, mcm, sets, prev_end, metric=cfg.metric,
                                beam=cfg.beam, max_expansions=budget)
        dev = engine.combine(db, mcm, sets, prev_end, metric=cfg.metric)
        assert dev.plan == ref.plan
        assert dev.explored == ref.explored


def test_device_beam_interpret_kernel_parity():
    """``use_kernel=True, interpret=True``: the Pallas ``scar_search``
    screening kernel (interpret mode, so it runs off-TPU) slots into the
    protocol combine with unchanged bit-parity."""
    sc = get_scenario("xr7_ar_gaming")
    mcm = make_mcm("het_sides", n_pe=256)
    cfg = SearchConfig()
    db, windows = _windows(sc, mcm, cfg)
    sets, prev_end = windows[0]
    ref = reference_combine(db, mcm, sets, prev_end, metric=cfg.metric,
                            beam=cfg.beam)
    dev = DeviceBeamEngine(beam=cfg.beam, use_kernel=True,
                           interpret=True).combine(db, mcm, sets, prev_end,
                                                   metric=cfg.metric)
    assert dev.plan == ref.plan
    assert dev.explored == ref.explored


def test_device_beam_combine_refuses_tpu(monkeypatch):
    """The float64 protocol form is a CPU parity form: on a TPU, whose
    float64 is not IEEE-exact, it raises instead of returning near-misses."""
    from repro.core import evaluator

    sc = get_scenario("xr7_ar_gaming")
    mcm = make_mcm("het_sides", n_pe=256)
    cfg = SearchConfig()
    db, windows = _windows(sc, mcm, cfg)
    sets, prev_end = windows[0]
    monkeypatch.setattr(evaluator, "_jax_platform", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU parity form"):
        DeviceBeamEngine(beam=cfg.beam).combine(db, mcm, sets, prev_end,
                                                metric=cfg.metric)


# ------------------------ fused schedule contract ---------------------------

def test_fused_schedule_matches_host_and_sync_contract(monkeypatch):
    """``algo="beam_jax"`` end to end: identical window plans and schedule
    metrics to the host beam pipeline, with exactly ONE counted host-device
    fetch per window — while the split jax pipeline pays one per scored
    batch (>= one per (model, window))."""
    # pin: the host/split baselines must not be rerouted by the CI shard env
    monkeypatch.delenv("SCAR_SEARCH_BACKEND", raising=False)
    sc = get_scenario("dc4_lms_seg_image")
    mcm = make_mcm("het_cb", n_pe=4096)
    host = schedule(sc, mcm, SearchConfig(algo="beam"))

    lp.reset_sync_count()
    dev = schedule(sc, mcm, SearchConfig(algo="beam_jax"))
    dev_syncs = lp.sync_count()
    assert dev_syncs == len(dev.windows)

    assert all(h.plan == d.plan for h, d in zip(host.windows, dev.windows))
    assert dev.result.latency == host.result.latency
    assert dev.result.energy == host.result.energy

    # the split pipeline on the same jax backend: one fetch per batch
    lp.reset_sync_count()
    split = schedule(sc, mcm, SearchConfig(algo="beam",
                                           eval_backend="jax_ref"))
    split_syncs = lp.sync_count()
    n_batches = sum(len(w.plan.plans) for w in split.windows)
    assert split_syncs >= n_batches > dev_syncs


@pytest.mark.parametrize("form", ["beam_jax", "jax_ref"])
@pytest.mark.parametrize("scenario,pattern,size", [
    ("xr8_outdoors", "het_sides", 3), ("xr8_outdoors", "het_cb", 3),
    ("xr7_ar_gaming", "het_sides", 6)])
def test_float32_plans_match_host_under_latency(monkeypatch, scenario,
                                                pattern, size, form):
    """Under ``metric="latency"`` a window's plan is often decided by the
    beam's tie rule: many placements share one bottleneck segment, and so
    one float64 latency.  The float32 paths — the fused device search and
    the split jax_ref scoring — keep those ties, and return the host numpy
    beam's plans, latency and energy, window by window."""
    monkeypatch.delenv("SCAR_SEARCH_BACKEND", raising=False)
    sc = get_scenario(scenario)
    mcm = make_mcm(pattern, rows=size, cols=size, n_pe=256)
    host = schedule(sc, mcm, SearchConfig(algo="beam", eval_backend="numpy",
                                          metric="latency"))
    cfg = (SearchConfig(algo="beam_jax", metric="latency") if form == "beam_jax"
           else SearchConfig(algo="beam", eval_backend="jax_ref",
                             metric="latency"))
    got = schedule(sc, mcm, cfg)
    assert [w.plan for w in got.windows] == [w.plan for w in host.windows]
    for g, h in zip(got.windows, host.windows):
        assert g.result.latency == h.result.latency
        assert g.result.energy == h.result.energy


@pytest.mark.parametrize("metric,tied", [("latency", 5), ("edp", 2)])
def test_fused_schedule_counts_tied_picks(monkeypatch, metric, tied):
    """``engine.window.picks`` rises by one per window the fused search
    plans, and ``engine.window.tied_picks`` by one per window whose best
    final beam row ties the next on the ordering score.  On xr8's 3x3
    het_sides package every latency window is tied on its bottleneck
    segment; under EDP, where energy separates the placements, two are."""
    from repro import obs
    from repro.core.engine import _tied_pick

    monkeypatch.delenv("SCAR_SEARCH_BACKEND", raising=False)
    picks0 = obs.registry.value("engine.window.picks")
    tied0 = obs.registry.value("engine.window.tied_picks")
    out = schedule(get_scenario("xr8_outdoors"),
                   make_mcm("het_sides", rows=3, cols=3, n_pe=256),
                   SearchConfig(algo="beam_jax", metric=metric))
    assert obs.registry.value("engine.window.picks") - picks0 == len(
        out.windows)
    assert obs.registry.value("engine.window.tied_picks") - tied0 == tied

    lat = np.array([[0.5, 0.5, 0.7], [0.3, 0.3, 0.4]], np.float32)
    energy = np.array([[1.0, 1.0, 1.0], [2.0, 1.0, 3.0]], np.float32)
    assert _tied_pick(lat, energy, np.array([3, 3]), "latency")
    assert not _tied_pick(lat, energy, np.array([3, 3]), "edp")
    assert not _tied_pick(lat, energy, np.array([3, 1]), "latency")


def test_fused_schedule_respects_env_override(monkeypatch):
    """SCAR_SEARCH_BACKEND=beam_jax reroutes a beam schedule through the
    fused device path (the CI shard mechanism)."""
    sc = get_scenario("xr7_ar_gaming")
    mcm = make_mcm("het_sides", n_pe=256)
    monkeypatch.delenv("SCAR_SEARCH_BACKEND", raising=False)
    host = schedule(sc, mcm, SearchConfig(algo="beam"))
    monkeypatch.setenv("SCAR_SEARCH_BACKEND", "beam_jax")
    lp.reset_sync_count()
    dev = schedule(sc, mcm, SearchConfig(algo="beam"))
    assert lp.sync_count() == len(dev.windows)
    assert all(h.plan == d.plan for h, d in zip(host.windows, dev.windows))


# ------------------------- one upload per window ----------------------------

@pytest.mark.parametrize("pattern,comm_model", [("het_cb", "analytic"),
                                                ("het_cross", "congestion")])
def test_fused_schedule_uploads_once_per_window(monkeypatch, pattern,
                                                comm_model):
    """Each window stages its inputs in one buffer and copies it to the
    device once: ``engine.window.uploads`` rises by one per window, as the
    fetch count does."""
    from repro import obs

    monkeypatch.delenv("SCAR_SEARCH_BACKEND", raising=False)
    uploads0, syncs0 = obs.registry.value("engine.window.uploads"), \
        lp.sync_count()
    dev = schedule(get_scenario("dc4_lms_seg_image"),
                   make_mcm(pattern, n_pe=4096),
                   SearchConfig(algo="beam_jax", comm_model=comm_model))
    uploads = obs.registry.value("engine.window.uploads") - uploads0
    assert uploads == len(dev.windows) == lp.sync_count() - syncs0


def test_fused_program_call_makes_no_host_to_device_copy(monkeypatch):
    """Every argument of the fused window program is already on the device
    when it is called: with host-to-device transfers disallowed around the
    call, a ``beam_jax`` schedule still runs and plans as the host beam."""
    import jax

    from repro.core import device_search as ds

    monkeypatch.delenv("SCAR_SEARCH_BACKEND", raising=False)
    program = ds.fused_program

    def guarded(*args, **kwargs):
        with jax.transfer_guard_host_to_device("disallow"):
            return program(*args, **kwargs)

    monkeypatch.setattr(ds, "fused_program", guarded)
    sc = get_scenario("dc4_lms_seg_image")
    mcm = make_mcm("het_cb", n_pe=4096)
    host = schedule(sc, mcm, SearchConfig(algo="beam"))
    dev = schedule(sc, mcm, SearchConfig(algo="beam_jax"))
    assert [w.plan for w in dev.windows] == [w.plan for w in host.windows]


@pytest.mark.parametrize("comm_model", ["analytic", "congestion"])
@pytest.mark.parametrize("dense", [True, False])
def test_staged_window_round_trips_bitwise(monkeypatch, dense, comm_model):
    """One window's inputs, as the engine hands them to ``stage_window``,
    come back from ``unstage_window`` inside a jit bit for bit, slot by
    slot: float32 ``inf``, a negative int32 anchor and uint32 words with
    the high bit set included."""
    import jax

    from repro.core import device_search as ds

    class _Captured(Exception):
        pass

    staged = []
    stage = ds.stage_window

    def spy(inputs):
        staged.append(inputs)
        return stage(inputs)

    def stop(*args, **kwargs):
        raise _Captured

    monkeypatch.delenv("SCAR_SEARCH_BACKEND", raising=False)
    monkeypatch.setattr(DeviceBeamEngine, "uses_kernels", lambda self: dense)
    monkeypatch.setattr(ds, "stage_window", spy)
    monkeypatch.setattr(ds, "fused_program", stop)
    with pytest.raises(_Captured):
        schedule(get_scenario("dc4_lms_seg_image"),
                 make_mcm("het_cross", n_pe=4096),
                 SearchConfig(algo="beam_jax", comm_model=comm_model))
    (args, words, tiers, n_real), *rest = staged[0]
    lat_tab, words = args[0].copy(), words.copy()
    lat_tab[0, 0] = np.inf
    words[0, 0] = np.uint32(0x80000001)
    args = (lat_tab,) + args[1:10] + (np.int32(-7),) + args[11:]
    inputs = ((args, words, tiers, n_real), *rest)
    assert (args[6].shape[1] > 1) == dense      # the dense seg_id slot

    buf, layout = ds.stage_window(inputs)
    assert buf.dtype == np.uint32
    back = jax.jit(ds.unstage_window, static_argnums=1)(buf, layout)
    assert jax.tree.structure(back) == jax.tree.structure(inputs)
    leaves = jax.tree.leaves(inputs)
    assert len(leaves) == 16 * len(inputs)
    for want, got in zip(leaves, jax.tree.leaves(back)):
        want, got = np.asarray(want), np.asarray(got)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
    assert buf.nbytes == sum(a.nbytes for a in map(np.asarray, leaves))


# ------------------------- shared quantisation ------------------------------

def test_quantize_scores_jax_matches_numpy():
    """The traceable quantiser agrees with the host helper on the shared
    candidate-ordering grain (within the grain itself: XLA's log10 can land
    one representable value away from libm's at a bucket boundary — the
    documented caveat — so the contract is same-bucket-or-adjacent, not
    bitwise), and exact tie collapse is preserved: inputs the host helper
    maps to one value stay collapsed on device too."""
    import jax
    import jax.numpy as jnp
    from repro.core.quantize import quantize_scores_jax

    rng = np.random.default_rng(0)
    s = np.concatenate([
        10.0 ** rng.uniform(-9, 9, 512),
        [0.0, np.inf, 1.0, 1.0 + 1e-12],
    ])
    with lp.enable_x64():
        got = np.asarray(jax.jit(
            lambda x: quantize_scores_jax(x, sig=SCORE_SIG))(s))
    ref = quantize_scores(s, sig=SCORE_SIG)
    np.testing.assert_allclose(got, ref, rtol=10.0 ** -SCORE_SIG)
    # the majority agree bitwise; only log10 boundary cases may not
    assert np.mean(got == ref) > 0.9
    # zeros / inf pass through exactly
    np.testing.assert_array_equal(got[-4:-2], [0.0, np.inf])
    # f32 noise below the grain collapses to the same bucket (the property
    # the fused path relies on; cf. test_quantize_scores_absorbs_f32_noise)
    base = np.float32(1.2345678)
    noisy = base * (1 + np.float32(1e-7))
    q = np.asarray(quantize_scores_jax(jnp.asarray([base, noisy]),
                                       sig=SCORE_SIG))
    assert q[0] == q[1]

    # float32 device values land in the same grain as the host quantiser
    s32 = s.astype(np.float32)
    got32 = np.asarray(quantize_scores_jax(jnp.asarray(s32), sig=SCORE_SIG))
    ref32 = quantize_scores(s32.astype(np.float64), sig=SCORE_SIG)
    np.testing.assert_allclose(got32, ref32, rtol=10.0 ** -SCORE_SIG)


def test_quantize_scores_jax_rounds_float32_digits_exactly():
    """A float32 score lands in the bucket the host quantiser gives the
    same value: its digits are rounded exactly, not after a float32
    ``x / scale`` that rounds once more near a bucket boundary.  Scores
    from 1e-8 to 1e5, the range the planner's metrics span."""
    import jax
    import jax.numpy as jnp
    from repro.core.quantize import quantize_scores_jax

    rng = np.random.default_rng(7)
    x = np.concatenate([10.0 ** rng.uniform(-8, 5, 50_000),
                        rng.uniform(0.01, 0.05, 50_000)]).astype(np.float32)
    got = np.asarray(jax.jit(
        lambda v: quantize_scores_jax(v, sig=SCORE_SIG))(jnp.asarray(x)))
    x64 = x.astype(np.float64)
    scale = 10.0 ** (np.floor(np.log10(x64)) - SCORE_SIG)
    want = np.round(quantize_scores(x64, sig=SCORE_SIG) / scale)
    assert (np.round(got.astype(np.float64) / scale) == want).all()


# --------------------------- bucketing helpers ------------------------------

def test_bucket_size_shapes():
    from repro.core.device_search import bucket_size
    assert bucket_size(1) == 256
    assert bucket_size(256) == 256
    assert bucket_size(257) == 512
    assert bucket_size(8192) == 8192
    assert bucket_size(8193) == 16384 or bucket_size(8193) == 8192 * 2
    assert bucket_size(40000) == 40960          # multiple of 8192, not 65536
    for n in (1, 100, 5000, 47104, 100000):
        b = bucket_size(n)
        assert b >= n
        assert b == 256 or b % 256 == 0


def test_pool_widths_scale_with_keep():
    from repro.core.device_search import pool_widths
    t0, t1 = pool_widths(48)
    assert t0 >= 4 * 48 and t1 >= 2 * 48
    t0b, t1b = pool_widths(1024)
    assert t0b == 4096 and t1b == 2048
