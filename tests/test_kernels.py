"""Per-kernel tests: shape/dtype sweeps, interpret-mode kernel vs ref.py
oracle (deliverable c).  The randomised scar_eval-vs-core-evaluator property
lives in ``test_cost_properties.py`` (hypothesis-gated)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import mha
from repro.kernels.ssd_scan import gla


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,Hq,Hkv,D", [
    (1, 128, 1, 1, 64), (2, 256, 4, 2, 64), (1, 256, 8, 1, 128),
    (2, 384, 6, 2, 64), (1, 512, 2, 2, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, S, Hq, Hkv, D, causal, dt):
    ks = jax.random.split(jax.random.PRNGKey(B * S + D), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dt)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dt)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dt)
    out = mha(q, k, v, causal=causal, interpret=True)
    ref = mha(q, k, v, causal=causal, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dt))


def test_flash_attention_block_shapes_sweep():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    ref = mha(q, k, v, causal=True, use_kernel=False)
    for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256), (128, 256)]:
        out = mha(q, k, v, causal=True, block_q=bq, block_k=bk,
                  interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,L,H,N,P,chunk", [
    (1, 128, 1, 16, 16, 64), (2, 256, 2, 64, 64, 128),
    (1, 512, 4, 32, 64, 128), (1, 256, 2, 64, 64, 256),
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_ref(B, L, H, N, P, chunk, dt):
    ks = jax.random.split(jax.random.PRNGKey(L + N), 4)
    q = jax.random.normal(ks[0], (B, L, H, N), dt)
    k = jax.random.normal(ks[1], (B, L, H, N), dt)
    v = jax.random.normal(ks[2], (B, L, H, P), dt)
    a = -jax.nn.softplus(jax.random.normal(ks[3], (B, L, H)))
    out = gla(q, k, v, a, chunk=chunk, interpret=True)
    ref = gla(q, k, v, a, chunk=chunk, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dt))


def test_ssd_scan_state_carry_across_chunks():
    """Decay ~ 1 (a ~ 0): output at position t is the running sum of kv —
    checks the scratch state survives chunk boundaries."""
    B, L, H, N, P = 1, 256, 1, 8, 8
    q = jnp.ones((B, L, H, N)) / N
    k = jnp.ones((B, L, H, N))
    v = jnp.ones((B, L, H, P))
    a = jnp.zeros((B, L, H))
    out = gla(q, k, v, a, chunk=64, interpret=True)
    expect = jnp.arange(1, L + 1, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out[0, :, 0, 0]),
                               np.asarray(expect), rtol=1e-5)


@pytest.mark.parametrize("pipelined", [True, False])
@pytest.mark.parametrize("prev_end", [None, 3])
def test_scar_eval_kernel_matches_core_evaluator_seeded(pipelined, prev_end):
    """Kernel == jax_ref form == numpy core evaluator on a seeded random
    plan batch, in both latency modes (the bridge used to hard-code
    ``pipelined=True``) and with/without a locality anchor."""
    from candidate_utils import random_candidate_batch
    from repro.core import get_scenario, make_mcm
    from repro.core.cost import eval_model_candidates
    from repro.core.maestro import build_cost_db
    from repro.kernels.scar_eval import evaluate, pack_candidates

    sc = get_scenario("xr10_vr_gaming")
    mcm = make_mcm("het_sides", n_pe=256)
    db = build_cost_db(sc, mcm.classes, mcm.pkg)
    cand = random_candidate_batch(np.random.default_rng(7), db, mcm)
    lat_ref, e_ref = eval_model_candidates(db, mcm, cand, n_active=2,
                                           prev_end=prev_end,
                                           pipelined=pipelined)
    args, statics, Breal = pack_candidates(db, mcm, cand, n_active=2,
                                           prev_end=prev_end, pad_b=16,
                                           pipelined=pipelined)
    out_k = np.asarray(evaluate(*args, **statics, block_b=16,
                                interpret=True))[:Breal]
    out_r = np.asarray(evaluate(*args, **statics,
                                use_kernel=False))[:Breal]
    np.testing.assert_allclose(out_k[:, 0], lat_ref, rtol=1e-5)
    np.testing.assert_allclose(out_k[:, 1], e_ref, rtol=1e-5)
    np.testing.assert_allclose(out_r[:, 0], lat_ref, rtol=1e-5)
    np.testing.assert_allclose(out_r[:, 1], e_ref, rtol=1e-5)


def test_scar_eval_dense_ref_matches_kernel():
    """``scar_eval_ref`` (the dense one-hot jnp oracle the Pallas kernel is
    written against) still mirrors the kernel block-for-block, on
    candidate-major inputs with a mix of pipelined and sequential rows."""
    from repro.kernels.scar_eval import scar_eval, scar_eval_ref
    rng = np.random.default_rng(0)
    B, L, C, S = 32, 12, 2, 4
    lat_tab = jnp.asarray(rng.uniform(0, 1e-3, (L, C)), jnp.float32)
    e_tab = jnp.asarray(rng.uniform(0, 1e-2, (L, C)), jnp.float32)
    cls = rng.integers(0, C, (B, L))
    seg = np.sort(rng.integers(0, S, (B, L)), axis=1)
    for b in range(B):
        _, inv = np.unique(seg[b], return_inverse=True)
        seg[b] = inv
    n_segs = seg.max(axis=1) + 1
    cls = jnp.asarray(cls.T, jnp.int32)                    # [L, B]
    seg = jnp.asarray(seg.T, jnp.int32)
    valid = jnp.asarray(np.arange(S)[:, None] < n_segs[None], jnp.float32)
    comm_lat = jnp.asarray(rng.uniform(0, 1e-4, (S, B)), jnp.float32) * valid
    comm_e = jnp.asarray(rng.uniform(0, 1e-3, (S, B)), jnp.float32) * valid
    pipe = jnp.asarray(rng.integers(0, 2, (1, B)), jnp.float32)
    out_k = scar_eval(lat_tab, e_tab, cls, seg, comm_lat, comm_e,
                      valid, pipe, block_b=16, interpret=True)
    out_r = scar_eval_ref(lat_tab, e_tab, cls, seg, comm_lat, comm_e,
                          valid, pipe)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-12)


@pytest.mark.parametrize("Bm,N,W", [(4, 64, 2), (48, 300, 8), (1, 17, 2)])
def test_scar_search_conflict_counts_match_ref(Bm, N, W):
    """Pallas kernel (interpret, padded-block path) and jax_ref form both
    reproduce the scalar popcount oracle, including zero masks (conflict-free
    everywhere) and a full-overlap row."""
    from repro.kernels.scar_search import (conflict_counts,
                                           conflict_counts_ref)
    rng = np.random.default_rng(Bm * N)
    beam = rng.integers(0, 2 ** 32, (Bm, W), dtype=np.uint32)
    cand = rng.integers(0, 2 ** 32, (N, W), dtype=np.uint32)
    beam[0] = 0                       # empty beam mask: zero conflicts
    cand[-1] = beam[-1]               # full overlap: popcount of the row
    ref = conflict_counts_ref(beam, cand)
    out_r = np.asarray(conflict_counts(jnp.asarray(beam), jnp.asarray(cand)))
    out_k = np.asarray(conflict_counts(jnp.asarray(beam), jnp.asarray(cand),
                                       use_kernel=True, interpret=True,
                                       block_n=32))
    np.testing.assert_array_equal(out_r, ref)
    np.testing.assert_array_equal(out_k, ref)
    assert (out_r[0] == 0).all()
    assert out_r[-1, -1] == sum(int(w).bit_count() for w in beam[-1])


def test_scar_search_masked_topk_matches_ref():
    """lax.top_k lowest-flat-index tie rule == the oracle's stable sort,
    exercised on exact ties, an all-invalid row, and k > n_valid padding."""
    from repro.kernels.scar_search import masked_topk, masked_topk_ref
    scores = np.array([3.0, 1.0, 2.0, 1.0, 2.0, 0.5], np.float32)
    valid = np.array([1, 1, 1, 1, 0, 1], bool)
    for k in (2, 4, 6):
        rv, ri = masked_topk_ref(scores, valid, k)
        dv, di = masked_topk(jnp.asarray(scores), jnp.asarray(valid), k)
        np.testing.assert_array_equal(np.asarray(dv), rv)
        np.testing.assert_array_equal(np.asarray(di), ri)
    # exact tie at 1.0 resolves to index 1 before index 3
    _, ri = masked_topk_ref(scores, valid, 3)
    assert list(ri) == [5, 1, 3]
    # all-invalid: every slot pads with (+inf, -1)
    dv, di = masked_topk(jnp.asarray(scores), jnp.zeros(6, bool), 4)
    assert np.isinf(np.asarray(dv)).all() and (np.asarray(di) == -1).all()
