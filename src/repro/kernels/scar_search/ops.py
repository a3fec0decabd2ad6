"""Jitted wrappers + traceable forms for the search-screening ops.

Two ops back the device beam search:

* ``conflict_counts`` — [Bm, N] popcounts of beam x candidate occupancy
  intersections.  ``use_kernel=True`` runs the Pallas kernel (TPU;
  ``interpret=True`` anywhere), ``use_kernel=False`` the pure-jnp jax_ref
  form.  ``conflict_counts_traceable`` is the un-jitted dispatch the fused
  search program composes under its own jit.
* ``masked_topk`` — smallest-k selection over a validity mask with the flat
  lowest-index tie rule (``lax.top_k`` on negated scores; XLA top-k breaks
  equal values by lower index, which is exactly the host beam engines'
  stable row-major acceptance order).  Callers that need the quantised
  tie-break (the fused per-model candidate ordering) quantise scores with
  ``core.quantize.quantize_scores_jax`` before calling.

Scalar oracles live in ``ref.py``; parity is pinned by
``tests/test_kernels.py`` (interpret mode) and the engine-level tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import scar_search


def conflict_counts_traceable(beam_words, cand_words, *,
                              use_kernel: bool = False,
                              interpret: bool = False,
                              block_n: int = 2048):
    """[Bm, N] int32 intersection popcounts (traceable dispatch)."""
    if use_kernel:
        n = cand_words.shape[0]
        pad = (-n) % block_n
        if pad:
            cand_words = jnp.concatenate(
                [cand_words,
                 jnp.zeros((pad,) + cand_words.shape[1:], cand_words.dtype)])
        out = scar_search(beam_words, cand_words.T, block_n=block_n,
                          interpret=interpret)
        return out[:, :n]
    inter = beam_words[:, None, :] & cand_words[None, :, :]
    return jnp.sum(jax.lax.population_count(inter), axis=-1).astype(jnp.int32)


conflict_counts = partial(jax.jit, static_argnames=(
    "use_kernel", "interpret", "block_n"))(conflict_counts_traceable)


def masked_topk(scores, valid, k: int):
    """(values[k], indices[k]) of the k smallest valid entries.

    Invalid entries never win; slots past the valid count return
    ``(+inf, -1)``.  Equal scores resolve to the lower index (the host
    engines' stable flat acceptance order).  Traceable — compose under jit;
    ``ref.masked_topk_ref`` is the oracle.
    """
    neg = jnp.where(valid, -scores, -jnp.inf)
    # scarlint: ignore[SL004] -- generic top-k primitive: callers that need
    # the quantised tie-break pass quantize_scores_jax output (see docstring)
    vals, idx = jax.lax.top_k(neg, k)
    return (jnp.where(vals == -jnp.inf, jnp.inf, -vals),
            jnp.where(vals == -jnp.inf, -1, idx))
