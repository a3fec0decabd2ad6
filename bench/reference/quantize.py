"""Shared score quantisation for cross-backend ordering decisions.

Every place the pipeline turns scores into an *ordering* — the SEG top-k,
the ``build_candidates`` (tier, score) lexsort, the refine relocate screen,
and the device search path's on-device top-k — rounds scores to a fixed
number of significant digits first, so that

* structurally tied candidates (identical segments summed in a different
  order by a batched pass) compare exactly equal and fall back to stable
  enumeration order, and
* float32 device scores and float64 host scores land in the same bucket for
  anything but true near-ties at a quantisation boundary, so host and device
  tie-breaks cannot drift apart.

``quantize_scores`` is the numpy form (moved here from ``segmentation``,
which re-exports it for backward compatibility); ``quantize_scores_jax`` is
the traceable ``jax.numpy`` form used *inside* jitted device programs — the
same rounding rule expressed with ``where`` masks instead of boolean
indexing, so it can be composed into the fused search program.

``SCORE_SIG`` is the candidate-ordering parameter: ``sig = 5`` rounds to 6
significant digits — coarse enough to absorb float32 backend noise
(documented in ``sched.build_candidates``), fine enough that genuinely
different plans never collide.  The SEG stage keeps its finer default
(``sig = 11``) because it only ever compares float64 against float64.
"""
from __future__ import annotations

import numpy as np

# 6 significant digits: the shared host/device candidate-ordering grain.
SCORE_SIG = 5


def quantize_scores(scores: np.ndarray, sig: int = 11) -> np.ndarray:
    """Round to ``sig + 1`` significant digits (12 at the default).

    Non-finite and zero entries pass through unchanged, so +inf padding and
    empty-segment zeros keep their exact values and ordering.
    """
    out = np.asarray(scores, dtype=np.float64).copy()
    nz = np.isfinite(out) & (out != 0)
    exp = np.floor(np.log10(np.abs(out[nz])))
    scale = 10.0 ** (exp - sig)
    out[nz] = np.round(out[nz] / scale) * scale
    return out
