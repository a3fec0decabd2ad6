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

from typing import Any

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


def quantize_scores_jax(scores: Any, sig: int = SCORE_SIG) -> Any:
    """Traceable form of ``quantize_scores`` for use inside jitted programs.

    Same rounding rule (round to ``sig + 1`` significant digits; zeros and
    non-finite values pass through), computed in the input dtype — float32
    on the fused device search path, float64 under ``enable_x64``.  The
    only representational difference from the numpy form is the masked
    ``where`` arithmetic (no boolean indexing under trace); values quantised
    in float64 agree bitwise with the host helper up to libm ``log10``
    behaviour at exact powers of ten.

    A float32 score is rounded to its digits exactly
    (``_round_digits_f32``), so it lands in the bucket the host helper
    gives the same value: a float32 ``x / scale`` would round once more
    and move scores near a bucket boundary into the next one.
    """
    import jax.numpy as jnp

    x = scores
    nz = jnp.isfinite(x) & (x != 0)
    ax = jnp.where(nz, jnp.abs(x), 1.0)          # dummy 1.0 keeps log finite
    exp = jnp.floor(jnp.log10(ax))
    scale = 10.0 ** (exp - jnp.asarray(sig, exp.dtype))
    digits = jnp.round(ax / scale)
    if x.dtype == jnp.float32:
        digits = _round_digits_f32(ax, exp, sig, digits)
    return jnp.where(nz, jnp.sign(x) * digits * scale, x)


_MAX_POW5 = 13                  # 5**13 < 2**31: the factor fits a uint32


def _round_digits_f32(ax: Any, exp: Any, sig: int, approx: Any) -> Any:
    """``round(ax * 10**(sig - exp))``, half to even, exact, for float32.

    ``ax = m * 2**e`` with a 24-bit integer ``m``, and ``10**j = 5**j *
    2**j``, so the digits are ``m * 5**j`` shifted right by ``-(e + j)``
    bits: a 55-bit product, carried in two uint32 words.  Values outside
    that range (``j`` not in ``[0, 13]``, a shift not in ``[1, 63]``,
    subnormals) keep ``approx``.
    """
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32
    bits = jax.lax.bitcast_convert_type(ax, u32)
    biased = (bits >> 23).astype(jnp.int32)
    m = (bits & u32(0x7FFFFF)) | u32(0x800000)
    j = (sig - exp).astype(jnp.int32)
    r = 150 - biased - j                         # right shift, -(e + j)
    ok = (biased > 0) & (j >= 0) & (j <= _MAX_POW5) & (r >= 1) & (r <= 63)
    f = jnp.asarray([5 ** k for k in range(_MAX_POW5 + 1)],
                    u32)[jnp.clip(j, 0, _MAX_POW5)]
    # m * f = hi * 2**32 + lo, from 16-bit limbs (every partial < 2**32)
    m0, m1 = m & u32(0xFFFF), m >> 16
    f0, f1 = f & u32(0xFFFF), f >> 16
    low = m0 * f0
    mid = m0 * f1 + m1 * f0
    lo = low + (mid << 16)
    hi = m1 * f1 + (mid >> 16) + (lo < low).astype(u32)
    # shift right by r, keeping what falls off (rem) to round half to even
    big = r >= 32
    rs = jnp.clip(r, 1, 31).astype(u32)
    rb = jnp.clip(r - 32, 0, 31).astype(u32)
    one = u32(1)
    q = jnp.where(big, hi >> rb, (lo >> rs) | (hi << (32 - rs)))
    rem_hi = jnp.where(big, hi & ((one << rb) - one), u32(0))
    rem_lo = jnp.where(big, lo, lo & ((one << rs) - one))
    half_hi = jnp.where(big & (r > 32),
                        one << jnp.clip(r - 33, 0, 31).astype(u32), u32(0))
    half_lo = jnp.where(big, jnp.where(r == 32, u32(0x80000000), u32(0)),
                        one << (rs - one))
    above = (rem_hi > half_hi) | ((rem_hi == half_hi) & (rem_lo > half_lo))
    tie = (rem_hi == half_hi) & (rem_lo == half_lo)
    q = q + (above | (tie & ((q & one) == one))).astype(u32)
    return jnp.where(ok, q.astype(ax.dtype), approx)
