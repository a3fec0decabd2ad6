"""SCAR search-screening Pallas kernel: occupancy-mask AND + popcount.

The device beam search's per-stage hot op is the disjointness screen: every
(beam item, candidate) pair ANDs its packed occupancy words and tests for
zero — O(beam x N x W) integer work over a candidate pool that reaches
~50k rows per model on 16x16 meshes.  This kernel tiles the candidate axis
into VMEM-resident blocks and emits the popcount of each intersection
(``conflicts[b, n] == 0`` <=> disjoint; the count itself mirrors
``engine.batched_fitness``'s ``np.bitwise_count`` overlap accounting).

Inputs:
  beam_words  [Bm, W]  uint32  packed beam occupancy (W = 2 * ceil(C / 64))
  cand_t      [W, N]   uint32  packed candidate occupancy, word-major
Output:
  conflicts   [Bm, N]  int32   popcount of the word-wise AND

Candidates arrive word-major so the candidate axis fills the vector lanes:
the kernel accumulates one ``[Bm, bn]`` popcount plane per word (W is 2 at
6x6 and 8 at 16x16).  The row-major ``[Bm, bn, W]`` intersection would put
W on the lanes, pad it to 128 in VMEM, and unroll into code that took the
TPU compiler ~50 s for one 16x16 block.  Popcounts are summed as int32: the
TPU compiler has no reduction over unsigned integers.

``ops.conflict_counts`` is the jitted wrapper (jax_ref twin:
``ops.conflict_counts_traceable``); ``ref.conflict_counts_ref`` the scalar
oracle.  Like ``scar_eval``, the kernel targets TPU and runs anywhere under
``interpret=True`` for tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Block index constant of the index maps.  A bare ``0`` becomes int64 when
# the caller traces under x64 (``engine.DeviceBeamEngine.combine``), and
# the TPU compiler refuses int64 block indices.
_ZERO = np.int32(0)


def _search_kernel(beam_ref, cand_ref, out_ref):
    acc = jnp.zeros(out_ref.shape, jnp.int32)             # [Bm, bn]
    for w in range(cand_ref.shape[0]):
        inter = beam_ref[:, w:w + 1] & cand_ref[w:w + 1, :]
        acc += jax.lax.population_count(inter).astype(jnp.int32)
    out_ref[...] = acc


def scar_search(beam_words, cand_t, *, block_n: int = 2048,
                interpret: bool = False):
    bm, w = beam_words.shape
    n = cand_t.shape[1]
    block_n = min(block_n, n)
    assert n % block_n == 0
    grid = (n // block_n,)
    return pl.pallas_call(
        _search_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, w), lambda b: (_ZERO, _ZERO)),
            pl.BlockSpec((w, block_n), lambda b: (_ZERO, b)),
        ],
        out_specs=pl.BlockSpec((bm, block_n), lambda b: (_ZERO, b)),
        out_shape=jax.ShapeDtypeStruct((bm, n), jnp.int32),
        interpret=interpret,
    )(beam_words, cand_t)
