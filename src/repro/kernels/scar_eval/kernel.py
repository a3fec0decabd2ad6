"""SCAR schedule-evaluation Pallas kernel.

The SCHED engine's hot loop scores 10^4-10^5 candidate (segmentation x
placement) plans per window: gather per-(layer, chiplet-class) costs, reduce
per segment, add communication terms, combine (max for pipelined latency,
sum for energy).  The kernel tiles the candidate batch into VMEM-resident
blocks with the candidate axis on the vector lanes, and does all of it as
float32 selects, adds and compares on the VPU.

Inputs (candidate-major on the last axis):
  lat_tab, e_tab   [L, C]  f32    per-(layer, class) costs
  layer_cls        [L, B]  int32  chiplet class of each layer's segment
  seg_id           [L, B]  int32  segment of each layer
  comm_lat, comm_e [S, B]  f32    per-segment ip/op communication terms
  seg_valid        [S, B]  f32    1.0 for live segments
  pipe             [1, B]  f32    1.0 -> pipelined (max), 0.0 -> sequential
Output:
  out              [2, B]  f32    (window latency, window energy)

Candidate-major blocks keep every array lane-dense: C (2) and S (<= 6) on
the lanes, as a ``[B, L, S]`` one-hot would put them, pad to 128 in VMEM,
and a 16x16 window at L = 56 then needs about the whole 16 MiB of scoped
VMEM.  Segment sums are exact float32 adds of the selected layer costs,
with no matrix unit in the way (its default float32 contraction is not
full precision).

The per-layer ids and the comm terms are produced on device by the jitted
wrapper (``ops.evaluate``), which runs the shared
``repro.core.cost.comm_from_parts`` geometry; ``ref.scar_eval_ref`` is the
block-semantics oracle this kernel is tested against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG = -1e30

# Block index constant of the index maps: a bare ``0`` becomes int64 under
# x64, and the TPU compiler refuses int64 block indices.
_ZERO = np.int32(0)


def _scar_kernel(lat_ref, e_ref, cls_ref, seg_ref, clat_ref, ce_ref,
                 valid_ref, pipe_ref, out_ref):
    cls = cls_ref[...]                                     # [L, bt]
    seg = seg_ref[...]
    lat_layer = jnp.zeros(cls.shape, jnp.float32)
    e_layer = jnp.zeros(cls.shape, jnp.float32)
    for c in range(lat_ref.shape[1]):
        hit = cls == c
        lat_layer = jnp.where(hit, lat_ref[:, c:c + 1], lat_layer)
        e_layer = jnp.where(hit, e_ref[:, c:c + 1], e_layer)
    lat_max = jnp.full(pipe_ref.shape, NEG, jnp.float32)   # [1, bt]
    lat_sum = jnp.zeros(pipe_ref.shape, jnp.float32)
    energy = jnp.zeros(pipe_ref.shape, jnp.float32)
    n_seg = jnp.zeros(pipe_ref.shape, jnp.float32)
    for s in range(clat_ref.shape[0]):
        in_s = seg == s
        valid = valid_ref[s:s + 1, :]
        seg_lat = (jnp.sum(jnp.where(in_s, lat_layer, 0.0), axis=0,
                           keepdims=True) + clat_ref[s:s + 1, :])
        seg_e = (jnp.sum(jnp.where(in_s, e_layer, 0.0), axis=0,
                         keepdims=True) + ce_ref[s:s + 1, :]) * valid
        lat_max = jnp.maximum(lat_max, jnp.where(valid > 0, seg_lat, NEG))
        lat_sum = lat_sum + seg_lat * valid
        energy = energy + seg_e
        n_seg = n_seg + valid
    pipe = pipe_ref[...] * (n_seg > 1)
    out_ref[0:1, :] = jnp.where(pipe > 0, lat_max, lat_sum)
    out_ref[1:2, :] = energy


def scar_eval(lat_tab, e_tab, layer_cls, seg_id, comm_lat, comm_e,
              seg_valid, pipe, *, block_b: int = 128,
              interpret: bool = False):
    L, B = layer_cls.shape
    C = lat_tab.shape[1]
    S = comm_lat.shape[0]
    block_b = min(block_b, B)
    assert B % block_b == 0
    grid = (B // block_b,)

    def cand_block(rows):
        return pl.BlockSpec((rows, block_b), lambda b: (_ZERO, b))

    return pl.pallas_call(
        _scar_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((L, C), lambda b: (_ZERO, _ZERO)),
            pl.BlockSpec((L, C), lambda b: (_ZERO, _ZERO)),
            cand_block(L), cand_block(L),
            cand_block(S), cand_block(S), cand_block(S),
            cand_block(1),
        ],
        out_specs=cand_block(2),
        out_shape=jax.ShapeDtypeStruct((2, B), jnp.float32),
        interpret=interpret,
    )(lat_tab, e_tab, layer_cls, seg_id, comm_lat, comm_e, seg_valid, pipe)
