"""Chunked SSD / gated-linear-attention scan Pallas kernel (Mamba-2, mLSTM).

Grid (BH, n_chunks): the chunk dimension is ``arbitrary`` — the recurrent
state [N, P] lives in VMEM scratch and carries across chunk iterations of
the same (batch, head) program.  Per chunk: an intra-chunk masked quadratic
(two [c x N]/[c x c] MXU matmuls) plus the inter-chunk state contribution.

o_t = q_t . S_t,   S_t = exp(a_t) S_{t-1} + k_t^T v_t   (a_t <= 0)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(q_ref, k_ref, v_ref, a_ref, o_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0].astype(jnp.float32)          # [c, N]
    k = k_ref[0].astype(jnp.float32)          # [c, N]
    v = v_ref[0].astype(jnp.float32)          # [c, P]
    a = a_ref[0].astype(jnp.float32)          # [c]
    cum = jnp.cumsum(a)                       # [c]
    total = cum[-1]

    # intra-chunk: scores gated by exp(cum_i - cum_j) on the causal triangle
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [c, c]
    rel = cum[:, None] - cum[None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    gate = jnp.where(cols <= rows, jnp.exp(rel), 0.0)
    intra = jax.lax.dot_general(s * gate, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # inter-chunk: q decayed from chunk start picks up the carried state
    state = state_ref[...]                    # [N, P]
    q_dec = q * jnp.exp(cum)[:, None]
    inter = jax.lax.dot_general(q_dec, state, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0] = (intra + inter).astype(o_ref.dtype)

    # state update: S <- exp(total) S + (k * exp(total - cum))^T v
    k_dec = k * jnp.exp(total - cum)[:, None]
    state_ref[...] = state * jnp.exp(total) + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def ssd_scan(q: jax.Array, k: jax.Array, v: jax.Array, a: jax.Array, *,
             chunk: int = 128, interpret: bool = False) -> jax.Array:
    """q,k: [BH, L, N]; v: [BH, L, P]; a: [BH, L] log-decay (<=0)."""
    BH, L, N = q.shape
    P = v.shape[-1]
    chunk = min(chunk, L)
    assert L % chunk == 0
    nc = L // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk), lambda b, c: (b, c)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, L, P), v.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, a)
