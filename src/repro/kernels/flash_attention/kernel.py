"""Flash attention Pallas TPU kernel: blockwise online-softmax attention.

Targets the MXU: 128-aligned (block_q x head_dim) @ (head_dim x block_k)
matmuls with f32 accumulation in VMEM scratch; the KV stream is the
``arbitrary`` grid dimension so consecutive kv blocks reuse the resident
q block.  Causal masking skips fully-masked kv blocks.

Layout: q [BH, S, D]; k,v [BKV, S, D] with BH = BKV * group (GQA: the
index_map points each q head at its kv head).  Accumulator, row-max and
row-sum live in VMEM scratch across the kv grid dimension.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_k: int, sm_scale: float, causal: bool,
                  n_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # skip kv blocks strictly above the causal diagonal
    run = jnp.logical_or(jnp.bool_(not causal),
                         k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                          # [bq, bk]
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]                       # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                    # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)           # [bq, 1]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, sm_scale: float | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: [BH, Sq, D]; k, v: [BKV, Skv, D]; BH = BKV * group."""
    BH, Sq, D = q.shape
    BKV, Skv, _ = k.shape
    assert BH % BKV == 0, "q heads must be a multiple of kv heads"
    group = BH // BKV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    nq, nk = Sq // block_q, Skv // block_k
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, sm_scale=scale,
        causal=causal, n_kv_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, qi, ki, g=group: (b // g, ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, qi, ki, g=group: (b // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
