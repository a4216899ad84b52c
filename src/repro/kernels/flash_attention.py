"""Pallas TPU tiled causal flash attention (prefill hot path).

Grid (B, H, n_q_tiles, n_k_tiles); online softmax across the k-tile axis
with VMEM accumulators.  MXU-aligned (block_q x block_k) score tiles;
causal masking skips nothing structurally (masked tiles contribute zero)
— tile-level early-exit is a recorded §Perf candidate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_q: int, block_k: int, scale: float, n_k: int,
            causal: bool):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(cols <= rows, s, NEG_INF)

    m_prev = m_ref[...][:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = (l_ref[...][:, 0] * alpha + jnp.sum(p, axis=1))[:, None]
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new[:, None]

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool):
    """q, k, v: (B, H, T, D) (GQA pre-expanded).  Returns (B, H, T, D)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    assert T % block_q == 0 and S % block_k == 0
    n_q, n_k = T // block_q, S // block_k
    scale = D ** -0.5

    def q_map(b, h, qi, ki):
        return (b, h, qi, 0)

    def k_map(b, h, qi, ki):
        return (b, h, ki, 0)

    def o_map(b, h, qi, ki):
        return (b, h, qi, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, n_k=n_k, causal=causal),
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_k, D), k_map),
            pl.BlockSpec((1, 1, block_k, D), k_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), o_map),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out
