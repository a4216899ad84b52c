"""Pallas TPU chunked gated-linear-attention kernel (Mamba2/SSD scalar
decay) — the SSM-family hot spot (zamba2 backbone; rwkv6 uses per-channel
decay and keeps the jnp chunked path, see models/gla.py).

Grid (B, H, n_chunks); the recurrent state S (N, P) lives in VMEM scratch
and carries across the sequential chunk axis.  Within a chunk everything
is MXU matmuls on (C, N)/(C, P) tiles:

    y      = (q * exp(L)) @ S  +  tril((q @ k^T) * exp(L_i - L_j)) @ v
    S_next = exp(L_C) * S + (k * exp(L_C - L))^T @ v

with L the inclusive cumsum of the per-step log-decay (<= 0, so every
exponent is <= 0 after clamping — numerically stable, cf. models/gla.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, lw_ref, y_ref, s_out_ref, s_ref,
            *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (C, N)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)              # (C, P)
    lw = lw_ref[0, 0].astype(jnp.float32)            # (C, 1)
    L = jnp.cumsum(lw[:, 0])                         # (C,) inclusive

    # inter-chunk: read carried state with decay exp(L_i)
    q_dec = q * jnp.exp(L)[:, None]
    y_inter = jax.lax.dot_general(q_dec, s_ref[...],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # intra-chunk: A_ij = (q_i . k_j) * exp(L_i - L_j), j <= i
    A = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    dl = jnp.minimum(L[:, None] - L[None, :], 0.0)   # clamp masked region
    A = A * jnp.exp(dl)
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    A = jnp.where(j_idx <= i_idx, A, 0.0)
    y = y_inter + jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S' = exp(L_C) S + (k * exp(L_C - L))^T v
    L_tot = L[-1]
    k_scaled = k * jnp.exp(L_tot - L)[:, None]
    s_ref[...] = jnp.exp(L_tot) * s_ref[...] + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ci == n_chunks - 1)
    def _final():
        s_out_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gla_scan_scalar(q, k, v, logw, *, chunk: int = 64,
                    interpret: bool):
    """q, k: (B, H, T, N); v: (B, H, T, P); logw: (B, H, T) scalar decay
    (<= 0).  Returns (y: (B, H, T, P), S: (B, H, N, P) fp32)."""
    B, H, T, N = q.shape
    P = v.shape[-1]
    assert T % chunk == 0, f"T={T} % chunk={chunk}"
    nc = T // chunk
    lw = logw[..., None]                             # (B, H, T, 1)

    def tile_map(b, h, ci):
        return (b, h, ci, 0)

    def s_map(b, h, ci):
        return (b, h, 0, 0)

    y, s_out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=nc),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, N), tile_map),
            pl.BlockSpec((1, 1, chunk, N), tile_map),
            pl.BlockSpec((1, 1, chunk, P), tile_map),
            pl.BlockSpec((1, 1, chunk, 1), tile_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), tile_map),
            pl.BlockSpec((1, 1, N, P), s_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, P), q.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(q, k, v, lw)
    return y, s_out
