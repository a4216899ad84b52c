"""Shared model layers: norms, RoPE, SwiGLU MLP, embeddings.

All params are plain nested dicts of jnp arrays; init fns take an rng key.
Compute dtype is bf16 by default (params stored fp32, cast at use).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

COMPUTE_DTYPE = jnp.bfloat16


def _dense_init(key, shape, scale: Optional[float] = None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale)


def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"]).astype(dtype)


def init_layernorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * params["scale"] + params["bias"]).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., T, H, D) or (..., T, D); positions: broadcastable to (..., T)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                    # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, D/2)
    if x.ndim == angles.ndim + 1:                          # has heads axis
        angles = angles[..., None, :]                      # (..., T, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(k1, (d_model, d_ff)),
        "w_up": _dense_init(k2, (d_model, d_ff)),
        "w_down": _dense_init(k3, (d_ff, d_model)),
    }


def swiglu(params, x):
    dt = x.dtype
    g = x @ params["w_gate"].astype(dt)
    u = x @ params["w_up"].astype(dt)
    return (jax.nn.silu(g) * u) @ params["w_down"].astype(dt)


def init_gelu_mlp(key, d_model: int, d_ff: int):
    k1, k2 = jax.random.split(key)
    return {
        "w_in": _dense_init(k1, (d_model, d_ff)),
        "b_in": jnp.zeros((d_ff,), jnp.float32),
        "w_out": _dense_init(k2, (d_ff, d_model)),
        "b_out": jnp.zeros((d_model,), jnp.float32),
    }


def gelu_mlp(params, x):
    dt = x.dtype
    h = jax.nn.gelu(x @ params["w_in"].astype(dt) + params["b_in"].astype(dt))
    return h @ params["w_out"].astype(dt) + params["b_out"].astype(dt)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int):
    return {"table": jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02}


def embed(params, tokens, dtype=COMPUTE_DTYPE):
    return params["table"].astype(dtype)[tokens]


def unembed(params, x, table=None):
    """Project to vocab logits.  ``table`` overrides (tied embeddings)."""
    w = table if table is not None else params["table"]
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T
