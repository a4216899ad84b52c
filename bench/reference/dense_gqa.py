"""Plain float32 reference of a dense decoder with grouped-query
attention (Qwen2, Mistral): token embedding, then per layer RMSNorm,
q/k/v projections (with biases where the configuration has them),
rotary position embedding (half-split pairs, theta from the config),
causal softmax attention in which query head ``h`` reads KV head
``h // (heads / kv_heads)``, the output projection, RMSNorm and a SiLU
gated MLP, each with a residual; a final RMSNorm and the LM head (the
embedding table where tied).

Written from the published architecture in straightforward ``jax.numpy``
with no kernels, cache or batching; it imports nothing of the program.
``forward_hidden`` runs teacher-forced over one whole sequence.  With
``quant="fp8"`` every projection and the head multiply float8 (e4m3)
operands, scaled per output channel for weights and per token for
activations: the precision control of the comparison.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale along ``axis``,
    returned dequantized in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _E4M3_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant: Optional[str]):
    """x (..., k) @ w (k, n) in float32 at full precision; with fp8 both
    operands are first rounded to e4m3 (x per row, w per column)."""
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (T, H, D), positions 0..T-1."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(hp: Dict, quant, x, lp):
    T = x.shape[0]
    nq, nkv, hd = hp["num_attention_heads"], hp["num_key_value_heads"], \
        hp["head_dim"]
    eps = float(hp["rms_norm_eps"])
    a = lp["attn"]
    h = _rmsnorm(x, lp["ln1"]["scale"], eps)
    q, k, v = (_mm(h, a["wq"], quant), _mm(h, a["wk"], quant),
               _mm(h, a["wv"], quant))
    if hp["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    theta = float(hp["rope_theta"])
    q = _rope(q.reshape(T, nq, hd), theta)
    k = _rope(k.reshape(T, nkv, hd), theta)
    v = v.reshape(T, nkv, hd)
    g = nq // nkv
    k = jnp.repeat(k, g, axis=1)           # kv head j serves q heads j*g..
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(T, nq * hd), a["wo"], quant)
    h = _rmsnorm(x, lp["ln2"]["scale"], eps)
    f = lp["ffn"]
    m = jax.nn.silu(_mm(h, f["w_gate"], quant)) * _mm(h, f["w_up"], quant)
    return x + _mm(m, f["w_down"], quant), None


def forward_hidden(params, hp: Dict, tokens, quant: Optional[str] = None):
    """Final-normed hidden states (T, d) of ``tokens`` (T,)."""
    x = params["embed"]["table"][tokens]
    x, _ = jax.lax.scan(functools.partial(_layer, hp, quant), x,
                        params["layers"])
    return _rmsnorm(x, params["final_norm"]["scale"],
                    float(hp["rms_norm_eps"]))


def head_table(params, hp: Dict):
    return params["embed" if hp["tie_word_embeddings"] else "lm_head"][
        "table"]


def logits_at(params, hp: Dict, hidden, positions, quant=None):
    """(P, V) float32 logits at ``positions`` of ``hidden``."""
    h = hidden[positions]
    w = head_table(params, hp)
    if quant == "fp8":
        return jnp.matmul(_q8(h, -1), _q8(w, -1).T, precision=HIGHEST)
    return jnp.matmul(h, w.T, precision=HIGHEST)
