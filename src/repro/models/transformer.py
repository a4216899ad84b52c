"""Model assembly for all assigned architectures.

Layer stacks are built as *stacked* param pytrees and executed with
``jax.lax.scan`` so the lowered HLO stays small at 512-device dry-run scale.
Heterogeneous layer patterns scan over *periods*:

  * uniform (mistral-nemo, qwen2, llama3.2, llava, olmoe):
      one scan over n_layers (MoE FFN handled inside the body);
  * deepseek-v2: dense layer 0 (skip_first MoE) + scan over layers 1..L-1;
  * gemma3 (local_global:R): scan over periods of (R local + 1 global);
      local layers use a sliding window and a *ring* decode cache of size W;
  * zamba2: scan over periods of (every-1 mamba2 + 1 SHARED attention
      block) + a remainder mamba-only scan — attention params are a single
      shared block (zamba2's defining trick);
  * rwkv: one scan over n_layers of RWKV6 blocks (constant-size state);
  * whisper: encoder scan (bidirectional) + decoder scan (self + cross).

Public entry points (all pure functions of (params, inputs)):
  init_params(cfg, key)
  forward_train(params, cfg, tokens, extra_embeds=None) -> (logits, aux_loss)
  prefill(params, cfg, tokens, extra_embeds=None, cache_len=S) -> (logits, caches)
  decode_step(params, cfg, caches, token, pos) -> (logits, caches)
  init_caches(cfg, batch, cache_len) -> caches pytree (zeros)
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mamba2, moe, rwkv6
from repro.models.attention import MLACache

CD = L.COMPUTE_DTYPE


# ---------------------------------------------------------------------------
# Cache pytrees
# ---------------------------------------------------------------------------

class KVCaches(NamedTuple):
    k: jnp.ndarray            # (L, B, S, Hkv, D)
    v: jnp.ndarray


class MLACaches(NamedTuple):
    latent: jnp.ndarray       # (L, B, S, r)
    k_rope: jnp.ndarray       # (L, B, S, rope_dim)


class Gemma3Caches(NamedTuple):
    local_k: jnp.ndarray      # (P, R, B, W, Hkv, D)  ring buffers
    local_v: jnp.ndarray
    global_k: jnp.ndarray     # (P, B, S, Hkv, D)
    global_v: jnp.ndarray


class Zamba2Caches(NamedTuple):
    conv_p: jnp.ndarray       # (P, R, B, K-1, C)
    ssm_p: jnp.ndarray        # (P, R, B, H, N, Pd)
    conv_rem: jnp.ndarray     # (rem, B, K-1, C)
    ssm_rem: jnp.ndarray      # (rem, B, H, N, Pd)
    attn_k: jnp.ndarray       # (P, B, S, Hkv, D)
    attn_v: jnp.ndarray


class RWKVCaches(NamedTuple):
    shift_tm: jnp.ndarray     # (L, B, d)
    shift_cm: jnp.ndarray     # (L, B, d)
    S: jnp.ndarray            # (L, B, H, N, N) fp32


class WhisperCaches(NamedTuple):
    self_k: jnp.ndarray       # (L, B, S, Hkv, D)
    self_v: jnp.ndarray
    cross_k: jnp.ndarray      # (L, B, S_enc, Hkv, D)
    cross_v: jnp.ndarray


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _stack_init(key, n: int, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def _pattern(cfg: ModelConfig) -> str:
    lp = cfg.layer_pattern
    if lp.startswith("local_global"):
        return "gemma3"
    return lp  # uniform | zamba2 | rwkv


def _gemma3_ratio(cfg: ModelConfig) -> int:
    return int(cfg.layer_pattern.split(":")[1])


def _moe_layer(cfg: ModelConfig) -> bool:
    return cfg.moe is not None


# ---------------------------------------------------------------------------
# Uniform decoder layer (dense / MoE / MLA)
# ---------------------------------------------------------------------------

def _init_uniform_layer(cfg: ModelConfig, use_moe: bool):
    def init(key):
        k1, k2 = jax.random.split(key)
        p = {"ln1": L.init_rmsnorm(cfg.d_model), "ln2": L.init_rmsnorm(cfg.d_model)}
        if cfg.mla is not None:
            p["attn"] = attn.init_mla(k1, cfg)
        else:
            p["attn"] = attn.init_gqa(k1, cfg)
        if use_moe:
            p["ffn"] = moe.init_moe(k2, cfg)
        else:
            p["ffn"] = L.init_swiglu(k2, cfg.d_model, cfg.d_ff)
        return p
    return init


def _uniform_layer_seq(lp, x, cfg: ModelConfig, positions, use_moe: bool,
                       window=None):
    """Sequence mode; returns (x, cache_kv, aux)."""
    from repro.models.sharding import constrain_batch
    x = constrain_batch(x)
    h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
    if cfg.mla is not None:
        a, cache = attn.mla_forward(lp["attn"], h, cfg, positions)
    else:
        a, cache = attn.gqa_forward(lp["attn"], h, cfg, positions, window=window)
    x = x + a
    h = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
    if use_moe:
        f, aux = moe.moe_forward(lp["ffn"], h, cfg)
    else:
        f, aux = L.swiglu(lp["ffn"], h), jnp.float32(0.0)
    return x + f, cache, aux


def _uniform_layer_decode(lp, x, cfg: ModelConfig, cache, pos, use_moe: bool,
                          window=None, ring: bool = False):
    from repro.models.sharding import constrain_batch
    x = constrain_batch(x)
    h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
    if cfg.mla is not None:
        a, cache = attn.mla_decode(lp["attn"], h, cfg, cache, pos)
    else:
        ck, cv = cache
        a, ck, cv = attn.gqa_decode(lp["attn"], h, cfg, ck, cv, pos,
                                    window=window, ring=ring)
        cache = (ck, cv)
    x = x + a
    h = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
    if use_moe:
        f, _ = moe.moe_forward(lp["ffn"], h, cfg)
    else:
        f = L.swiglu(lp["ffn"], h)
    return x + f, cache


# ---------------------------------------------------------------------------
# init_params
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Any:
    keys = jax.random.split(key, 8)
    pat = _pattern(cfg)
    params: dict = {
        "embed": L.init_embedding(keys[0], cfg.vocab_size, cfg.d_model),
        "final_norm": L.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "table": jax.random.normal(keys[1], (cfg.vocab_size, cfg.d_model),
                                       jnp.float32) * 0.02}

    if cfg.encoder_decoder:
        params.update(_init_whisper(cfg, keys[2]))
        return params

    if pat == "uniform":
        use_moe = _moe_layer(cfg)
        if cfg.moe is not None and cfg.moe.layer_pattern == "skip_first":
            params["layer0"] = _init_uniform_layer(cfg, use_moe=False)(keys[3])
            params["layers"] = _stack_init(
                keys[2], cfg.n_layers - 1, _init_uniform_layer(cfg, use_moe))
        else:
            params["layers"] = _stack_init(
                keys[2], cfg.n_layers, _init_uniform_layer(cfg, use_moe))
    elif pat == "gemma3":
        R = _gemma3_ratio(cfg)
        period = R + 1
        assert cfg.n_layers % period == 0, \
            f"gemma3 pattern needs n_layers % {period} == 0"
        n_periods = cfg.n_layers // period
        init_one = _init_uniform_layer(cfg, use_moe=False)

        def init_period(key):
            ks = jax.random.split(key, period)
            return {"local": jax.vmap(init_one)(ks[:R]),
                    "global": init_one(ks[R])}
        params["periods"] = _stack_init(keys[2], n_periods, init_period)
    elif pat == "zamba2":
        every = cfg.hybrid_attn_every
        R = every - 1                       # mamba layers per period
        n_periods = cfg.n_layers // every
        rem = cfg.n_layers % every

        def init_mamba_layer(key):
            k1, k2 = jax.random.split(key)
            return {"ln": L.init_rmsnorm(cfg.d_model),
                    "mamba": mamba2.init_mamba2_block(k1, cfg)}

        def init_period(key):
            ks = jax.random.split(key, R)
            return jax.vmap(init_mamba_layer)(ks)

        params["mamba_p"] = _stack_init(keys[2], n_periods, init_period)
        if rem:
            params["mamba_rem"] = _stack_init(keys[3], rem, init_mamba_layer)
        params["attn_shared"] = {
            "ln1": L.init_rmsnorm(cfg.d_model),
            "ln2": L.init_rmsnorm(cfg.d_model),
            "attn": attn.init_gqa(keys[4], cfg),
            "ffn": L.init_swiglu(keys[5], cfg.d_model, cfg.d_ff),
        }
    elif pat == "rwkv":
        def init_layer(key):
            return {"ln1": L.init_rmsnorm(cfg.d_model),
                    "block": rwkv6.init_rwkv_block(key, cfg)}
        params["layers"] = _stack_init(keys[2], cfg.n_layers, init_layer)
    else:
        raise ValueError(f"unknown layer pattern {cfg.layer_pattern}")

    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        # projector stub: frontend embeddings arrive pre-projected at d_embed;
        # a single linear maps them into the LM (identity-shaped when equal).
        params["mm_proj"] = {
            "w": jax.random.normal(keys[6], (cfg.frontend.d_embed, cfg.d_model),
                                   jnp.float32) * (cfg.frontend.d_embed ** -0.5)}
    return params


def _init_whisper(cfg: ModelConfig, key):
    ks = jax.random.split(key, 4)

    def init_enc_layer(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": L.init_layernorm(cfg.d_model),
                "attn": attn.init_gqa(k1, cfg),
                "ln2": L.init_layernorm(cfg.d_model),
                "mlp": L.init_gelu_mlp(k2, cfg.d_model, cfg.d_ff)}

    def init_dec_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"ln1": L.init_layernorm(cfg.d_model),
                "self_attn": attn.init_gqa(k1, cfg),
                "ln2": L.init_layernorm(cfg.d_model),
                "cross_attn": attn.init_cross_attention(k2, cfg),
                "ln3": L.init_layernorm(cfg.d_model),
                "mlp": L.init_gelu_mlp(k3, cfg.d_model, cfg.d_ff)}

    return {
        "enc_layers": _stack_init(ks[0], cfg.n_encoder_layers, init_enc_layer),
        "enc_norm": L.init_layernorm(cfg.d_model),
        "dec_layers": _stack_init(ks[1], cfg.n_layers, init_dec_layer),
    }


# ---------------------------------------------------------------------------
# Whisper encoder / decoder
# ---------------------------------------------------------------------------

def _sinusoid_pos(T: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(T)[:, None].astype(jnp.float32)
    i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    angles = pos / (10000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], -1)


def whisper_encode(params, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    """frames: (B, S_enc, d) precomputed conv-frontend embeddings (stub)."""
    x = frames.astype(CD) + _sinusoid_pos(frames.shape[1], cfg.d_model).astype(CD)

    def body(x, lp):
        from repro.models.sharding import constrain_batch
        x = constrain_batch(x)
        h = L.layernorm(lp["ln1"], x, cfg.norm_eps)
        x = x + _bidir_attn(lp["attn"], h, cfg)     # bidirectional, no mask
        h = L.layernorm(lp["ln2"], x, cfg.norm_eps)
        return x + L.gelu_mlp(lp["mlp"], h), None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return L.layernorm(params["enc_norm"], x, cfg.norm_eps)


def _bidir_attn(p, h, cfg: ModelConfig):
    B, T, _ = h.shape
    q, k, v = attn._project_qkv(p, h, cfg)
    scale = 1.0 / (cfg.resolved_head_dim ** 0.5)
    out = attn._sdpa(q, k, v, None, scale)
    return out.reshape(B, T, -1) @ p["wo"].astype(h.dtype)


def whisper_decode_seq(params, cfg: ModelConfig, tokens, enc_out,
                       last_only: bool = False, return_hidden: bool = False):
    """Teacher-forced decoder pass.  Returns (logits, caches-as-(k,v) stacks)."""
    B, T = tokens.shape
    x = L.embed(params["embed"], tokens)
    x = x + _sinusoid_pos(T, cfg.d_model).astype(CD)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))

    def body(x, lp):
        from repro.models.sharding import constrain_batch
        x = constrain_batch(x)
        h = L.layernorm(lp["ln1"], x, cfg.norm_eps)
        a, (k, v) = attn.gqa_forward(lp["self_attn"], h, cfg, positions)
        x = x + a
        h = L.layernorm(lp["ln2"], x, cfg.norm_eps)
        ckv = attn.project_cross_kv(lp["cross_attn"], enc_out, cfg)
        x = x + attn.cross_attention(lp["cross_attn"], h, ckv, cfg)
        h = L.layernorm(lp["ln3"], x, cfg.norm_eps)
        return x + L.gelu_mlp(lp["mlp"], h), (k, v, ckv[0], ckv[1])

    x, caches = jax.lax.scan(body, x, params["dec_layers"])
    if return_hidden:
        return x, caches
    if last_only:
        x = x[:, -1:]
    logits = L.unembed(params["embed"], x)   # whisper ties embeddings
    return logits, caches


def whisper_decode_step(params, cfg: ModelConfig, caches: WhisperCaches,
                        token, pos):
    B = token.shape[0]
    x = L.embed(params["embed"], token[:, None])
    pe = jax.lax.dynamic_slice_in_dim(
        _sinusoid_pos(caches.self_k.shape[2], cfg.d_model), pos, 1, 0)
    x = x + pe.astype(CD)

    def body(x, xs):
        lp, sk, sv, ck, cv = xs
        h = L.layernorm(lp["ln1"], x, cfg.norm_eps)
        a, sk, sv = attn.gqa_decode(lp["self_attn"], h, cfg, sk, sv, pos)
        x = x + a
        h = L.layernorm(lp["ln2"], x, cfg.norm_eps)
        x = x + attn.cross_attention(lp["cross_attn"], h, (ck, cv), cfg)
        h = L.layernorm(lp["ln3"], x, cfg.norm_eps)
        return x + L.gelu_mlp(lp["mlp"], h), (sk, sv)

    x, (sk, sv) = jax.lax.scan(
        body, x, (params["dec_layers"], caches.self_k, caches.self_v,
                  caches.cross_k, caches.cross_v))
    logits = L.unembed(params["embed"], x[:, 0])
    return logits, WhisperCaches(self_k=sk, self_v=sv,
                                 cross_k=caches.cross_k, cross_v=caches.cross_v)


# ---------------------------------------------------------------------------
# Sequence-mode forward (train / prefill) for decoder-only stacks
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ModelConfig, tokens, extra_embeds,
                  dtype=CD):
    """tokens: (B, T_text); extra_embeds: (B, T_img, d_embed) or None.
    VLM: image embeds are projected and *prepended* to the text tokens."""
    x = L.embed(params["embed"], tokens, dtype)
    if extra_embeds is not None and "mm_proj" in params:
        img = extra_embeds.astype(CD) @ params["mm_proj"]["w"].astype(CD)
        x = jnp.concatenate([img, x], axis=1)
    B, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    return x, positions


def forward_seq(params, cfg: ModelConfig, tokens, extra_embeds=None,
                remat: bool = False, encoder_frames=None,
                last_only: bool = False, return_hidden: bool = False,
                compute_dtype=CD):
    """Full-sequence forward.  Returns (logits, caches_stacked, aux_loss).
    ``compute_dtype``: activation dtype (bf16 as served; a float32
    reference passes ``jnp.float32``).

    caches_stacked layouts match the per-pattern decode caches but with
    S == T (prefill length); ``init_caches``+``write`` extends them.
    """
    if cfg.encoder_decoder:
        enc_out = whisper_encode(params, cfg, encoder_frames)
        logits, caches = whisper_decode_seq(params, cfg, tokens, enc_out,
                                            last_only=last_only,
                                            return_hidden=return_hidden)
        return logits, caches, jnp.float32(0.0)

    pat = _pattern(cfg)
    x, positions = _embed_inputs(params, cfg, tokens, extra_embeds,
                                 compute_dtype)
    aux_total = jnp.float32(0.0)
    caches = None

    if pat == "uniform":
        use_moe = _moe_layer(cfg)
        skip_first = cfg.moe is not None and cfg.moe.layer_pattern == "skip_first"

        def body(carry, lp):
            x, aux = carry
            x, cache, a = _uniform_layer_seq(lp, x, cfg, positions, use_moe)
            return (x, aux + a), cache

        bodyf = jax.checkpoint(body) if remat else body
        if skip_first:
            x, c0, a0 = _uniform_layer_seq(params["layer0"], x, cfg,
                                           positions, use_moe=False)
            aux_total += a0
        (x, aux_total2), caches = jax.lax.scan(bodyf, (x, aux_total),
                                               params["layers"])
        aux_total = aux_total2
        if skip_first:
            caches = {"first": c0, "rest": caches}
    elif pat == "gemma3":
        R = _gemma3_ratio(cfg)

        def body(x, lp):
            local_caches = []
            for i in range(R):
                lpi = jax.tree.map(lambda a: a[i], lp["local"])
                x, c, _ = _uniform_layer_seq(lpi, x, cfg, positions,
                                             use_moe=False,
                                             window=cfg.sliding_window)
                local_caches.append(c)
            x, cg, _ = _uniform_layer_seq(lp["global"], x, cfg, positions,
                                          use_moe=False)
            lk = jnp.stack([c[0] for c in local_caches])
            lv = jnp.stack([c[1] for c in local_caches])
            return x, (lk, lv, cg[0], cg[1])

        bodyf = jax.checkpoint(body) if remat else body
        x, caches = jax.lax.scan(bodyf, x, params["periods"])
    elif pat == "zamba2":
        x, caches = _zamba2_forward_seq(params, cfg, x, positions,
                                        remat=remat)
    elif pat == "rwkv":
        B = x.shape[0]
        def body(x, lp):
            from repro.models.sharding import constrain_batch
            x = constrain_batch(x)
            h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
            st = rwkv6.init_rwkv_state(cfg, B, dtype=x.dtype)
            h, new_st = rwkv6.rwkv_block_forward(lp["block"], h, cfg, st)
            return x + h, new_st

        bodyf = jax.checkpoint(body) if remat else body
        x, states = jax.lax.scan(bodyf, x, params["layers"])
        caches = states
    else:
        raise ValueError(pat)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return x, caches, aux_total
    if last_only:
        x = x[:, -1:]          # avoid materializing (B, T, V) logits
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = L.unembed(head, x)
    return logits, caches, aux_total


def _zamba2_forward_seq(params, cfg: ModelConfig, x, positions, remat=False):
    every = cfg.hybrid_attn_every
    R = every - 1
    B = x.shape[0]

    def mamba_apply(lp, x):
        from repro.models.sharding import constrain_batch
        x = constrain_batch(x)
        h = L.rmsnorm(lp["ln"], x, cfg.norm_eps)
        st = mamba2.init_mamba_state(cfg, B, dtype=x.dtype)
        h, new_st = mamba2.mamba2_block_forward(lp["mamba"], h, cfg, st)
        return x + h, new_st

    ap = params["attn_shared"]

    def period_body(x, lp):
        convs, ssms = [], []
        for i in range(R):
            lpi = jax.tree.map(lambda a: a[i], lp)
            x, st = mamba_apply(lpi, x)
            convs.append(st.conv)
            ssms.append(st.S)
        h = L.rmsnorm(ap["ln1"], x, cfg.norm_eps)
        a, (k, v) = attn.gqa_forward(ap["attn"], h, cfg, positions)
        x = x + a
        h = L.rmsnorm(ap["ln2"], x, cfg.norm_eps)
        x = x + L.swiglu(ap["ffn"], h)
        return x, (jnp.stack(convs), jnp.stack(ssms), k, v)

    bodyf = jax.checkpoint(period_body) if remat else period_body
    x, (conv_p, ssm_p, ak, av) = jax.lax.scan(bodyf, x, params["mamba_p"])

    conv_rem = ssm_rem = None
    if "mamba_rem" in params:
        def rem_body(x, lp):
            x, st = mamba_apply(lp, x)
            return x, (st.conv, st.S)
        x, (conv_rem, ssm_rem) = jax.lax.scan(rem_body, x, params["mamba_rem"])

    return x, (conv_p, ssm_p, conv_rem, ssm_rem, ak, av)


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, cache_len: int,
                kv_dtype=None):
    """Zero-initialized decode caches (pytree of arrays).  ``kv_dtype``
    overrides the KV storage dtype for attention caches (e.g. jnp.int8 for
    the quantized-cache §Perf variant); recurrent/MLA states keep their
    native dtypes."""
    CDkv = kv_dtype if kv_dtype is not None else CD  # attention KV only
    hd = cfg.resolved_head_dim
    Hkv = cfg.n_kv_heads
    pat = _pattern(cfg)
    if cfg.encoder_decoder:
        return WhisperCaches(
            self_k=jnp.zeros((cfg.n_layers, batch, cache_len, Hkv, hd), CDkv),
            self_v=jnp.zeros((cfg.n_layers, batch, cache_len, Hkv, hd), CDkv),
            cross_k=jnp.zeros((cfg.n_layers, batch, cfg.n_encoder_tokens, Hkv, hd), CD),
            cross_v=jnp.zeros((cfg.n_layers, batch, cfg.n_encoder_tokens, Hkv, hd), CD),
        )
    if cfg.mla is not None:
        m = cfg.mla
        lat = jnp.zeros((cfg.n_layers, batch, cache_len, m.kv_lora_rank), CD)
        kr = jnp.zeros((cfg.n_layers, batch, cache_len, m.rope_head_dim), CD)
        return MLACaches(latent=lat, k_rope=kr)
    if pat == "uniform":
        return KVCaches(
            k=jnp.zeros((cfg.n_layers, batch, cache_len, Hkv, hd), CDkv),
            v=jnp.zeros((cfg.n_layers, batch, cache_len, Hkv, hd), CDkv))
    if pat == "gemma3":
        R = _gemma3_ratio(cfg)
        P = cfg.n_layers // (R + 1)
        W = min(cfg.sliding_window, cache_len)
        return Gemma3Caches(
            local_k=jnp.zeros((P, R, batch, W, Hkv, hd), CDkv),
            local_v=jnp.zeros((P, R, batch, W, Hkv, hd), CDkv),
            global_k=jnp.zeros((P, batch, cache_len, Hkv, hd), CDkv),
            global_v=jnp.zeros((P, batch, cache_len, Hkv, hd), CDkv))
    if pat == "zamba2":
        every = cfg.hybrid_attn_every
        R = every - 1
        P = cfg.n_layers // every
        rem = cfg.n_layers % every
        s, d_inner, H, conv_ch = mamba2._dims(cfg)
        K = s.conv_kernel
        return Zamba2Caches(
            conv_p=jnp.zeros((P, R, batch, K - 1, conv_ch), CD),
            ssm_p=jnp.zeros((P, R, batch, H, s.state_dim, s.head_dim), jnp.float32),
            conv_rem=jnp.zeros((max(rem, 1), batch, K - 1, conv_ch), CD),
            ssm_rem=jnp.zeros((max(rem, 1), batch, H, s.state_dim, s.head_dim), jnp.float32),
            attn_k=jnp.zeros((P, batch, cache_len, Hkv, hd), CDkv),
            attn_v=jnp.zeros((P, batch, cache_len, Hkv, hd), CDkv))
    if pat == "rwkv":
        r = cfg.rwkv
        return RWKVCaches(
            shift_tm=jnp.zeros((cfg.n_layers, batch, cfg.d_model), CD),
            shift_cm=jnp.zeros((cfg.n_layers, batch, cfg.d_model), CD),
            S=jnp.zeros((cfg.n_layers, batch, cfg.n_heads, r.head_dim,
                         r.head_dim), jnp.float32))
    raise ValueError(pat)


# ---------------------------------------------------------------------------
# decode_step
# ---------------------------------------------------------------------------

def decode_step(params, cfg: ModelConfig, caches, token, pos):
    """One decode step.  token: (B,) int32; pos: scalar int32 (tokens so far).
    Returns (logits (B, V), new caches)."""
    if cfg.encoder_decoder:
        return whisper_decode_step(params, cfg, caches, token, pos)

    pat = _pattern(cfg)
    x = L.embed(params["embed"], token[:, None])   # (B, 1, d)

    if pat == "uniform":
        use_moe = _moe_layer(cfg)
        skip_first = cfg.moe is not None and cfg.moe.layer_pattern == "skip_first"
        if cfg.mla is not None:
            def body(x, xs):
                lp, lat, kr = xs
                x, c = _uniform_layer_decode(lp, x, cfg, MLACache(lat, kr),
                                             pos, use_moe)
                return x, (c.latent, c.k_rope)
            lat_all, kr_all = caches.latent, caches.k_rope
            if skip_first:
                c0 = MLACache(lat_all[0], kr_all[0])
                x, c0 = _uniform_layer_decode(params["layer0"], x, cfg, c0,
                                              pos, use_moe=False)
                x, (lat_r, kr_r) = jax.lax.scan(
                    body, x, (params["layers"], lat_all[1:], kr_all[1:]))
                lat_new = jnp.concatenate([c0.latent[None], lat_r])
                kr_new = jnp.concatenate([c0.k_rope[None], kr_r])
            else:
                x, (lat_new, kr_new) = jax.lax.scan(
                    body, x, (params["layers"], lat_all, kr_all))
            new_caches = MLACaches(latent=lat_new, k_rope=kr_new)
        else:
            def body(x, xs):
                lp, ck, cv = xs
                x, (ck, cv) = _uniform_layer_decode(lp, x, cfg, (ck, cv),
                                                    pos, use_moe)
                return x, (ck, cv)
            x, (k_new, v_new) = jax.lax.scan(
                body, x, (params["layers"], caches.k, caches.v))
            new_caches = KVCaches(k=k_new, v=v_new)
    elif pat == "gemma3":
        R = _gemma3_ratio(cfg)
        W = caches.local_k.shape[3]

        def body(x, xs):
            lp, lk, lv, gk, gv = xs
            lks, lvs = [], []
            for i in range(R):
                lpi = jax.tree.map(lambda a: a[i], lp["local"])
                x, (cki, cvi) = _uniform_layer_decode(
                    lpi, x, cfg, (lk[i], lv[i]), pos, use_moe=False,
                    window=W, ring=True)
                lks.append(cki)
                lvs.append(cvi)
            x, (gk, gv) = _uniform_layer_decode(lp["global"], x, cfg,
                                                (gk, gv), pos, use_moe=False)
            return x, (jnp.stack(lks), jnp.stack(lvs), gk, gv)

        x, (lk, lv, gk, gv) = jax.lax.scan(
            body, x, (params["periods"], caches.local_k, caches.local_v,
                      caches.global_k, caches.global_v))
        new_caches = Gemma3Caches(lk, lv, gk, gv)
    elif pat == "zamba2":
        x, new_caches = _zamba2_decode(params, cfg, caches, x, pos)
    elif pat == "rwkv":
        def body(x, xs):
            lp, stm, scm, S = xs
            h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
            st = rwkv6.RWKVState(stm, scm, S)
            h, st = rwkv6.rwkv_block_decode(lp["block"], h, cfg, st)
            return x + h, (st.shift_tm, st.shift_cm, st.S)
        x, (stm, scm, S) = jax.lax.scan(
            body, x, (params["layers"], caches.shift_tm, caches.shift_cm,
                      caches.S))
        new_caches = RWKVCaches(stm, scm, S)
    else:
        raise ValueError(pat)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = L.unembed(head, x[:, 0])
    return logits, new_caches


def _zamba2_decode(params, cfg: ModelConfig, caches: Zamba2Caches, x, pos):
    every = cfg.hybrid_attn_every
    R = every - 1
    ap = params["attn_shared"]

    def mamba_apply(lp, x, conv, S):
        h = L.rmsnorm(lp["ln"], x, cfg.norm_eps)
        st = mamba2.MambaState(conv=conv, S=S)
        h, st = mamba2.mamba2_block_decode(lp["mamba"], h, cfg, st)
        return x + h, st

    def body(x, xs):
        lp, conv, S, ak, av = xs
        convs, ssms = [], []
        for i in range(R):
            lpi = jax.tree.map(lambda a: a[i], lp)
            x, st = mamba_apply(lpi, x, conv[i], S[i])
            convs.append(st.conv)
            ssms.append(st.S)
        h = L.rmsnorm(ap["ln1"], x, cfg.norm_eps)
        a, ak, av = attn.gqa_decode(ap["attn"], h, cfg, ak, av, pos)
        x = x + a
        h = L.rmsnorm(ap["ln2"], x, cfg.norm_eps)
        x = x + L.swiglu(ap["ffn"], h)
        return x, (jnp.stack(convs), jnp.stack(ssms), ak, av)

    x, (conv_p, ssm_p, ak, av) = jax.lax.scan(
        body, x, (params["mamba_p"], caches.conv_p, caches.ssm_p,
                  caches.attn_k, caches.attn_v))

    conv_rem, ssm_rem = caches.conv_rem, caches.ssm_rem
    if "mamba_rem" in params:
        def rem_body(x, xs):
            lp, conv, S = xs
            x, st = mamba_apply(lp, x, conv, S)
            return x, (st.conv, st.S)
        x, (conv_rem, ssm_rem) = jax.lax.scan(
            rem_body, x, (params["mamba_rem"], caches.conv_rem,
                          caches.ssm_rem))

    return x, Zamba2Caches(conv_p, ssm_p, conv_rem, ssm_rem, ak, av)
