"""Paged-KV decode step for the serving engine (real-model mode).

Runs a uniform-pattern GQA transformer one token per sequence against the
paged GPU pool via block tables, using the Pallas paged-attention kernel.
The engine pads the batch to a fixed size; padding rows point their block
table at a reserved trash block and are masked by the caller.

Hot-path contract (see DESIGN.md §3):
  * ``paged_decode_step`` / ``paged_decode_step_device`` DONATE the pool
    operand — the per-layer KV write is an in-place scatter, not a
    full-pool copy per token.  Callers must rebind their pool reference to
    the returned array; the donated input buffer is invalid afterwards.
  * ``paged_decode_step_device`` additionally donates and returns the
    context-length and last-token arrays so steady-state decode keeps its
    entire per-step state device-resident (the DecodeRunner threads it).
  * Sampling (temperature / top-k / top-p) is fused into the device step;
    the parameters are traced scalars, so greedy (temperature == 0) and
    sampled runs share ONE compiled variant and greedy stays bit-exact
    argmax.  The per-row PRNG-key array holds position-independent BASE
    keys: it is read-only (neither donated nor returned — never rebind
    it per step); the step folds each row's position in on device.
  * Shapes (batch, n_pages) must be bucketed by the caller — every unique
    shape is one XLA compilation.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_mod


def supports_paged(cfg: ModelConfig) -> bool:
    return (cfg.layer_pattern == "uniform" and cfg.mla is None
            and not cfg.encoder_decoder)


def _decode_hidden(params, pool, block_tables, context_lens, tokens,
                   cfg: ModelConfig, axis_name=None):
    """Shared decode body up to the final norm: one token per row
    through the paged pool.  Returns (x_last (B, d), new_pool) — the
    unembed is left to the caller so the mesh step can vocab-shard it.

    ``axis_name`` is the tensor-parallel mesh axis when this body runs
    under ``shard_map`` (DESIGN.md §9): ``cfg`` then describes the
    LOCAL head counts, the per-layer attention runs over this shard's
    heads only (per-head compute is independent, so every shard's
    output is bit-identical to the corresponding head slice of the
    single-device run), and the head outputs are all-gathered —
    a pure concatenation, no float reduction — before the replicated
    ``wo`` matmul.  ``axis_name=None`` is the unsharded path,
    byte-for-byte the pre-mesh code."""
    assert supports_paged(cfg), cfg.name
    B = tokens.shape[0]
    bs = pool.shape[3]
    x = L.embed(params["embed"], tokens[:, None])          # (B, 1, d)
    positions = context_lens[:, None]                      # rope positions
    scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
    use_moe = cfg.moe is not None
    barange = jnp.arange(B)

    def body(carry, xs):
        x, pool = carry
        lp, layer = xs
        h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q, k, v = attn._project_qkv(lp["attn"], h, cfg)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        blk = block_tables[barange, context_lens // bs]
        off = context_lens % bs
        pool = pool.at[layer, 0, blk, off].set(k[:, 0].astype(pool.dtype))
        pool = pool.at[layer, 1, blk, off].set(v[:, 0].astype(pool.dtype))
        with jax.named_scope("paged_attn"):
            a = ops.paged_attention(q[:, 0], pool, layer,
                                    block_tables, context_lens + 1, scale)
        if axis_name is not None:
            # concat this shard's head outputs with the others' (device
            # order == head order, bit-exact) ahead of the replicated wo
            a = jax.lax.all_gather(a, axis_name, axis=1, tiled=True)
        x = x + (a.reshape(B, 1, -1) @ lp["attn"]["wo"].astype(x.dtype))
        h = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        if use_moe:
            f, _ = moe_mod.moe_forward(lp["ffn"], h, cfg)
        else:
            f = L.swiglu(lp["ffn"], h)
        return (x + f, pool), None

    # the pool rides the carry whole, written in place and read by the
    # kernel at ``layer``: a layer sliced out as a scan input is staged
    # through VMEM and written back, a copy each way per layer
    with jax.named_scope("decode_layers"):
        (x, new_pool), _ = jax.lax.scan(
            body, (x, pool), (params["layers"], jnp.arange(pool.shape[0])))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x[:, 0], new_pool


def _head(params, cfg: ModelConfig):
    """The LM head's parameters: the embedding table where tied."""
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]


def _decode_core(params, pool, block_tables, context_lens, tokens,
                 cfg: ModelConfig, axis_name=None):
    """Legacy full-logits decode body (hidden body + replicated unembed).
    Returns (next_tokens, logits, new_pool)."""
    x_last, new_pool = _decode_hidden(params, pool, block_tables,
                                      context_lens, tokens, cfg,
                                      axis_name=axis_name)
    with jax.named_scope("unembed"):
        logits = L.unembed(_head(params, cfg), x_last)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tokens, logits, new_pool


def sample_tokens(logits, keys, ctx, sampling):
    """Fused temperature / top-k / top-p sampling, stateless per step.

    The per-row draw key is derived ON DEVICE as ``fold_in(keys[i],
    ctx[i])`` — ``keys`` holds each row's position-independent base key
    (folded from (seed, rid) at registration), so the random stream is a
    pure function of (seed, rid, position): reproducible under any
    preemption order, row re-registration or bucket rebuild, with no key
    state to thread between steps.

    ``sampling`` is a PER-ROW traced (B, 3) float32 array of
    ``[temperature, top_k, top_p]`` columns, so every request carries
    its own configuration while ONE compiled variant per batch bucket
    serves any mix (the array's shape follows the bucket, never the
    values).  Rows with ``temperature <= 0`` take bit-exact greedy
    argmax; an all-greedy batch skips the sort/softmax/Gumbel machinery
    entirely through a batch-level ``lax.cond`` — the greedy hot path
    stays argmax-only at runtime.

    logits: (B, V); keys: (B, 2) uint32 threefry key data; ctx: (B,)
    i32 positions; sampling: (B, 3) f32 per-row [temperature, top_k,
    top_p] (top_k column 0 = disabled; stored as float, exact for any
    realistic k).  Returns tokens (B,) i32.
    """
    with jax.named_scope("sampling"):
        return _sample_tokens(logits, keys, ctx, sampling)


def _sample_tokens(logits, keys, ctx, sampling):
    B, V = logits.shape
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = sampling[:, 0]
    top_k = sampling[:, 1].astype(jnp.int32)
    top_p = sampling[:, 2]

    def _sampled(_):
        scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
        sorted_lg = jnp.sort(scaled, axis=-1)[:, ::-1]
        k_eff = jnp.clip(jnp.where(top_k > 0, top_k, V), 1,
                         V).astype(jnp.int32)
        kth = jnp.take_along_axis(sorted_lg, (k_eff - 1)[:, None],
                                  axis=-1)
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # nucleus: keep the smallest prefix whose mass reaches top_p (the
        # mass BEFORE an index must be < top_p; index 0 is always kept)
        keep = (cum - probs) < top_p[:, None]
        pth = jnp.min(jnp.where(keep, sorted_lg, jnp.inf), axis=-1,
                      keepdims=True)
        masked = jnp.where(scaled >= jnp.maximum(kth, pth), scaled,
                           -jnp.inf)

        def one_row(key, pos, row_logits):
            g = jax.random.gumbel(jax.random.fold_in(key, pos), (V,),
                                  jnp.float32)
            return jnp.argmax(row_logits + g).astype(jnp.int32)

        drawn = jax.vmap(one_row)(keys, ctx, masked)
        return jnp.where(temp > 0.0, drawn, greedy)

    return jax.lax.cond(jnp.any(temp > 0.0), _sampled, lambda _: greedy,
                        None)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def paged_decode_step(params, pool, block_tables, context_lens, tokens,
                      *, cfg: ModelConfig):
    """pool: (L, 2, nb, bs, Hkv, D) — DONATED (in-place KV write);
    block_tables: (B, n_pages) int32; context_lens: (B,) tokens already
    cached; tokens: (B,) int32 current input tokens.
    Returns (next_tokens, logits, new_pool)."""
    return _decode_core(params, pool, block_tables, context_lens, tokens, cfg)


def _device_step_core(params, pool, block_tables, context_lens, tokens,
                      active, keys, sampling, cfg: ModelConfig,
                      axis_name=None, n_shards=1):
    """Body shared by the single-device and mesh-sharded device steps.

    Under the mesh (``axis_name`` set, ``n_shards > 1``, vocab divisible)
    the unembed is VOCAB-SHARDED: each shard matmuls only its (V/n, d)
    row slice of the head table and the greedy winner is combined from a
    tiny all-gathered (n, B) candidate pair — per-shard max value plus
    global argmax index — instead of every shard redundantly computing
    the full (B, V) logits.  ``jnp.argmax`` picks the FIRST maximum and
    shard order equals vocab order, so taking the lowest shard among
    value ties reproduces the replicated argmax bit-exactly.  Batches
    with any sampled row fall back (one ``lax.cond`` branch, same
    compiled variant) to all-gathering the full logits, which a tiled
    concat makes bit-identical to the replicated unembed."""
    x_last, new_pool = _decode_hidden(params, pool, block_tables,
                                      context_lens, tokens, cfg,
                                      axis_name=axis_name)
    head = _head(params, cfg)
    if axis_name is None or n_shards <= 1 \
            or cfg.vocab_size % n_shards != 0:
        with jax.named_scope("unembed"):
            logits = L.unembed(head, x_last)
        nxt = sample_tokens(logits, keys, context_lens, sampling)
    else:
        B = tokens.shape[0]
        Vs = cfg.vocab_size // n_shards
        shard = jax.lax.axis_index(axis_name)
        with jax.named_scope("unembed"):
            w_local = jax.lax.dynamic_slice_in_dim(
                head["table"], shard * Vs, Vs, axis=0)
            local = L.unembed(head, x_last, table=w_local)  # (B, V/n)
        vals = jnp.max(local, axis=-1)
        idxs = (jnp.argmax(local, axis=-1)
                + shard * Vs).astype(jnp.int32)
        all_vals = jax.lax.all_gather(vals, axis_name)     # (n, B)
        all_idxs = jax.lax.all_gather(idxs, axis_name)     # (n, B)
        best = jnp.argmax(all_vals, axis=0)                # first max wins
        greedy = jnp.take_along_axis(all_idxs, best[None, :], axis=0)[0]

        def _sampled(_):
            full = jax.lax.all_gather(local, axis_name, axis=1,
                                      tiled=True)          # (B, V)
            return sample_tokens(full, keys, context_lens, sampling)

        nxt = jax.lax.cond(jnp.any(sampling[:, 0] > 0.0), _sampled,
                           lambda _: greedy, None)
    new_ctx = jnp.where(active, context_lens + 1, context_lens)
    new_tok = jnp.where(active, nxt, tokens)
    return nxt, new_pool, new_ctx, new_tok


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnums=(1, 3, 4))
def paged_decode_step_device(params, pool, block_tables, context_lens,
                             tokens, active, keys, sampling, *,
                             cfg: ModelConfig):
    """Device-resident variant for the DecodeRunner: pool, context_lens
    and tokens are DONATED and threaded step to step without host
    round-trips.  ``active``: (B,) bool — rows decoding this step.
    Inactive rows keep their state and their (masked, trash-directed)
    compute is discarded.  ``keys``: (B, 2) uint32 per-row POSITION-
    INDEPENDENT base PRNG keys (the step folds the position in — see
    ``sample_tokens``); ``sampling``: (B, 3) f32 per-row traced
    [temperature, top_k, top_p] (temperature 0 is greedy).
    Returns (next_tokens, new_pool, new_ctx, new_tokens)."""
    return _device_step_core(params, pool, block_tables, context_lens,
                             tokens, active, keys, sampling, cfg)


def shard_local_config(cfg: ModelConfig, n_shards: int) -> ModelConfig:
    """The per-shard view of ``cfg`` under ``n_shards``-way tensor
    parallelism over heads: q and kv head counts divide by the shard
    count (GQA grouping preserved); ``head_dim`` is pinned to the
    resolved value so the division never changes it."""
    import dataclasses
    if n_shards == 1:
        return cfg
    assert shardable_heads(cfg, n_shards), (cfg.name, n_shards)
    return dataclasses.replace(
        cfg, n_heads=cfg.n_heads // n_shards,
        n_kv_heads=cfg.n_kv_heads // n_shards,
        head_dim=cfg.resolved_head_dim)


def shardable_heads(cfg: ModelConfig, n_shards: int) -> bool:
    """True when ``cfg``'s heads divide evenly over ``n_shards`` model-
    parallel shards (the head-sharded serving layout's precondition)."""
    return (n_shards >= 1 and cfg.n_heads % n_shards == 0
            and cfg.n_kv_heads % n_shards == 0)


def _sharded_device_step(params, pool, block_tables, context_lens,
                         tokens, active, keys, sampling, *,
                         cfg: ModelConfig, mesh):
    """Mesh-sharded decode step: tensor-parallel over the ``"model"``
    axis with the KV pool head-sharded (DESIGN.md §9).  Per-shard
    compute covers that shard's heads only; head outputs are
    all-gathered (pure concat) before the replicated ``wo`` and the MLP
    runs replicated, while the unembed is VOCAB-SHARDED with a tiny
    per-shard greedy-candidate gather (see ``_device_step_core``) — no
    float reduction ever crosses shards, so the token stream is
    bit-identical to the single-device step (mesh (1,1) degenerates to
    it exactly).
    """
    from repro.models.sharding import (pool_pspec, rep_pspec,
                                       serving_param_pspecs)
    n = mesh.shape["model"]
    local_cfg = shard_local_config(cfg, n)
    body = functools.partial(_device_step_core, cfg=local_cfg,
                             axis_name="model", n_shards=n)
    rep = rep_pspec()
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(serving_param_pspecs(params), pool_pspec(), rep, rep,
                  rep, rep, rep, rep),
        out_specs=(rep, pool_pspec(), rep, rep),
        check_vma=False,       # pallas_call has no replication rule
    )(params, pool, block_tables, context_lens, tokens, active, keys,
      sampling)


# the jitted sharded step: donation and static-arg layout mirror
# ``paged_decode_step_device`` exactly (fslint FS001/FS002 see through
# the ``jax.jit(shard_map-wrapping-fn)`` assignment form)
paged_decode_step_device_sharded = jax.jit(
    _sharded_device_step, static_argnames=("cfg", "mesh"),
    donate_argnums=(1, 3, 4))


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill_kv(params, tokens, *, cfg: ModelConfig):
    """Full-context prefill returning per-layer K/V for pool insertion.
    tokens: (1, T).  Returns (last_logits (V,), k, v: (L, T, Hkv, D)).

    Exact-shape legacy path (one compiled variant per prompt length);
    the engine's runner prefills through the bucketed chunked forward
    (``prefill_kv_chunk``) instead — this survives as the parity
    reference and for one-shot tools."""
    from repro.models import transformer as T
    logits, caches, _ = T.forward_seq(params, cfg, tokens, remat=False)
    k, v = caches                                          # (L, 1, T, H, D)
    return logits[0, -1], k[:, 0], v[:, 0]


def _prefill_chunk_core(params, tokens, k_carry, v_carry, prefix_len,
                        chunk_len, cfg: ModelConfig, axis_name=None):
    """Body shared by the single-device and mesh-sharded chunk forwards
    (``axis_name`` semantics as in ``_decode_core``: local heads +
    head-concat all-gather before the replicated ``wo``)."""
    assert supports_paged(cfg), cfg.name
    B, C_pad = tokens.shape
    S_pad = k_carry.shape[1]
    x = L.embed(params["embed"], tokens)                   # (1, C_pad, d)
    positions = prefix_len + jnp.arange(C_pad)[None, :]
    scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
    use_moe = cfg.moe is not None
    # query i (absolute position prefix_len + i) attends keys [0, abs_i]
    mask = (jnp.arange(S_pad)[None, :]
            <= positions[0][:, None])[None, None]          # (1,1,C_pad,S_pad)

    def body(x, xs):
        lp, kc, vc = xs                                    # kc: (S_pad, H, D)
        h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q, k, v = attn._project_qkv(lp["attn"], h, cfg)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice(kc, k[0], (prefix_len, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v[0], (prefix_len, 0, 0))
        a = attn._sdpa(q, kc[None], vc[None], mask, scale)
        if axis_name is not None:
            a = jax.lax.all_gather(a, axis_name, axis=2, tiled=True)
        x = x + (a.reshape(B, C_pad, -1) @ lp["attn"]["wo"].astype(x.dtype))
        h = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        if use_moe:
            f, _ = moe_mod.moe_forward(lp["ffn"], h, cfg)
        else:
            f = L.swiglu(lp["ffn"], h)
        return x + f, (kc, vc)

    with jax.named_scope("prefill_forward"):
        x, (k_carry, v_carry) = jax.lax.scan(
            body, x, (params["layers"], k_carry, v_carry))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    # unembed ONLY the last real position (row-wise matmul is bitwise
    # independent of the batch of rows, so this equals slicing the full
    # (C_pad, V) logits at (C_pad - 1)x the flops)
    x_last = jax.lax.dynamic_index_in_dim(x[0], chunk_len - 1, axis=0,
                                          keepdims=True)  # (1, d)
    with jax.named_scope("unembed"):
        logits = L.unembed(_head(params, cfg), x_last)[0]
    return logits, k_carry, v_carry


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnums=(2, 3))
def prefill_kv_chunk(params, tokens, k_carry, v_carry, prefix_len,
                     chunk_len, *, cfg: ModelConfig):
    """One chunk of a position-masked chunked prefill (DESIGN.md §5).

    The chunk's K/V is written into the per-layer carry buffers at
    ``prefix_len`` BEFORE attention runs, so every query attends one
    contiguous key buffer whose valid keys occupy exactly positions
    ``[0, prefix_len + q_rel]`` — the same masked-tail layout the
    monolithic causal forward sees.  Masked keys contribute exactly-zero
    probability terms, which keeps the chunked forward BIT-EXACT with
    the monolithic ``prefill_kv`` for any chunking (asserted by
    tests/test_chunked_prefill.py); greedy decode parity therefore
    survives the chunked admission path unchanged.

    tokens: (1, C_pad) int32 — chunk tokens, zero-padded to the pow2
      chunk bucket (pad positions are masked: no real query attends a
      key at position >= prefix_len + chunk_len);
    k_carry, v_carry: (L, S_pad, Hkv, D) — DONATED carry buffers sized
      by the caller to S_pad >= prefix_len + C_pad (pow2-bucketed);
      rows [0, prefix_len) hold the previous chunks' K/V;
    prefix_len, chunk_len: traced i32 scalars — real tokens already in
      the carry / real tokens in this chunk.

    Returns (last_logits (V,) — position prefix_len + chunk_len - 1,
    k_carry', v_carry').  Every unique (C_pad, S_pad) pair is one XLA
    compilation: O(log^2 max_len) variants over any mix of prompt
    lengths and chunk sizes (the ``kernels.ops.prefill_chunk`` wrapper
    owns the bucketing)."""
    return _prefill_chunk_core(params, tokens, k_carry, v_carry,
                               prefix_len, chunk_len, cfg)


def _sharded_prefill_chunk(params, tokens, k_carry, v_carry, prefix_len,
                           chunk_len, *, cfg: ModelConfig, mesh):
    """Mesh-sharded chunk forward (DESIGN.md §9): the carries are
    head-sharded over ``"model"``, per-shard attention covers local
    heads only, and the head-concat all-gather before the replicated
    ``wo`` keeps the logits bit-identical to ``prefill_kv_chunk``."""
    from repro.models.sharding import (carry_pspec, rep_pspec,
                                       serving_param_pspecs)
    local_cfg = shard_local_config(cfg, mesh.shape["model"])
    body = functools.partial(_prefill_chunk_core, cfg=local_cfg,
                             axis_name="model")
    rep = rep_pspec()
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(serving_param_pspecs(params), rep, carry_pspec(),
                  carry_pspec(), rep, rep),
        out_specs=(rep, carry_pspec(), carry_pspec()),
        check_vma=False,
    )(params, tokens, k_carry, v_carry, prefix_len, chunk_len)


prefill_kv_chunk_sharded = jax.jit(
    _sharded_prefill_chunk, static_argnames=("cfg", "mesh"),
    donate_argnums=(2, 3))
