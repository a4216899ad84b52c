"""Jit'd public wrappers around the Pallas kernels.

The backend decides how the kernels run (``interpret_mode``): the Pallas
interpreter on the CPU, compiled Mosaic kernels on a TPU.  The kernel
modules themselves take ``interpret`` as a required argument, so a
compile for a described (not attached) chip can ask for Mosaic while
the process's backend is the CPU.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import block_copy as _bc
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa

# backend -> are the Pallas kernels compiled by Mosaic (else interpreted)?
_MOSAIC_COMPILED = {"cpu": False, "tpu": True}


@functools.cache
def interpret_mode() -> bool:
    """True on the CPU (Pallas interpreter), False on a TPU (compiled
    Mosaic).  Any other backend has no kernels here and is an error —
    never a silent fallback to the interpreter."""
    backend = jax.default_backend()
    try:
        return not _MOSAIC_COMPILED[backend]
    except KeyError:
        raise RuntimeError(f"no Pallas kernels for backend {backend!r}; "
                           "run on a TPU, or on the CPU (interpret "
                           "mode)") from None


def paged_attention(q, kv_pool, layer, block_tables, context_lens, scale,
                    pages_per_tile=None):
    return _pa.paged_attention(q, kv_pool, layer, block_tables, context_lens,
                               scale, pages_per_tile=pages_per_tile,
                               interpret=interpret_mode())


def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128):
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret_mode())


def copy_blocks(src_pool, dst_pool, src_blocks, dst_blocks):
    """Per-block scattered copy (vLLM baseline data plane)."""
    return _bc.block_copy(src_pool, dst_pool,
                          jnp.asarray(src_blocks, jnp.int32),
                          jnp.asarray(dst_blocks, jnp.int32),
                          interpret=interpret_mode())


def copy_block_runs(src_pool, dst_pool, runs: Sequence[Tuple[int, int]],
                    dst_starts: Sequence[int]):
    """Grouped copy: runs[i]=(src_start, n_blocks) -> dst_starts[i]."""
    if not runs:
        return dst_pool
    src_starts = jnp.asarray([r[0] for r in runs], jnp.int32)
    lens = jnp.asarray([r[1] for r in runs], jnp.int32)
    dsts = jnp.asarray(list(dst_starts), jnp.int32)
    run_blocks = int(max(r[1] for r in runs))
    return _bc.block_copy_grouped(
        src_pool, dst_pool, src_starts, dsts, lens, run_blocks=run_blocks,
        interpret=interpret_mode())


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def slab_bucket_blocks(n_blocks: int) -> int:
    """Pow2 slab block count the staged swap kernels are bucketed to —
    the ONE place that defines it, so host-side staging buffers
    (``PagedPools.copy_in_staged``) can never diverge from the size the
    jitted scatter asserts against."""
    return _next_pow2(n_blocks)


def _pad_runs(runs: Sequence[Tuple[int, int]]
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int, int, int]:
    """Bucket one swap's runs for the jitted staged copies: pad the run
    list to a pow2 count (zero-length runs mask off), size the slab and
    the per-run grid extent to pow2s.  Returns (src_starts, slab_offsets,
    lens, n_runs_pad, n_slab, run_blocks) — O(log^2) compiled variants
    over any mix of swap shapes."""
    n_runs = _next_pow2(len(runs))
    src = np.zeros((n_runs,), np.int32)
    dst = np.zeros((n_runs,), np.int32)
    lens = np.zeros((n_runs,), np.int32)
    off = 0
    for i, (start, n) in enumerate(runs):
        src[i] = start
        dst[i] = off
        lens[i] = n
        off += n
    return (src, dst, lens, n_runs, _next_pow2(off),
            _next_pow2(int(max(n for _, n in runs))))


def _gather_swap_body(pool, src_starts, dst_starts, lens, *,
                      n_slab: int, run_blocks: int, interpret: bool):
    """Shared gather body: stages pool runs into a zeroed slab.  The slab
    keeps the (bs, H, D) block element axes SEPARATE (5-D) so the head
    axis survives as the shard axis under the mesh layout — each shard
    copies its local heads."""
    L, K, nb, bs, H, D = pool.shape
    with jax.named_scope("swap_gather"):
        slab0 = jnp.zeros((L * K, n_slab, bs, H, D), pool.dtype)
        return _bc.copy_runs(pool.reshape(L * K, nb, bs, H, D), slab0,
                             src_starts, dst_starts, lens,
                             run_blocks=run_blocks, interpret=interpret)


def _scatter_swap_body(pool, slab, src_starts, dst_starts, lens, *,
                       run_blocks: int, interpret: bool):
    L, K, nb, bs, H, D = pool.shape
    with jax.named_scope("swap_scatter"):
        p5 = _bc.copy_runs(slab, pool.reshape(L * K, nb, bs, H, D),
                           src_starts, dst_starts, lens,
                           run_blocks=run_blocks, interpret=interpret)
        return p5.reshape(pool.shape)


_gather_swap = jax.jit(_gather_swap_body,
                       static_argnames=("n_slab", "run_blocks", "interpret"))

_scatter_swap = jax.jit(_scatter_swap_body,
                        static_argnames=("run_blocks", "interpret"),
                        donate_argnums=(0,))


def _gather_swap_sharded_impl(pool, src_starts, dst_starts, lens, *,
                              n_slab: int, run_blocks: int, interpret: bool,
                              mesh):
    """Per-shard staged gather (DESIGN.md §9): the pool's head axis is
    partitioned over ``model``; every shard runs the SAME run-coalesced
    kernel over its local heads, producing a head-sharded slab — the d2h
    leg is then one transfer per shard, each 1/M the single-device
    bytes."""
    from repro.models.sharding import pool_pspec, rep_pspec, slab_pspec
    body = functools.partial(_gather_swap_body, n_slab=n_slab,
                             run_blocks=run_blocks, interpret=interpret)
    rep = rep_pspec()
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(pool_pspec(), rep, rep, rep),
                         out_specs=slab_pspec(),
                         check_vma=False)(pool, src_starts, dst_starts, lens)


def _scatter_swap_sharded_impl(pool, slab, src_starts, dst_starts, lens, *,
                               run_blocks: int, interpret: bool, mesh):
    from repro.models.sharding import pool_pspec, rep_pspec, slab_pspec
    body = functools.partial(_scatter_swap_body, run_blocks=run_blocks,
                             interpret=interpret)
    rep = rep_pspec()
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(pool_pspec(), slab_pspec(), rep, rep, rep),
                         out_specs=pool_pspec(),
                         check_vma=False)(pool, slab, src_starts, dst_starts,
                                          lens)


# jitted sharded variants: same donation / bucketing contract as the
# single-device pair (mesh is static — one variant per (mesh, buckets))
_gather_swap_sharded = jax.jit(
    _gather_swap_sharded_impl,
    static_argnames=("n_slab", "run_blocks", "interpret", "mesh"))

_scatter_swap_sharded = jax.jit(
    _scatter_swap_sharded_impl,
    static_argnames=("run_blocks", "interpret", "mesh"),
    donate_argnums=(0,))


def gather_swap_runs(pool, runs: Sequence[Tuple[int, int]], mesh=None):
    """Run-coalesced staged swap-out gather: copy the pool blocks named by
    ``runs`` [(start, n_blocks)] into one contiguous device staging slab
    (one grouped kernel over runs), so the d2h leg is a SINGLE transfer
    of the slab instead of N scattered per-block copies.

    pool: (L, 2, nb, bs, Hkv, D) — read only (not donated; the gather
    never invalidates the live pool).  Returns (slab, n_blocks) where
    slab is (L*2, n_slab_pow2, bs, Hkv, D); blocks [n_blocks:] are
    padding.  All shapes are pow2-bucketed so the jit cache stays
    O(log^2).  With ``mesh`` the gather runs per shard under
    ``shard_map`` and the slab comes back head-sharded (one host
    transfer per shard)."""
    assert runs, "gather_swap_runs needs at least one run"
    src, dst, lens, _, n_slab, run_blocks = _pad_runs(runs)
    fn = _gather_swap if mesh is None else functools.partial(
        _gather_swap_sharded, mesh=mesh)
    slab = fn(pool, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(lens),
              n_slab=n_slab, run_blocks=run_blocks,
              interpret=interpret_mode())
    return slab, int(sum(n for _, n in runs))


def scatter_swap_runs(pool, slab, runs: Sequence[Tuple[int, int]],
                      mesh=None):
    """Run-coalesced staged swap-in scatter: copy slab blocks [0, total)
    into the pool blocks named by ``runs``.  pool is DONATED — the write
    is in place and the caller MUST rebind its reference to the returned
    array (owner-of-record protocol, DESIGN.md §4.2).  slab: (L*2,
    n_slab_pow2, bs, Hkv, D) as produced by the host staging path —
    head-sharded under ``mesh``, where each shard scatters its local
    heads in place."""
    assert runs, "scatter_swap_runs needs at least one run"
    src, dst, lens, _, n_slab, run_blocks = _pad_runs(runs)
    assert slab.shape[1] == n_slab, (slab.shape, n_slab)
    fn = _scatter_swap if mesh is None else functools.partial(
        _scatter_swap_sharded, mesh=mesh)
    # gather offsets are the slab side here: slab[dst] -> pool[src]
    return fn(pool, slab, jnp.asarray(dst), jnp.asarray(src),
              jnp.asarray(lens), run_blocks=run_blocks,
              interpret=interpret_mode())


def swap_gather_cache_size() -> int:
    """Compiled-variant count of the staged gather, single-device and
    sharded variants combined (bucketing metric)."""
    return int(_gather_swap._cache_size()
               + _gather_swap_sharded._cache_size())


def swap_scatter_cache_size() -> int:
    """Compiled-variant count of the staged scatter, single-device and
    sharded variants combined (bucketing metric)."""
    return int(_scatter_swap._cache_size()
               + _scatter_swap_sharded._cache_size())


@functools.partial(jax.jit, static_argnames=("block_size",),
                   donate_argnums=(0,))
def _insert_prefill(pool, k, v, blocks, *, block_size: int):
    L, T, H, D = k.shape
    P = T // block_size
    with jax.named_scope("prefill_insert"):
        kv = jnp.stack([k, v], axis=1).reshape(L, 2, P, block_size, H, D)
        return pool.at[:, :, blocks].set(kv.astype(pool.dtype))


def insert_prefill(pool, k, v, blocks, block_size: int):
    """Scatter block-aligned prefill K/V into the paged pool through a
    block table row — the runner-managed replacement for the host-side
    ``PagedPools.write_tokens`` path.

    pool: (L, 2, nb, bs, Hkv, D) — DONATED; the caller must rebind.
    k, v: (L, T_pad, Hkv, D) with T_pad == len(blocks) * block_size; the
    caller pads the token axis up to the page bucket (pad pages point at
    the trash block, the partial last real page is zero-padded — both
    regions sit beyond the context length and are masked by attention).
    blocks: (P,) int page ids, one per block_size tokens.
    """
    return _insert_prefill(pool, k, v, jnp.asarray(blocks, jnp.int32),
                           block_size=block_size)


def insert_prefill_cache_size() -> int:
    """Compiled-variant count of the prefill scatter (bucketing metric)."""
    return int(_insert_prefill._cache_size())


# ---------------------------------------------------------------------------
# bucketed chunked prefill (DESIGN.md §5)
# ---------------------------------------------------------------------------


def _zeros_carry(shape, params, mesh):
    """Zeroed prefill carry on the params' device, head-sharded when
    ``mesh`` is given."""
    if mesh is None:
        sharding = jax.tree.leaves(params)[0].sharding
    else:
        from jax.sharding import NamedSharding
        from repro.models.sharding import carry_pspec
        sharding = NamedSharding(mesh, carry_pspec())
    return jnp.zeros(shape, jnp.bfloat16, device=sharding)


@functools.partial(jax.jit, static_argnames=("n_new",))
def _grow_carry(carry, *, n_new: int):
    """Copy a (L, S_old, H, D) prefill carry into a longer zeroed buffer
    (pow2 token bucket).  Not donated: the output shape differs, so XLA
    could never reuse the old buffer anyway (it would only warn)."""
    with jax.named_scope("carry_grow"):
        return jax.lax.dynamic_update_slice(
            jnp.zeros(carry.shape[:1] + (n_new,) + carry.shape[2:],
                      carry.dtype),
            carry, (0, 0, 0, 0))


@functools.partial(jax.jit, static_argnames=("n",))
def _slice_tokens(kv, start, *, n: int):
    """(L, S_pad, H, D)[:, start:start+n] with a TRACED start so one
    compiled variant serves every chunk offset."""
    return jax.lax.dynamic_slice_in_dim(kv, start, n, axis=1)


def prefill_chunk(params, tokens: Sequence[int], k_carry, v_carry,
                  prefix_len: int, *, cfg, block_size: int, mesh=None):
    """Bucketed wrapper around ``models.paged.prefill_kv_chunk``: pad the
    chunk to a pow2 token bucket (>= one page so the pool insert stays
    block-aligned), grow the carry buffers to a pow2 bucket holding
    ``prefix_len + chunk_pad`` tokens, and run the position-masked chunk
    forward with the real lengths as TRACED scalars — every unique
    (chunk_bucket, carry_bucket) pair is ONE compiled variant, so any mix
    of prompt lengths and chunk sizes compiles O(log^2 max_len) variants
    (mirroring the swap-run wrappers above).

    ``k_carry``/``v_carry``: None to start a prefill, else the buffers
    returned by the previous chunk (DONATED — rebind).  Returns
    (last_logits, k_carry', v_carry', k_chunk, v_chunk) where k_chunk /
    v_chunk are (L, chunk_pad, Hkv, D) ready for ``insert_prefill``.
    With ``mesh`` the chunk forward runs head-sharded under ``shard_map``
    (``prefill_kv_chunk_sharded``) with head-sharded carries — bit-exact
    with the single-device path (DESIGN.md §9)."""
    from repro.models.paged import prefill_kv_chunk, prefill_kv_chunk_sharded
    n = len(tokens)
    assert n > 0, "prefill_chunk needs at least one token"
    c_pad = max(_next_pow2(n), block_size)
    toks = np.zeros((1, c_pad), np.int32)
    toks[0, :n] = tokens
    need = prefix_len + c_pad
    if k_carry is None:
        s_pad = _next_pow2(need)
        shape = (cfg.n_layers, s_pad, cfg.n_kv_heads, cfg.resolved_head_dim)
        k_carry = _zeros_carry(shape, params, mesh)
        v_carry = _zeros_carry(shape, params, mesh)
    elif k_carry.shape[1] < need:
        s_pad = _next_pow2(need)
        k_carry = _grow_carry(k_carry, n_new=s_pad)
        v_carry = _grow_carry(v_carry, n_new=s_pad)
    if mesh is None:
        logits, k_carry, v_carry = prefill_kv_chunk(
            params, jnp.asarray(toks), k_carry, v_carry,
            jnp.int32(prefix_len), jnp.int32(n), cfg=cfg)
    else:
        logits, k_carry, v_carry = prefill_kv_chunk_sharded(
            params, jnp.asarray(toks), k_carry, v_carry,
            jnp.int32(prefix_len), jnp.int32(n), cfg=cfg, mesh=mesh)
    start = jnp.int32(prefix_len)
    k_chunk = _slice_tokens(k_carry, start, n=c_pad)
    v_chunk = _slice_tokens(v_carry, start, n=c_pad)
    return logits, k_carry, v_carry, k_chunk, v_chunk


def prefill_chunk_cache_size() -> int:
    """Compiled-variant count of the chunked prefill forward, single-
    device and sharded variants combined (the bucketing metric asserted
    by the prompt-length-sweep test)."""
    from repro.models.paged import prefill_kv_chunk, prefill_kv_chunk_sharded
    return int(prefill_kv_chunk._cache_size()
               + prefill_kv_chunk_sharded._cache_size())


@jax.jit
def _seed_carry(pool, blocks):
    """Gather pool pages into contiguous (L, P_pad*bs, H, D) K/V carry
    buffers.  Specializes on (pool shape, P_pad) — pow2-padded pages,
    O(log) variants."""
    L, K, _, bs, H, D = pool.shape
    with jax.named_scope("carry_seed"):
        kv = pool[:, :, blocks]                 # (L, 2, P_pad, bs, H, D)
        kv = kv.reshape(L, K, blocks.shape[0] * bs, H, D)
        return kv[:, 0], kv[:, 1]


def seed_prefill_carry(pool, block_ids: Sequence[int], start_tokens: int,
                       *, trash: int):
    """Initialize a chunked prefill's carry from KV already RESIDENT in
    the pool — the reuse mechanism's restored prefix — so chunking can
    start at ``start_tokens`` instead of recomputing (and re-billing)
    the prefix.  Pool values are bit-identical to what recomputing would
    produce (DESIGN.md §5.1), so downstream chunks and the emitted
    tokens are unchanged.  ``start_tokens`` must be block-aligned; the
    gathered page list is pow2-padded with trash pages whose junk rows
    sit at positions >= start_tokens — overwritten by the chunk writes
    before any real query can attend them (same invariant as the chunk
    pad tail).  Returns (k_carry, v_carry)."""
    bs = pool.shape[3]
    assert start_tokens > 0 and start_tokens % bs == 0, start_tokens
    nblk = start_tokens // bs
    blocks = np.full((_next_pow2(nblk),), trash, np.int32)
    blocks[:nblk] = list(block_ids)[:nblk]
    return _seed_carry(pool, jnp.asarray(blocks))


def gla_scan_scalar(q, k, v, logw, *, chunk=64):
    """Chunked scalar-decay gated linear attention (Mamba2/SSD hot path)."""
    from repro.kernels import gla_scan as _gla
    return _gla.gla_scan_scalar(q, k, v, logw, chunk=chunk,
                                interpret=interpret_mode())
