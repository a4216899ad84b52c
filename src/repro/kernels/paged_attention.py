"""Pallas TPU paged-attention decode kernel.

One query token per sequence attends to a paged KV pool through a block
table (vLLM-style).  TPU adaptation: the block table is scalar-prefetched
so each KV page is DMA'd HBM->VMEM via the BlockSpec index_map (no gather
materialization); online softmax runs on (group x page-tile) tiles so the
MXU sees (group, D) x (D, tile_tokens) matmuls.

``pages_per_compute_block`` (ppcb) streams several KV pages per grid step:
one grid step DMAs ppcb pages (one BlockSpec operand per page, all
resolved through the prefetched block table) and reduces them as a single
(group, ppcb*bs) tile — fewer grid steps and bigger MXU tiles than the
one-page-per-step baseline.  A ragged final tile is padded with page 0 and
masked by the context length (padded token positions are always
>= context_len, so their logits are NEG_INF).

Grid: (B, n_tiles).  Each grid step fetches whole pages — block
``(1, bs, Hkv, D)``, whose two minor dims equal the pool's, as Mosaic's
tiling rule requires (a one-head ``(1, bs, 1, D)`` block is refused for
v5e) — and loops over the KV heads inside the kernel.  Accumulators live
in per-head VMEM scratch and the output is written on the last grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, ctx_ref, q_ref, *refs,
            bs: int, hkv: int, scale: float, n_tiles: int, ppcb: int):
    k_refs = refs[:ppcb]
    v_refs = refs[ppcb:2 * ppcb]
    o_ref = refs[2 * ppcb]
    m_ref, l_ref, acc_ref = refs[2 * ppcb + 1:2 * ppcb + 4]
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]
    tile = ppcb * bs
    token_ids = i * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    mask = token_ids < ctx                               # (1, tile)
    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32)             # (group, D)
        # ppcb pages fused into one (ppcb*bs, D) KV tile of head h
        k = jnp.concatenate([r[0, :, h, :] for r in k_refs],
                            axis=0).astype(jnp.float32)
        v = jnp.concatenate([r[0, :, h, :] for r in v_refs],
                            axis=0).astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[h][:, 0]                          # (group,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])                  # (group, tile)
        l_new = l_ref[h][:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[h] = acc_ref[h] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new[:, None]
        l_ref[h] = l_new[:, None]

    @pl.when(i == n_tiles - 1)
    def _finish():
        for h in range(hkv):
            l = jnp.maximum(l_ref[h], 1e-30)             # guard ctx == 0
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",
                                             "pages_per_compute_block",
                                             "interpret"))
def paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                    scale: float, pages_per_compute_block: int = 1, *,
                    interpret: bool) -> jnp.ndarray:
    """q: (B, Hq, D); k_pool/v_pool: (nb, bs, Hkv, D);
    block_tables: (B, n_pages) int32; context_lens: (B,) int32.
    ``pages_per_compute_block``: KV pages streamed per grid step.
    Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    nb, bs, Hkv, _ = k_pool.shape
    n_pages = block_tables.shape[1]
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, D)

    ppcb = max(1, min(pages_per_compute_block, n_pages))
    n_tiles = -(-n_pages // ppcb)
    pad = n_tiles * ppcb - n_pages
    if pad:
        # pad with page 0; padded positions are >= context_len so masked
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
    npp = n_tiles * ppcb
    flat_bt = block_tables.reshape(-1).astype(jnp.int32)

    def q_map(b, i, bt, ctx):
        return (b, 0, 0, 0)

    def kv_map(j):
        def index_map(b, i, bt, ctx):
            return (bt[b * npp + i * ppcb + j], 0, 0, 0)
        return index_map

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_tiles),
        in_specs=(
            [pl.BlockSpec((1, Hkv, group, D), q_map)]
            + [pl.BlockSpec((1, bs, Hkv, D), kv_map(j)) for j in range(ppcb)]
            + [pl.BlockSpec((1, bs, Hkv, D), kv_map(j)) for j in range(ppcb)]
        ),
        out_specs=pl.BlockSpec((1, Hkv, group, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, hkv=Hkv, scale=scale,
                          n_tiles=n_tiles, ppcb=ppcb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=interpret,
    )(flat_bt, context_lens.astype(jnp.int32), qg,
      *([k_pool] * ppcb), *([v_pool] * ppcb))
    return out.reshape(B, Hq, D)
