"""Pallas TPU paged-attention decode kernel.

One query token per sequence attends to a paged KV pool through a block
table (vLLM-style).  The work of a call follows each row's live context,
not the padded ``(batch, pages)`` bucket it is compiled for:

- The kernel reads the whole ``(L, 2, nb, bs, Hkv, D)`` pool where it
  lies in HBM, at the layer it is given; nothing slices or stages a
  layer first.  A page is one contiguous ``(bs, Hkv, D)`` block, fetched
  by one DMA through the scalar-prefetched block table: the pool keeps
  the layout the swap plane, prefill insert and head-sharded mesh share.
- The grid has one step per row.  Inside it a loop runs over the row's
  ``cdiv(ctx, tile)`` tiles of ``pages_per_tile`` pages, and each tile
  fetches only the pages that hold a live token: a page past the row's
  last live page is never read.
- Tiles are double-buffered: tile t+1's page copies (or the next row's
  first tile, on a row's last tile) start before tile t is computed.
- A tile is one pair of matmuls for all heads.  Its K and V are read as
  ``(tile * Hkv, D)`` rows, token-major with the KV heads interleaved as
  the page holds them, so no head is gathered out of the page; a query
  head's logit against another KV head's row is masked like a position
  ``>= ctx``.  Online softmax over ``(Hq, tile * Hkv)``; logits, ``p``,
  ``m``, ``l`` and the accumulator are float32.  A masked term is an
  exact zero: a buffer slot whose page is not fetched holds zeros or an
  earlier live page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
TILE_ROWS = 256         # K/V rows (tokens x KV heads) a tile holds


def tile_pages(block_size: int, kv_heads: int, n_pages: int) -> int:
    """Pages per tile: ``TILE_ROWS`` rows of K and V, at most the
    bucket's page count."""
    return max(1, min(n_pages, TILE_ROWS // (block_size * kv_heads)))


def _kernel(bt_ref, ctx_ref, layer_ref, q_ref, kv_hbm, o_ref,
            k_buf, v_buf, sems, state, m_ref, l_ref, acc_ref,
            *, ppt: int, bs: int, hkv: int, n_pages: int, scale: float):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    k_hbm = kv_hbm.at[layer_ref[0], 0]
    v_hbm = kv_hbm.at[layer_ref[0], 1]
    tile = ppt * bs
    hq, D = acc_ref.shape
    rows = tile * hkv

    def page_copies(row, t, slot):
        """(live, K copy, V copy) of each page slot of tile t of ``row``."""
        n_live = (ctx_ref[row] + bs - 1) // bs
        out = []
        for j in range(ppt):
            i = t * ppt + j
            page = bt_ref[row * n_pages + jnp.minimum(i, n_pages - 1)]
            out.append((i < n_live,
                        pltpu.make_async_copy(k_hbm.at[page],
                                              k_buf.at[slot, j],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[page],
                                              v_buf.at[slot, j],
                                              sems.at[1, slot])))
        return out

    def start(row, t, slot):
        for live, k_cp, v_cp in page_copies(row, t, slot):
            @pl.when(live)
            def _():
                k_cp.start()
                v_cp.start()

    def wait(row, t, slot):
        for live, k_cp, v_cp in page_copies(row, t, slot):
            @pl.when(live)
            def _():
                k_cp.wait()
                v_cp.wait()

    # state[0]: the slot that holds (or receives) this row's first tile;
    # state[1]: 1 when the previous row has already started that fetch
    @pl.when(b == 0)
    def _():
        state[0] = 0
        state[1] = 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    ctx = ctx_ref[b]
    nt = (ctx + tile - 1) // tile
    first_slot = state[0]

    @pl.when(jnp.logical_and(nt > 0, state[1] == 0))
    def _():
        start(b, 0, first_slot)

    state[1] = 0
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # row r of a tile is token r // hkv of KV head r % hkv; query head h
    # reads KV head h // group
    col = jax.lax.broadcasted_iota(jnp.int32, (hq, rows), 1)
    token = col // hkv
    own_head = (col % hkv == jax.lax.broadcasted_iota(
        jnp.int32, (hq, rows), 0) // (hq // hkv))
    q = q_ref[0]                                             # (Hq, D)

    def body(t, slot):
        nxt = 1 - slot

        @pl.when(t + 1 < nt)
        def _():
            start(b, t + 1, nxt)

        @pl.when(jnp.logical_and(t + 1 == nt, b + 1 < n_rows))
        def _():
            @pl.when(ctx_ref[b + 1] > 0)
            def _():
                start(b + 1, 0, nxt)
                state[1] = 1

        wait(b, t, slot)
        k = k_buf[slot].reshape(rows, D)
        v = v_buf[slot].reshape(rows, D)
        dt = jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(q.astype(dt), k.astype(dt),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        valid = jnp.logical_and(own_head, t * tile + token < ctx)
        s = jnp.where(valid, s * scale, NEG_INF)             # (Hq, rows)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return nxt

    state[0] = jax.lax.fori_loop(0, nt, body, first_slot)
    l = jnp.maximum(l_ref[...], 1e-30)                       # guard ctx == 0
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_tile",
                                             "interpret"))
def paged_attention(q, kv_pool, layer, block_tables, context_lens,
                    scale: float, *, pages_per_tile: int | None = None,
                    interpret: bool) -> jnp.ndarray:
    """q: (B, Hq, D); kv_pool: (L, 2, nb, bs, Hkv, D) (0 = K, 1 = V);
    layer: int32 scalar; block_tables: (B, n_pages) int32;
    context_lens: (B,) int32, each at most ``n_pages * bs``.
    ``pages_per_tile`` defaults to ``tile_pages(bs, Hkv, n_pages)``.
    Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    bs, Hkv = kv_pool.shape[3:5]
    n_pages = block_tables.shape[1]
    ppt = max(1, min(pages_per_tile or tile_pages(bs, Hkv, n_pages),
                     n_pages))
    if Hkv == 1:
        # Mosaic cannot slice a page out of a (..., bs, 1, D) pool
        kv_pool = kv_pool[..., 0, :]
    page = kv_pool.shape[3:]

    def row_map(b, bt, ctx, layer):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, Hq, D), row_map),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, Hq, D), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, ppt, *page), kv_pool.dtype),
            pltpu.VMEM((2, ppt, *page), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, ppt=ppt, bs=bs, hkv=Hkv,
                          n_pages=n_pages, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        # rows run in order: a row's last tile prefetches the next row's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32),
      context_lens.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, kv_pool)
