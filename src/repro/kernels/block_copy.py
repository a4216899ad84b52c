"""Pallas block-copy kernels — the swap data plane.

Every copy is a list of *runs*: ``src[:, s:s+n] -> dst[:, d:d+n]`` along
the block axis (axis 1) of two arrays with equal trailing dims.  One grid
step per run issues the run as HBM->HBM DMAs (``memory_space=pl.ANY`` +
``pltpu.make_async_copy``).  The run length ``n`` is a runtime scalar, so
the run is cut at its binary digits: at most ``log2(run_blocks) + 1``
DMAs of static power-of-two extents, each one contiguous descriptor —
fewer, larger transfers, the Dynamic Block Group Manager's point.  No
block passes through VMEM, so no tiling rule constrains the trailing
``(bs, Hkv, D)`` layout, and one shard's local heads under ``shard_map``
copy as they are.  Blocks outside the runs are never written: ``dst`` is
aliased into the output.

``block_copy`` (one block per index, the vLLM per-block baseline) and
``block_copy_grouped`` are the 2-D ``(nb, E)`` views of the same kernel.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _runs_kernel(src_ref, dst_ref, len_ref, s_hbm, d_hbm, o_hbm, sem, *,
                 nbits: int):
    del d_hbm                                    # aliased to o_hbm
    r = pl.program_id(0)
    n = len_ref[r]
    copies = []
    for k in range(nbits):
        # digit k of n covers blocks [off, off + 2^k), off = higher digits
        off = (n >> (k + 1)) << (k + 1)
        copies.append((k, pltpu.make_async_copy(
            s_hbm.at[:, pl.ds(src_ref[r] + off, 1 << k)],
            o_hbm.at[:, pl.ds(dst_ref[r] + off, 1 << k)], sem.at[k])))
    for k, c in copies:
        pl.when((n >> k) & 1 == 1)(c.start)
    for k, c in copies:
        pl.when((n >> k) & 1 == 1)(c.wait)


def copy_runs(src, dst, src_starts, dst_starts, run_lens, *,
              run_blocks: int, interpret: bool) -> jnp.ndarray:
    """Copy runs ``src[:, s:s+l] -> dst[:, d:d+l]`` and return the new
    ``dst`` (aliased: callers jit this with ``dst`` donated for an
    in-place write).  ``src``/``dst``: ``(C, nb, ...)`` with equal
    trailing dims; ``src_starts``/``dst_starts``/``run_lens``: ``(n_runs,)``
    int32 — zero-length runs copy nothing; ``run_blocks``: static bound
    on every run length.  NOT jitted here: the jitted (bucketed,
    donating) wrappers live in ``kernels/ops.py``."""
    nbits = max(1, run_blocks).bit_length()
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(src_starts.shape[0],),
        in_specs=[any_spec, any_spec],
        out_specs=any_spec,
        scratch_shapes=[pltpu.SemaphoreType.DMA((nbits,))],
    )
    return pl.pallas_call(
        functools.partial(_runs_kernel, nbits=nbits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst.shape, dst.dtype),
        input_output_aliases={4: 0},       # dst (5th operand) -> output
        interpret=interpret,
    )(src_starts.astype(jnp.int32), dst_starts.astype(jnp.int32),
      run_lens.astype(jnp.int32), src, dst)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_copy(src_pool, dst_pool, src_blocks, dst_blocks, *,
               interpret: bool) -> jnp.ndarray:
    """Copy src_pool[src_blocks[i]] -> dst_pool[dst_blocks[i]], one DMA
    per block.  src_pool: (nb_src, E); dst_pool: (nb_dst, E); indices:
    (n,) int32.  Returns the updated dst pool."""
    ones = jnp.ones(src_blocks.shape, jnp.int32)
    return copy_runs(src_pool[None], dst_pool[None], src_blocks, dst_blocks,
                     ones, run_blocks=1, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("run_blocks", "interpret"))
def block_copy_grouped(src_pool, dst_pool, src_starts, dst_starts, run_lens,
                       *, run_blocks: int, interpret: bool) -> jnp.ndarray:
    """Copy contiguous runs: src_pool[s:s+l] -> dst_pool[d:d+l] per run.
    src_pool: (nb_src, E); dst_pool: (nb_dst, E); ``run_blocks`` is the
    static max run extent."""
    return copy_runs(src_pool[None], dst_pool[None], src_starts, dst_starts,
                     run_lens, run_blocks=run_blocks, interpret=interpret)[0]


def runs_to_indices(runs: List[Tuple[int, int]]) -> List[int]:
    """Expand [(start, n)] runs to ONE flat per-block index list."""
    idx: List[int] = []
    for start, n in runs:
        idx.extend(range(start, start + n))
    return idx


def trim_runs(runs: List[Tuple[int, int]], n_blocks: int
              ) -> List[Tuple[int, int]]:
    """First ``n_blocks`` blocks of [(start, n)] runs (a partially backed
    transfer: the CPU copy may be shorter than the GPU allocation when
    contamination capped the reuse increment)."""
    out: List[Tuple[int, int]] = []
    for start, n in runs:
        if n_blocks <= 0:
            break
        take = min(n, n_blocks)
        out.append((start, take))
        n_blocks -= take
    return out


def split_runs(runs: List[Tuple[int, int]], chunk_blocks: int
               ) -> List[List[Tuple[int, int]]]:
    """Split [(start, n)] runs into chunks of <= chunk_blocks blocks each
    (a run crossing a chunk boundary is cut).  ``chunk_blocks <= 0``
    disables chunking.  The engine dispatches one swap task per chunk so
    a long transfer interleaves with decode steps instead of serializing
    behind the pool lock."""
    if chunk_blocks <= 0:
        return [list(runs)] if runs else []
    chunks: List[List[Tuple[int, int]]] = []
    cur: List[Tuple[int, int]] = []
    room = chunk_blocks
    for start, n in runs:
        while n > 0:
            take = min(n, room)
            cur.append((start, take))
            start += take
            n -= take
            room -= take
            if room == 0:
                chunks.append(cur)
                cur = []
                room = chunk_blocks
    if cur:
        chunks.append(cur)
    return chunks
