"""Paged KV cache pools (device + host) and block tables.

The GPU pool is a jnp array of shape (L, 2, num_blocks, block_size, Hkv, D)
(2 = K/V); the CPU pool is numpy with num_cpu_blocks storing the bf16 BIT
PATTERN as uint16 (half the host memory of a float32 store, and the d2h
leg needs no dtype conversion).  The serving engine moves whole blocks
between them through the swap channel; the model decode step reads the GPU
pool through a block table (see kernels/paged_attention).

Two data planes (DESIGN.md §4):
  * ``copy_out`` / ``copy_in`` — the host-mediated baseline (a blocking
    gather of the live pool, an un-donated full-pool ``.at[].set``); kept
    for parity tests and the swap_path benchmark baseline.
  * ``copy_out_staged`` / ``copy_in_staged`` — the engine's path: a
    grouped Pallas kernel stages a request's blocks into one contiguous
    device slab (one DMA chain per run), the slab crosses the PCIe/host
    link as a SINGLE transfer, and the swap-in scatter DONATES the pool
    (in-place write).  ``copy_in_staged`` rebinds ``self.gpu`` — the pool
    object is the owner-of-record; callers serialize under the engine's
    pool lock.

For trace-driven benchmarks the pools can be ``data=False`` (bookkeeping
only) so thousand-conversation runs stay fast.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops


@dataclass
class PoolSpec:
    n_layers: int
    n_kv_heads: int
    head_dim: int
    block_size: int          # tokens per block
    num_gpu_blocks: int
    num_cpu_blocks: int
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: ModelConfig, num_gpu_blocks: int,
                    num_cpu_blocks: int, block_size: int = 16) -> "PoolSpec":
        return cls(n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                   head_dim=cfg.resolved_head_dim, block_size=block_size,
                   num_gpu_blocks=num_gpu_blocks,
                   num_cpu_blocks=num_cpu_blocks)

    def block_bytes(self) -> int:
        """Bytes of ONE block across all layers and K+V (what one swap of
        one block moves)."""
        itemsize = 2 if self.dtype == "bfloat16" else 4
        return (self.n_layers * 2 * self.block_size * self.n_kv_heads
                * self.head_dim * itemsize)


class PagedPools:
    def __init__(self, spec: PoolSpec, with_data: bool = True, mesh=None,
                 stage_blocks: int = 16, device=None):
        """``device``: the JAX device the GPU pool lives on without a
        mesh (default: the default device); swap-in slabs are uploaded
        straight to it.

        ``mesh``: a ("data", "model") jax mesh — the GPU pool's KV
        head axis is then partitioned over ``model`` (NamedSharding,
        DESIGN.md §9) and every staged swap runs per shard: the slab
        stays head-sharded and the host link carries one transfer per
        chunk PER SHARD.  A 1-device mesh is normalized to None — the
        single-device data plane is byte-identical to the pre-mesh code
        (and the sharded path degrades to it bit-exactly).

        ``stage_blocks``: double-buffer granularity of ``copy_in_staged``
        — a swap-in larger than this many blocks is uploaded in
        stage-sized sub-slabs so the host gather + h2d of sub-slab k+1
        overlap the (async-dispatched, donated) scatter of sub-slab k.
        Each sub-slab counts as its own staged call in the transfer
        accounting.  <= 0 disables the split (one slab per call)."""
        self.spec = spec
        self.with_data = with_data
        self.stage_blocks = stage_blocks
        if mesh is not None and mesh.size == 1:
            mesh = None
        self.mesh = mesh
        self.device = device
        self.n_shards = 1 if mesh is None else int(mesh.shape["model"])
        if mesh is not None:
            assert spec.n_kv_heads % self.n_shards == 0, (
                spec.n_kv_heads, self.n_shards)
        # host-transfer accounting (asserted by the per-shard swap tests;
        # each count is one device<->host hop — a sharded slab moves
        # n_shards of them, each 1/n_shards the bytes)
        self.d2h_transfers = 0
        self.h2d_transfers = 0
        self.staged_out_calls = 0
        self.staged_in_calls = 0
        if with_data:
            s = spec
            self.gpu = jnp.zeros((s.n_layers, 2, s.num_gpu_blocks,
                                  s.block_size, s.n_kv_heads, s.head_dim),
                                 jnp.bfloat16, device=device)
            if mesh is not None:
                from repro.models.sharding import pool_pspec
                self.gpu = jax.device_put(
                    self.gpu, jax.sharding.NamedSharding(mesh, pool_pspec()))
            # bf16 bit pattern: uint16 halves host memory vs the old
            # float32 store and the staged d2h path copies bytes verbatim
            self.cpu = np.zeros((s.n_layers, 2, s.num_cpu_blocks,
                                 s.block_size, s.n_kv_heads, s.head_dim),
                                np.uint16)
        else:
            self.gpu = None
            self.cpu = None

    def cpu_bf16(self) -> np.ndarray:
        """The host pool reinterpreted as bfloat16 (zero-copy view)."""
        return self.cpu.view(jnp.bfloat16)

    # -- baseline data plane (parity tests, swap_path benchmark) ----------

    def copy_out(self, gpu_blocks: List[int], cpu_blocks: List[int]) -> None:
        """GPU -> CPU block copy (d2h) — host-mediated baseline: one
        blocking gather of the live pool per call."""
        if not self.with_data:
            return
        g = np.asarray(self.gpu[:, :, np.asarray(gpu_blocks)])
        self.cpu[:, :, np.asarray(cpu_blocks)] = g.view(np.uint16)

    def copy_in(self, cpu_blocks: List[int], gpu_blocks: List[int]) -> None:
        """CPU -> GPU block copy (h2d), routed through the staged
        donating path.  This used to be an un-donated whole-pool
        ``.at[].set`` (fslint FS006); the staged route is bit-exact and
        writes in place.  Order-preserving run coalescing keeps the
        positional cpu<->gpu block pairing of the flat-list API."""
        if not self.with_data or not gpu_blocks:
            return
        runs: List[Tuple[int, int]] = []
        for b in gpu_blocks:
            if runs and b == runs[-1][0] + runs[-1][1]:
                runs[-1] = (runs[-1][0], runs[-1][1] + 1)
            else:
                runs.append((b, 1))
        self.copy_in_staged(cpu_blocks, runs)

    # -- staged data plane (the engine's swap path, DESIGN.md §4) ---------

    def copy_out_staged(self, gpu_runs: Sequence[Tuple[int, int]],
                        cpu_blocks: List[int], *, deferred: bool = False
                        ) -> Optional[Callable[[], None]]:
        """GPU -> CPU via the device staging slab: one grouped gather
        kernel coalesces ``gpu_runs`` [(start, n)] into a contiguous
        slab, then ONE d2h transfer moves the slab; the host side is a
        single vectorized store of the bf16 bit pattern.

        ``deferred``: wait only for the gather, the one part that reads
        the pool, and return the rest (the transfer and the store) for
        the caller to run, which may do so without the pool lock."""
        if not self.with_data or not gpu_runs:
            return None
        slab, total = ops.gather_swap_runs(self.gpu, gpu_runs,
                                           mesh=self.mesh)
        assert total == len(cpu_blocks), (total, len(cpu_blocks))
        self.staged_out_calls += 1
        self.d2h_transfers += len(slab.sharding.device_set)

        def store() -> None:
            # ONE d2h per shard of the whole pow2 slab (head-sharded under
            # a mesh), trimmed to ``total`` on the host: a slice on the
            # device would build a program for every block count new to
            # the process
            host = np.asarray(slab)[:, :total]
            s = self.spec
            self.cpu[:, :, np.asarray(cpu_blocks)] = \
                host.view(np.uint16).reshape(s.n_layers, 2, total,
                                             s.block_size, s.n_kv_heads,
                                             s.head_dim)
        if not deferred:
            store()
            return None
        slab.block_until_ready()
        return store

    def copy_in_staged(self, cpu_blocks: List[int],
                       gpu_runs: Sequence[Tuple[int, int]],
                       stage_blocks: Optional[int] = None) -> None:
        """CPU -> GPU via the host staging slab: a vectorized host
        gather, ONE h2d transfer per sub-slab, then a grouped scatter
        kernel with the pool DONATED (in-place write, never a full-pool
        copy).  REBINDS ``self.gpu`` — the pools object is the pool's
        owner-of-record; callers must hold the engine's pool lock.

        Double buffering: a call larger than ``stage_blocks`` (ctor
        default) is split into stage-sized sub-slabs.  The scatter of
        sub-slab k dispatches asynchronously (JAX async dispatch; the
        donation chain sequences it after sub-slab k-1's), so sub-slab
        k+1's host gather and upload run WHILE k scatters — the h2d leg
        and the device-side scatter pipeline instead of serializing.
        Each sub-slab counts as its own staged call, preserving the
        transfer-accounting invariant ``h2d_transfers == n_shards *
        staged_in_calls``.  One ``block_until_ready`` at the end keeps
        the residency contract of the single-slab path."""
        if not self.with_data or not gpu_runs:
            return
        s = self.spec
        total = sum(n for _, n in gpu_runs)
        assert total == len(cpu_blocks), (total, len(cpu_blocks))
        stage = self.stage_blocks if stage_blocks is None else stage_blocks
        if stage <= 0 or total <= stage:
            stages: List[List[Tuple[int, int]]] = [list(gpu_runs)]
        else:
            from repro.kernels.block_copy import split_runs
            stages = split_runs(gpu_runs, stage)
        C = s.n_layers * 2
        if self.mesh is not None:
            from repro.models.sharding import slab_pspec
            sharding = jax.sharding.NamedSharding(self.mesh, slab_pspec())
        pos = 0
        for runs_c in stages:
            cnt = sum(n for _, n in runs_c)
            # zeros, not empty: the pow2 pad tail is masked off by the
            # run lengths, but it IS uploaded and streamed through the
            # kernel — uninitialized bytes decode to NaN/denormal bf16,
            # which measurably slows the copy (earns nothing: one memset)
            slab = np.zeros((C, ops.slab_bucket_blocks(cnt), s.block_size,
                             s.n_kv_heads, s.head_dim), np.uint16)
            slab[:, :cnt] = self.cpu[
                :, :, np.asarray(cpu_blocks[pos:pos + cnt])].reshape(
                C, cnt, s.block_size, s.n_kv_heads, s.head_dim)
            pos += cnt
            # ONE h2d per shard (bucketed slab; head-sharded under a mesh)
            if self.mesh is None:
                dev = jax.device_put(slab.view(jnp.bfloat16), self.device)
            else:
                dev = jax.device_put(slab.view(jnp.bfloat16), sharding)
            self.staged_in_calls += 1
            self.h2d_transfers += len(dev.sharding.device_set)
            self.gpu = ops.scatter_swap_runs(self.gpu, dev, runs_c,
                                             mesh=self.mesh)
        # Materialize before the caller releases the pool lock: a swap
        # task's future completing must mean THE DATA IS RESIDENT
        # (step-1 promotes on it).  A lazy donated scatter escaping the
        # lock both outlives the locals backing its host staging slab
        # and interleaves with the decode thread's donating dispatches
        # on the same pool chain — observed torn KV under storm
        # preemption (CPU donation is in-place).  The wait costs
        # worker-thread time only — never simulated time.
        jax.block_until_ready(self.gpu)

    def write_tokens(self, block_ids: List[int], token_offset: int,
                     k: np.ndarray, v: np.ndarray) -> None:
        """Write per-layer K/V for contiguous tokens into the paged GPU pool.
        k, v: (L, T, Hkv, D); token_offset = index of first token in request.

        Host-side data-plane utility (tools/tests/parity baselines): the
        engine's prefill path now inserts KV on device through the
        DecodeRunner (``kernels.ops.insert_prefill``, DESIGN.md §3.5)."""
        if not self.with_data:
            return
        bs = self.spec.block_size
        k = np.asarray(k)
        v = np.asarray(v)
        T = k.shape[1]
        if T == 0:
            return
        if token_offset % bs == 0:
            # fused path (the engine always writes block-aligned): one
            # scatter for all touched blocks instead of 2 updates each.
            # The zero-padded tail of a partial last block lies beyond the
            # context length — masked by attention and overwritten by the
            # decode step before it ever becomes visible.
            L, _, H, D = k.shape
            nblk = (T + bs - 1) // bs
            pad = nblk * bs - T
            if pad:
                pw = ((0, 0), (0, pad), (0, 0), (0, 0))
                k = np.pad(k, pw)
                v = np.pad(v, pw)
            b0 = token_offset // bs
            blocks = np.asarray(block_ids[b0:b0 + nblk])
            kv = np.stack([k, v], axis=1).reshape(L, 2, nblk, bs, H, D)
            # fslint: disable=FS006(host-side tool/test utility, not on the serving path)
            self.gpu = self.gpu.at[:, :, blocks].set(
                jnp.asarray(kv, jnp.bfloat16))
            return
        gpu = self.gpu
        for t0 in range(0, T, bs):
            t1 = min(t0 + bs, T)
            tok = token_offset + t0
            blk = block_ids[tok // bs]
            off = tok % bs
            # fslint: disable=FS006(host-side tool/test utility, not on the serving path)
            gpu = gpu.at[:, 0, blk, off:off + (t1 - t0)].set(
                jnp.asarray(k[:, t0:t1], jnp.bfloat16))
            # fslint: disable=FS006(host-side tool/test utility, not on the serving path)
            gpu = gpu.at[:, 1, blk, off:off + (t1 - t0)].set(
                jnp.asarray(v[:, t0:t1], jnp.bfloat16))
        self.gpu = gpu

    def read_tokens(self, block_ids: List[int], n_tokens: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Gather (L, T, Hkv, D) K and V for the first n_tokens of a request."""
        assert self.with_data
        bs = self.spec.block_size
        n_blocks = (n_tokens + bs - 1) // bs
        blocks = np.asarray(block_ids[:n_blocks])
        g = np.asarray(self.gpu[:, :, blocks])      # (L, 2, nb, bs, H, D)
        L, _, nb, _, H, D = g.shape
        flat = g.reshape(L, 2, nb * bs, H, D)[:, :, :n_tokens]
        return flat[:, 0], flat[:, 1]
