"""Kernel micro-benchmarks (interpret-mode wall time is NOT TPU time —
these measure call overhead and validate the grouped-copy op-count
advantage; the structural perf story lives in the roofline report)."""
import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv_line
from repro.kernels.ops import copy_block_runs, copy_blocks, paged_attention


def _time(fn, n=5):
    fn()                                     # compile
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n * 1e6


def main(emit=print):
    key = jax.random.PRNGKey(0)
    B, Hq, Hkv, D, bs, npages = 4, 8, 2, 64, 16, 8
    nb = 64
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    kp = jax.random.normal(ks[1], (nb, bs, Hkv, D), jnp.float32)
    vp = jax.random.normal(ks[2], (nb, bs, Hkv, D), jnp.float32)
    bt = jax.random.permutation(ks[3], nb)[:B * npages].reshape(B, npages)
    ctx = jnp.full((B,), npages * bs, jnp.int32)
    t = _time(lambda: paged_attention(q, jnp.stack([kp, vp])[None], 0,
                                      bt.astype(jnp.int32),
                                      ctx, D ** -0.5))
    emit(csv_line("kernel_paged_attention_interp", t,
                  f"B{B}xH{Hq}x{npages}pages"))

    src = jax.random.normal(key, (64, 2048), jnp.float32)
    dst = jnp.zeros((64, 2048), jnp.float32)
    si = jnp.arange(32, dtype=jnp.int32)
    di = jnp.arange(32, 64, dtype=jnp.int32)
    t_pb = _time(lambda: copy_blocks(src, dst, si, di))
    t_gr = _time(lambda: copy_block_runs(src, dst, [(0, 32)], [32]))
    emit(csv_line("kernel_block_copy_per_block", t_pb, "ops=32"))
    emit(csv_line("kernel_block_copy_grouped", t_gr,
                  f"ops=1 speed_ratio={t_pb / max(t_gr, 1e-9):.2f}x"))


if __name__ == "__main__":
    main()
