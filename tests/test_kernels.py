"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, in the mode
the backend selects (the interpreter on the CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import (copy_block_runs, copy_blocks, flash_attention,
                               gla_scan_scalar, interpret_mode,
                               paged_attention)
from repro.kernels.ref import block_copy_ref, mha_ref, paged_attention_ref


def test_backend_selects_interpret_mode():
    """The CPU backend runs the Pallas interpreter; a TPU would compile
    Mosaic kernels (``tests/test_tpu_compile.py`` compiles them for a
    described v5e)."""
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True


@pytest.mark.parametrize("B,Hq,Hkv,D,bs,npages", [
    (1, 4, 4, 64, 16, 2),      # MHA
    (3, 8, 2, 64, 16, 4),      # GQA group=4
    (2, 16, 16, 128, 16, 3),   # MHA wide head
    (2, 12, 2, 128, 32, 2),    # qwen2-like, bigger block
    (1, 8, 1, 64, 16, 8),      # MQA
    (2, 12, 2, 128, 16, 4),    # qwen2-1.5b widths (group 6, bs 16)
    (3, 8, 4, 128, 16, 5),     # Hkv 4, group 2
    (2, 32, 8, 128, 16, 3),    # Hkv 8, group 4
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, Hq, Hkv, D, bs, npages, dtype):
    key = jax.random.PRNGKey(42)
    nb = npages * B + 3
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kp = jax.random.normal(ks[1], (nb, bs, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (nb, bs, Hkv, D), dtype)
    bt = jax.random.permutation(ks[3], nb)[:B * npages] \
        .reshape(B, npages).astype(jnp.int32)
    # context lens including edge cases: 1 token, partial block, full
    lens = np.linspace(1, npages * bs, B).astype(np.int32)
    ctx = jnp.asarray(lens)
    scale = D ** -0.5
    kv = jnp.stack([kp, vp])
    out = paged_attention(q, kv[None], 0, bt, ctx, scale)
    ref = paged_attention_ref(q, kv, bt, ctx, scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("ppt", [1, 2, 4])
@pytest.mark.parametrize("npages", [3, 4, 5, 8])
@pytest.mark.parametrize("Hq,Hkv,D", [(8, 2, 64), (12, 2, 128)])
def test_paged_attention_multipage_tiles(ppt, npages, Hq, Hkv, D):
    """Tiles of ``ppt`` pages; npages not divisible by ppt exercises the
    ragged final tile."""
    B, bs = 2, 16
    nb = npages * B + 3
    ks = jax.random.split(jax.random.PRNGKey(42), 4)
    q = jax.random.normal(ks[0], (B, Hq, D))
    kp = jax.random.normal(ks[1], (nb, bs, Hkv, D))
    vp = jax.random.normal(ks[2], (nb, bs, Hkv, D))
    bt = jax.random.permutation(ks[3], nb)[:B * npages] \
        .reshape(B, npages).astype(jnp.int32)
    ctx = jnp.asarray(np.linspace(1, npages * bs, B).astype(np.int32))
    scale = D ** -0.5
    kv = jnp.stack([kp, vp])
    out = paged_attention(q, kv[None], 0, bt, ctx, scale, pages_per_tile=ppt)
    ref = paged_attention_ref(q, kv, bt, ctx, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-5, rtol=1e-5)


def _live_rows_case(Hkv, seed=7):
    """Rows as the decode step sends them: two padding rows (context 1,
    every page the trash page), a ragged last tile, a row that fills the
    bucket.  Returns the finite pools, a copy of them whose pages past
    each row's last live page hold NaN, and the call's arguments."""
    B, Hq, D, bs, npages = 4, 2 * Hkv, 128, 16, 8
    trash = 0
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    nb = 1 + 2 * npages
    q = jax.random.normal(ks[0], (B, Hq, D))
    kp = jax.random.normal(ks[1], (nb, bs, Hkv, D))
    vp = jax.random.normal(ks[2], (nb, bs, Hkv, D))
    pages = np.asarray(jax.random.permutation(ks[3], np.arange(1, nb)))
    ctx = np.asarray([1, 2 * bs + 5, npages * bs, 1], np.int32)
    bt = np.full((B, npages), trash, np.int32)
    bt[1, :] = pages[:npages]
    bt[2, :] = pages[npages:2 * npages]
    dead = np.zeros((nb,), bool)
    for b in (1, 2):
        live = -(-int(ctx[b]) // bs)
        dead[bt[b, live:]] = True
    nan = jnp.where(jnp.asarray(dead)[:, None, None, None], jnp.nan, 1.0)
    return (kp, vp), (kp * nan, vp * nan), (q, jnp.asarray(bt),
                                           jnp.asarray(ctx), D ** -0.5)


@pytest.mark.parametrize("ppt", [1, 2, 4])
@pytest.mark.parametrize("Hkv", [1, 2])
def test_paged_attention_never_reads_dead_pages(Hkv, ppt):
    """Pages past each row's last live page hold NaN: the output still
    matches the reference on the finite pool, so no dead page is read."""
    (kp, vp), (kn, vn), (q, bt, ctx, scale) = _live_rows_case(Hkv)
    assert bool(jnp.any(jnp.isnan(kn)))
    # the poisoned pool as layer 1 of two, behind a finite layer 0
    pool = jnp.stack([jnp.stack([kp, vp]), jnp.stack([kn, vn])])
    out = paged_attention(q, pool, 1, bt, ctx, scale, pages_per_tile=ppt)
    ref = paged_attention_ref(q, jnp.stack([kp, vp]), bt, ctx, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("ppt", [None, 1, 4])
def test_paged_attention_padding_ragged_and_full_rows(ppt):
    """Padding rows (context 1 on the trash page), a ragged last tile and
    a row at the full bucket, at the tile the shapes choose and others."""
    (kp, vp), _, (q, bt, ctx, scale) = _live_rows_case(2)
    kv = jnp.stack([kp, vp])
    out = paged_attention(q, kv[None], 0, bt, ctx, scale, pages_per_tile=ppt)
    ref = paged_attention_ref(q, kv, bt, ctx, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-5, rtol=1e-5)


def test_paged_attention_zero_context_is_finite():
    q = jnp.ones((2, 4, 64))
    kp = jnp.ones((4, 16, 2, 64))
    vp = jnp.ones((4, 16, 2, 64))
    bt = jnp.zeros((2, 2), jnp.int32)
    ctx = jnp.array([0, 5], jnp.int32)
    out = paged_attention(q, jnp.stack([kp, vp])[None], 0, bt, ctx, 0.125)
    assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("n_src,n_dst,n_copy,E", [
    (8, 8, 3, 128), (16, 4, 4, 256), (32, 32, 32, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_copy_sweep(n_src, n_dst, n_copy, E, dtype):
    key = jax.random.PRNGKey(0)
    src = jax.random.normal(key, (n_src, E), dtype)
    dst = jnp.zeros((n_dst, E), dtype)
    rng = np.random.RandomState(1)
    si = jnp.asarray(rng.choice(n_src, n_copy, replace=False), jnp.int32)
    di = jnp.asarray(rng.choice(n_dst, n_copy, replace=False), jnp.int32)
    out = copy_blocks(src, dst, si, di)
    ref = block_copy_ref(src, dst, si, di)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("runs", [
    [(0, 4), (10, 2)],
    [(3, 1)],
    [(0, 8), (8, 8), (20, 4)],
    [(1, 7), (12, 5), (24, 3)],     # lengths cut into several DMAs
])
def test_block_copy_grouped_sweep(runs):
    key = jax.random.PRNGKey(7)
    src = jax.random.normal(key, (32, 96), jnp.float32)
    dst = jnp.zeros((40, 96), jnp.float32)
    dst_starts = []
    d = 1
    for _, n in runs:
        dst_starts.append(d)
        d += n + 1
    ref = dst
    for (s, n), ds in zip(runs, dst_starts):
        ref = ref.at[ds:ds + n].set(src[s:s + n])
    out = copy_block_runs(src, dst, runs, dst_starts)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("B,H,T,D,bq,bk", [
    (1, 2, 128, 64, 64, 64),
    (2, 4, 256, 64, 128, 64),
    (1, 1, 512, 128, 128, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, T, D, bq, bk, causal, dtype):
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), dtype)
               for kk in jax.random.split(key, 3))
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = mha_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                  v.transpose(0, 2, 1, 3), causal=causal).transpose(0, 2, 1, 3)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# Pallas chunked GLA (Mamba2/SSD scalar decay)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,T,N,P,chunk", [
    (1, 1, 64, 8, 8, 16),
    (2, 3, 128, 16, 32, 32),
    (1, 2, 96, 32, 16, 32),      # T not a chunk multiple of 64
    (2, 1, 64, 64, 64, 64),      # one chunk == T
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gla_scan_scalar_sweep(B, H, T, N, P, chunk, dtype):
    from repro.models.gla import gla_scan_ref
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = (jax.random.normal(ks[0], (B, H, T, N)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, H, T, N)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (B, H, T, P)) * 0.5).astype(dtype)
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, T)) * 0.5 - 1.0)
    y, S = gla_scan_scalar(q, k, v, logw, chunk=chunk)
    ref, S_ref = gla_scan_ref(
        q, k, v, jnp.broadcast_to(logw[..., None], (B, H, T, N)),
        mode="mamba")
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref),
                               atol=tol, rtol=tol)
