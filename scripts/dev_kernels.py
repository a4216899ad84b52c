import jax, jax.numpy as jnp, numpy as np
from repro.kernels.ref import paged_attention_ref, block_copy_ref
from repro.kernels.ops import copy_block_runs, copy_blocks, paged_attention

key = jax.random.PRNGKey(0)
B, Hq, Hkv, D, bs, nb, npages = 3, 8, 2, 64, 16, 32, 4
ks = jax.random.split(key, 5)
q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
kp = jax.random.normal(ks[1], (nb, bs, Hkv, D), jnp.float32)
vp = jax.random.normal(ks[2], (nb, bs, Hkv, D), jnp.float32)
bt = jax.random.permutation(ks[3], nb)[:B * npages].reshape(B, npages).astype(jnp.int32)
ctx = jnp.array([5, 33, 64], jnp.int32)
ref = paged_attention_ref(q, jnp.stack([kp, vp]), bt, ctx, 0.125)
out = paged_attention(q, jnp.stack([kp, vp])[None], 0, bt, ctx, 0.125)
print("paged_attention maxerr", float(jnp.max(jnp.abs(ref - out))))

# block copy
E = 128
src = jax.random.normal(ks[4], (16, E), jnp.float32)
dst = jnp.zeros((12, E), jnp.float32)
si = jnp.array([3, 7, 1], jnp.int32)
di = jnp.array([0, 5, 11], jnp.int32)
ref2 = block_copy_ref(src, dst, si, di)
try:
    out2 = copy_blocks(src, dst, si, di)
    print("block_copy maxerr", float(jnp.max(jnp.abs(ref2 - out2))))
except Exception as e:
    print("block_copy FAIL:", type(e).__name__, e)

# grouped
ref3 = dst
for s, d, l in [(0, 2, 2), (8, 6, 4)]:
    ref3 = ref3.at[d:d + l].set(src[s:s + l])
try:
    out3 = copy_block_runs(src, dst, [(0, 2), (8, 4)], [2, 6])
    print("block_copy_grouped maxerr", float(jnp.max(jnp.abs(ref3 - out3))))
except Exception as e:
    print("block_copy_grouped FAIL:", type(e).__name__, e)
