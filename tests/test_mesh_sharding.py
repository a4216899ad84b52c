"""Mesh-sharded serving (ISSUE 8): tensor-parallel KV pool + per-shard
staged swap plane must be BIT-IDENTICAL to the single-device engine.

The multi-device tests run in subprocesses because
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` must be set
before the first jax import (tests/conftest.py deliberately keeps the
main pytest process at 1 device — smoke tests and benches depend on
that).  Each subprocess runs BOTH mesh shapes so the comparison shares
one process's params/schedule exactly.
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_forced(code, n_devices=4, timeout=900):
    env = {**os.environ, "PYTHONPATH": SRC,
           "XLA_FLAGS":
           f"--xla_force_host_platform_device_count={n_devices}"}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


# ---------------------------------------------------------------------------
# mesh plumbing (single-device process)
# ---------------------------------------------------------------------------


def test_make_serving_mesh_identity_and_device_check():
    from repro.launch.mesh import make_serving_mesh
    assert make_serving_mesh((1, 1)) is None
    with pytest.raises(ValueError, match="devices"):
        make_serving_mesh((1, 64))


def test_sim_engine_ignores_mesh_shape():
    """Sim mode has no device data plane: mesh_shape must be accepted
    and produce byte-identical simulated runs."""
    from repro.core import EngineConfig, FastSwitchEngine
    from repro.data.priority import PriorityTrace
    from repro.data.sharegpt import Conversation, Turn

    def run(shape):
        convs = [Conversation(conv_id=i, arrival_s=0.05 * i,
                              turns=[Turn(40, 30), Turn(20, 20)],
                              think_time_s=0.3) for i in range(6)]
        cfg = EngineConfig(mode="sim", num_gpu_blocks=32,
                           num_cpu_blocks=256, max_running=3,
                           swap_chunk_blocks=2,
                           mesh_shape=shape).with_policy("fastswitch")
        eng = FastSwitchEngine(cfg, convs,
                               trace=PriorityTrace("random", 0.5, seed=5))
        eng.run(max_iterations=50_000)
        assert eng.done()
        # drop host wall-clock keys — everything simulated must match
        return {k: v for k, v in eng.metrics.summary().items()
                if "wall" not in k}

    assert run((1, 1)) == run((1, 4))


def test_shard_local_config_divides_heads():
    from repro.models.paged import shard_local_config, shardable_heads
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("llama3.2-3b")   # 4 q / 2 kv smoke heads
    assert shardable_heads(cfg, 1) and shardable_heads(cfg, 2)
    assert not shardable_heads(cfg, 4)      # 2 kv heads can't split 4-way
    loc = shard_local_config(cfg, 2)
    assert loc.n_heads == cfg.n_heads // 2
    assert loc.n_kv_heads == cfg.n_kv_heads // 2
    assert loc.resolved_head_dim == cfg.resolved_head_dim


# ---------------------------------------------------------------------------
# real-mode engine: 4-way mesh bit-parity under storm preemption + swap
# (ISSUE 8 acceptance) + per-shard transfer accounting + jit-cache bound
# ---------------------------------------------------------------------------

ENGINE_PARITY = """
import dataclasses
import jax
import numpy as np
from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.core import EngineConfig, FastSwitchEngine
from repro.core.decode_runner import DecodeRunner
from repro.data.priority import PriorityTrace
from repro.data.sharegpt import Conversation, Turn

assert len(jax.devices()) == 4, jax.devices()
# uniform 4-head config so the model axis can split 4 ways
cfg_m = dataclasses.replace(get_smoke_config("llama3.2-3b"),
                            n_heads=4, n_kv_heads=4, head_dim=16,
                            d_model=64, n_layers=2, d_ff=128,
                            vocab_size=256)
mb = {"cfg": cfg_m, "params": T.init_params(cfg_m, jax.random.PRNGKey(0))}

def mk():
    return [Conversation(conv_id=i, arrival_s=0.0,
                         turns=[Turn(16, 12), Turn(8, 8)],
                         think_time_s=0.2) for i in range(4)]

def run(shape):
    cfg = EngineConfig(mode="real", num_gpu_blocks=8, num_cpu_blocks=256,
                       max_running=4, max_batch=4, block_size=16,
                       swap_chunk_blocks=1,
                       mesh_shape=shape).with_policy("fastswitch")
    eng = FastSwitchEngine(cfg, mk(),
                           trace=PriorityTrace("random", 0.5, seed=13),
                           model_bundle=mb)
    eng.run(max_iterations=20_000)
    assert eng.done()
    assert eng.metrics.preemptions > 0, "schedule never preempted"
    assert eng.metrics.swap_in_count > 0, "schedule never swapped in"
    return {c: list(h) for c, h in eng._token_hist_by_conv.items()}, eng

c0 = DecodeRunner.jit_cache_size()
h1, e1 = run((1, 1))
h4, e4 = run((1, 4))
assert h1 == h4, "mesh (1,4) token histories diverge from single-device"
# staged swap plane: EXACTLY one host transfer per chunk per shard
assert e4.pools.n_shards == 4
assert e4.pools.staged_out_calls > 0 and e4.pools.staged_in_calls > 0
assert e4.pools.d2h_transfers == 4 * e4.pools.staged_out_calls, (
    e4.pools.d2h_transfers, e4.pools.staged_out_calls)
assert e4.pools.h2d_transfers == 4 * e4.pools.staged_in_calls, (
    e4.pools.h2d_transfers, e4.pools.staged_in_calls)
assert e1.pools.n_shards == 1
assert e1.pools.d2h_transfers == e1.pools.staged_out_calls
# jit-variant budget (fslint FS002 discipline): the whole storm run —
# BOTH mesh shapes, every batch/chunk bucket — stays within the
# pow2-bucketed variant bound (4 batch buckets per variant family)
compiles = DecodeRunner.jit_cache_size() - c0
assert compiles <= 8, f"decode-step variants exploded: {compiles}"
print("ENGINE_PARITY_OK", sum(len(v) for v in h1.values()), compiles)
"""


def test_real_engine_4way_mesh_bit_parity_under_storm():
    out = _run_forced(ENGINE_PARITY)
    assert "ENGINE_PARITY_OK" in out


# ---------------------------------------------------------------------------
# vocab-sharded unembed: greedy candidate gather + sampled-row fallback
# must be bit-exact with the single-device full-logits step
# ---------------------------------------------------------------------------

SAMPLED_UNEMBED_PARITY = """
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.models.paged import (paged_decode_step_device,
                                paged_decode_step_device_sharded)

assert len(jax.devices()) == 4
cfg = dataclasses.replace(get_smoke_config("llama3.2-3b"),
                          n_heads=4, n_kv_heads=4, head_dim=16,
                          d_model=64, n_layers=2, d_ff=128,
                          vocab_size=256)
params = T.init_params(cfg, jax.random.PRNGKey(0))
mesh = jax.make_mesh((1, 4), ("data", "model"))

B, n_pages, bs = 4, 4, 16
rng = np.random.RandomState(3)
pool_shape = (cfg.n_layers, 2, B * n_pages + 1, bs, cfg.n_kv_heads,
              cfg.head_dim)
pool0 = rng.randn(*pool_shape).astype(np.float32)
tables = np.arange(B * n_pages, dtype=np.int32).reshape(B, n_pages)
ctx = np.array([5, 17, 30, 47], np.int32)
toks = rng.randint(0, cfg.vocab_size, size=(B,)).astype(np.int32)
active = np.ones((B,), bool)
keys = jnp.stack([jax.random.PRNGKey(100 + i) for i in range(B)])

def run(fn, sampling, **kw):
    nxt, _, new_ctx, new_tok = fn(
        params, jnp.asarray(pool0), jnp.asarray(tables),
        jnp.asarray(ctx), jnp.asarray(toks), jnp.asarray(active), keys,
        jnp.asarray(sampling, jnp.float32), cfg=cfg, **kw)
    return np.asarray(nxt), np.asarray(new_ctx), np.asarray(new_tok)

greedy = np.zeros((B, 3), np.float32); greedy[:, 2] = 1.0
mixed = greedy.copy()
mixed[1] = (0.8, 5, 0.9)      # top-k + nucleus sampled row
mixed[3] = (1.3, 0, 0.7)      # nucleus-only sampled row

for sampling in (greedy, mixed):
    a = run(paged_decode_step_device, sampling)
    b = run(paged_decode_step_device_sharded, sampling, mesh=mesh)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
print("SAMPLED_UNEMBED_OK")
"""


def test_vocab_sharded_unembed_greedy_and_sampled_parity():
    out = _run_forced(SAMPLED_UNEMBED_PARITY)
    assert "SAMPLED_UNEMBED_OK" in out


# ---------------------------------------------------------------------------
# per-shard staged slab round trip (bit-exact, incl. partial last block)
# ---------------------------------------------------------------------------

SLAB_ROUND_TRIP = """
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
from repro.cache.paged import PagedPools, PoolSpec

assert len(jax.devices()) == 4
spec = PoolSpec(n_layers=2, n_kv_heads=4, head_dim=16, block_size=16,
                num_gpu_blocks=12, num_cpu_blocks=24)
mesh = jax.make_mesh((1, 4), ("data", "model"))

def fill(pools, seed):
    rng = np.random.RandomState(seed)
    full = rng.randn(*pools.gpu.shape).astype(np.float32)
    pools.gpu = jax.device_put(
        jnp.asarray(full, pools.gpu.dtype), pools.gpu.sharding)

for shape, n_shards in ((None, 1), (mesh, 4)):
    pools = PagedPools(spec, mesh=shape)
    assert pools.n_shards == n_shards
    fill(pools, 7)
    before = np.asarray(pools.gpu).copy()
    # swap out 5 blocks as 2 chunks — the 2nd is a PARTIAL last chunk
    # (3 blocks into a 4-block slab bucket)
    pools.copy_out_staged([(1, 2)], [0, 1])
    pools.copy_out_staged([(4, 3)], [2, 3, 4])
    # clobber exactly the swapped-out gpu blocks, then stage back in
    for lo, hi in ((1, 3), (4, 7)):
        z = jnp.zeros_like(pools.gpu[:, :, lo:hi])
        pools.gpu = pools.gpu.at[:, :, lo:hi].set(z)
    pools.copy_in_staged([0, 1], [(1, 2)])
    pools.copy_in_staged([2, 3, 4], [(4, 3)])
    after = np.asarray(pools.gpu)
    np.testing.assert_array_equal(before, after)
    assert pools.d2h_transfers == n_shards * 2, pools.d2h_transfers
    assert pools.h2d_transfers == n_shards * 2, pools.h2d_transfers
    # sharded pool really is head-sharded over the mesh
    if shape is not None:
        assert len(pools.gpu.sharding.device_set) == 4

# cross-mode: slab staged OUT on the mesh, read back on host, must
# equal the single-device bytes (layout is shard-transparent)
p1 = PagedPools(spec, mesh=None)
p4 = PagedPools(spec, mesh=mesh)
fill(p1, 11)
fill(p4, 11)
for p in (p1, p4):
    p.copy_out_staged([(2, 3)], [5, 6, 7])
np.testing.assert_array_equal(p1.cpu[:, :, 5:8], p4.cpu[:, :, 5:8])
print("SLAB_ROUND_TRIP_OK")
"""


def test_per_shard_slab_round_trip_bit_exact():
    out = _run_forced(SLAB_ROUND_TRIP)
    assert "SLAB_ROUND_TRIP_OK" in out


REPLICA_PLACEMENT = r"""
import jax
import numpy as np
from repro.core import EngineConfig, SamplingParams, ServingEngine
from repro.launch.serve import _build_real_bundle, replica_devices

bundle = _build_real_bundle("qwen2-1.5b", 0)
assert bundle["cfg"].name == "qwen2-1.5b-smoke"     # the CPU's size
devices = replica_devices(6)
assert devices == [jax.devices()[i % 4] for i in range(6)]
cfg = EngineConfig(mode="real", num_gpu_blocks=32, num_cpu_blocks=64,
                   max_running=2, max_batch=2).with_policy("fastswitch")
prompt = [(5 * i + 1) % bundle["cfg"].vocab_size for i in range(37)]
streams = []
for dev in devices[:4]:
    eng = ServingEngine(cfg, model_bundle=bundle, stream_tokens=True,
                        device=dev)
    eng.add_request(prompt, SamplingParams(max_tokens=6))
    toks = []
    while eng.has_work():
        for out in eng.step():
            toks += out.token_ids or []
    eng.shutdown()
    r = eng.runner
    placed = [eng.pools.gpu, r._bt, r._ctx, r._tok, r._active, r._keys,
              r._sampling] + jax.tree.leaves(r._params)
    assert all(x.devices() == {dev} for x in placed), dev
    streams.append(toks)
assert len(streams[0]) == 6 and all(s == streams[0] for s in streams)
print("REPLICA_PLACEMENT_OK")
"""


def test_replicas_each_serve_from_their_own_device():
    """``run_serve``'s placement: replica i keeps its pool, params and
    decode state on ``jax.devices()[i % n]`` and serves the same tokens
    as every other replica."""
    out = _run_forced(REPLICA_PLACEMENT)
    assert "REPLICA_PLACEMENT_OK" in out
