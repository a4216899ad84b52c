"""Compile the served path's Pallas kernels for a described TPU v5e.

Interpret mode (what every other kernel test runs on the CPU) never
checks Mosaic's tiling rules or VMEM limits; the TPU compiler installed
with JAX does, for a chip that is described and not attached.  Shapes
are the benchmarked configurations' serving widths, block size 16, a
bf16 pool and bf16 queries (the dtype the decode step passes):
Qwen2-1.5B (28 layers, 12/2 heads x 128, a 1,024-block pool) and
Mistral-Nemo-12B at 4 of its 40 layers (32/8 heads x 128, a 4,608-block
pool).

The topology is described inside a module-scoped fixture, never while
a module is imported: only one process at a time may load the TPU
library, so the worker that runs this file loads it and the others
never try.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention

D, BS = 128, 16
# (layers, q heads, KV heads, pool blocks)
QWEN2 = (28, 12, 2, 1024)
NEMO = (4, 32, 8, 4608)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without the chip: keep the cache off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("model,B,n_pages", [
    pytest.param(QWEN2, 32, 256, id="chat-bucket"),
    pytest.param(QWEN2, 3, 5, id="ragged"),
    pytest.param(NEMO, 32, 128, id="nemo-bulk-bucket"),
    pytest.param(NEMO, 3, 5, id="nemo-ragged")])
def test_paged_attention_compiles_for_v5e(one_chip, model, B, n_pages):
    """Each cell's bucket (the chat cell's 32 rows x 256 pages, the bulk
    cell's 32 x 128), and a small bucket whose page count no power of
    two divides; the tile comes from the shapes."""
    L, HQ, HKV, NB = model
    fn = functools.partial(paged_attention, scale=D ** -0.5,
                           interpret=False)
    compiled = jax.jit(fn).lower(
        _spec((B, HQ, D), jnp.bfloat16, one_chip),
        _spec((L, 2, NB, BS, HKV, D), jnp.bfloat16, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((B, n_pages), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("model,n_slab,run_blocks", [
    pytest.param(QWEN2, 64, 8, id="64-8"),
    pytest.param(QWEN2, 256, 256, id="256-256"),
    pytest.param(NEMO, 64, 64, id="nemo-64-64")])
def test_staged_gather_compiles_for_v5e(one_chip, model, n_slab, run_blocks):
    L, _, HKV, NB = model
    n_runs = 8
    compiled = ops._gather_swap.lower(
        _spec((L, 2, NB, BS, HKV, D), jnp.bfloat16, one_chip),
        _spec((n_runs,), jnp.int32, one_chip),
        _spec((n_runs,), jnp.int32, one_chip),
        _spec((n_runs,), jnp.int32, one_chip),
        n_slab=n_slab, run_blocks=run_blocks, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_donated_staged_scatter_compiles_for_v5e(one_chip):
    L, _, HKV, NB = QWEN2
    n_runs, n_slab, run_blocks = 8, 64, 8
    compiled = ops._scatter_swap.lower(
        _spec((L, 2, NB, BS, HKV, D), jnp.bfloat16, one_chip),
        _spec((L * 2, n_slab, BS, HKV, D), jnp.bfloat16, one_chip),
        _spec((n_runs,), jnp.int32, one_chip),
        _spec((n_runs,), jnp.int32, one_chip),
        _spec((n_runs,), jnp.int32, one_chip),
        run_blocks=run_blocks, interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the pool is donated and aliased through the kernel: written in
    # place, never copied whole
    assert "input_output_alias" in text
