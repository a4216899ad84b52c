"""Compile a configuration's decode step and largest prefill chunk for a
described (not attached) TPU v5e and print what the compiler says about
their memory.  No chip is needed; nothing runs.

    JAX_PLATFORMS=cpu python3 bench/rehearse_v5e.py <config> <batch> <pages> <chunk> <carry> <pool_blocks>

e.g. ``mistral-nemo-12b-d4 32 128 1024 1024 4608``.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv) -> int:
    name, batch, pages, chunk, carry, nb = argv[0], *map(int, argv[1:6])
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import model
    from repro.kernels import ops
    from repro.models.paged import paged_decode_step_device, prefill_kv_chunk

    jax.config.update("jax_enable_compilation_cache", False)
    ops.interpret_mode = lambda: False        # compile the Mosaic kernels
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    hp = model.load_config(REPO / "bench" / "configs" / f"{name}.json")
    cfg = model.program_config(hp)
    spec = tuple((p, tuple(s)) for p, s in model._paths(model._shapes(hp)))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(lambda k: model._make(k, spec=spec),
                       jax.random.key(0)))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    L, H, D = hp["num_hidden_layers"], hp["num_key_value_heads"], \
        hp["head_dim"]
    pool = s((L, 2, nb, 16, H, D), jnp.bfloat16)
    out = {"config": name, "param_bytes": model.param_bytes(hp),
           "pool_bytes": L * 2 * nb * 16 * H * D * 2}

    def mem(c):
        m = c.memory_analysis()
        return {k: int(getattr(m, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")}

    dec = paged_decode_step_device.lower(
        params, pool, s((batch, pages), jnp.int32), s((batch,), jnp.int32),
        s((batch,), jnp.int32), s((batch,), jnp.bool_),
        s((batch, 2), jnp.uint32), s((batch, 3), jnp.float32),
        cfg=cfg).compile()
    out["decode"] = dict(mem(dec), batch=batch, pages=pages,
                         mosaic="tpu_custom_call" in dec.as_text())
    pre = prefill_kv_chunk.lower(
        params, s((1, chunk), jnp.int32), s((L, carry, H, D), jnp.bfloat16),
        s((L, carry, H, D), jnp.bfloat16), s((), jnp.int32),
        s((), jnp.int32), cfg=cfg).compile()
    out["prefill"] = dict(mem(pre), chunk=chunk, carry=carry)
    out["largest_step_bytes"] = out["param_bytes"] + out["pool_bytes"] + max(
        out["decode"]["temp_size_in_bytes"],
        out["prefill"]["temp_size_in_bytes"])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
