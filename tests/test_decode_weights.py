"""The decode step's compute-dtype weight view (``paged.decode_weights``)
and the gather-then-cast embedding lookup.

The runner converts the layer weights the decode body only ever casts
to bfloat16 once, at its build, instead of on every step; the lookup
gathers the batch's rows before it casts them.  Both are the same
arithmetic on fewer bytes: the served tokens and the pool stay
bit-identical to the step that casts the float32 tree itself, and the
step's program converts no weight table.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex
from jax.sharding import Mesh, NamedSharding

from repro.configs import get_smoke_config
from repro.core import decode_runner, tracing
from repro.core.decode_runner import DecodeRequestView, DecodeRunner
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.paged import (decode_weights, paged_decode_step_device,
                                paged_decode_step_device_sharded)
from repro.models.sharding import serving_param_pspecs

BS, NB, N_ROWS, N_STEPS = 4, 16, 3, 10
# smoke configs: Qwen2's is tied with QKV bias, Nemo's untied without
ARCHS = {"tied-bias": "qwen2-1.5b", "untied": "mistral-nemo-12b"}
CAST = {("layers", "attn", n) for n in ("wq", "wk", "wv", "wo", "bq", "bk",
                                        "bv")} \
    | {("layers", "ffn", n) for n in ("w_gate", "w_up", "w_down")}


def _names(path):
    return tuple(k.key for k in path)


def _model(case):
    cfg = get_smoke_config(ARCHS[case])
    return cfg, T.init_params(cfg, jax.random.PRNGKey(3))


def _precast(params):
    """``params`` with the layers' projections and MLP matrices handed in
    as bfloat16, as a caller holding serving-dtype weights would."""
    return jax.tree_util.tree_map_with_path(
        lambda p, x: x.astype(jnp.bfloat16) if _names(p) in CAST else x,
        params)


def _serve(cfg, params, parent_path=False):
    """Greedy-decode ``N_STEPS`` tokens for ``N_ROWS`` rows from empty
    contexts through a DecodeRunner.  ``parent_path``: the step reads the
    float32 tree and casts each weight itself, as before the view."""
    runner = DecodeRunner({"cfg": cfg, "params": params}, block_size=BS,
                          trash_block=NB - 1)
    if parent_path:
        runner._decode_params = runner._params
    pool = jnp.zeros((cfg.n_layers, 2, NB, BS, cfg.n_kv_heads,
                      cfg.resolved_head_dim), jnp.bfloat16)
    hists = [[11 + 7 * i] for i in range(N_ROWS)]
    for ctx in range(N_STEPS):
        views = [DecodeRequestView(i, [4 * i + j for j in range(ctx // BS + 1)],
                                   hists[i]) for i in range(N_ROWS)]
        pool = runner.decode(views, pool)
    runner.flush()
    return hists, np.asarray(pool).view(np.uint16)


@pytest.mark.parametrize("case", ["tied-bias", "untied", "embed"])
def test_decode_weight_view_is_bit_exact(case):
    """Tokens and pool of the runner's view over float32 weights, of the
    same weights handed in as bfloat16, and of the step that casts the
    float32 tree itself are one and the same; and the lookup that
    gathers, then casts, equals the one that casts the whole table."""
    if case == "embed":
        for seed, (V, d, B) in enumerate([(512, 256, 4), (1000, 96, 33)]):
            kt, kx = jax.random.split(jax.random.PRNGKey(seed))
            table = jax.random.normal(kt, (V, d), jnp.float32) * 3.0
            toks = jax.random.randint(kx, (B, 1), 0, V)
            want = table.astype(jnp.bfloat16)[toks]
            for got in (L.embed({"table": table}, toks),
                        jax.jit(L.embed)({"table": table}, toks)):
                assert got.dtype == jnp.bfloat16
                np.testing.assert_array_equal(
                    np.asarray(got).view(np.uint16),
                    np.asarray(want).view(np.uint16))
        return
    cfg, params = _model(case)
    hists, pool = _serve(cfg, params)
    assert all(len(h) == N_STEPS + 1 for h in hists)
    for other in (_serve(cfg, _precast(params)),
                  _serve(cfg, params, parent_path=True)):
        assert other[0] == hists
        np.testing.assert_array_equal(other[1], pool)


def _converts(jaxpr, stack=""):
    """(scope path, operand aval, result aval) of every dtype conversion
    in ``jaxpr`` and the programs it nests, kernels aside."""
    for e in jaxpr.eqns:
        path = "/".join(s for s in (stack, str(e.source_info.name_stack)) if s)
        if e.primitive.name == "convert_element_type":
            yield path, e.invars[0].aval, e.outvars[0].aval
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(j, jex.ClosedJaxpr):
                    j = j.jaxpr
                if isinstance(j, jex.Jaxpr):
                    yield from _converts(j, path)


@pytest.mark.parametrize("case", ["tied-bias", "untied"])
def test_decode_step_converts_no_weight_table(case, monkeypatch):
    """The runner's decode step, single-device and on a (1, 1) mesh,
    casts no stacked weight, no layer's slice of one and no (V, d)
    table; the embedding converts only the batch's (B, 1, d) rows.  The
    ``decode.weights`` mark counts what the view converted: something
    for float32 weights, nothing for bfloat16 ones, which come back as
    the same arrays, like every leaf the view leaves alone."""
    monkeypatch.setattr(tracing, "_TRACER", tracing.Tracer())
    read = []

    def step(params, *args, **kw):
        read.append(params)
        return paged_decode_step_device(params, *args, **kw)

    monkeypatch.setattr(decode_runner, "paged_decode_step_device", step)
    cfg, params = _model(case)
    runner = DecodeRunner({"cfg": cfg, "params": params}, block_size=BS,
                          trash_block=NB - 1)
    pool = jnp.zeros((cfg.n_layers, 2, NB, BS, cfg.n_kv_heads,
                      cfg.resolved_head_dim), jnp.bfloat16)
    pool = runner.decode(
        [DecodeRequestView(i, [i], [5 + i]) for i in range(N_ROWS)], pool)
    view = read[0]
    assert view is runner._decode_params
    weights = set()
    for p, x in jax.tree_util.tree_leaves_with_path(params):
        if x.ndim >= 2 or _names(p)[0] == "layers":
            weights |= {x.shape, x.shape[1:]}
    B, P = 4, 2
    args = (pool, jnp.zeros((B, P), jnp.int32), jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros((B, 3), jnp.float32))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    for traced in (paged_decode_step_device.trace(view, *args, cfg=cfg),
                   paged_decode_step_device_sharded.trace(
                       view, *args, cfg=cfg, mesh=mesh)):
        traced.lower()
        convs = list(_converts(traced.jaxpr.jaxpr))
        floats = [(s, a, b) for s, a, b in convs
                  if a.dtype == jnp.float32 and b.dtype == jnp.bfloat16]
        assert floats
        assert not [c for c in floats if c[1].shape in weights], floats
        embeds = [(a.shape, b.dtype) for s, a, b in convs
                  if "embed" in s.split("/")]
        assert embeds == [((B, 1, cfg.d_model), jnp.bfloat16)], embeds

    marks = [m.data for m in tracing.tracer().marks()
             if m.name == "decode.weights"]
    cast = [x for p, x in jax.tree_util.tree_leaves_with_path(params)
            if _names(p) in CAST]
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    assert marks[0]["leaves_cast"] == len(cast) == (10 if cfg.qkv_bias else 7)
    assert marks[0]["cast_bytes"] == sum(x.nbytes for x in cast) > 0
    assert marks[0]["f32_bytes"] >= head["table"].nbytes
    for p, x in jax.tree_util.tree_leaves_with_path(view):
        orig = params
        for k in _names(p):
            orig = orig[k]
        if _names(p) in CAST:
            assert x.dtype == jnp.bfloat16 and x.shape == orig.shape
        else:
            assert x is orig, _names(p)

    again = DecodeRunner({"cfg": cfg, "params": view}, block_size=BS,
                         trash_block=NB - 1)
    assert tracing.tracer().marks()[-1].data == {
        "cast_bytes": 0, "leaves_cast": 0, "f32_bytes": marks[0]["f32_bytes"]}
    assert all(a is b for a, b in zip(jax.tree.leaves(again._decode_params),
                                      jax.tree.leaves(view)))

    # on a mesh the view keeps each leaf's placement
    placed = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                          params, serving_param_pspecs(params))
    on_mesh, _ = decode_weights(placed, cfg)
    assert all(a.sharding == b.sharding for a, b in
               zip(jax.tree.leaves(on_mesh), jax.tree.leaves(placed)))
