"""A configuration file, the program's model config built from it, the
benchmark's own weights, and the operation counts of a decode step.

The weights are the benchmark's: made on the device from ``--seed`` in
one jitted call, in the dtype the program serves (float32), in the
layout the program's uniform decoder reads.  The reference reads the
same arrays, so nothing compared comes from the program.
"""
from __future__ import annotations

import functools
import json
import math
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp


def load_config(path: Path) -> Dict:
    with open(path) as f:
        hp = json.load(f)
    hp["head_dim"] = hp.get("head_dim") or hp.get("assumed", {}).get(
        "head_dim") or hp["hidden_size"] // hp["num_attention_heads"]
    hp["qkv_bias"] = bool(hp.get("assumed", {}).get("qkv_bias", False))
    return hp


def program_config(hp: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=hp["name"], arch_type="dense", source=hp["source"],
        n_layers=hp["num_hidden_layers"], d_model=hp["hidden_size"],
        n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"],
        d_ff=hp["intermediate_size"], vocab_size=hp["vocab_size"],
        head_dim=hp["head_dim"], qkv_bias=hp["qkv_bias"],
        rope_theta=float(hp["rope_theta"]),
        max_seq_len=hp["max_position_embeddings"],
        norm_eps=float(hp["rms_norm_eps"]),
        tie_embeddings=bool(hp["tie_word_embeddings"]))


def _shapes(hp: Dict) -> Dict:
    d, f, V = hp["hidden_size"], hp["intermediate_size"], hp["vocab_size"]
    L, hd = hp["num_hidden_layers"], hp["head_dim"]
    nq, nkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    attn = {"wq": (L, d, nq * hd), "wk": (L, d, nkv * hd),
            "wv": (L, d, nkv * hd), "wo": (L, nq * hd, d)}
    if hp["qkv_bias"]:
        attn.update(bq=(L, nq * hd), bk=(L, nkv * hd), bv=(L, nkv * hd))
    tree = {"embed": {"table": (V, d)}, "final_norm": {"scale": (d,)},
            "layers": {"ln1": {"scale": (L, d)}, "ln2": {"scale": (L, d)},
                       "attn": attn,
                       "ffn": {"w_gate": (L, d, f), "w_up": (L, d, f),
                               "w_down": (L, f, d)}}}
    if not hp["tie_word_embeddings"]:
        tree["lm_head"] = {"table": (V, d)}
    return tree


def _leaf(key, path: str, shape) -> jnp.ndarray:
    name = path.rsplit("/", 1)[-1]
    x = jax.random.normal(key, shape, jnp.float32)
    if name == "table":
        return x * 0.02
    if name == "scale":                    # norm gains around 1
        return 1.0 + 0.05 * x
    if name.startswith("b"):               # q/k/v biases
        return 0.02 * x
    return x * (1.0 / math.sqrt(shape[-2]))   # fan-in scaled projection


def _paths(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        p = f"{prefix}/{k}"
        if isinstance(v, dict):
            yield from _paths(v, p)
        else:
            yield p, v


@functools.partial(jax.jit, static_argnames=("spec",))
def _make(key, *, spec):
    leaves = list(spec)
    keys = jax.random.split(key, len(leaves))
    out: Dict = {}
    for k, (path, shape) in zip(keys, leaves):
        node = out
        parts = path.strip("/").split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = _leaf(k, path, shape)
    return out


def make_params(hp: Dict, seed: int, device=None):
    """The weights for ``hp`` from ``seed``, made on ``device`` in one
    jitted call (float32, the program's layout)."""
    spec = tuple((p, tuple(s)) for p, s in _paths(_shapes(hp)))
    seed %= 2 ** 64                 # keys hold 32 bits: fold in the rest
    key = jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed >> 32)
    if device is not None:
        key = jax.device_put(key, device)
    return _make(key, spec=spec)


def param_bytes(hp: Dict) -> int:
    return 4 * sum(math.prod(s) for _, s in _paths(_shapes(hp)))


# ---------------------------------------------------------------------------
# operation and byte counts
# ---------------------------------------------------------------------------


def matmul_params_per_token(hp: Dict) -> int:
    """Weights a token multiplies through: every layer's projections and
    the LM head (the embedding lookup is a gather, not a matmul)."""
    d, f, V = hp["hidden_size"], hp["intermediate_size"], hp["vocab_size"]
    hd, nq, nkv = hp["head_dim"], hp["num_attention_heads"], \
        hp["num_key_value_heads"]
    per_layer = d * (nq + 2 * nkv) * hd + nq * hd * d + 3 * d * f
    return hp["num_hidden_layers"] * per_layer + V * d


def decode_flops(hp: Dict, context_lens) -> float:
    """FLOPs one decode step needs for rows with these live context
    lengths (after the step's token): 2 per weight per row, plus the
    attention scores and the weighted sum over each row's context."""
    hd, nq = hp["head_dim"], hp["num_attention_heads"]
    dense = 2.0 * matmul_params_per_token(hp) * len(context_lens)
    attn = 4.0 * hp["num_hidden_layers"] * nq * hd * float(sum(context_lens))
    return dense + attn


def prefill_flops(hp: Dict, new_tokens: int, prefix: int) -> float:
    """FLOPs of a prefill of ``new_tokens`` after ``prefix`` cached ones
    (causal attention counted over the keys each query sees)."""
    hd, nq = hp["head_dim"], hp["num_attention_heads"]
    keys = new_tokens * prefix + new_tokens * (new_tokens + 1) / 2
    return (2.0 * (matmul_params_per_token(hp) - hp["vocab_size"]
                   * hp["hidden_size"]) * new_tokens
            + 4.0 * hp["num_hidden_layers"] * nq * hd * keys
            + 2.0 * hp["vocab_size"] * hp["hidden_size"])


def paged_attention_bytes(hp: Dict, context_lens, kv_bytes: int = 2
                          ) -> float:
    """Bytes one paged-attention call (one layer) must move for rows with
    these live context lengths: their K and V, plus q in and out."""
    hd, nq, nkv = hp["head_dim"], hp["num_attention_heads"], \
        hp["num_key_value_heads"]
    kv = 2.0 * nkv * hd * kv_bytes * float(sum(context_lens))
    qo = 2.0 * nq * hd * kv_bytes * len(context_lens)
    return kv + qo


def paged_attention_flops(hp: Dict, context_lens) -> float:
    hd, nq = hp["head_dim"], hp["num_attention_heads"]
    return 4.0 * nq * hd * float(sum(context_lens))
