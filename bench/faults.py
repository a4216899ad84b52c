"""Faults planted in the program's decode step, to show the correctness
check catches them: the KV the step writes dropped (its state returned
unchanged), half the batch left out, the token altered where it is
produced.  Used by the tests and by ``control.py --fault``; the
benchmark's own runs never plant one."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _core():
    from repro.models import paged
    return paged._device_step_core


def state_unchanged(params, pool, bt, ctx, tok, act, keys, smp, *, cfg):
    nxt, _, ctx2, tok2 = _core()(params, pool, bt, ctx, tok, act, keys,
                                 smp, cfg)
    return nxt, pool, ctx2, tok2          # the KV written is dropped


def half_batch(params, pool, bt, ctx, tok, act, keys, smp, *, cfg):
    nxt, pool2, ctx2, _ = _core()(params, pool, bt, ctx, tok, act, keys,
                                  smp, cfg)
    keep = jnp.arange(tok.shape[0]) < tok.shape[0] // 2
    nxt = jnp.where(keep, nxt, tok)        # upper rows left out
    return nxt, pool2, ctx2, jnp.where(act, nxt, tok)


def token_altered(params, pool, bt, ctx, tok, act, keys, smp, *, cfg):
    nxt, pool2, ctx2, _ = _core()(params, pool, bt, ctx, tok, act, keys,
                                  smp, cfg)
    nxt = (nxt + 1) % cfg.vocab_size
    return nxt, pool2, ctx2, jnp.where(act, nxt, tok)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}


def plant(name: str) -> None:
    """Put fault ``name`` in place of the decode runner's step."""
    import repro.core.decode_runner as dr
    dr.paged_decode_step_device = jax.jit(
        FAULTS[name], static_argnames=("cfg",), donate_argnums=(1, 3, 4))
