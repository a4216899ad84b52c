"""Integration tests: the CLI launchers and checkpointing round-trips."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ENV = {**os.environ, "PYTHONPATH": SRC}


def _run(args, timeout=300):
    return subprocess.run([sys.executable, *args], env=ENV, cwd=SRC + "/..",
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_dryrun_cli_single_case():
    r = _run(["-m", "repro.launch.dryrun", "--arch", "qwen2-1.5b",
              "--shape", "decode_32k"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK   qwen2-1.5b x decode_32k" in r.stdout


@pytest.mark.slow
def test_serve_cli_sim():
    r = _run(["-m", "repro.launch.serve", "--policy", "fastswitch",
              "--conversations", "20", "--gpu-blocks", "512"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "fastswitch" in r.stdout


@pytest.mark.slow
def test_train_cli():
    r = _run(["-m", "repro.launch.train", "--arch", "qwen2-1.5b",
              "--steps", "3", "--batch", "2", "--seq", "32"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "step" in r.stdout


def test_checkpoint_roundtrip(tmp_path):
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.train.checkpoint import load_checkpoint, save_checkpoint
    cfg = get_smoke_config("llama3.2-3b")
    params = T.init_params(cfg, jax.random.PRNGKey(3))
    path = str(tmp_path / "ckpt.npz")
    save_checkpoint(path, params)
    restored = load_checkpoint(path, params)
    flat1 = jax.tree.leaves(params)
    flat2 = jax.tree.leaves(restored)
    assert len(flat1) == len(flat2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_optimizer_state(tmp_path):
    from repro.configs import get_smoke_config
    from repro.models import steps, transformer as T
    from repro.train.checkpoint import load_checkpoint, save_checkpoint
    from repro.train.optimizer import adamw_init
    cfg = get_smoke_config("qwen2-1.5b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    params, opt, _ = steps.train_step(params, opt,
                                      {"tokens": tokens, "labels": tokens},
                                      cfg=cfg)
    path = str(tmp_path / "opt.npz")
    save_checkpoint(path, opt)
    restored = load_checkpoint(path, opt)
    assert int(restored.step) == 1
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(opt.mu)[0]),
        np.asarray(jax.tree.leaves(restored.mu)[0]))


def test_compile_cache_dir_env_wins_else_fixed_checkout_dir(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` is used as set, with no directory
    set in code; without it the cache sits at one fixed directory in
    the checkout."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jc")
        jax.config.update("jax_compilation_cache_dir", "/elsewhere/jc")
        assert compile_cache.enable_compile_cache() == "/elsewhere/jc"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/jc"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
