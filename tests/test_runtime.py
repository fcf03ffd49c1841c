"""Checkpoint manager, data pipeline, grad compression, the compile
cache, import hygiene.  (The fault runtime's unit coverage moved to
tests/test_fault.py.)"""
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import compress
from repro.train.checkpoint import CheckpointManager
from repro.train.data import GANPipeline, Prefetcher, TokenPipeline


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def make_state(v=0.0):
    return {"params": {"w": jnp.full((4, 4), v), "b": jnp.zeros((3,))},
            "step": jnp.asarray(int(v), jnp.int32)}


def test_checkpoint_roundtrip(tmp_path):
    ckpt = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    ckpt.save(5, make_state(5.0))
    assert ckpt.latest_step() == 5
    restored = ckpt.restore(make_state(0.0))
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 5.0)
    assert int(restored["step"]) == 5


def test_checkpoint_gc_and_latest(tmp_path):
    ckpt = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for s in (1, 2, 3, 4):
        ckpt.save(s, make_state(float(s)))
    dirs = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert sorted(dirs) == ["step_00000003", "step_00000004"]
    assert ckpt.latest_step() == 4


def test_checkpoint_async(tmp_path):
    ckpt = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    ckpt.save(7, make_state(7.0))
    ckpt.wait()
    assert ckpt.latest_step() == 7


def test_checkpoint_restore_with_dtype_cast(tmp_path):
    ckpt = CheckpointManager(str(tmp_path), async_save=False)
    state = {"w": jnp.ones((4,), jnp.bfloat16)}
    ckpt.save(1, state)
    restored = ckpt.restore({"w": jnp.zeros((4,), jnp.bfloat16)})
    assert restored["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_by_step():
    from repro.configs import registry
    cfg = registry.get_reduced("llama3.2-1b")
    p1 = TokenPipeline(cfg, 4, 16, seed=7)
    p2 = TokenPipeline(cfg, 4, 16, seed=7)
    b1, b2 = p1.batch_at(123), p2.batch_at(123)
    np.testing.assert_array_equal(b1["inputs"], b2["inputs"])
    b3 = p1.batch_at(124)
    assert not np.array_equal(b1["inputs"], b3["inputs"])


def test_prefetcher_yields_in_order():
    from repro.configs import registry
    cfg = registry.get_reduced("llama3.2-1b")
    pipe = TokenPipeline(cfg, 2, 8, seed=1)
    pf = Prefetcher(pipe, start_step=0, depth=2)
    try:
        a = pf.next()
        np.testing.assert_array_equal(a["inputs"], pipe.batch_at(0)["inputs"])
        b = pf.next()
        np.testing.assert_array_equal(b["inputs"], pipe.batch_at(1)["inputs"])
    finally:
        pf.close()


def test_gan_pipeline_shapes():
    from repro.models.gan import DCGAN
    p = GANPipeline(DCGAN, 4, 64)
    b = p.batch_at(0)
    assert b["z"].shape == (4, 100) and b["real"].shape == (4, 64, 64, 3)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bounded():
    g = jnp.asarray(np.random.default_rng(0).standard_normal(1000),
                    jnp.float32)
    q, scale, err = compress.quantize_int8(g, jnp.zeros_like(g))
    deq = compress.dequantize_int8(q, scale)
    max_err = float(jnp.max(jnp.abs(deq - g)))
    assert max_err <= float(scale) / 2 + 1e-6
    np.testing.assert_allclose(np.asarray(err), np.asarray(g - deq),
                               atol=1e-6)


def test_error_feedback_reduces_bias():
    """With error feedback the *averaged* quantization error shrinks vs
    without it (unbiased over steps)."""
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.standard_normal(512) * 1e-3, jnp.float32)
    err = jnp.zeros_like(g_true)
    acc_fb, acc_nofb = [], []
    for _ in range(50):
        q, s, err = compress.quantize_int8(g_true, err)
        acc_fb.append(compress.dequantize_int8(q, s))
        q2, s2, _ = compress.quantize_int8(g_true, jnp.zeros_like(g_true))
        acc_nofb.append(compress.dequantize_int8(q2, s2))
    mean_fb = np.mean(np.stack(acc_fb), axis=0)
    mean_nofb = np.mean(np.stack(acc_nofb), axis=0)
    assert (np.abs(mean_fb - np.asarray(g_true)).mean()
            <= np.abs(mean_nofb - np.asarray(g_true)).mean() + 1e-9)


# ---------------------------------------------------------------------------
# compile cache + import hygiene
# ---------------------------------------------------------------------------

def test_compile_cache_dir(monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set; else
    the fixed ``<checkout>/.jax_cache`` (git-ignored)."""
    from repro.runtime import compile_cache
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == old
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = pathlib.Path(compile_cache.enable_compile_cache())
        assert jax.config.jax_compilation_cache_dir == str(got)
        root = pathlib.Path(__file__).resolve().parent.parent
        assert got == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_importing_the_package_initialises_no_backend():
    """A parent that touched a backend would hold the chip: importing any
    module of the package (models, serving, kernels, ...) must not."""
    code = (
        "import importlib, pkgutil\n"
        "from jax._src import xla_bridge\n"
        "import repro\n"
        "for m in pkgutil.walk_packages(repro.__path__, 'repro.'):\n"
        "    importlib.import_module(m.name)\n"
        "    assert not xla_bridge._backends, m.name\n"
        "print('imported, no backend')\n")
    src = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "imported, no backend" in out.stdout
